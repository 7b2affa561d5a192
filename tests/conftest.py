"""Test configuration.

Tests run on a virtual 8-device CPU mesh (multi-chip hardware is not
available in CI): the env vars must be set before jax is first imported,
hence this conftest sets them at collection time. The real-TPU benchmark
path is exercised separately by bench.py.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# the crypto kernels are large HLO graphs: cache compilations across
# runs. JAX_COMPILATION_CACHE_DIR places the cache when set (JAX reads
# it); otherwise the suite keeps its own fixed directory — moving it
# turns the next run cold
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir", "/tmp/ouroboros-jax-cache")
jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)


import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running integration test")


@pytest.fixture
def index_entries_built(monkeypatch) -> list:
    """Every `IndexEntry` constructed while the test runs lands in the
    list returned: the storage tests count constructions, they do not
    time them."""
    from ouroboros_consensus_tpu.storage.immutable import IndexEntry

    built: list = []
    init = IndexEntry.__init__

    def counting(self, *a, **kw):
        built.append(1)
        init(self, *a, **kw)

    monkeypatch.setattr(IndexEntry, "__init__", counting)
    return built
