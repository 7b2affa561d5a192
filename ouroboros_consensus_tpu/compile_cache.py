"""The one place that says where JAX keeps its persistent compile cache.

Every entry point that traces a program (db_analyser, db_synthesizer,
chip_smoke.py, bench.py's device child, the profiling scripts) calls
`configure()` before its first trace. The crypto stage programs cost
minutes to compile; a cache that moves, or that each caller places for
itself, never hits.
"""

from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
# fixed, inside the checkout, git-ignored: the path is part of the
# cache key, so it must not depend on the runtime, the caller or the cwd
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def configure() -> str:
    """Place the persistent compile cache; -> the directory in use.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX has read it already and
    no directory is set in code (whoever runs the program places the
    cache). Where it is not, the cache lives in `DEFAULT_DIR`."""
    import jax

    path = os.environ.get(_ENV)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return path
