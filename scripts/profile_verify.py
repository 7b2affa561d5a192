"""Time the three sub-verifiers + the fused verify_praos on random inputs.

Validity doesn't affect timing (batch-uniform mask-lane control flow), so
random garbage with the right shapes measures the real kernel cost.
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import numpy as np
from jax import numpy as jnp

from ouroboros_consensus_tpu.ops import ecvrf_batch, ed25519_batch, kes_batch
from ouroboros_consensus_tpu.protocol import batch as pbatch

B = int(sys.argv[1]) if len(sys.argv) > 1 else 4096
NB = 4  # sha512 blocks per message
DEPTH = 7
rng = np.random.default_rng(0)


def b8(*shape):
    return jnp.asarray(rng.integers(0, 256, size=shape, dtype=np.uint8))


def _sync(out):
    # a host transfer: the result is on the host when this returns
    return jax.tree.map(np.asarray, out)


def timeit(name, fn, *args, n=5):
    fn_j = jax.jit(fn)
    t0 = time.perf_counter()
    _sync(fn_j(*args))
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn_j(*args)
    _sync(out)
    dt = (time.perf_counter() - t0) / n
    print(
        f"{name:22s} {dt*1e3:9.2f} ms  ({dt*1e9/B:9.1f} ns/lane)  "
        f"compile {compile_s:.1f}s",
        flush=True,
    )
    return dt


ed_args = (
    b8(B, 32), b8(B, 32), b8(B, 32),
    jnp.asarray(rng.integers(0, 2**32, size=(B, NB, 16, 2), dtype=np.uint32)),
    jnp.full((B,), NB, jnp.int32),
)
kes_args = (
    b8(B, 32), jnp.asarray(rng.integers(0, 128, size=(B,), dtype=np.int32)),
    b8(B, 32), b8(B, 32), b8(B, 32), b8(B, DEPTH, 32),
    jnp.asarray(rng.integers(0, 2**32, size=(B, NB, 16, 2), dtype=np.uint32)),
    jnp.full((B,), NB, jnp.int32),
)
vrf_args = (b8(B, 32), b8(B, 32), b8(B, 16), b8(B, 32), b8(B, 32))

print(f"batch = {B}, device = {jax.devices()[0]}")
timeit("ed25519.verify", ed25519_batch.verify, *ed_args)
timeit("kes.verify", kes_batch.verify, *kes_args)
timeit("ecvrf.verify", ecvrf_batch.verify, *vrf_args)

full_args = (
    *ed_args, *kes_args, *vrf_args,
    b8(B, 64), b8(B, 32), b8(B, 32),
)
timeit("verify_praos (fused)", pbatch.verify_praos, *full_args)
