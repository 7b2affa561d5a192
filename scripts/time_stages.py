"""Quick per-stage hot timing on the live device (ground-truth A/B for
kernel changes). Compiles the requested stages fresh (the persistent
cache keys on source, so edited kernels recompile once) and prints hot
rates in the same format as aot_smoke.py.

Usage: python scripts/time_stages.py [ed vrf kes finish] (default: ed vrf)
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np

import jax

from ouroboros_consensus_tpu import compile_cache

compile_cache.configure()

os.environ["OCT_PK_AOT"] = "0"  # jit path only — we are timing edits
# before the bench import: bench.py resolves BENCH_HEADERS at import
# time, and stage timing wants the 100k chain even when the 1M cache
# exists (its open alone is multi-second)
os.environ.setdefault("BENCH_HEADERS", "100000")

from bench import KES_DEPTH, MAX_BATCH, build_or_load_chain  # noqa: E402
from ouroboros_consensus_tpu.ops.pk import kernels as K  # noqa: E402
from ouroboros_consensus_tpu.protocol import batch as pbatch  # noqa: E402
from ouroboros_consensus_tpu.tools import db_analyser as ana  # noqa: E402

B = MAX_BATCH


def main():
    which = sys.argv[1:] or ["ed", "vrf"]
    dev = jax.devices()[0]
    print(f"device: {dev} platform={dev.platform}", flush=True)
    path, params, lview = build_or_load_chain()
    imm = ana.open_immutable(path, validate_all=False)
    res = ana.ValidationResult()
    hvs = []
    for hv in ana._stream_views(imm, res):
        hvs.append(hv)
        if len(hvs) >= B:
            break
    pre = pbatch.host_prechecks(params, lview, hvs)
    staged = pbatch.stage(params, lview, None, hvs, pre.kes_evolution)
    padded = pbatch.pad_batch_to(staged, pbatch.bucket_size(len(hvs)))
    cols = pbatch.flatten_batch(padded)
    stages = dict(K.split_stage_fns(KES_DEPTH))

    t0 = time.monotonic()
    limb = stages["relayout"](*cols)
    jax.tree.map(np.asarray, limb)
    print(f"relayout first {time.monotonic()-t0:.2f}s", flush=True)
    import jax.numpy as jnp

    n_live = jax.device_put(K.all_tiles(B))  # every tile: a full window
    args = dict(K.stage_operands(limb, n_live))

    outs = {}
    for name in ("vrf", "ed", "kes", "finish"):
        if name not in which:
            continue
        if name == "finish":
            vrf_out = outs.get("vrf") or stages["vrf"](*args["vrf"])
            z = (jnp.zeros((1, B), jnp.int32), jnp.zeros((80, B), jnp.int32))
            a = K.finish_operands(limb, z, z, vrf_out, n_live)
        else:
            a = args[name]
        fn = stages[name]
        t0 = time.monotonic()
        out = fn(*a)
        jax.tree.map(np.asarray, out)
        first = time.monotonic() - t0
        # aot_smoke methodology: n async dispatches, materialize ONCE —
        # the per-call D2H (vrf points are 13 MB)
        # otherwise swamps the kernel time
        n = 6
        t0 = time.monotonic()
        for _ in range(n):
            out = fn(*a)
        jax.tree.map(np.asarray, out)
        hot = (time.monotonic() - t0) / n
        outs[name] = out
        print(f"{name:8s} first {first:7.2f}s  hot {hot*1e3:8.1f}ms  "
              f"({B/hot:9.0f} lanes/s)", flush=True)


if __name__ == "__main__":
    main()
