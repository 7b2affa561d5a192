"""Device-side AOT smoke + stage timing: the never-measured vrf/finish
stage timings first, before anything that can wedge.

Loads the serialized v5e executables from scripts/aot_cache (compiled
devicelessly by aot_precompile.py), runs each on real staged inputs, and
prints per-stage hot rates — flushing after EVERY stage so a run that
wedges still leaves a partial table in its log. Ends with the
composed 5-stage dispatch cross-checked against the native verifier.

Stage order: relayout (cheap, produces the limb-first inputs) -> vrf ->
finish (the never-measured pair) -> ed -> kes -> composed.
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np

import jax

from bench import KES_DEPTH, MAX_BATCH, build_or_load_chain
from ouroboros_consensus_tpu.ops.pk import aot
from ouroboros_consensus_tpu.ops.pk import kernels as K
from ouroboros_consensus_tpu.protocol import batch as pbatch
from ouroboros_consensus_tpu.tools import db_analyser as ana

B = MAX_BATCH


def main():
    dev = jax.devices()[0]
    print(f"device: {dev} platform={dev.platform}", flush=True)
    path, params, lview = build_or_load_chain()

    # real staged batch: first B headers of the bench chain
    imm = ana.open_immutable(path, validate_all=False)
    res = ana.ValidationResult()
    hvs = []
    for hv in ana._stream_views(imm, res):
        hvs.append(hv)
        if len(hvs) >= B:
            break
    pre = pbatch.host_prechecks(params, lview, hvs)
    eta0 = None  # the bench chain's first epoch runs on the neutral nonce
    staged = pbatch.stage(params, lview, eta0, hvs, pre.kes_evolution)
    padded = pbatch.pad_batch_to(staged, pbatch.bucket_size(len(hvs)))
    cols = pbatch.flatten_batch(padded)
    print(f"staged {len(hvs)} headers -> bucket "
          f"{padded.beta.shape[0]}", flush=True)

    def timed(name, fn, *args, n=3):
        t0 = time.monotonic()
        out = fn(*args)
        jax.tree.map(np.asarray, out)
        first = time.monotonic() - t0
        t0 = time.monotonic()
        for _ in range(n):
            out = fn(*args)
        jax.tree.map(np.asarray, out)
        hot = (time.monotonic() - t0) / n
        print(f"AOT {name:8s} first {first:7.2f}s  hot {hot*1e3:8.1f}ms  "
              f"({B/hot:9.0f} lanes/s)", flush=True)
        return out

    def load(name, args):
        sig = aot.sig_of(args)
        ex = aot.load(name, B, KES_DEPTH, K.TILE, sig)
        if ex is None:
            print(f"AOT {name}: NO executable for sig={sig} — "
                  "falling back to jit", flush=True)
            return None
        return ex

    # relayout first: cheap, and the limb-first outputs feed the rest
    rel = load("relayout", cols)
    stages = dict(K.split_stage_fns(KES_DEPTH))
    t0 = time.monotonic()
    limb = (rel or stages["relayout"])(*cols)
    jax.tree.map(np.asarray, limb)
    print(f"relayout ({'AOT' if rel else 'jit'}): "
          f"{time.monotonic()-t0:.2f}s", flush=True)
    import jax.numpy as jnp

    n_live = jax.device_put(K.all_tiles(B))  # every tile: a full window
    ops = dict(K.stage_operands(limb, n_live))

    # vrf FIRST (never measured on hardware)
    vrf = load("vrf", ops["vrf"])
    vrf_out = timed("vrf", vrf or stages["vrf"], *ops["vrf"])

    # finish next: ed/kes verdict inputs are dummies (zeros) — valid for
    # TIMING; correctness is the composed check below
    z = (jnp.zeros((1, B), jnp.int32), jnp.zeros((80, B), jnp.int32))
    fin_args = K.finish_operands(limb, z, z, vrf_out, n_live)
    fin = load("finish", fin_args)
    timed("finish", fin or stages["finish"], *fin_args)

    ed = load("ed", ops["ed"])
    timed("ed", ed or stages["ed"], *ops["ed"])

    kes = load("kes", ops["kes"])
    timed("kes", kes or stages["kes"], *ops["kes"])

    # composed production dispatch (AOT executables via _stage_call) +
    # correctness vs the native verifier on the real (unpadded) lanes
    t0 = time.monotonic()
    out = K.verify_praos_split(*cols, kes_depth=KES_DEPTH)
    v = pbatch._pk_materialize(out, len(hvs))
    wall = time.monotonic() - t0
    print(f"composed split dispatch: {wall:.2f}s "
          f"({len(hvs)/wall:.0f} headers/s incl. host)", flush=True)
    t0 = time.monotonic()
    out = K.verify_praos_split(*cols, kes_depth=KES_DEPTH)
    v = pbatch._pk_materialize(out, len(hvs))
    wall = time.monotonic() - t0
    print(f"composed hot: {wall*1e3:.1f}ms "
          f"({padded.beta.shape[0]/wall:.0f} lanes/s)", flush=True)

    vn = pbatch.run_batch_native(params, lview, eta0, hvs[:64], pre)
    mism = [
        (i, f)
        for i in range(64)
        for f in ("ok_ocert_sig", "ok_kes_sig", "ok_vrf")
        if bool(getattr(v, f)[i]) != bool(getattr(vn, f)[i])
    ]
    print(f"verdict cross-check vs native (64 lanes): "
          f"{'OK' if not mism else mism}", flush=True)
    assert not mism


if __name__ == "__main__":
    main()
