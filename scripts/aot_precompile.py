"""Deviceless AOT artifact BUILDER for the v5e stage programs.

Compiles every per-stage jit of the production pk dispatch
(ops/pk/kernels.verify_praos_split) against a v5e TopologyDescription
using libtpu's compile-only client — no device — and saves the PJRT
executables into the build-pinned artifact store (ops/pk/aot.py:
scripts/aot_cache/<build-slug>/ + manifest).  A run on the chip
(OCT_PK_AOT=1) then loads instead of compiling, where the runtime
accepts a deviceless executable.

The store is keyed by RUNTIME BUILD: export
``OCT_AOT_BUILD_ID='<platform_version>'`` (take it from a previous
round's banked ``build_id``) so the artifacts are filed under the
runtime that will load them — without it they land under this box's
own build and the TPU child skips them as ``wrong_build`` (a zero-cost
skip, not a ~15 s rejected deserialize; the child's write-back then
populates the store itself).

Shape discovery replays the EXACT batching the bench replay performs
(epoch segments -> max_batch slices -> power-of-two padding) over the
cached bench chain, so every executable matches a real batch signature
— including the per-batch KES hash-block count, which tracks the
longest signed header bytes in each batch.

Usage: python scripts/aot_precompile.py [--check]
  --check: compile nothing — verify every manifest entry of the
           CURRENT build's store deserializes under this runtime
           (exit 1 on any problem).
Env: BENCH_HEADERS/BENCH_KES_DEPTH/BENCH_MAX_BATCH as bench.py;
     OCT_AOT_BUILD_ID pins artifact provenance (see above).
"""

import functools
import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["OCT_PK_INTERPRET"] = "0"  # real Mosaic lowering from CPU
os.environ.setdefault("OCT_PK_HASH_IMPL", "unrolled")  # TPU hash path

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402

from bench import KES_DEPTH, MAX_BATCH, build_or_load_chain  # noqa: E402
from ouroboros_consensus_tpu.ops.pk import aot  # noqa: E402
from ouroboros_consensus_tpu.ops.pk import kernels as K  # noqa: E402
from ouroboros_consensus_tpu.protocol import batch as pbatch  # noqa: E402
from ouroboros_consensus_tpu.tools import db_analyser as ana  # noqa: E402

TOPOLOGY = os.environ.get("OCT_AOT_TOPOLOGY", "v5e:2x2")
# wall budget for THIS precompile run (seconds; 0 = unlimited). Stages
# whose octwall-predicted compile wall cannot fit the remaining budget
# are skipped (recorded in the manifest) instead of blowing it.
AOT_BUDGET = float(os.environ.get("OCT_AOT_BUDGET", "0") or 0)
_T0 = time.time()


def _predicted_wall(stage: str) -> float | None:
    """octwall pinned prediction for a stage's graph twin (dict lookup,
    no tracing). The model is calibrated on first-execute walls, which
    bound the lower+compile bracket here from above — conservative in
    the safe direction for the budget skip."""
    from ouroboros_consensus_tpu.analysis import costmodel

    g = costmodel.stage_graph(stage)
    return costmodel.predicted_wall(g) if g else None


def discover_batches(path, params):
    """Yield (bucket, representative HeaderView with the longest signed
    bytes) per distinct (bucket, max-signed-len) over the replay's exact
    batch slicing."""
    imm = ana.open_immutable(path, validate_all=False)
    res = ana.ValidationResult()
    seen = {}
    for seg in ana._epoch_segments(params, ana._stream_views(imm, res)):
        for i in range(0, len(seg), MAX_BATCH):
            sub = seg[i : i + MAX_BATCH]
            bucket = pbatch.bucket_size(len(sub))
            rep = max(sub, key=lambda hv: len(hv.signed_bytes))
            key = (bucket, len(rep.signed_bytes), len(rep.ocert.signable()))
            if key not in seen:
                seen[key] = (bucket, rep)
    return list(seen.values())


def staged_sds(params, lview, bucket, rep, sharding):
    """ShapeDtypeStructs for the relayout stage: stage a tiny batch
    around the representative header, pad to the bucket — per-column
    shapes depend only on (bucket, longest message), so these equal the
    real batch's."""
    hvs = [rep] * 8
    pre = pbatch.host_prechecks(params, lview, hvs)
    staged = pbatch.stage(params, lview, b"\x00" * 32, hvs, pre.kes_evolution)
    padded = pbatch.pad_batch_to(staged, bucket)
    cols = pbatch.flatten_batch(padded)
    return [
        jax.ShapeDtypeStruct(np.asarray(c).shape, np.asarray(c).dtype,
                             sharding=sharding)
        for c in cols
    ]


def packed_sds(params, lview, bucket, rep, sharding):
    """(layout, unpack-arg SDS list, reduce-arg SDS list) for the PACKED
    dispatch (the production default), or None when the representative
    header does not qualify for packed staging."""
    hvs = [rep] * 8
    res = pbatch.stage_packed(params, lview, b"\x00" * 32, hvs)
    if res is None:
        return None
    layout, parr = res
    parr = pbatch.pad_packed_to(parr, bucket)

    def sds(a):
        a = np.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)

    unpack_in = [sds(c) for c in parr]  # body .. nonce
    i32 = np.int32
    red_in = [
        jax.ShapeDtypeStruct((5, bucket), i32, sharding=sharding),  # flags
        jax.ShapeDtypeStruct((32, bucket), i32, sharding=sharding),  # eta
    ]
    return layout, unpack_in, red_in


def compile_stage(name, fn, in_sds, b, manifest, kes_depth=KES_DEPTH,
                  tile=K.TILE, wall_label=None):
    """Compile-and-save one stage; returns True iff a FRESH executable
    was written (False = an on-disk entry was reused). The unified
    aggregate programs pass kes_depth=0, tile=0 — the store key
    protocol/batch._warm_timed loads them back under (the layout's
    depth is baked into the program, not the key)."""
    sig = aot.sig_of(in_sds)
    path = aot.stage_path(name, b, kes_depth, tile, sig)
    key = aot.entry_key(name, b, kes_depth, tile, sig)
    # cached means artifact AND manifest row: a crash between the
    # artifact write and the manifest update (or a corrupt manifest)
    # orphans the file — load() gates on the manifest, so an orphan is
    # permanently "missing" unless the builder heals the row here
    if os.path.exists(path) and key in aot.read_manifest():
        print(f"  {name:8s} sig={sig} — cached", flush=True)
        return False
    predicted = _predicted_wall(wall_label or name)
    if AOT_BUDGET and predicted is not None:
        remaining = AOT_BUDGET - (time.time() - _T0)
        if predicted > remaining:
            print(f"  {name:8s} sig={sig} — SKIPPED: predicted "
                  f"{predicted:.0f}s compile > {remaining:.0f}s of "
                  "OCT_AOT_BUDGET left", flush=True)
            manifest.append({
                "stage": name, "b": b, "sig": sig, "skipped": True,
                "predicted_s": round(predicted, 1),
                "budget_left_s": round(remaining, 1),
            })
            return False
    t0 = time.time()
    lowered = jax.jit(fn).trace(*in_sds).lower(lowering_platforms=("tpu",))
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    meta = {
        "stage": name, "b": b, "kes_depth": kes_depth, "tile": tile,
        "sig": sig, "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1), "topology": TOPOLOGY,
        "jax": jax.__version__,
        "hash_impl": os.environ.get("OCT_PK_HASH_IMPL", ""),
    }
    p = aot.save(name, b, kes_depth, tile, sig, compiled, meta)
    meta["bytes"] = os.path.getsize(p)
    if predicted is not None:
        meta["predicted_s"] = round(predicted, 1)
    manifest.append(meta)
    pred_note = (f" (octwall predicted {predicted:.0f}s)"
                 if predicted is not None else "")
    print(f"  {name:8s} sig={sig} lower {t_lower:6.1f}s compile "
          f"{t_compile:6.1f}s -> {meta['bytes']/1e6:.1f} MB{pred_note}",
          flush=True)
    return True


def check() -> int:
    """--check: every manifest entry of the current build's store must
    deserialize under THIS runtime (the store's loadability contract —
    run it on the target box before a bench session)."""
    ok, problems = aot.check_store()
    print(f"store {aot.store_dir()} (build {aot.build_id()!r}): "
          f"{ok} entr(y/ies) deserialize clean")
    for p in problems:
        print(f"  PROBLEM: {p}")
    return 1 if problems else 0


def main():
    t0 = time.time()
    path, params, lview = build_or_load_chain()
    topo = topologies.get_topology_desc(TOPOLOGY, "tpu")
    shard = jax.sharding.SingleDeviceSharding(topo.devices[0])
    combos = discover_batches(path, params)
    print(f"discovered {len(combos)} distinct batch signature(s) in "
          f"{time.time()-t0:.1f}s: "
          f"{[(b, len(r.signed_bytes)) for b, r in combos]}", flush=True)
    print(f"store: {aot.store_dir()} (build {aot.build_id()!r})", flush=True)
    if not os.environ.get("OCT_AOT_BUILD_ID"):
        print("# note: OCT_AOT_BUILD_ID unset — artifacts are pinned to "
              "THIS box's runtime; a TPU child on another build will "
              "skip them as wrong_build", flush=True)

    # compile-run log (predicted vs actual walls per stage) beside the
    # store's own provenance manifest
    manifest = []
    manifest_path = os.path.join(aot.aot_dir(), "COMPILE_LOG.json")
    if os.path.exists(manifest_path):
        with open(manifest_path) as f:
            manifest = json.load(f)
    fresh: list = []
    for bucket, rep in combos:
        print(f"batch bucket={bucket} kes_msg={len(rep.signed_bytes)}B",
              flush=True)
        rel_sds = staged_sds(params, lview, bucket, rep, shard)
        # batch-compatible chains stage 22 columns (announced u, v in
        # place of the 16-byte challenge) and dispatch the vrf_bc stage
        bc = len(rel_sds) == 22
        relayout_name = "relayout_bc" if bc else "relayout"
        relayout_fn = (K.staged_to_limb_first_bc if bc
                       else K.staged_to_limb_first)
        limb = jax.eval_shape(relayout_fn, *rel_sds)
        limb = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=shard)
                for s in limb]
        # the stages' operands as the dispatch cuts them, the live-tile
        # count ([1] int32, `kernels._call`) last: `aot.sig_of` of these
        # is how a run finds the program again
        _shard = lambda s: jax.ShapeDtypeStruct(  # noqa: E731
            s.shape, s.dtype, sharding=shard)
        n_live = jax.ShapeDtypeStruct((1,), np.int32, sharding=shard)
        (_, ed_in), (_, kes_in), (vrf_name, vrf_in) = K.stage_operands(
            limb, n_live)
        kes_fn = K.kes_points_at(KES_DEPTH)
        vrf_fn = K.vrf_points_bc if bc else K.vrf_points
        outs = [
            [_shard(o) for o in jax.eval_shape(fn, *ops)]
            for fn, ops in ((K.ed_points, ed_in), (kes_fn, kes_in),
                            (vrf_fn, vrf_in))
        ]
        fin_in = K.finish_operands(limb, *outs, n_live)
        # vrf/finish first: the stages never yet timed on hardware
        fresh.append(compile_stage(vrf_name, vrf_fn, vrf_in, bucket, manifest))
        fresh.append(compile_stage("finish", K.finish, fin_in, bucket, manifest))
        fresh.append(compile_stage("ed", K.ed_points, ed_in, bucket, manifest))
        fresh.append(compile_stage("kes", kes_fn, kes_in, bucket, manifest))
        # packed dispatch stages (the production default): unpack
        # replaces relayout on the packed wire format; reduce packs the
        # verdict bits and casts the eta column to uint8 (the host folds
        # the nonces). The crypto stages above are SHARED between the
        # packed and staged paths (one program form: `kernels._call`).
        pk = packed_sds(params, lview, bucket, rep, shard)
        if pk is not None:
            layout, unpack_in, red_in = pk
            fresh.append(compile_stage(K.packed_unpack_name(layout),
                                       K._mk_packed_unpack(layout),
                                       unpack_in, bucket, manifest))
            fresh.append(compile_stage("reduce", K.reduce_fn,
                                       red_in, bucket, manifest))
            # UNIFIED aggregated window programs (round 15): the
            # one-RLC monolith ("all", the production default) and the
            # OCT_RLC_ALL=0 kill-switch ("vrf"), compiled under the
            # EXACT store rows protocol/batch._warm_timed loads —
            # name = _store_name(label), b = padded lanes,
            # kes_depth = tile = 0, sig over the runtime call args
            # (the unpack columns)
            if layout.vrf_proof_len == 128:
                for mode in ("all", "vrf"):
                    label = (f"{pbatch._AGG_STAGE_FAMILY[mode]}:"
                             f"{layout.body_len}b")
                    fresh.append(compile_stage(
                        pbatch._store_name(label),
                        pbatch._packed_agg_fn(layout, mode),
                        unpack_in, bucket, manifest,
                        kes_depth=0, tile=0, wall_label=label,
                    ))
        # generic-fallback relayout (mixed-layout windows)
        fresh.append(compile_stage(relayout_name, relayout_fn, rel_sds, bucket,
                      manifest))
        # tmp -> fsync -> rename: the compile log lives inside the AOT
        # store dir, so it rides the store's durability protocol
        tmp = manifest_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(manifest, f, indent=1)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, manifest_path)
    # forge pipeline programs (PR 18): the election sweep at its
    # production bucket and the OCert batch signer at its padding
    # quantum, compiled under the EXACT store rows protocol/forge's
    # _jit_of -> _warm_timed loads (kes_depth = tile = 0, b = the
    # dispatch lane count, sig over the runtime call columns). The
    # signable length is derived from a zero proto-OCert so the row's
    # KES hash-block count tracks the real message, not a guess.
    from ouroboros_consensus_tpu.ops import ed25519_batch  # noqa: E402
    from ouroboros_consensus_tpu.protocol import forge as pforge  # noqa: E402
    from ouroboros_consensus_tpu.protocol.views import OCert  # noqa: E402

    fb = pforge.FORGE_BUCKET
    u8 = lambda *s: jax.ShapeDtypeStruct(s, np.uint8, sharding=shard)  # noqa: E731
    sweep_in = [
        u8(fb, 32), u8(fb, 32), u8(fb, 32),
        jax.ShapeDtypeStruct((fb,), np.int32, sharding=shard),
        u8(32), u8(fb, 32), u8(fb, 32),
    ]
    fresh.append(compile_stage("forge_sweep", pforge._SWEEP_FN, sweep_in,
                               fb, manifest, kes_depth=0, tile=0))
    # neutral-nonce variant (epoch 0 of a fresh chain): same family,
    # statically nonce-free — its own store row, no [32] nonce arg
    sweep_n_in = sweep_in[:4] + sweep_in[5:]
    fresh.append(compile_stage("forge_sweep-neutral",
                               pforge._make_sweep_neutral(pforge._SWEEP_FN),
                               sweep_n_in, fb, manifest, kes_depth=0,
                               tile=0))
    sb = pforge._SIGN_BUCKET
    msg = OCert(b"\0" * 32, 0, 0, b"").signable()
    sign_cols = ed25519_batch.stage_sign_np([b"\0" * 32] * sb, [msg] * sb)
    sign_in = [jax.ShapeDtypeStruct(np.asarray(c).shape,
                                    np.asarray(c).dtype, sharding=shard)
               for c in sign_cols]
    fresh.append(compile_stage("forge_sign", pforge._SIGN_FN, sign_in,
                               sb, manifest, kes_depth=0, tile=0))
    tmp = manifest_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(manifest, f, indent=1)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, manifest_path)
    # clear a persisted per-build rejection ONLY when this run wrote
    # EVERY entry itself: a cached early-return may be reusing exactly
    # the stale executables the REJECTED marker records (fresh saves
    # post-date the marker anyway — ops/pk/aot.load trusts those — but
    # an all-fresh store deserves a clean slate)
    if fresh and all(fresh):
        aot.clear_rejection()
    print(f"done in {time.time()-t0:.0f}s; store manifest: "
          f"{aot.manifest_path()}; compile log: {manifest_path}",
          flush=True)


if __name__ == "__main__":
    if "--check" in sys.argv[1:]:
        sys.exit(check())
    main()
