#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the system still starts on the chip.

Drives the repository's main path — `db_analyser.revalidate(backend=
"device")`, the `db-analyser --only-validation` replay — once, in ONE
process, on one TPU chip, through the Pallas per-lane kernels at the
production window width (8192 lanes, KES depth 7, 128-byte VRF proofs),
over a chain of two whole epochs synthesized from genesis, and checks it
against the native C++ verifier. Each phase prints one JSON line; the
last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and the exit code is 0 only when every phase passed ON A TPU. Without a
chip the default invocation fails at the first phase. It sets no
environment variable and starts no process that imports JAX.

    python chip_smoke.py                 # the chip run (needs one TPU chip)
    python chip_smoke.py --cpu-rehearsal # control flow only, tiny, on the CPU
                                         # XLA twin; never prints "ok": true
                                         # and never exits 0

The compile cache goes where JAX_COMPILATION_CACHE_DIR says, else to
`.jax_cache/` in the checkout (ouroboros_consensus_tpu/compile_cache.py).
What the script generates (the chain, both native libraries) lands in
git-ignored places inside the checkout; it reads nothing else that git
would not commit.
"""

from __future__ import annotations

import argparse
import dataclasses
import faulthandler
import json
import os
import sys
import time
from fractions import Fraction

REPO = os.path.dirname(os.path.abspath(__file__))
SCRATCH = os.path.join(REPO, ".chip_smoke")
# the driver allows 1200 s; a hang must leave stacks and a non-zero exit
DEADLINE_S = 1150
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
# the per-lane stage programs of the packed pk dispatch
# (ops/pk/kernels.verify_praos_packed_split)
PK_STAGES = ("unpack_", "ed@", "kes@", "vrf_bc@", "finish@", "reduce@")
# exit code of a --cpu-rehearsal whose control flow passed: NOT a chip run
REHEARSAL_RC = 2


def emit(phase: str, t0: float, **fields) -> None:
    print(json.dumps({"phase": phase,
                      "seconds": round(time.monotonic() - t0, 3), **fields}),
          flush=True)


def check(cond, phase: str, what: str, **detail) -> None:
    """A failed check prints what failed and ends the run non-zero."""
    if cond:
        return
    print(json.dumps({"phase": phase, "failed": what, **detail}), flush=True)
    raise SystemExit(1)


class CompileCounter:
    """JAX's own record of every program it built (a compile or a
    persistent-cache load), by jitted function name."""

    def __init__(self):
        import jax.monitoring as mon

        self.built: list[tuple[str, float]] = []
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.built.append((str(kw.get("fun_name", "?")), duration))

    def _on_event(self, event, **kw):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def mark(self) -> tuple[int, int]:
        return len(self.built), self.cache_hits

    def since(self, mark) -> dict:
        built = self.built[mark[0]:]
        by_name: dict[str, list] = {}
        for name, d in built:
            row = by_name.setdefault(name, [0, 0.0])
            row[0] += 1
            row[1] = round(row[1] + d, 1)
        return {
            "programs_built": len(built),
            "cache_hits": self.cache_hits - mark[1],
            "built_s": round(sum(d for _, d in built), 1),
            # {jitted function: [programs, backend compile/load seconds]}
            "by_name": dict(sorted(by_name.items(),
                                   key=lambda kv: -kv[1][1])[:16]),
        }


def _peak_bytes() -> int | None:
    """Peak device memory so far, where the backend reports it."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _cache_entries(path: str) -> int:
    try:
        return sum(1 for e in os.scandir(path) if e.is_file())
    except FileNotFoundError:
        return 0


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_device(rehearsal: bool):
    t0 = time.monotonic()
    import jax

    devs = jax.devices()
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs)}
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — a version string, not a phase
        libtpu = None
    if not rehearsal:
        check(d0.platform == "tpu", "device",
              "no TPU: jax.devices()[0].platform is not 'tpu'", **device)
    from ouroboros_consensus_tpu.ops.pk import hashes, kernels
    from ouroboros_consensus_tpu.protocol import batch as pbatch

    seams = {"pk_interpret": kernels._interpret(),
             "hashes_unrolled": hashes._unrolled(),
             "impl": pbatch._impl(),
             "agg_default": pbatch._agg_enabled()}
    if not rehearsal:
        # each seam quietly takes its CPU branch when it finds no chip
        check(seams["pk_interpret"] is False, "device",
              "ops.pk.kernels would run Pallas in interpret mode", **seams)
        check(seams["hashes_unrolled"] is True, "device",
              "ops.pk.hashes would run the rolled XLA twins", **seams)
        check(seams["impl"] == "pk", "device",
              "protocol.batch would dispatch the XLA twin", **seams)
        check(seams["agg_default"] is False, "device",
              "the default bc path is the aggregate monolith", **seams)
    emit("device", t0, **device, jax=jax.__version__, libtpu=libtpu,
         platform_version=str(d0.client.platform_version)[:80], **seams,
         oct_env=sorted(k for k in os.environ if k.startswith("OCT_")))
    return device


def phase_native():
    """Both native libraries must be REAL: a missing compiler otherwise
    turns into pure-Python signing and scanning without a word, ~50x
    slower, and would be read as a slow chip."""
    t0 = time.monotonic()
    from ouroboros_consensus_tpu import native_loader as nl

    pairs = {"headerscan": (nl._SRC, nl._SO), "hostcrypto": (nl._CSRC, nl._CSO)}
    had = {k: os.path.exists(so) and
           os.path.getmtime(so) >= os.path.getmtime(src)
           for k, (src, so) in pairs.items()}
    check(nl.load() is not None, "native",
          "native/headerscan.cpp did not build or load (no g++?)")
    check(nl.load_crypto() is not None, "native",
          "native/hostcrypto.cpp did not build or load (no g++?)")
    for src, so in pairs.values():
        check(os.path.getmtime(so) >= os.path.getmtime(src), "native",
              f"{os.path.basename(so)} is older than its source")
    emit("native", t0, built_this_run={k: not v for k, v in had.items()})


def smoke_params(kes_depth: int):
    """bench.py's `bench_params()`: mainnet-shaped ratios — f = 1/2,
    43,200-slot epochs, k = 2160, 3,600-slot KES periods."""
    from ouroboros_consensus_tpu.protocol import praos

    return praos.PraosParams(
        slots_per_kes_period=3600, max_kes_evolutions=62,
        security_param=2160, active_slot_coeff=Fraction(1, 2),
        epoch_length=43200, kes_depth=kes_depth,
    )


def phase_chain(a, cc):
    """Synthesize the chain (deterministic: every key derives from the
    pool's index) under a fixed git-ignored directory, reused when
    complete. Width is the production one; SCALE is cut to two whole
    epochs for the call's time."""
    t0 = time.monotonic()
    mark = cc.mark()
    from ouroboros_consensus_tpu.tools import db_synthesizer as synth

    params = smoke_params(a.kes_depth)
    pools, lview = synth.make_credentials(1, kes_depth=a.kes_depth)
    limit = (synth.ForgeLimit(blocks=a.blocks) if a.blocks
             else synth.ForgeLimit(epochs=a.epochs))
    name = (f"chain_b{a.blocks}" if a.blocks else f"chain_e{a.epochs}")
    path = os.path.join(SCRATCH, f"{name}_d{a.kes_depth}")
    marker = os.path.join(path, "COMPLETE")
    reused = os.path.exists(marker)
    if not reused:
        import shutil

        shutil.rmtree(path, ignore_errors=True)
        os.makedirs(path, exist_ok=True)
        # vrf_backend="host": forging must not touch the device
        res = synth.synthesize(path, params, pools, lview, limit,
                               vrf_backend="host")
        with open(marker, "w") as f:
            f.write(str(res.n_blocks))
    with open(marker) as f:
        n_blocks = int(f.read())
    emit("chain", t0, path=os.path.relpath(path, REPO), reused=reused,
         headers=n_blocks, pools=1, kes_depth=a.kes_depth,
         vrf_proof_bytes=128, max_batch=a.max_batch,
         device_programs_built=cc.since(mark)["programs_built"],
         scale_cut=(f"{a.blocks} blocks" if a.blocks
                    else f"{a.epochs} whole epochs from genesis "
                         "(BASELINE.json replays 1M headers)"))
    return path, params, pools, lview


def _state_doc(st) -> dict:
    return {
        "last_slot": st.last_slot,
        "counters": {k.hex(): v for k, v in st.ocert_counters.items()},
        **{f: (getattr(st, f) or b"").hex()
           for f in ("evolving_nonce", "candidate_nonce", "epoch_nonce",
                     "lab_nonce", "last_epoch_block_nonce")},
    }


def phase_reference(a, path, params, lview):
    t0 = time.monotonic()
    from ouroboros_consensus_tpu.tools import db_analyser as ana

    ref = ana.revalidate(path, params, lview, backend="native",
                         validate_all="stream", max_batch=a.max_batch,
                         max_headers=a.max_headers)
    check(ref.error is None and ref.n_valid == ref.n_blocks > 0,
          "reference", "the native verifier rejects the synthesized chain",
          n_valid=ref.n_valid, n_blocks=ref.n_blocks, error=repr(ref.error))
    emit("reference", t0, backend="native", n_valid=ref.n_valid,
         error=None, final_state=_state_doc(ref.final_state))
    return ref


def _replay_once(a, path, params, lview, cc):
    from ouroboros_consensus_tpu.tools import db_analyser as ana

    mark = cc.mark()
    t0 = time.monotonic()
    r = ana.revalidate(path, params, lview, backend="device",
                       validate_all="stream", max_batch=a.max_batch,
                       max_headers=a.max_headers, collect_phases=True)
    return r, time.monotonic() - t0, cc.since(mark)


def phase_replay(a, path, params, lview, ref, cc, rec, cache_dir,
                 rehearsal: bool):
    """Two device replays: the first pays every trace, lowering and
    compile (set-up); the second is warm. Both must equal the native
    reference, and nothing may have hidden the chip."""
    from ouroboros_consensus_tpu.obs.warmup import WARMUP
    from ouroboros_consensus_tpu.utils.trace import RecoveryEvent, WindowStaged

    t0 = time.monotonic()
    entries_before = _cache_entries(cache_dir)
    results = []
    n_stages = []  # first-execute notes in the warmup report, per pass
    for which in ("setup", "warm"):
        n_ev = len(rec.events)
        r, wall, built = _replay_once(a, path, params, lview, cc)
        evs = [e for _, e in rec.events[n_ev:]]
        results.append((which, r, wall, built, evs))
        n_stages.append(len(WARMUP.report()["stages"]))
        same = (r.error is None and r.n_valid == ref.n_valid
                and r.final_state == ref.final_state)
        check(same, "replay",
              f"{which} device replay disagrees with the native reference",
              n_valid=[r.n_valid, ref.n_valid], error=repr(r.error),
              device_state=_state_doc(r.final_state),
              native_state=_state_doc(ref.final_state))
    (_, r1, wall1, built1, evs1), (_, r2, wall2, built2, evs2) = results
    staged = [e for e in evs1 + evs2 if isinstance(e, WindowStaged)]
    lane_counts = sorted({e.lanes_padded for e in staged})
    report = WARMUP.report()
    emit("replay", t0, n_valid=r2.n_valid, headers=r2.n_blocks,
         setup_pass_s=round(wall1, 3), setup_pass_built=built1,
         warm_pass_s=round(wall2, 3),
         warm_headers_per_s=round(r2.n_valid / wall2, 1),
         warm_phases_s={k: round(v, 3) for k, v in sorted(r2.phases.items())},
         n_windows=r2.n_windows, packed_windows=r2.packed_windows,
         h2d_bytes=r2.h2d_bytes, d2h_bytes=r2.d2h_bytes,
         lane_counts=lane_counts,
         stage_setup_s={k: [v["wall_s"], v["via"]]
                        for k, v in report["stages"].items()},
         cache_dir=cache_dir, cache_entries_before=entries_before,
         cache_entries_after=_cache_entries(cache_dir),
         device_peak_bytes=_peak_bytes(),
         note="one smoke reading, not a benchmark")

    # -- nothing hid the chip ------------------------------------------------
    t1 = time.monotonic()
    ph = "nothing-hid-the-chip"
    from ouroboros_consensus_tpu.protocol import batch as pbatch

    impl = pbatch._impl()
    recov = [dataclasses.asdict(e) for e in evs1 + evs2
             if isinstance(e, RecoveryEvent)]
    check(not recov and not report["recovery"], ph,
          "the recovery ladder fired: a fallback path produced verdicts",
          events=recov or report["recovery"])
    bad = [dataclasses.asdict(e) for e in staged
           if e.outcome != "packed" or e.gate is not None]
    check(not bad, ph, "a window left the packed per-lane path", windows=bad[:8])
    for r in (r1, r2):
        check(r.n_windows > 0 and r.packed_windows == r.n_windows, ph,
              "a window failed the packing check and staged the old way",
              n_windows=r.n_windows, packed_windows=r.packed_windows)
    check(len(lane_counts) == 1
          and lane_counts[0] == pbatch.bucket_size(a.max_batch), ph,
          "windows were dispatched at more than one lane count",
          lane_counts=lane_counts)
    aot_bad = [e for e in report["aot_events"]
               if e["outcome"] in ("run_failed", "rejected", "failed")]
    check(not aot_bad, ph, "a stored executable died and gave way to the jit",
          events=aot_bad)
    if not rehearsal:
        check(impl == "pk", ph, "the implementation is not pk", impl=impl)
        stray = {k: v for k, v in report["stages"].items()
                 if not k.startswith(PK_STAGES) or v["via"] not in ("jit", "aot")}
        check(not stray, ph,
              "a first execute ran outside the per-lane pk stages",
              stages=stray)
        check(all(any(k.startswith(p) for k in report["stages"])
                  for p in PK_STAGES), ph,
              "a per-lane pk stage never ran", stages=sorted(report["stages"]))
    check(built2["programs_built"] == 0 and n_stages[0] == n_stages[1], ph,
          "the warm pass set a program up (compile, cache load or a new "
          "first-execute note)", first_execute_notes=n_stages, **built2)
    emit(ph, t1, impl=impl, recovery_events=0,
         windows=len(staged), all_packed=True, lane_counts=lane_counts,
         stages_set_up=n_stages[1], warm_pass_programs_built=0)


def _flip(b: bytes, i: int) -> bytes:
    return b[:i] + bytes([b[i] ^ 1]) + b[i + 1:]


def phase_wrong_header(a, path, params, pools, lview, seed: int, cc):
    """A verifier that answers "valid" to everything passes every phase
    above. Take one production-width window of the chain's own header
    views and corrupt ONE lane in each of three copies — the OCert
    signature, the KES signature, the VRF proof (body re-signed, so the
    proof is the first thing wrong) — and hold
    `validate_chain(backend="device")` to the sequential host fold: same
    first-failure index, same error repr, same state. One window per
    corruption; each reports its first (only) failure."""
    t0 = time.monotonic()
    import random

    from ouroboros_consensus_tpu.ops.host import kes as host_kes
    from ouroboros_consensus_tpu.protocol import batch as pbatch
    from ouroboros_consensus_tpu.protocol import praos
    from ouroboros_consensus_tpu.protocol.views import ViewColumns
    from ouroboros_consensus_tpu.tools import db_analyser as ana

    ph = "wrong-header"
    # the first uniform-width segment of the replay's own window stream
    # that fills a whole window; the host fold carries the state up to it
    imm = ana.open_immutable(path, validate_all=False)
    st = praos.PraosState()
    window = None
    for seg in ana._epoch_window_segments(
            params, ana._stream_windows(imm, ana.ValidationResult())):
        if len(seg) >= a.max_batch:
            window = seg[: a.max_batch]
            break
        for hv in (seg.views() if isinstance(seg, ViewColumns) else seg):
            st = praos.update(params, hv, hv.slot,
                              praos.tick(params, lview, hv.slot, st))
    check(window is not None, ph, "no segment fills a whole window")
    views = window.views() if isinstance(window, ViewColumns) else list(window)
    pool = pools[0]
    rng = random.Random(seed)
    lanes = rng.sample(range(len(views) // 2, len(views)), 3)

    def bad_ocert(hv):
        sigma = _flip(hv.ocert.sigma, 32)
        o = hv.signed_bytes.index(hv.ocert.sigma)
        body = hv.signed_bytes[:o] + sigma + hv.signed_bytes[o + 64:]
        return dataclasses.replace(
            hv, ocert=dataclasses.replace(hv.ocert, sigma=sigma),
            signed_bytes=body)

    def bad_kes(hv):
        return dataclasses.replace(hv, kes_sig=_flip(hv.kes_sig, 32))

    def bad_vrf(hv):
        proof = _flip(hv.vrf_proof, len(hv.vrf_proof) - 32)
        o = hv.signed_bytes.index(hv.vrf_proof)
        body = (hv.signed_bytes[:o] + proof
                + hv.signed_bytes[o + len(proof):])
        t = params.kes_period_of(hv.slot) - hv.ocert.kes_period
        sig = host_kes.sign(pool.kes_seed, pool.kes_depth, t, body)
        return dataclasses.replace(hv, vrf_proof=proof, signed_bytes=body,
                                   kes_sig=sig)

    mark = cc.mark()
    cases = []
    for (what, corrupt), lane in zip(
            (("ocert-signature", bad_ocert), ("kes-signature", bad_kes),
             ("vrf-proof", bad_vrf)), lanes):
        hvs = list(views)
        hvs[lane] = corrupt(hvs[lane])
        # the host reference fold: sequential tick + update
        hst, hn, herr = st, 0, None
        for hv in hvs:
            try:
                hst = praos.update(params, hv, hv.slot,
                                   praos.tick(params, lview, hv.slot, hst))
            except praos.PraosValidationError as e:
                herr = e
                break
            hn += 1
        cols = ViewColumns.from_views(hvs)
        res = pbatch.validate_chain(
            params, lambda _e: lview, st, cols if cols is not None else hvs,
            max_batch=a.max_batch, backend="device")
        case = {"corrupted": what, "lane": lane,
                "host": [hn, repr(herr)],
                "device": [res.n_valid, repr(res.error)]}
        check(herr is not None and hn == lane, ph,
              "the host fold did not fail at the corrupted lane", **case)
        check(res.n_valid == hn and repr(res.error) == repr(herr)
              and res.state == hst, ph,
              "device and host fold disagree on the corrupted window", **case)
        cases.append(case)
    kinds = {c["host"][1].split("(")[0] for c in cases}
    check(len(kinds) == 3, ph, "the three corruptions did not give three "
          "distinct errors", cases=cases)
    # the ladder would re-run a failed window on the twin or the host
    # fold and the answers would still match
    from ouroboros_consensus_tpu.obs.warmup import WARMUP

    check(not WARMUP.report()["recovery"], ph,
          "the recovery ladder fired on a corrupted window",
          events=WARMUP.report()["recovery"])
    emit(ph, t0, window_headers=len(views), seed=seed, cases=cases,
         built=cc.since(mark))


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="picks the lanes the last phase corrupts")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="control flow only: a dozen headers at 8 lanes on "
                         "the CPU XLA twin; never prints ok:true, exits "
                         f"{REHEARSAL_RC} when it passes")
    a = ap.parse_args(argv)
    a.epochs, a.blocks, a.kes_depth = 2, 0, 7
    a.max_batch, a.max_headers = 8192, None
    if a.cpu_rehearsal:
        # the twin compiles the whole fused program per header layout
        # and lane count: one lane count, two layouts
        a.blocks, a.max_batch, a.max_headers = 300, 8, 12
    faulthandler.enable()
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
    sys.path.insert(0, REPO)

    device = phase_device(a.cpu_rehearsal)
    from ouroboros_consensus_tpu import compile_cache, obs

    cache_dir = compile_cache.configure()  # before the first trace
    cc = CompileCounter()
    phase_native()
    path, params, pools, lview = phase_chain(a, cc)
    ref = phase_reference(a, path, params, lview)
    # the flight recorder rides the replays as bench.py's device child
    # installs it: per-window spans, recovery events, gate attribution
    rec = obs.install()
    try:
        phase_replay(a, path, params, lview, ref, cc, rec, cache_dir,
                     a.cpu_rehearsal)
        phase_wrong_header(a, path, params, pools, lview, a.seed, cc)
    finally:
        obs.uninstall()
    faulthandler.cancel_dump_traceback_later()
    if a.cpu_rehearsal:
        print(json.dumps({"ok": False, "rehearsal": "cpu control flow passed; "
                          "not a chip run", "device": device}), flush=True)
        return REHEARSAL_RC
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
