"""Profiled end-to-end device replay: where does the wall time go?

Runs the SAME replay as bench.py's device child but with the
protocol/batch Enclose brackets (stage / dispatch / materialize /
epilogue) collected, plus disk-stream and segmentation timings, and
prints a budget table. This is the round-5 item-3 instrument: the gap
between the composed kernel rate (~11.6k lanes/s hot) and the
end-to-end rate (5.3k headers/s, BENCH r5 first run) has to be
attributed before it can be closed.

`--host` runs the HOST-PIPELINE-ONLY replay instead: stream the chain,
segment it, run host_prechecks + packed staging per window — no device
dispatch at all. This measures the host pipeline CEILING (µs/header of
view-stream + prechecks + stage; its reciprocal is the best rate any
device can be fed at) and is CPU-verifiable on a box with no
accelerator. A/B the columnar window pipeline against the per-object
one with OCT_COLUMNAR=0 (round-8 acceptance metric); OCT_TRACE=1
installs the obs flight recorder — per-window spans only, so the
ceiling must stay within 2% of OCT_TRACE=0 (round-9 acceptance).

`--trace-out=PATH` (device replay) writes the flight recorder's event
stream as a Chrome trace-event JSON after the hot replay — load it at
ui.perfetto.dev or chrome://tracing — and prints the
dispatch->materialize latency p50/p99.

`--overlap-ab` runs the STUBBED-CRYPTO DEVICE TWIN A/B for the
round-10 threaded staging pipeline: the same end-to-end replay with
crypto hash-stubbed (testing/stubs — compiles in seconds on XLA:CPU)
and a simulated per-window device latency (`OCT_TWIN_DEVICE_MS`,
default 40 — a sleep in materialize, GIL-released exactly like a real
device wait), once with `OCT_STAGE_THREAD=0` (inline staging) and once
with `=1` (producer thread + segment prefetch). On a staging-bound
profile with >= 2 host cores the threaded run must be >= 1.3x the
inline run (the acceptance gate; exit 1 below it); the
`oct_window_*_seconds` histogram p50s are printed as the overlap
evidence (staging wall unchanged per window while end-to-end shrinks).
On a SINGLE-core host the gate is advisory only: the producer/prefetch
threads and the main loop serialize on the one core and the GIL, the
round-9 materialize worker already hides the device sleeps, and the
measured A/B lands at parity (0.97-1.24x across profiles on this box)
— the harness reports the ratio and the per-phase evidence either way
so a TPU session can bank the real number.

Usage:  python scripts/profile_replay.py [--host] [--overlap-ab]
        [--trace-out=f.json] [n_headers]   (default 100000)
"""

import os
import sys
import time
from collections import defaultdict

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax

from ouroboros_consensus_tpu import compile_cache

compile_cache.configure()

ARGS = [a for a in sys.argv[1:] if not a.startswith("--")]
HOST_ONLY = "--host" in sys.argv[1:]
OVERLAP_AB = "--overlap-ab" in sys.argv[1:]
TRACE_OUT = next(
    (a.split("=", 1)[1] for a in sys.argv[1:]
     if a.startswith("--trace-out=")), None,
)
N = int(ARGS[0]) if ARGS else 100_000


def host_ceiling():
    """Host-pipeline-only replay: window stream -> epoch segmentation ->
    host_prechecks -> packed staging (+ bucket pad), timed per phase.
    No verdicts are produced (no device); the epoch nonce fed to staging
    comes from a genesis tick — staging cost does not depend on the
    nonce VALUE, only its presence, so the measured work is identical
    to the real replay's stage bracket."""
    os.environ.setdefault("BENCH_HEADERS", str(N))
    import numpy as np

    import bench
    from ouroboros_consensus_tpu import obs
    from ouroboros_consensus_tpu.protocol import batch as pbatch
    from ouroboros_consensus_tpu.protocol import praos
    from ouroboros_consensus_tpu.protocol.views import ViewColumns
    from ouroboros_consensus_tpu.storage import sidecar as sidecar_mod
    from ouroboros_consensus_tpu.tools import db_analyser as ana
    from ouroboros_consensus_tpu.utils.trace import EncloseEvent

    path, params, lview = bench.build_or_load_chain()
    columnar = ana._columnar_enabled()
    mode = "columnar (ViewColumns)" if columnar else "per-object (HeaderView)"
    # the round-17 mmap-vs-parse wall split rides the nested
    # "stream-mmap"/"stream-parse" Enclose brackets — per-CHUNK events
    # (a handful per run), collected by a local tracer the recorder
    # chains behind exactly as in main()
    split = defaultdict(float)

    def _split_tracer(ev):
        if isinstance(ev, EncloseEvent) and ev.edge == "end" \
                and ev.label in ("stream-mmap", "stream-parse"):
            split[ev.label] += ev.duration

    pbatch.set_batch_tracer(_split_tracer)
    # the acceptance A/B: OCT_TRACE=1 must not tax the host ceiling —
    # the recorder hangs off BATCH_TRACER and sees per-window events
    # only, none of which this host-only loop emits per header
    traced = obs.maybe_install()
    # the live plane rides the same bound: with OCT_HEARTBEAT + the
    # stall watchdog armed the hot ceiling must stay within 2% of
    # OCT_TRACE=0 (one atomic file rewrite per ~2 s — nothing per
    # header; round-11 acceptance)
    from ouroboros_consensus_tpu.obs import live as _live

    plane = _live.maybe_arm()
    print(f"host pipeline: {mode} (OCT_TRACE={'1' if traced else '0'}, "
          f"live={'armed' if plane else 'off'})", flush=True)

    try:
        for attempt in ("warm", "hot"):
            split.clear()
            sidecar_mod.reset_counters()
            res = ana.ValidationResult()
            imm = ana.open_immutable(path, validate_all="stream")
            t_stream = t_pre = t_stage = 0.0
            nh = nwin = npacked = 0
            t0 = time.monotonic()

            def timed_windows():
                nonlocal t_stream
                it = ana._stream_windows(imm, res)
                while True:
                    ts = time.monotonic()
                    try:
                        win = next(it)
                    except StopIteration:
                        t_stream += time.monotonic() - ts
                        return
                    t_stream += time.monotonic() - ts
                    yield win

            wins = ana._cap_windows(timed_windows(), N)
            state = praos.PraosState()
            for seg in ana._epoch_window_segments(params, wins):
                ticked = praos.tick(
                    params, lview, pbatch._slot_at(seg, 0), state
                )
                eta0 = ticked.state.epoch_nonce
                w, seg_n = 0, len(seg)
                while w < seg_n:
                    j = pbatch._proof_break(seg, w, min(w + bench.MAX_BATCH, seg_n))
                    win = seg[w:j]
                    ts = time.monotonic()
                    pre = pbatch.host_prechecks(params, lview, win)
                    t_pre += time.monotonic() - ts
                    ts = time.monotonic()
                    packed = None
                    if isinstance(win, ViewColumns) and isinstance(
                        pre, pbatch.ColumnChecks
                    ):
                        packed = pbatch.stage_packed_columns(
                            params, lview, eta0, win, pre
                        )
                    elif not isinstance(win, ViewColumns):
                        packed = pbatch.stage_packed(params, lview, eta0, win)
                    if packed is None:
                        pbatch.stage_any(params, lview, eta0, win, pre)
                    else:
                        pbatch.pad_packed_to(
                            packed[1], pbatch.bucket_size(len(win))
                        )
                        npacked += 1
                    t_stage += time.monotonic() - ts
                    nh += len(win)
                    nwin += 1
                    w = j
            wall = time.monotonic() - t0
            host_s = t_stream + t_pre + t_stage
            print(f"\n== {attempt}: {nh} headers, host pipeline {host_s:.2f}s "
                  f"(ceiling {nh/host_s:.0f} headers/s; wall {wall:.2f}s)",
                  flush=True)
            for label, secs in (("view-stream", t_stream),
                                ("prechecks", t_pre), ("stage", t_stage)):
                print(f"  {label:12s} {secs:8.2f}s  {secs/nh*1e6:7.2f} us/header")
            print(f"  windows: {nwin} ({npacked} packed)")
            sc_counts = sidecar_mod.counters()
            if any(sc_counts.values()) or split:
                print(f"  sidecar: {sc_counts} | "
                      f"mmap {split['stream-mmap']:.3f}s / "
                      f"parse {split['stream-parse']:.3f}s")
        # one run-ledger record per invocation (obs/ledger.py): the hot
        # attempt's ceiling + phase walls, with full env/git provenance
        from ouroboros_consensus_tpu.obs import ledger

        ledger.record_replay(
            "profile_replay",
            recorder=obs.recorder() if traced else None,
            config={"n": N, "mode": "host", "columnar": columnar,
                    "traced": traced,
                    "sidecar": sidecar_mod.enabled()},
            result={
                "headers": nh, "host_s": round(host_s, 3),
                "ceiling_per_s": round(nh / host_s, 1),
                "windows": nwin, "packed_windows": npacked,
                "sidecar": sc_counts,
            },
            wall_s=wall,
            phases_s={"view-stream": round(t_stream, 3),
                      "prechecks": round(t_pre, 3),
                      "stage": round(t_stage, 3),
                      "stream-mmap": round(split["stream-mmap"], 3),
                      "stream-parse": round(split["stream-parse"], 3)},
        )
    finally:
        # a raising replay must still disarm the live plane — the
        # unwind is what keeps maybe_arm re-entrant for the next run;
        # and the split tracer must not leak into the next run
        if plane is not None:
            plane.disarm()
        pbatch.set_batch_tracer(None)


def main():
    os.environ.setdefault("BENCH_HEADERS", str(N))
    import bench
    from ouroboros_consensus_tpu import obs
    from ouroboros_consensus_tpu.protocol import batch as pbatch
    from ouroboros_consensus_tpu.tools import db_analyser as ana
    from ouroboros_consensus_tpu.utils.trace import EncloseEvent, TransferEvent

    path, params, lview = bench.build_or_load_chain()
    dev = jax.devices()[0]
    print(f"device: {dev} platform={dev.platform}", flush=True)

    tot = defaultdict(float)
    cnt = defaultdict(int)
    xfer = defaultdict(int)  # h2d/d2h bytes + packed/generic window counts

    def tracer(ev):
        if isinstance(ev, EncloseEvent) and ev.edge == "end":
            tot[ev.label] += ev.duration
            cnt[ev.label] += 1
        elif isinstance(ev, TransferEvent):
            xfer["h2d"] += ev.h2d_bytes
            xfer["d2h"] += ev.d2h_bytes
            if ev.phase == "dispatch":
                xfer["packed" if ev.packed else "generic"] += 1

    pbatch.set_batch_tracer(tracer)
    # the flight recorder chains BEHIND the local tracer (obs.install
    # preserves it) — spans + histograms + the Perfetto event stream
    rec = obs.install() if (TRACE_OUT or obs.enabled()) else None
    try:

        # instrument the window stream (disk read + native parse + column
        # build) by timing the generator pulls
        stream_s = 0.0
        orig_stream = ana._stream_windows

        def timed_stream(imm, res):
            nonlocal stream_s
            it = orig_stream(imm, res)
            while True:
                t0 = time.monotonic()
                try:
                    win = next(it)
                except StopIteration:
                    stream_s += time.monotonic() - t0
                    return
                stream_s += time.monotonic() - t0
                yield win

        for attempt in ("warm", "hot"):
            tot.clear(); cnt.clear(); xfer.clear(); stream_s = 0.0
            ana._stream_windows = lambda imm, res: timed_stream(imm, res)
            t0 = time.monotonic()
            r = ana.revalidate(
                path, params, lview, backend="device", validate_all=True,
                max_batch=bench.MAX_BATCH,
            )
            wall = time.monotonic() - t0
            ana._stream_windows = orig_stream
            assert r.error is None and r.n_valid == r.n_blocks
            print(f"\n== {attempt}: {r.n_valid} headers in {wall:.2f}s "
                  f"({r.n_valid/wall:.0f} headers/s)", flush=True)
            accounted = 0.0
            for label in ("stage", "dispatch", "materialize", "epilogue"):
                if cnt[label]:
                    print(f"  {label:12s} {tot[label]:8.2f}s  x{cnt[label]:4d} "
                          f"({tot[label]/wall*100:5.1f}%)")
                    accounted += tot[label]
            print(f"  {'view-stream':12s} {stream_s:8.2f}s          "
                  f"({stream_s/wall*100:5.1f}%)")
            other = wall - accounted - stream_s
            print(f"  {'other':12s} {other:8.2f}s          "
                  f"({other/wall*100:5.1f}%)")
            nwin = xfer["packed"] + xfer["generic"]
            if nwin:
                print(
                    f"  windows: {nwin} ({xfer['packed']} packed) | "
                    f"H2D {xfer['h2d']/nwin/1e3:.1f} KB/window | "
                    f"D2H {xfer['d2h']/nwin/1e3:.1f} KB/window"
                )
        if rec is not None:
            s = rec.latency_summary()
            if s["windows"]:
                p50 = s["device_latency_p50_s"]
                p99 = s["device_latency_p99_s"]
                print(
                    f"\ndispatch->materialize latency over {s['windows']} "
                    f"windows: p50 {p50*1e3:.1f} ms | p99 {p99*1e3:.1f} ms"
                )
            if TRACE_OUT:
                from ouroboros_consensus_tpu.obs import perfetto

                doc = rec.write_chrome_trace(TRACE_OUT)
                errs = perfetto.validate_chrome_trace(doc)
                print(f"chrome trace: {TRACE_OUT} "
                      f"({len(doc['traceEvents'])} events"
                      f"{'' if not errs else f', INVALID: {errs[:3]}'})")
    finally:
        # unwind even when revalidate raises: the recorder and the
        # module-level tracer hook must not leak into the next run
        if rec is not None:
            obs.uninstall()
        pbatch.set_batch_tracer(None)
    # one run-ledger record per invocation: the hot replay's rate, phase
    # walls and boundary bytes, plus the warmup/resource ledgers
    from ouroboros_consensus_tpu.obs import ledger

    nwin = xfer["packed"] + xfer["generic"]
    ledger.record_replay(
        "profile_replay",
        recorder=rec,
        config={"n": N, "mode": "device", "platform": dev.platform},
        result={
            "headers": r.n_valid, "wall_s": round(wall, 3),
            "rate_per_s": round(r.n_valid / wall, 1),
            "windows": nwin, "packed_windows": xfer["packed"],
            "h2d_bytes": int(xfer["h2d"]), "d2h_bytes": int(xfer["d2h"]),
        },
        wall_s=wall,
        phases_s={k: round(v, 3) for k, v in sorted(tot.items())},
    )


def overlap_ab():
    """The staging-overlap acceptance harness (round 10): stubbed
    crypto + simulated device latency, OCT_STAGE_THREAD off vs on."""
    os.environ.setdefault("BENCH_HEADERS", str(N))
    os.environ["OCT_TRACE"] = "1"

    import bench
    from ouroboros_consensus_tpu import obs
    from ouroboros_consensus_tpu.obs import ledger
    from ouroboros_consensus_tpu.protocol import batch as pbatch
    from ouroboros_consensus_tpu.testing import stubs
    from ouroboros_consensus_tpu.tools import db_analyser as ana

    path, params, lview = bench.build_or_load_chain()
    stubs.install_stub_crypto()
    # the simulated device wait per window: a sleep inside
    # materialize releases the GIL, so staging/prefetch threads overlap
    # it exactly as they would a real device round trip
    twin_ms = float(os.environ.get("OCT_TWIN_DEVICE_MS", "40"))
    max_batch = int(os.environ.get("OCT_AB_MAX_BATCH", "1024"))
    orig_mat = pbatch.materialize_verdicts

    def slow_materialize(tagged, b):
        time.sleep(twin_ms / 1e3)
        return orig_mat(tagged, b)

    pbatch.materialize_verdicts = slow_materialize
    # OCT_AB_DEPTH (default 1): pipeline depth for BOTH runs. Depth 1
    # isolates the staging thread's contribution — the thread-off
    # baseline is then fully serial (stage -> dispatch -> device wait
    # -> epilogue per window), which is the honest control on a 1-core
    # host where the depth-3 in-loop overlap already saturates the GIL
    # (measured there: thread-on is CPU-bound at ~1.2x). On a
    # multi-core host / real device run with OCT_AB_DEPTH=3.
    depth = int(os.environ.get("OCT_AB_DEPTH", "1"))
    # the entry the replay calls (db_analyser hands it the whole stream
    # of segments; validate_chain goes through it too)
    orig_vs = pbatch.validate_stream

    def vs_depth(*a, **k):
        k.setdefault("pipeline_depth", depth)
        return orig_vs(*a, **k)

    pbatch.validate_stream = vs_depth
    print(f"overlap A/B: stubbed crypto, twin device latency "
          f"{twin_ms:.0f} ms/window, max_batch={max_batch}, "
          f"pipeline_depth={depth}", flush=True)

    walls: dict[str, float] = {}
    summaries: dict[str, dict] = {}
    for label, thread in (("warmup", "1"), ("thread-off", "0"),
                          ("thread-on", "1")):
        os.environ["OCT_STAGE_THREAD"] = thread
        rec = obs.install()
        rec.clear()
        t0 = time.monotonic()
        try:
            r = ana.revalidate(path, params, lview, backend="device",
                               validate_all="stream", max_batch=max_batch)
            wall = time.monotonic() - t0
        finally:
            obs.uninstall()
        assert r.error is None and r.n_valid == r.n_blocks > 0
        walls[label] = wall
        summaries[label] = rec.latency_summary()
        print(f"  {label:10s} {r.n_valid} headers in {wall:6.2f}s "
              f"({r.n_valid / wall:8.0f} headers/s)", flush=True)

    ratio = walls["thread-off"] / walls["thread-on"]
    print(f"\npipeline-thread-on / off speedup: {ratio:.2f}x "
          f"({walls['thread-off']:.2f}s -> {walls['thread-on']:.2f}s)")
    print("per-window p50s (oct_window_*_seconds) — the overlap "
          "evidence: staging wall per window is unchanged while the "
          "end-to-end wall shrinks:")
    for phase in ("stage", "dispatch", "materialize", "epilogue"):
        off = summaries["thread-off"].get(f"{phase}_p50_s")
        on = summaries["thread-on"].get(f"{phase}_p50_s")
        print(f"  {phase:12s} off {off if off is None else round(off, 4)}"
              f"  on {on if on is None else round(on, 4)}")
    ledger.record_replay(
        "profile_replay",
        recorder=None,
        config={"n": N, "mode": "overlap-ab", "twin_device_ms": twin_ms,
                "max_batch": max_batch},
        result={"wall_off_s": round(walls["thread-off"], 3),
                "wall_on_s": round(walls["thread-on"], 3),
                "speedup": round(ratio, 3)},
        wall_s=sum(walls.values()),
    )
    cores = len(os.sched_getaffinity(0)) if hasattr(os, "sched_getaffinity") \
        else (os.cpu_count() or 1)
    if ratio < 1.3:
        if cores < 2:
            # one core: the producer/prefetch threads and the main loop
            # serialize on the GIL and the round-9 worker already hides
            # the device sleeps — parity is the EXPECTED result here,
            # not a failure of the mechanism (module docstring)
            print(f"note: speedup {ratio:.2f}x on a single-core host — "
                  "the >=1.3x bound applies on >=2 cores / a real "
                  "device; reporting only")
            return 0
        print(f"WARNING: speedup {ratio:.2f}x below the 1.3x acceptance "
              "bound on this profile")
        return 1
    return 0


if __name__ == "__main__":
    if HOST_ONLY:
        host_ceiling()
    elif OVERLAP_AB:
        sys.exit(overlap_ab())
    else:
        main()
