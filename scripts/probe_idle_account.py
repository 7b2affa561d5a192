#!/usr/bin/env python3
"""The device's idle account of whole replays on the chip (obs/idle.py),
against its own self-check and against the profiler's trace of the same
stretch.

    python3 scripts/probe_idle_account.py --seed <n> [--replays 12]
        [--trace-seconds 0.75] <cell> [<cell> ...]

Per cell, in one process: the benchmark's cell by name, its chain from
benchmark/_cache/ (forged there by a run of the benchmark with the same
seed, or now by the cell's kind), one set-up replay, then `--replays`
whole replays through the benchmark's own `replay_once` behind the flight
recorder, the profiler tracing `--trace-seconds` of them as the
benchmark's `xplane.Stretch` does. One JSON line a cell:

  * `check`: the largest over the replays of |device-idle.open - open|
    and |device-idle.segment-wait - segment-wait| (nothing is in flight
    under either: 0 but for the clocks);
  * `split`: the total and each cause, seconds a replay (mean);
  * `trace`: on the trace's clock (the program's intervals laid on it by
    the `bench:sync` offset, `xplane.clock_offset_ns`), the device's idle
    seconds in the stretch by the trace and by the program (the
    stretch's time outside every replay is idle for both); the trace's
    idle inside the program's idle and inside its busy intervals, the
    program's idle the trace shows busy; and, for each of the program's
    gaps against the trace's gaps inside it, how far its start
    (`t_ready`) lies from the first one's start and its end (`t_launch`)
    from the last one's end (ms; > 0: the program's is later), and the
    device's work between those two (ms).

`--cpu-rehearsal` drives the same control flow on the CPU: the mix's
rehearsal sizes and hash-only stub crypto (testing/stubs), no trace; its
numbers are no device's.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _union(intervals) -> list:
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _covered(a, b, union) -> float:
    """How much of [a, b] the disjoint intervals `union` cover."""
    return sum(max(0.0, min(b, y) - max(a, x)) for x, y in union)


def _offsets(xs) -> dict:
    return {"median": statistics.median(xs) if xs else None,
            "min": min(xs, default=None), "max": max(xs, default=None),
            "sum": sum(xs), "n": len(xs)}


def _check(results) -> dict:
    d_open = [abs(r.phases["device-idle.open"] - r.phases.get("open", 0.0))
              for r in results]
    d_wait = [abs(r.phases["device-idle.segment-wait"]
                  - r.phases.get("segment-wait", 0.0)) for r in results]
    return {"open_vs_wall_s_max": max(d_open),
            "segment_wait_vs_wall_s_max": max(d_wait),
            "replays": len(results)}


def _split(results) -> dict:
    from ouroboros_consensus_tpu.obs import idle

    keys = ["device-idle"] + ["device-idle." + c for c in idle.CAUSES]
    out = {k: statistics.fmean(r.phases[k] for r in results) for k in keys}
    out["replay"] = statistics.fmean(r.phases["replay"] for r in results)
    return out


def _trace(results, replay_spans, stretch) -> dict:
    from benchmark import xplane
    from benchmark.readers import trace_idle_in_span

    trace = xplane.load(stretch.path(), keep_host=(xplane.SYNC_ANNOTATION,))
    offset = xplane.clock_offset_ns(trace, stretch.sync_mono_ns)
    if offset is None:
        return {"error": "no bench:sync annotation in the trace"}
    lo = stretch.start_mono_ns - offset
    hi = stretch.stop_mono_ns - offset
    dev = trace_idle_in_span.idle_gaps(trace, lo, hi)

    def on(t_s):
        return t_s * 1e9 - offset

    def clipped(a, b):
        a, b = max(on(a), lo), min(on(b), hi)
        return [a, b] if b > a else None

    prog = _union(g for r in results for a, b, _ in r.idle_gaps
                  if (g := clipped(a, b)))
    replays = _union(g for e in replay_spans
                     if (g := clipped(e.t - e.duration, e.t)))
    outside, at = [], lo  # the stretch outside every replay
    for a, b in replays:
        if a > at:
            outside.append([at, a])
        at = max(at, b)
    if hi > at:
        outside.append([at, hi])
    prog_all = _union(prog + outside)
    in_prog_idle = sum(_covered(a, b, prog_all) for a, b in dev)
    # each of the program's gaps against the trace's gaps inside it: how
    # far its start (`t_ready`) lies after the first one's start, its end
    # (`t_launch`) after the last one's end, and the device's work between
    # them (ms); gaps cut by the stretch's edges are left out
    starts, ends, inside = [], [], []
    for ga, gb in prog:
        mine = [(a, b) for a, b in dev if b > ga and a < gb]
        if not mine or ga <= lo or gb >= hi:
            continue
        starts.append((ga - mine[0][0]) / 1e6)
        ends.append((gb - mine[-1][1]) / 1e6)
        inside.append((mine[-1][1] - mine[0][0]
                       - sum(b - a for a, b in mine)) / 1e6)
    idle_trace = sum(b - a for a, b in dev) / 1e9
    idle_prog = sum(b - a for a, b in prog_all) / 1e9
    return {
        "stretch_s": (hi - lo) / 1e9,
        "idle_s_trace": idle_trace,
        "idle_s_program": idle_prog,
        "between_replays_s": sum(b - a for a, b in outside) / 1e9,
        "program_over_trace": idle_prog / idle_trace if idle_trace else None,
        "trace_idle_in_program_idle_s": in_prog_idle / 1e9,
        "trace_idle_in_program_busy_s": idle_trace - in_prog_idle / 1e9,
        "program_idle_device_busy_s": idle_prog - in_prog_idle / 1e9,
        "gaps_trace": len(dev), "gaps_program": len(prog),
        "t_ready_minus_trace_ms": _offsets(starts),
        "t_launch_minus_trace_ms": _offsets(ends),
        "device_busy_inside_program_gap_ms": _offsets(inside),
    }


def run_cell(name: str, seed: int, replays: int, trace_s: float,
             rehearsal: bool = False) -> dict:
    from benchmark import xplane
    from benchmark.manifest import Manifest
    from ouroboros_consensus_tpu import obs
    from ouroboros_consensus_tpu.utils.trace import EncloseEvent, WindowSpan

    cell = Manifest(ROOT).cell(name)
    kind = importlib.import_module(f"benchmark.traffic.{cell.traffic['kind']}")
    kind.place_caches(cell, rehearsal)
    inp = kind.make_inputs(cell, seed, rehearsal)
    kind.replay_once(inp)  # set-up: every program built or loaded
    rec = obs.install()
    try:
        n_ev = len(rec.events)
        stretch = None
        if trace_s and not rehearsal:
            trace_dir = os.path.join(ROOT, "benchmark", "_cache",
                                     f"idle-trace-{name}-s{seed}")
            stretch = xplane.Stretch(
                trace_dir, trace_s,
                retired=lambda: [e.t_materialized
                                 for _, e in rec.events[n_ev:]
                                 if isinstance(e, WindowSpan)])
        results = [kind.replay_once(inp)[0] for _ in range(replays)]
        spans = [e for _, e in rec.events[n_ev:]
                 if isinstance(e, EncloseEvent) and e.edge == "end"
                 and e.label == "replay"]
    finally:
        obs.uninstall()
    out = {"cell": name, "seed": seed, "check": _check(results),
           "split": _split(results)}
    if stretch is not None:
        out["trace"] = _trace(results, spans, stretch)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cells", nargs="+")
    ap.add_argument("--seed", type=int, action="append", required=True,
                    help="one for all cells, or one a cell")
    ap.add_argument("--replays", type=int, default=12)
    ap.add_argument("--trace-seconds", type=float, default=0.75)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    a = ap.parse_args()
    if a.cpu_rehearsal:
        from ouroboros_consensus_tpu.testing import stubs

        stubs.install_stub_crypto()
    seeds = a.seed * len(a.cells) if len(a.seed) == 1 else a.seed
    for name, seed in zip(a.cells, seeds):
        print(json.dumps(run_cell(name, seed, a.replays, a.trace_seconds,
                                  a.cpu_rehearsal)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
