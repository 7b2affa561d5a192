#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chip; its one child forges the chain on the
CPU (`traffic/replay._forge_in_child`) and has ended before set-up goes on.
The cell, its configuration, its traffic mix and its per-layer metrics are
found by name (benchmark/manifest.py). Context goes on earlier JSON lines;
the last line of standard output is the result:

    {"correct", "attempted", "failed", "metrics", "device", ["breakdown"], "compared"}

with the cell's end-to-end metrics under --trace 0 and its per-layer metrics
under --trace 1. Each number that `correct` compared stands beside its limit
in the last lines of standard error and under `compared`. No TPU, or fewer
chips than the cell asks for: exit 3, no result. A run that is no
measurement (a program built inside the window, a fallback fired): exit 4,
no result. `--cpu-rehearsal` drives the control flow at a tiny size on the
CPU, exits 2 when it passes and never prints a result line.
"""

from __future__ import annotations

import argparse
import faulthandler
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, readers, xplane  # noqa: E402
from benchmark.harness import FailedRun, emit, say  # noqa: E402
from benchmark.manifest import Manifest, ManifestError  # noqa: E402

# a run has 360 s; a hang must leave stacks and a non-zero exit. The first
# run of a cell in a checkout compiles and has 1200 s.
DEADLINE_S = 1150


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="control flow only, tiny, on the CPU; exits "
                         f"{harness.RC_REHEARSAL}, prints no result line")
    return ap.parse_args(argv)


def traced_sources(out: dict) -> dict | None:
    """Reduce the run's profiler trace and lay the program's spans on it."""
    with open(os.path.join(ROOT, "benchmark", "trace_modules.json")) as f:
        stage_map = json.load(f)["stages"]
    st = out["stretch"]
    t_load = time.monotonic()
    trace = xplane.load(out["trace_path"])
    t_reduce = time.monotonic()
    offset = xplane.clock_offset_ns(trace, st.sync_mono_ns)
    if offset is None:
        emit("trace", path=os.path.relpath(out["trace_path"], ROOT),
             error="the benchmark's own annotation is not in the trace")
        return None

    def on_trace(t_s):
        return t_s * 1e9 - offset

    t0, t1 = out["window"]
    window = [st.start_mono_ns - offset,
              min(st.stop_mono_ns - offset, on_trace(t1))]
    replays, t = [], t0
    for w in out["replay_walls"]:
        replays.append([on_trace(t), on_trace(t + w)])
        t += w
    red = xplane.reduce(
        trace, stage_map, window, replays,
        xplane.phases_on_trace(out["sources"]["window_spans"], offset))
    emit("trace", path=os.path.relpath(out["trace_path"], ROOT),
         bytes=os.path.getsize(out["trace_path"]),
         traced_s=round((st.stop_mono_ns - st.start_mono_ns) / 1e9, 3),
         profiler_stop_s=round(st.stop_s, 1),
         load_s=round(t_reduce - t_load, 1),
         reduce_s=round(time.monotonic() - t_reduce, 1),
         reduced={k: v for k, v in (red or {}).items()
                  if k not in ("device_ops", "idle_gaps")})
    return red


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell = Manifest(ROOT).cell(args.workload)
    except ManifestError as e:
        say(f"benchmark: {e}")
        return 1
    try:
        kind = importlib.import_module(
            f"benchmark.traffic.{cell.traffic['kind']}")
        device = harness.acquire_device(cell.chips, args.cpu_rehearsal)
        out = kind.run(cell, args, device)
        if args.trace and out.get("trace_path"):
            out["sources"]["trace"] = traced_sources(out)
    except FailedRun as e:
        emit("failed_run", what=e.what, **e.detail)
        say(f"benchmark: FAILED RUN, no result: {e.what}")
        return e.rc

    if args.trace:
        values = {m.name: readers.read(m.spec, out["sources"])
                  for m in cell.per_layer}
    else:
        values = {m.name: out["end_to_end"].get(m.name)
                  for m in cell.end_to_end}
    units = {m.name: m.unit for m in cell.per_layer + cell.end_to_end}
    metrics = {n: {"value": v, "unit": units[n]}
               for n, v in values.items() if v is not None}
    dev = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
    result = {"correct": bool(out["correct"]),
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": dev}
    tr = out["sources"].get("trace")
    if args.trace and tr:
        dev.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    result["compared"] = out["compared"]
    for name, c in out["compared"].items():
        say(f"compared {name}: value {c['value']} limit {c['limit']}")
    say(f"correct: {result['correct']}")
    if args.cpu_rehearsal:
        emit("rehearsal", note="cpu control flow passed; not a chip run, "
             "no result line", would_be=result)
        return harness.RC_REHEARSAL
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    faulthandler.enable()
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
    sys.exit(main())
