#!/usr/bin/env python3
"""The control of `correct`, and the program's reading beside it, on several
seeds in one process behind one set-up.

    python3 benchmark/control.py --workload <cell> --seeds 11,12,13

The configurations state no precision to lower; their guarantee is exact
verdicts. The control breaks it the way a later PR would be tempted to: the
device path with the VRF proof check left out (the `finish` stage's
`ok_vrf` verdict row forced to 1; the VRF stage is the dearest program).
For each seed this forges the chain, replays it once on the chip through
the normal entry point, judges the program as a benchmark run does, then
judges the control over the same replays and the same corrupted windows.
The program has to come out correct and the control not correct. The
benchmark's own runs never run this; benchmark/tests/ keeps it at a size a
test run can hold.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402


@contextlib.contextmanager
def skip_vrf_check():
    """The packed per-lane dispatch with `ok_vrf` forced true."""
    from ouroboros_consensus_tpu.ops.pk import kernels

    orig = kernels._stage_call

    def stage_call(name, fn, b, kes_depth, *args):
        out = orig(name, fn, b, kes_depth, *args)
        if name == "finish":
            flags, eta, lv = out
            # rows: ok_ocert_sig, ok_kes_sig, ok_vrf, ok_leader, ambiguous
            return flags.at[2].set(1), eta, lv
        return out

    kernels._stage_call = stage_call
    try:
        yield
    finally:
        kernels._stage_call = orig


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--replays", type=int, default=1,
                    help="whole replays judged per seed")
    a = ap.parse_args(argv)
    cell = Manifest(ROOT).cell(a.workload)
    kind = importlib.import_module(f"benchmark.traffic.{cell.traffic['kind']}")
    try:
        device = harness.acquire_device(cell.chips, rehearsal=False)
        kind.check_seams(False)
    except harness.FailedRun as e:
        harness.say(f"control: {e.what} {e.detail}")
        return e.rc
    from ouroboros_consensus_tpu import obs

    kind.place_caches(cell)
    ok = True
    obs.install()
    try:
        for seed in (int(s) for s in a.seeds.split(",")):
            t0 = time.monotonic()
            inp = kind.make_inputs(cell, seed, False)
            results = [kind.replay_once(inp)[0] for _ in range(a.replays)]
            mix = kind.mix_of(cell, False)
            correct, compared, _f, _d = kind.judge(
                inp, results, mix, seed)
            with skip_vrf_check():
                c_results = [kind.replay_once(inp)[0]
                             for _ in range(a.replays)]
                c_correct, c_compared, _f, c_detail = kind.judge(
                    inp, c_results, mix, seed)
            ok = ok and correct and not c_correct
            print(json.dumps({
                "seed": seed, "device": device,
                "seconds": round(time.monotonic() - t0, 1),
                "program": {"correct": correct, "compared": compared},
                "control": {"correct": c_correct, "compared": c_compared,
                            "cases": [[c["corrupted"], c["agree"]] for c in
                                      c_detail["wrong_header_cases"]]},
            }), flush=True)
    finally:
        obs.uninstall()
    print(json.dumps({"program_correct_and_control_not_on_every_seed": ok}),
          flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
