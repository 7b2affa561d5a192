"""The reduction from a profiler trace to busy time, idle share, per-stage
sums and the gap list, on a small synthetic trace in a v5e trace's layout."""

import json
import os

import pytest

from benchmark import xplane
from benchmark.readers import trace_idle, trace_module

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@pytest.fixture(scope="module")
def stage_map():
    with open(os.path.join(ROOT, "trace_modules.json")) as f:
        return json.load(f)["stages"]


@pytest.fixture(scope="module")
def trace():
    return xplane.load(os.path.join(HERE, "fixtures", "synthetic_trace.json"))


WINDOW = [900, 4000]  # the traced stretch of the synthetic trace
REPLAYS = [[900, 2100], [3000, 4000]]


def test_window_is_the_traced_stretch(trace, stage_map):
    red = xplane.reduce(trace, stage_map, WINDOW)
    assert red["window_s"] == pytest.approx((4000 - 900) / 1e9)
    assert red["chips"] == 1


def test_busy_is_the_union_of_op_intervals_inside_the_window(trace, stage_map):
    red = xplane.reduce(trace, stage_map, WINDOW, REPLAYS)
    # 100 + 250 + 500 + 400 + 50; the op at 9000 lies outside the window
    assert red["busy_s"] == pytest.approx(1300 / 1e9)
    assert red["idle_s"] == pytest.approx((3100 - 1300) / 1e9)
    idle = trace_idle.read({}, {"trace": red})
    assert idle == pytest.approx((1 - 1300 / 3100) * 100)


def test_per_stage_sums_are_module_durations(trace, stage_map):
    red = xplane.reduce(trace, stage_map, WINDOW, REPLAYS)
    assert red["stage_s"] == pytest.approx(
        {"unpack": 100e-9, "ed": 300e-9, "vrf": 500e-9, "kes": 400e-9})
    assert red["stage_runs"] == {"unpack": 1, "ed": 1, "vrf": 1, "kes": 1}
    assert red["unmatched_modules"] == [["jit_mystery(5)",
                                         pytest.approx(50e-9)]]
    src = {"trace": red}
    assert trace_module.read({"stage": "vrf", "scale": 1000}, src) == \
        pytest.approx(500e-9 * 1000)
    # a stage the trace never saw is left out, not reported as 0
    assert trace_module.read({"stage": "finish"}, src) is None


def test_overlapping_ops_are_not_counted_twice(stage_map):
    tr = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["a", 0, 100], ["b", 50, 100], ["c", 400, 10]]}]},
        ]}
    red = xplane.reduce(tr, stage_map, [0, 500])
    assert red["busy_s"] == pytest.approx(160e-9)
    assert red["device_ops"][0][0] in ("a", "b")


def test_gaps_go_to_what_the_host_was_doing(trace, stage_map):
    # gaps inside the window: 900-1000, 1100-1200, 1450-1500, 2000-3100,
    # 3500-3600, 3650-4000
    phases = {"materialize": [[2000, 2900]], "stage": [[3650, 4000]]}
    red = xplane.reduce(trace, stage_map, WINDOW, REPLAYS, phases)
    gaps = dict(red["idle_gaps"])
    assert gaps["materialize"] == pytest.approx(1100e-9)
    assert gaps["stage"] == pytest.approx(350e-9)
    # 900-1000, 1100-1200, 1450-1500, 3500-3600 lie inside a replay
    assert gaps["replay: outside the window spans"] == pytest.approx(350e-9)
    assert sum(gaps.values()) == pytest.approx(red["idle_s"])


def test_between_replays(stage_map):
    tr = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["a", 0, 100], ["a", 900, 100]]}]},
        ]}
    gaps = dict(xplane.reduce(tr, stage_map, [0, 1000],
                              [[0, 200], [800, 1000]])["idle_gaps"])
    # 100-900: 100 inside the first replay, 100 in the second, 600 between
    assert gaps == {"between replays": pytest.approx(800e-9)}


def test_a_module_run_cut_by_the_stretch_is_left_out(trace, stage_map):
    red = xplane.reduce(trace, stage_map, [900, 3300], REPLAYS)
    assert "kes" not in red["stage_s"]  # 3100..3500 ends outside
    assert red["busy_s"] == pytest.approx((100 + 250 + 500 + 200) / 1e9)


@pytest.fixture(scope="module")
def ends():
    with open(os.path.join(HERE, "fixtures", "recorded_trace_ends.json")) as f:
        return json.load(f)


def _whole_stretch(tr):
    """A stretch that holds every event of the trace (as PR 29's final
    draft-03 traced run held the event its profiler's stop had cut)."""
    evs = [e for pl in tr["planes"] for ln in pl["lines"]
           for e in ln["events"]]
    return [0, max(s + d for _, s, d in evs) + 1e6]


def test_a_program_cut_by_the_profilers_stop_is_not_counted(ends, stage_map):
    """The end of a trace recorded on the chip: the profiler stopped while
    `jit_ed_points` ran and shows it as 270 ns long, after the trace's last
    operation. Counted, it made `kernel_ms_per_window.ed` read 15.26 ms for
    16.95 (PR 29). The whole tenth run of `unpack` before it is counted."""
    tr = ends["cut"]
    mods = tr["planes"][0]["lines"][0]["events"]
    last = max(mods, key=lambda e: e[1])
    assert last[0].startswith("jit_ed_points") and last[2] < 1000
    red = xplane.reduce(tr, stage_map, _whole_stretch(tr))
    assert red["stage_runs"] == {"unpack": 10, "ed": 9, "kes": 9, "vrf": 9,
                                 "finish": 9, "reduce": 9}
    ed = trace_module.read({"stage": "ed", "scale": 1000}, {"trace": red})
    assert ed == pytest.approx(16.9507, abs=2e-4)
    assert trace_module.read({"stage": "unpack", "scale": 1000},
                             {"trace": red}) == pytest.approx(0.4076,
                                                              abs=5e-4)


def test_the_last_program_before_an_idle_stop_is_not_counted_either(
        ends, stage_map):
    """The profiler stopped on an idle device: the line's last event is a
    whole `jit_reduce_fn`, which nothing in the trace tells from a cut
    one. One whole run fewer; every mean as it was."""
    tr = ends["idle"]
    red = xplane.reduce(tr, stage_map, _whole_stretch(tr))
    assert red["stage_runs"] == {"unpack": 9, "ed": 9, "kes": 9, "vrf": 9,
                                 "finish": 9, "reduce": 8}
    assert trace_module.read({"stage": "vrf", "scale": 1000},
                             {"trace": red}) == pytest.approx(30.1499,
                                                              abs=2e-4)


def test_a_program_cut_after_its_first_operation_is_not_counted(
        ends, stage_map):
    """The end of a bc trace recorded on the chip: the profiler stopped
    17.05 ms into a `jit_vrf_points_bc` of 31.76 ms. It kept the program's
    first operation, dropped the one that was running, and shows the
    program as long as it had run. Counted, it would move the mean of
    eight runs from 31.76 ms to 29.92."""
    tr = ends["cut_inside"]
    mods = tr["planes"][0]["lines"][0]["events"]
    last = max(mods, key=lambda e: e[1])
    assert last[0].startswith("jit_vrf_points_bc")
    assert last[2] == pytest.approx(17.0457e6, abs=1e3)
    red = xplane.reduce(tr, stage_map, _whole_stretch(tr))
    assert red["stage_runs"] == {"unpack": 8, "ed": 8, "kes": 8, "vrf": 7,
                                 "finish": 7, "reduce": 7}
    assert trace_module.read({"stage": "vrf", "scale": 1000},
                             {"trace": red}) == pytest.approx(31.7613,
                                                              abs=2e-4)


def test_op_names_are_cut_short():
    assert xplane.short_name(
        "%while.44 = (s32[]{:T(128)}, s32[32]{0:T(128)S(1)}) while(...)"
    ) == "%while.44"
    assert len(xplane.short_name("x" * 500)) == xplane.NAME_MAX


def test_no_device_plane_reads_nothing(stage_map):
    assert xplane.reduce({"planes": []}, stage_map, [0, 1]) is None
    assert trace_idle.read({}, {"trace": None}) is None


def test_spans_are_laid_on_the_trace_clock(trace):
    off = xplane.clock_offset_ns(trace, 5_000_000_010)
    assert off == 5_000_000_000
    spans = [{"t_dispatch": 5.0000020, "dispatch_s": 1e-6, "stage_s": 0.5e-6,
              "t_materialized": 5.0000030, "materialize_s": 0.9e-6,
              "t_done": 5.0000032, "epilogue_s": 0.2e-6}]
    ph = xplane.phases_on_trace(spans, off)
    assert ph["dispatch"][0] == pytest.approx([1000, 2000])
    assert ph["stage"][0] == pytest.approx([500, 1000])
    assert ph["materialize"][0] == pytest.approx([2100, 3000])
    assert ph["epilogue"][0] == pytest.approx([3000, 3200])


def test_recorded_v5e_trace_reduces(stage_map):
    """A cut of a trace recorded on the chip (PR 27), where one is kept."""
    path = os.path.join(HERE, "fixtures", "recorded_v5e_trace.json")
    if not os.path.exists(path):
        pytest.skip("no recorded trace kept")
    tr = xplane.load(path)
    red = xplane.reduce(tr, stage_map, tr["window"], tr["replays"],
                        tr["host_phases"])
    assert red is not None and 0 < red["busy_s"] <= red["window_s"]
    assert set(red["stage_s"]) >= {"ed", "kes", "vrf", "finish", "unpack",
                                   "reduce"}
    assert red["unmatched_modules"] == []


def test_a_short_stretch_over_a_window_boundary(stage_map):
    """What a traced run reads today: under a second laid over the moment
    a window retires. The stage programs of the next window are whole;
    the long program cut at either end is no whole run, so it is not
    read, and neither is a roofline share that needs its time."""
    path = os.path.join(HERE, "fixtures", "recorded_v5e_trace.json")
    tr = xplane.load(path)
    # reduce of window 1 ends at 3627.24 ms, window 2's unpack starts 18 ms on
    red = xplane.reduce(tr, stage_map, [3_400e6, 4_100e6], tr["replays"])
    assert set(red["stage_s"]) == {"unpack", "ed", "kes", "vrf", "finish"}
    assert red["stage_runs"] == dict.fromkeys(red["stage_s"], 1)
    assert red["window_s"] == pytest.approx(0.7)
    # (the cut keeps 400 operations, all of the first window: no busy time
    # to check here)


def test_the_stretch_starts_before_the_next_window_retires():
    start = xplane.Stretch.start_time
    assert start([], 0.3, 99.0) == 99.0  # two have not retired: give up
    assert start([3.6], 0.3, 99.0) == 99.0
    assert start([3.6, 6.9], 0.3, 99.0) == pytest.approx(6.9 + 3.3 - 0.3)
    assert start([3.6, 6.9], 0.3, 8.0) == 8.0
    # windows shorter than the lead: at once (the time is in the past)
    assert start([1.00, 1.07], 0.3, 99.0) == pytest.approx(0.84)
