"""The share of the device's idle time that the program's own spans cover,
on a small synthetic trace whose host plane carries `oct:` annotations on
the lines of two threads: three idle gaps, one under `oct:dispatch`, one
under `oct:tick` + `oct:epilogue`, one under nothing but a wait."""

import copy
import json
import os

import pytest

from benchmark import xplane
from benchmark.manifest import Manifest
from benchmark.readers import trace_idle_in_span as reader

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FIXTURE = os.path.join(HERE, "fixtures", "annotated_trace.json")
WINDOW_S = 3890e-9
IDLE = 200 + 300 + 300

NEW = ("dispatch_ms_per_window", "stage_wait_ms_per_window",
       "windows_inflight_at_retire", "segment_wait_s_per_replay",
       "open_s_per_replay", "replay_self_s_per_replay",
       "idle_in_dispatch_pct", "idle_in_retire_pct",
       "idle_outside_spans_pct")


@pytest.fixture(scope="module")
def trace():
    return xplane.load(FIXTURE)


def spec_of(name: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def test_idle_gaps_open_at_the_end_of_the_sync_annotation(trace):
    gaps = reader.idle_gaps(trace, 110, 110 + WINDOW_S * 1e9)
    assert gaps == [[800, 1000], [1800, 2100], [3000, 3300]]


@pytest.mark.parametrize("name, covered", [
    ("idle_in_dispatch_pct", 200),  # the child span inside is not added
    ("idle_in_retire_pct", 300),  # tick and epilogue, back to back
    # the main thread only waits there; the staging thread's span over
    # the same gap is another thread's and takes nothing from it
    ("idle_outside_spans_pct", 300),
])
def test_the_three_shares_of_the_metric_files(trace, name, covered):
    got = reader.share(trace, spec_of(name), WINDOW_S)
    assert got == pytest.approx(covered / IDLE * 100)


def test_the_three_shares_sum_to_the_whole_idle_time(trace):
    total = sum(reader.share(trace, spec_of(n), WINDOW_S)
                for n in NEW if n.startswith("idle_"))
    assert total == pytest.approx(100.0)


def test_named_spans_are_taken_on_whatever_thread_recorded_them(trace):
    got = reader.share(trace, {"spans": ["oct:stage"]}, WINDOW_S)
    assert got == pytest.approx(250 / IDLE * 100)


def test_annotations_that_cover_no_idle_read_zero_not_none(trace):
    assert reader.share(trace, {"spans": ["oct:dispatch.ed"]},
                        WINDOW_S) == 0.0
    assert reader.share(trace, {"spans": ["oct:absent"]}, WINDOW_S) == 0.0


def test_a_trace_without_the_programs_annotations_reads_none(trace):
    bare = copy.deepcopy(trace)
    for pl in bare["planes"]:
        if not xplane.DEVICE_PLANE.match(pl["name"]):
            pl["lines"] = [ln for ln in pl["lines"]
                           if ln["events"][0][0] == xplane.SYNC_ANNOTATION]
    for name in NEW[6:]:
        assert reader.share(bare, spec_of(name), WINDOW_S) is None


def test_read_finds_the_runs_trace_and_is_silent_without_one(monkeypatch):
    src = {"trace": {"window_s": WINDOW_S, "busy_s": 1e-9}}
    monkeypatch.setattr(reader, "newest_trace", lambda: FIXTURE)
    assert reader.read(spec_of("idle_in_dispatch_pct"), src) == \
        pytest.approx(25.0)
    # an untraced run, and a traced one that left no file
    assert reader.read(spec_of("idle_in_dispatch_pct"), {"trace": None}) \
        is None
    monkeypatch.setattr(reader, "newest_trace", lambda: None)
    assert reader.read(spec_of("idle_in_dispatch_pct"), src) is None


def test_the_loader_keeps_every_program_span_and_the_sync():
    keep = reader._ProgramSpans()
    assert "oct:dispatch.reduce" in keep and xplane.SYNC_ANNOTATION in keep
    assert "jit_ed_points" not in keep and "$core.py:1 f" not in keep


@pytest.mark.parametrize("cell", ["replay-bc-2epoch",
                                  "replay-draft03-2epoch"])
def test_both_cells_load_the_nine_new_metric_files(cell):
    per_layer = {m.name: m for m in Manifest(ROOT).cell(cell).per_layer}
    for name in NEW:
        m = per_layer[name]
        assert m.moves == "replay_headers_per_s" and m.spec["kind"] in (
            "window_span", "phase_wall", "trace_idle_in_span")
