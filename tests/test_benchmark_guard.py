"""The benchmark's own checks, run by tier-1 (PERF.md section 7, 1 (i) of PR
31: `benchmark/tests/` is not collected by the driver's command, so a cell a
later PR adds was guarded only by a run made by hand).

Imported, not copied: every cell, configuration, traffic kind and reader that
`BENCHMARK.json` names is found by name and imports (`test_manifest`); the
readings of the replays' walls on planted walls and the readers
(`test_metrics`); kind `replay_stake` at test size, sound and with each
planted fault (`test_correct_stake`). The tests run here under this module's
name; their fixtures come with them. The stake-kind tests share one chain
under `benchmark/_cache/` by seed, so they want one worker: the driver's
`--dist loadfile` gives a file to one.
"""

from benchmark.tests.test_correct_stake import *  # noqa: F401,F403
from benchmark.tests.test_manifest import *  # noqa: F401,F403
from benchmark.tests.test_metrics import *  # noqa: F401,F403


def test_the_new_cell_is_among_the_guarded(m):  # noqa: F405 - test_manifest's
    cell = m.cell("replay-stakepools-2epoch")
    assert cell.traffic["kind"] == "replay_stake" and cell.chips == 1
    assert cell.config["pools"] >= 256  # windows over aggregate._DEDUPE_CAP
    names = {x.name for x in cell.per_layer}
    assert {"issuers_per_window", "kes_tails_per_window",
            "prechecks_ms_per_window",
            "epilogue_counters_ms_per_window"} <= names
    # the two cells that were there report them too (no `workloads` key)
    for other in ("replay-bc-2epoch", "replay-draft03-2epoch"):
        assert names == {x.name for x in m.cell(other).per_layer}


def test_every_cell_reports_30_per_layer_metrics_by_name(m):  # noqa: F405
    from benchmark.readers import phase_wall, window_span

    for cell in ("replay-bc-2epoch", "replay-draft03-2epoch",
                 "replay-stakepools-2epoch"):
        per_layer = {x.name: x for x in m.cell(cell).per_layer}
        # 30, and the 8 of the device's idle account, the off-CPU time
        # of dispatch and stage, and the collections; and the body
        # layouts a window holds
        assert len(per_layer) == 39
        layouts = per_layer["layouts_per_window"]
        assert layouts.spec == {"kind": "window_span", "key": "layouts"}
        assert (layouts.unit, layouts.layer) == ("layouts", "staging")
        index = per_layer["open_index_s_per_replay"]
        assert index.spec["kind"] == "phase_wall"
        assert index.spec["key"] == "open.index"
        assert (index.unit, index.layer) == ("s", "stream")
        tiles = per_layer["device_tiles_per_window"]
        assert tiles.spec == {"kind": "window_span", "key": "tiles_live"}
        assert (tiles.unit, tiles.layer) == ("tiles", "dispatch and kernels")
    # PR 38's span: per replay; a program without it (the parent commit's)
    # banks no such phase, and the line leaves the metric out
    phases = {"open": 0.9, "open.index": 0.06}
    assert phase_wall.read(index.spec, {"phase_wall": phases,
                                        "replays": 30}) == 0.002
    assert phase_wall.read(index.spec, {"phase_wall": {"open": 0.9},
                                        "replays": 30}) is None
    # read off the program's own spans; a program without the field (the
    # parent commit's) gives nothing to read, and the line leaves it out
    spans = [{"lanes": 10, "tiles_live": 1}, {"lanes": 8192, "tiles_live": 64}]
    assert window_span.read(tiles.spec, {"window_spans": spans}) == 32.5
    assert window_span.read(tiles.spec, {"window_spans": [{"lanes": 10}]}) is None


IDLE_ACCOUNT = {
    "device_idle_s_per_replay": ("phase_wall", "device-idle", "device"),
    "device_idle_s_per_replay.dispatch": ("phase_wall", "device-idle.dispatch",
                                          "dispatch and kernels"),
    "device_idle_s_per_replay.epilogue": ("phase_wall", "device-idle.epilogue",
                                          "epilogue"),
    "device_idle_s_per_replay.materialize": (
        "phase_wall", "device-idle.materialize", "window loop"),
    "device_idle_s_per_replay.unspanned": (
        "phase_wall", "device-idle.unspanned", "window loop"),
    "gc_s_per_replay": ("phase_wall", "gc", "window loop"),
    "dispatch_offcpu_ms_per_window": ("window_span", "dispatch_offcpu_s",
                                      "dispatch and kernels"),
    "stage_offcpu_ms_per_window": ("window_span", "stage_offcpu_s",
                                   "staging"),
}


def test_every_cell_reports_the_idle_account_and_reads_none_without_it(m):  # noqa: F405
    """The eight metrics of the device's idle account, the off-CPU time
    and the collections: in every cell (no `workloads` key), read by the
    readers that were there; a program without the phase or the field
    (the parent commit's) gives None, and the line leaves it out."""
    import pytest

    from benchmark import readers

    for cell in m.cells():
        per_layer = {x.name: x for x in cell.per_layer}
        for name, (kind, key, layer) in IDLE_ACCOUNT.items():
            x = per_layer[name]
            assert (x.spec["kind"], x.spec["key"], x.layer) == \
                (kind, key, layer)
            assert (x.source, x.better, x.moves) == \
                ("program_span", "lower", "replay_headers_per_s")
    now = {"replays": 4,
           "phase_wall": {"device-idle": 0.4, "device-idle.dispatch": 0.1,
                          "device-idle.epilogue": 0.04,
                          "device-idle.materialize": 0.0,
                          "device-idle.unspanned": 0.2, "gc": 0.002},
           "window_spans": [{"dispatch_offcpu_s": 0.001,
                             "stage_offcpu_s": 0.004},
                            {"dispatch_offcpu_s": 0.003,
                             "stage_offcpu_s": 0.0}]}
    before = {"replays": 4, "phase_wall": {"open": 0.1},
              "window_spans": [{"dispatch_s": 0.006}]}
    mine = [x for x in m.cell("replay-bc-2epoch").per_layer
            if x.name in IDLE_ACCOUNT]
    got = {x.name: readers.read(x.spec, now) for x in mine}
    assert got == pytest.approx({"device_idle_s_per_replay": 0.1,
                   "device_idle_s_per_replay.dispatch": 0.025,
                   "device_idle_s_per_replay.epilogue": 0.01,
                   "device_idle_s_per_replay.materialize": 0.0,
                   "device_idle_s_per_replay.unspanned": 0.05,
                   "gc_s_per_replay": 0.0005,
                   "dispatch_offcpu_ms_per_window": 2.0,
                   "stage_offcpu_ms_per_window": 2.0})
    assert all(readers.read(x.spec, before) is None for x in mine)
