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
        assert len(per_layer) == 30
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
