"""Every workload, configuration, traffic mix and per-layer metric that
BENCHMARK.json names is found by name, and the names and units are within
the characters the contract allows."""

import importlib
import json
import os
import re

import pytest

from benchmark.manifest import ROOT, Manifest, ManifestError

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def m():
    return Manifest()


def test_every_cell_loads_with_its_files(m):
    """Every cell by name from BENCHMARK.json, whatever a later PR adds:
    its configuration and traffic files, the generator of its traffic
    kind, and the reader and file of each per-layer metric it reports."""
    doc = m.doc
    cells = m.cells()
    assert [c.name for c in cells] == [w["name"] for w in doc["workloads"]]
    e2e = {e["name"] for e in doc["end_to_end"]}
    for c in cells:
        assert c.chips in (1, 4) and 0 < len(c.why) <= 200
        assert c.config["name"] == c.config_name
        kind = importlib.import_module(
            f"benchmark.traffic.{c.traffic['kind']}")
        assert callable(kind.run)
        reported = {x.name for x in c.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert c.per_layer
        for x in c.per_layer:
            reader = importlib.import_module(
                f"benchmark.readers.{x.spec['kind']}")
            assert callable(reader.read)
            assert x.moves in reported and x.moves in e2e and x.layer


def test_every_entry_is_reported_somewhere_and_used(m):
    doc = m.doc
    cells = m.cells()
    for key in ("end_to_end", "per_layer"):
        for e in doc[key]:
            assert any(e["name"] in {x.name for x in getattr(c, key)}
                       for c in cells), e["name"]
    used = {w["config"] for w in doc["workloads"]}
    assert used == {c["name"] for c in doc["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in doc["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in doc[key]]
        assert len(set(names)) == len(names)
    # one name for one layer, letter for letter: a layer with one metric
    # whose name differs from another only by case or spacing is a typo
    layers = {e["layer"] for e in doc["per_layer"]}
    assert len({" ".join(x.lower().split()) for x in layers}) == len(layers)


def test_names_and_units_are_within_the_allowed_characters(m):
    doc = m.doc
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in doc[key]:
            assert NAME.match(e["name"]), e["name"]
    for w in doc["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
    for e in doc["end_to_end"] + doc["per_layer"]:
        assert UNIT.match(e["unit"]), e["unit"]
        assert e["better"] in ("lower", "higher")
    units = {e["name"]: e["unit"] for e in doc["end_to_end"]}
    assert units["setup_s"] == "s"
    assert len(json.dumps(doc)) < 64 * 1024


def test_configuration_files_state_source_reduced_and_guarantees(m):
    for c in m.doc["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"] and len(c["source"]) <= 200
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert cfg["guarantees"] and cfg["assumed"]
        assert set(cfg.get("forge_env", {})) <= {"OCT_VRF_BATCH"}
    files = [c["file"] for c in m.doc["configs"]]
    assert len(set(files)) == len(files)


def test_files_under_paths_are_named_from_the_allowed_characters():
    import subprocess

    listed = subprocess.run(
        ["git", "ls-files", "--cached", "--others", "--exclude-standard",
         "benchmark"], cwd=ROOT, capture_output=True, text=True).stdout
    for path in listed.split():
        assert re.match(r"^[A-Za-z0-9_.\-/]+$", path), path


def test_an_unknown_cell_is_an_error(m):
    with pytest.raises(ManifestError):
        m.cell("replay-nothing")
