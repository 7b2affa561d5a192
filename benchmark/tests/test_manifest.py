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
    cells = m.cells()
    assert [c.name for c in cells] == ["replay-bc-2epoch",
                                       "replay-draft03-2epoch"]
    for c in cells:
        assert c.chips == 1 and len(c.why) <= 200
        assert c.config["name"] == c.config_name
        importlib.import_module(f"benchmark.traffic.{c.traffic['kind']}")
        assert {x.name for x in c.end_to_end} == {"replay_headers_per_s",
                                                  "setup_s"}
        assert len(c.per_layer) == 12
        for x in c.per_layer:
            importlib.import_module(f"benchmark.readers.{x.spec['kind']}")
            assert x.moves == "replay_headers_per_s" and x.layer


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
    assert units == {"replay_headers_per_s": "headers/s", "setup_s": "s"}
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
