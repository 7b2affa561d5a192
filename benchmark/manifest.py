"""BENCHMARK.json and the data files it names, loaded and checked.

The harness is driven by data: a cell, a configuration, a traffic mix and a
per-layer metric are each found BY NAME, so a later PR adds one as new files
plus entries in BENCHMARK.json and edits no file that is here.

    configuration  <name>  ->  the `file` its BENCHMARK.json entry names
    traffic mix    <name>  ->  benchmark/traffic/<name>.json  (parameters;
                               its "kind" names the generator module
                               benchmark/traffic/<kind>.py)
    per-layer      <name>  ->  benchmark/layer_metrics/<name>.json (its
                               "kind" names the reader module
                               benchmark/readers/<kind>.py)
"""

from __future__ import annotations

import json
import os
import re
from dataclasses import dataclass
from functools import cached_property

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(Exception):
    """BENCHMARK.json or a file it names is missing or malformed."""


def _load(path: str) -> dict:
    try:
        with open(path) as f:
            doc = json.load(f)
    except FileNotFoundError as e:
        raise ManifestError(f"missing {os.path.relpath(path, ROOT)}") from e
    except json.JSONDecodeError as e:
        raise ManifestError(f"{os.path.relpath(path, ROOT)}: {e}") from e
    if not isinstance(doc, dict):
        raise ManifestError(f"{os.path.relpath(path, ROOT)}: not an object")
    return doc


def _check_name(kind: str, name) -> None:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise ManifestError(f"{kind} name {name!r} is outside "
                            "[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    cells: tuple | None  # None: every cell
    spec: dict | None  # the per-layer metric's own file (None: end to end)
    layer: str | None = None
    moves: str | None = None

    def in_cell(self, cell: str) -> bool:
        return self.cells is None or cell in self.cells


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    why: str
    config_name: str
    config: dict  # the configuration's file
    traffic_name: str
    traffic: dict  # the traffic mix's file
    end_to_end: tuple  # Metric, ... that this cell reports
    per_layer: tuple


class Manifest:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.doc = _load(os.path.join(root, "BENCHMARK.json"))
        self.configs = {c["name"]: c for c in self.doc["configs"]}
        self.workloads = {w["name"]: w for w in self.doc["workloads"]}
        for kind, names in (("config", self.configs),
                            ("workload", self.workloads)):
            for n in names:
                _check_name(kind, n)

    def _metrics(self, key: str) -> list[Metric]:
        out = []
        for m in self.doc[key]:
            _check_name("metric", m["name"])
            if not UNIT_RE.match(m["unit"]):
                raise ManifestError(f"unit {m['unit']!r} of {m['name']}")
            if m["better"] not in ("lower", "higher"):
                raise ManifestError(f"better {m['better']!r} of {m['name']}")
            if m["source"] not in SOURCES:
                raise ManifestError(f"source {m['source']!r} of {m['name']}")
            spec = None
            if key == "per_layer":
                spec = _load(os.path.join(
                    self.root, "benchmark", "layer_metrics",
                    m["name"] + ".json"))
                _check_name("reader kind", spec.get("kind"))
                if m["moves"] not in {e["name"]
                                      for e in self.doc["end_to_end"]}:
                    raise ManifestError(f"{m['name']} moves {m['moves']!r}, "
                                        "which is no end-to-end metric")
            if "workloads" in m:
                cells = tuple(m["workloads"])
            elif key == "per_layer":
                # no list of its own: every cell that reports the
                # end-to-end metric it moves, those that later PRs add too
                moved = next(e for e in self.end_to_end
                             if e.name == m["moves"])
                cells = moved.cells
            else:
                cells = None
            for c in cells or ():
                if c not in self.workloads:
                    raise ManifestError(f"{m['name']} lists unknown cell {c}")
            out.append(Metric(m["name"], m["unit"], m["better"], m["source"],
                              cells, spec, m.get("layer"), m.get("moves")))
        return out

    @cached_property
    def end_to_end(self) -> list[Metric]:
        return self._metrics("end_to_end")

    @cached_property
    def per_layer(self) -> list[Metric]:
        return self._metrics("per_layer")

    def cell(self, name: str) -> Cell:
        w = self.workloads.get(name)
        if w is None:
            raise ManifestError(
                f"no workload {name!r} in BENCHMARK.json "
                f"(have: {', '.join(sorted(self.workloads))})")
        c = self.configs.get(w["config"])
        if c is None:
            raise ManifestError(f"{name}: unknown config {w['config']!r}")
        _check_name("traffic", w["traffic"])
        config = _load(os.path.join(self.root, c["file"]))
        traffic = _load(os.path.join(self.root, "benchmark", "traffic",
                                     w["traffic"] + ".json"))
        _check_name("traffic kind", traffic.get("kind"))
        if w["chips"] not in (1, 4):
            raise ManifestError(f"{name}: chips {w['chips']!r}")
        return Cell(
            name, w["chips"], w["why"], w["config"], config, w["traffic"],
            traffic,
            tuple(m for m in self.end_to_end if m.in_cell(name)),
            tuple(m for m in self.per_layer if m.in_cell(name)),
        )

    def cells(self) -> list[Cell]:
        return [self.cell(n) for n in self.workloads]
