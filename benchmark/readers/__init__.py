"""One reader module per kind of source: `read(spec, sources) -> float | None`.

`spec` is the per-layer metric's own file (benchmark/layer_metrics/<name>.json)
and `sources` what the traffic kind gathered in the run (spans, counters,
phase walls, the reduced trace). A reader that finds nothing to read returns
None, and the harness leaves the metric out of the line: it never returns 0
for something it could not see. A new metric over an existing kind is a new
JSON file; a new kind is a new module here. No file that exists is edited.
"""

from __future__ import annotations

import importlib


def read(spec: dict, sources: dict) -> float | None:
    mod = importlib.import_module(f"benchmark.readers.{spec['kind']}")
    return mod.read(spec, sources)
