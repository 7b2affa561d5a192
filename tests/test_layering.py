"""The arrow points one way: `analysis/` (with `scripts/`) reads the program
from outside, and nothing else under `ouroboros_consensus_tpu/` imports it.
A dispatch path that imports its own lint package carries that package's
policy, and its import cost, into every first execute."""

import ast
import os

PKG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "ouroboros_consensus_tpu")


def _imports_of_analysis(path: str, depth: int) -> list[int]:
    """Line numbers of every import of the `analysis` package in one
    module, `depth` package levels below `ouroboros_consensus_tpu`:
    absolute or relative, at top level or inside a function."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), path)
    absolute = "ouroboros_consensus_tpu.analysis"
    hits = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if node.level == 0:
                names = [mod] + [f"{mod}.{a.name}" for a in node.names]
            elif node.level == depth + 1:  # relative to the package root
                names = [f"ouroboros_consensus_tpu.{mod}".rstrip(".")]
                names += [f"{names[0]}.{a.name}" for a in node.names]
            else:
                continue
        else:
            continue
        if any(n == absolute or n.startswith(absolute + ".") for n in names):
            hits.append(node.lineno)
    return hits


def test_the_program_never_imports_its_analysis_package():
    offenders = []
    for root, dirs, files in os.walk(PKG):
        rel = os.path.relpath(root, PKG)
        if rel == "analysis" or rel.startswith("analysis" + os.sep):
            dirs[:] = []
            continue
        depth = 0 if rel == "." else rel.count(os.sep) + 1
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(root, name)
                offenders += [f"{os.path.relpath(path, PKG)}:{line}"
                              for line in _imports_of_analysis(path, depth)]
    assert offenders == []
