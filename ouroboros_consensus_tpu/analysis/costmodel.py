"""octwall — Pass 4: static compile-cost certification of the crypto
jaxprs, calibrated by the flight recorder.

BENCH r02-r05 banked no device number because first-execute compile
walls (~410 s on the composed programs) ate the bench budget — and the
repo already proved compile time is *steerable* from jaxpr structure
(PR 1: fencing the ladders cut the composed graph 355k -> 171k eqns,
chain depth 900 -> 114). PR 6's warmup recorder measures per-stage
first-execute walls after the fact; this pass predicts them BEFORE
anything compiles, and ratchets the prediction in the lint sweep. It
reads the program from outside: nothing on the dispatch path imports
it.

Three cooperating pieces:

  features  `extract_features` walks a traced jaxpr (reusing the
            Pass-2 trace cache — no XLA compile, no device) and
            extracts the structural features PR 1 showed drive the
            algebraic simplifier's 50-run-cap blowup: total/maximum
            per-computation equation counts, unfenced multiply-chain
            depth, fence (scan/while/pjit) counts and body sizes,
            fan-out, remat width, dot/gather counts, constant bytes.
            A `feature_hash` (blake2s of the canonical feature vector)
            identifies the exact graph structure; a measured wall
            recorded by obs/warmup.py joins its static features through
            to the pin its stage label names (`stage_graph`).

  model     a small feature-weighted model: predicted cold-compile
            wall = exp(b0 + sum b_i * log1p(feature_i)), coefficients
            constrained NON-NEGATIVE (more structure can never predict
            a cheaper compile — the ratchet depends on monotonicity).
            Fitted by `scripts/fit_costmodel.py` from the per-stage
            first-execute walls the warmup recorder banks into BENCH
            round JSONs plus local calibration runs; pinned with the
            per-graph features/predictions in analysis/costmodel.json.

  consumers `check_compile_wall` ratchets each registered graph's
            prediction against budgets.json's "compile_wall" section
            (scripts/lint.py exit 5, the `cost` CLI subcommand);
            `advisories` flags monolith computations and unfenced
            chains over budget, naming the source fence to split.

What the model does NOT predict: Pallas/Mosaic lowering walls (kernel
bodies are opaque to the jaxpr), device-side autotuning, persistent-
cache deserialization time, or the XLA version drift between the
calibration backend and the deployment runtime — predictions are a
structural estimate for the ratchet, not a profiler (see analysis/README.md, Pass 4)."""

from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import os
import time

from . import graphs

_COST_PATH = os.path.join(os.path.dirname(__file__), "costmodel.json")

# primitives whose operand gather/scatter indexing the simplifier's
# rewrite families interact with (cheap to count, cheap to fit)
_GATHER_PRIMS = {"gather", "dynamic_slice", "scatter", "scatter-add",
                 "dynamic_update_slice"}

# canonical feature order — the hash and the model read this tuple, so
# APPEND new features, never reorder (a reorder would silently unjoin
# every banked calibration row)
FEATURE_NAMES = (
    "eqns", "computations", "max_comp_eqns", "mul_chain_depth",
    "mul_count", "op_fanout", "remat_width", "fence_count",
    "max_body_eqns", "dot_count", "gather_count", "const_bytes",
)

# the subset the fitted model consumes (the rest are extracted for the
# advisories and for future re-fits without re-measuring). Chosen by
# subset search over the calibration rows: graph SIZE (eqns) carries
# most of the signal, with per-op premiums for the expensive families
# (multiplies feeding the simplifier's rewrite loop, MXU dots,
# fence subcomputations each compiled separately, gathers).
MODEL_FEATURES = (
    "eqns", "mul_count", "dot_count", "fence_count", "gather_count",
)

# a fitted prediction never goes below this (dispatch + tiny-program
# compile floor) — keeps log-space extrapolation honest on small graphs
MIN_PREDICTED_S = 0.05


def _src_of(eqn) -> str:
    from .absint import _src_of as src

    return src(eqn)


@dataclasses.dataclass
class CostFeatures:
    """Compile-cost features of one traced graph (one recursive walk,
    same fence/multiply vocabulary as the Pass-2 analyzer)."""

    name: str
    eqns: int = 0
    computations: int = 0
    max_comp_eqns: int = 0
    mul_chain_depth: int = 0
    mul_count: int = 0
    op_fanout: int = 0
    remat_width: int = 0
    fence_count: int = 0
    max_body_eqns: int = 0
    dot_count: int = 0
    gather_count: int = 0
    const_bytes: int = 0
    # pathology provenance (advisories name these)
    chain_src: str = ""
    monolith_src: str = "<top-level>"

    def to_dict(self) -> dict:
        return {k: int(getattr(self, k)) for k in FEATURE_NAMES}

    def hash(self) -> str:
        return feature_hash(self.to_dict())


def feature_hash(features: dict) -> str:
    """Stable digest of the canonical feature vector: the join key
    between a warmup-report stage note and the static features it was
    measured against."""
    vec = ",".join(f"{k}={int(features.get(k, 0))}" for k in FEATURE_NAMES)
    return hashlib.blake2s(vec.encode(), digest_size=8).hexdigest()


def _sub_closed(eqn):
    """(jaxpr, consts) pairs for every sub-computation of a fence eqn
    (graphs._sub_jaxprs strips ClosedJaxpr consts; the cost walk wants
    them for const_bytes)."""
    for v in eqn.params.values():
        vs = v if isinstance(v, (list, tuple)) else [v]
        for x in vs:
            consts = ()
            while hasattr(x, "jaxpr"):
                consts = getattr(x, "consts", ()) or consts
                x = x.jaxpr
            if hasattr(x, "eqns"):
                yield x, consts


def _const_nbytes(consts) -> int:
    import numpy as np

    total = 0
    for c in consts:
        try:
            total += int(np.asarray(c).nbytes)
        except Exception:
            pass
    return total


def _walk(jaxpr, f: CostFeatures, provenance: str) -> None:
    """One computation: mirrors graphs._analyze (fences separate
    computations, multiply chains reset at fences) plus the cost-only
    features and the source attribution the advisories need."""
    depth: dict[int, int] = {}
    uses: dict[int, int] = {}
    last_use: dict[int, int] = {}
    f.computations += 1
    comp_eqns = 0
    for i, eqn in enumerate(jaxpr.eqns):
        comp_eqns += 1
        f.eqns += 1
        prim = eqn.primitive.name
        is_mul = prim in graphs._MUL_PRIMS
        if is_mul:
            f.mul_count += 1
        if prim == "dot_general":
            f.dot_count += 1
        if prim in _GATHER_PRIMS:
            f.gather_count += 1
        in_depth = 0
        for v in eqn.invars:
            if hasattr(v, "val"):
                continue
            uses[id(v)] = uses.get(id(v), 0) + 1
            last_use[id(v)] = i
            in_depth = max(in_depth, depth.get(id(v), 0))
        if prim in graphs._FENCE_PRIMS:
            f.fence_count += 1
            before = f.eqns
            for sub, consts in _sub_closed(eqn):
                f.const_bytes += _const_nbytes(consts)
                _walk(sub, f, f"{prim}@{_src_of(eqn)}")
            f.max_body_eqns = max(f.max_body_eqns, f.eqns - before)
            out_depth = 0  # separate computation: the chain is fenced
        else:
            out_depth = in_depth + (1 if is_mul else 0)
            if out_depth > f.mul_chain_depth:
                f.mul_chain_depth = out_depth
                f.chain_src = _src_of(eqn)
        for v in eqn.outvars:
            depth[id(v)] = out_depth
    for v in jaxpr.outvars:
        if not hasattr(v, "val"):
            uses[id(v)] = uses.get(id(v), 0) + 1
            last_use[id(v)] = len(jaxpr.eqns)
    if uses:
        f.op_fanout = max(f.op_fanout, max(uses.values()))
    # live-interval sweep (remat pressure), same as Pass 2
    born: dict[int, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.outvars:
            born[id(v)] = i
    events: list[tuple[int, int]] = []
    for vid, b in born.items():
        events.append((b, 1))
        events.append((last_use.get(vid, b) + 1, -1))
    live = peak = 0
    for _, delta in sorted(events):
        live += delta
        peak = max(peak, live)
    f.remat_width = max(f.remat_width, peak)
    if comp_eqns > f.max_comp_eqns:
        f.max_comp_eqns = comp_eqns
        f.monolith_src = provenance


def extract_features(closed_jaxpr, name: str = "graph") -> CostFeatures:
    """Walk one traced jaxpr (no compile) into its cost features."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    f = CostFeatures(name=name)
    f.const_bytes += _const_nbytes(getattr(closed_jaxpr, "consts", ()))
    _walk(jaxpr, f, "<top-level>")
    return f


def graph_features(name: str, t: int | None = None) -> CostFeatures:
    """Features of a registered graph via the shared Pass-2 trace cache
    (one trace serves budgets, certification, point-ops AND cost)."""
    return extract_features(graphs.trace_graph(name, t), name)


# ---------------------------------------------------------------------------
# The fitted model (analysis/costmodel.json)
# ---------------------------------------------------------------------------


def load_cost(path: str | None = None) -> dict:
    with open(path or _COST_PATH, encoding="utf-8") as fh:
        return json.load(fh)


_CACHED: dict | None = None


def _cached_cost() -> dict | None:
    """costmodel.json, read once per process. Missing/invalid file ->
    None, never an exception."""
    global _CACHED
    if _CACHED is None:
        try:
            _CACHED = load_cost()
        except (OSError, json.JSONDecodeError, ValueError):
            _CACHED = {}
    return _CACHED or None


def predict(features: CostFeatures | dict,
            model: dict | None = None) -> float | None:
    """Predicted cold-compile wall (seconds) for a feature vector;
    None when no fitted model is available."""
    if model is None:
        cost = _cached_cost()
        model = (cost or {}).get("model")
    if not model or "coeffs" not in model:
        return None
    feats = features.to_dict() if isinstance(features, CostFeatures) \
        else features
    z = float(model.get("intercept", 0.0))
    for k, c in model["coeffs"].items():
        z += float(c) * math.log1p(max(0, int(feats.get(k, 0))))
    return max(MIN_PREDICTED_S, math.exp(z))


def fit_model(rows: list[tuple[dict, float]], ridge: float = 1e-2,
              backend: str = "") -> dict:
    """Non-negative log-log least squares over MODEL_FEATURES.
    `rows` = [(features_dict, measured_first_execute_s), ...].
    Coefficients are clipped to >= 0 and re-solved on the surviving
    support (more structure must never predict a cheaper compile)."""
    import numpy as np

    if len(rows) < 3:
        raise ValueError(f"need >= 3 calibration rows, got {len(rows)}")
    names = list(MODEL_FEATURES)
    X = np.array([
        [math.log1p(max(0, int(f.get(k, 0)))) for k in names]
        for f, _ in rows
    ])
    y = np.array([math.log(max(1e-3, float(w))) for _, w in rows])
    active = list(range(len(names)))
    for _ in range(len(names) + 1):
        A = np.hstack([np.ones((len(rows), 1)), X[:, active]])
        # ridge keeps the collinear size features stable on small
        # calibration sets; the intercept is not penalized
        reg = np.eye(A.shape[1]) * ridge
        reg[0, 0] = 0.0
        beta = np.linalg.solve(A.T @ A + reg, A.T @ y)
        neg = [active[j] for j in range(len(active)) if beta[1 + j] < 0]
        if not neg:
            break
        active = [j for j in active if j not in neg]
        if not active:
            beta = np.array([float(np.mean(y))])
            break
    coeffs = {names[j]: 0.0 for j in range(len(names))}
    for pos, j in enumerate(active):
        coeffs[names[j]] = round(float(beta[1 + pos]), 6)
    return {
        "intercept": round(float(beta[0]), 6),
        "coeffs": coeffs,
        "backend": backend,
        "rows": len(rows),
    }


def pin_payload(features: list[CostFeatures],
                model: dict | None) -> dict:
    """The costmodel.json "graphs" section: per graph the feature
    vector, its hash (the calibration join key) and the model's
    prediction — sorted-keys stable for CI diffing."""
    out: dict = {}
    for f in features:
        pred = predict(f, model) if model else None
        out[f.name] = {
            "features": f.to_dict(),
            "feature_hash": f.hash(),
            "predicted_s": None if pred is None else round(pred, 1),
        }
    return out


def write_cost(graphs_section: dict | None = None,
               model: dict | None = None,
               calibration: list | None = None,
               path: str | None = None) -> dict:
    """Rewrite costmodel.json, preserving whichever sections are not
    being replaced (lint --update-costs refreshes `graphs`;
    fit_costmodel refreshes `model` + `calibration`)."""
    global _CACHED
    path = path or _COST_PATH
    try:
        payload = load_cost(path)
    except (OSError, json.JSONDecodeError, ValueError):
        payload = {}
    payload["comment"] = (
        "octwall compile-cost model (analysis/costmodel.py). `model` = "
        "non-negative log-log coefficients fitted by "
        "scripts/fit_costmodel.py from warmup-recorder first-execute "
        "walls; `graphs` = per-graph feature vectors + hashes (the "
        "calibration join keys, regenerated by scripts/lint.py "
        "--update-costs) + predicted cold-compile walls; `calibration` "
        "= the measured rows the fit used. budgets.json's compile_wall "
        "section ratchets the predictions (lint exit 5)."
    )
    if model is not None:
        now = time.time()
        model = dict(model)
        model.setdefault("fitted_at", time.strftime(
            "%Y-%m-%d", time.gmtime(now)))
        payload["model"] = model
    if calibration is not None:
        payload["calibration"] = calibration
    if graphs_section is not None:
        payload["graphs"] = graphs_section
    elif model is not None and "graphs" in payload:
        # a re-fit invalidates every pinned prediction: recompute from
        # the STORED features (no re-tracing)
        for name, pin in payload["graphs"].items():
            pred = predict(pin["features"], payload["model"])
            pin["predicted_s"] = None if pred is None else round(pred, 1)
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(payload, fh, indent=2, sort_keys=True)
        fh.write("\n")
    _CACHED = None
    return payload


def pinned(name: str) -> dict | None:
    """The costmodel.json pin for one graph (features/hash/predicted),
    or None."""
    cost = _cached_cost()
    return (cost or {}).get("graphs", {}).get(name)


def predicted_wall(name: str) -> float | None:
    """Pinned predicted cold-compile wall for a registered graph —
    a dict lookup, NO tracing (safe on every hot path)."""
    pin = pinned(name)
    if not pin:
        return None
    v = pin.get("predicted_s")
    return None if v is None else float(v)


# ---------------------------------------------------------------------------
# Stage-name resolution (the warmup recorder's vocabulary)
# ---------------------------------------------------------------------------

# dispatch stage name (ops/pk/kernels._stage_call, protocol/batch
# _warm_timed) -> the registered graph that is its closest structural
# twin. The per-stage pk jits wrap exactly the *_core programs plus
# relayout glue; the packed/fused monoliths map to the composed
# registry graphs. `unpack_<digest>` stage names (layout-keyed) all
# resolve to packed_unpack. The `reduce` stage (bit packing and a cast:
# protocol/batch.verdict_pack) has no registered twin: the
# `verdict_reduce` graph is the retired on-device nonce scan, so its
# hash must not ride that stage's first-execute note.
STAGE_GRAPHS: dict[str, str] = {
    "ed": "ed_core",
    "kes": "kes_core",
    "vrf": "vrf_core",
    "vrf_bc": "vrf_bc_core",
    "finish": "finish_core",
    "relayout": "packed_unpack",
    "relayout_bc": "packed_unpack",
    "unpack": "packed_unpack",
    "agg-packed": "aggregate_core",
    "agg-vrf": "aggregate_vrf_core",
    "xla-packed": "verify_praos_core_bc",
    "xla-fused": "verify_praos_core",
    "xla-fused-bc": "verify_praos_core_bc",
    "msm": "msm",
}


def stage_graph(stage: str) -> str | None:
    """Registered-graph twin of a warmup stage label (strips the
    `@b<bucket>`, `:<lanes>l` and `:<layout>` qualifiers). The
    xla-packed label embeds the staged proof length (`:p80` draft-03 /
    `:p128` batch-compatible — protocol/batch._jitted_packed_xla),
    which selects between the two composed twins."""
    base = stage.split("@", 1)[0].split(":", 1)[0]
    if base.startswith("unpack_"):
        base = "unpack"
    if base == "xla-packed":
        return ("verify_praos_core" if ":p80" in stage
                else "verify_praos_core_bc")
    return STAGE_GRAPHS.get(base)


def stage_feature_hash(stage: str) -> str | None:
    """Pinned feature hash of the graph a warmup stage label names:
    scripts/fit_costmodel.py joins a report's stage walls to their
    static features through it (rounds banked before PR 34 carry the
    hash on the note; a note without one is joined by its label). Dict
    lookups only. `check_pins` keeps the pin equal to the tree's
    freshly-extracted features, so the label's pin is current structure
    on a green tree."""
    g = stage_graph(stage)
    if g is None:
        return None
    pin = pinned(g)
    return pin.get("feature_hash") if pin else None


def check_pins(features: list[CostFeatures]) -> list[str]:
    """Pin-freshness gate (scripts/lint.py, rides the cost pass): each
    graph's freshly-extracted feature hash must match its
    costmodel.json pin. A stale pin would join measured walls to the
    features of an OLD structure."""
    out: list[str] = []
    for f in features:
        pin = pinned(f.name)
        if pin is None:
            out.append(
                f"{f.name}: no costmodel.json pin "
                "(run scripts/lint.py --update-costs)"
            )
        elif pin.get("feature_hash") != f.hash():
            out.append(
                f"{f.name}: jaxpr features drifted from the "
                "costmodel.json pin — measured walls would join a "
                "stale structure (run scripts/lint.py --update-costs)"
            )
    return out


# ---------------------------------------------------------------------------
# Ratchet + pathology advisories (budgets.json "compile_wall")
# ---------------------------------------------------------------------------


def check_compile_wall(features: list[CostFeatures],
                       budgets: dict | None = None) -> list[str]:
    """Fifth ratcheted metric: per-graph predicted cold-compile walls
    vs budgets.json's "compile_wall" ceilings (scripts/lint.py exit 5).
    A registered graph missing from the section is itself a violation;
    the pathology advisories ride along so a violation names WHAT to
    split, not just that the prediction grew."""
    budgets = budgets if budgets is not None else graphs.load_budgets()
    sec = budgets.get("compile_wall", {})
    per_graph = sec.get("graphs", {})
    violations: list[str] = []
    for f in features:
        cfg = per_graph.get(f.name)
        if cfg is None:
            violations.append(
                f"{f.name}: no compile_wall entry in budgets.json "
                "(run scripts/lint.py --update-costs to pin it)"
            )
            continue
        pred = predict(f)
        if pred is None:
            violations.append(
                f"{f.name}: no fitted cost model "
                "(run scripts/fit_costmodel.py)"
            )
            continue
        ceiling = float(cfg["predicted_s_max"])
        adv = advisories(f, budgets)
        if pred > ceiling:
            msg = (f"{f.name}: predicted cold-compile wall {pred:.1f}s "
                   f"exceeds budget {ceiling:g}s")
            if adv:
                msg += " — " + "; ".join(adv)
            violations.append(msg)
        else:
            # the pathology detector fires on its own: a monolith or an
            # unfenced chain over the advisory budget is a violation
            # even while the wall prediction still fits its ceiling
            violations.extend(f"{f.name}: {a}" for a in adv)
    return violations


def advisories(f: CostFeatures, budgets: dict | None = None) -> list[str]:
    """Pathology detector: monolith computations and unfenced multiply
    chains over the advisory budget, each naming the source fence to
    split (the remediation PR 1 already proved works)."""
    budgets = budgets if budgets is not None else graphs.load_budgets()
    adv = budgets.get("compile_wall", {}).get("advisory", {})
    out: list[str] = []
    monolith = adv.get("monolith_eqns")
    if monolith and f.max_comp_eqns > int(monolith):
        out.append(
            f"monolith computation of {f.max_comp_eqns} eqns "
            f"({f.monolith_src}) exceeds the {monolith}-eqn advisory: "
            "split it behind a fori_loop/scan fence"
        )
    chain = adv.get("unfenced_chain")
    if chain and f.mul_chain_depth > int(chain):
        out.append(
            f"unfenced multiply chain of depth {f.mul_chain_depth} "
            f"(deepest at {f.chain_src}) exceeds the {chain}-deep "
            "advisory: fence the chain (fori_loop/scan) before the "
            "algebraic simplifier chews on it"
        )
    return out
