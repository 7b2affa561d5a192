#!/usr/bin/env python
"""Fit / validate the octwall compile-cost model (analysis/costmodel).

    python scripts/fit_costmodel.py --measure   # compile the calibration
                                                #   set on this box, fit,
                                                #   write costmodel.json
    python scripts/fit_costmodel.py --fit       # re-fit from the stored
                                                #   rows + banked bench
                                                #   warmup reports
    python scripts/fit_costmodel.py --check     # predicted-vs-measured:
                                                #   >= 80% of calibrated
                                                #   stages within 2x, else
                                                #   exit 1

Calibration rows come from two sources and are joined by the costmodel
feature hash, so every measured wall is matched EXACTLY to the static
features of the graph structure it was measured against:

  1. local calibration runs (--measure): a spread of synthetic jaxprs
     (multiply chains unfenced vs fori-fenced, elementwise ladders,
     scan bodies, dot stacks) plus the small/medium registry graphs,
     each compiled ONCE on this box (JAX_PLATFORMS=cpu) with its
     first-execute wall timed the same way obs/warmup.py times
     production stages;
  2. the per-stage first-execute walls the warmup recorder banks into
     BENCH round JSONs (`parsed.warmup_report.stages` — via=jit rows
     of PRs 8-33 carry a feature_hash and join only while it equals
     the pin's; a row without one joins the pin its stage label names).

The model extrapolates to the composed monoliths (aggregate_core at
330k eqns) from the measured small/medium spread — that extrapolation
is what the compile-wall ratchet needs: a structural estimate good to
~2x, not a profiler.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from ouroboros_consensus_tpu.analysis import costmodel, graphs  # noqa: E402

# registry graphs cheap enough to compile on the 1-core box; the
# composed cores (224k-330k eqns, many minutes each on XLA:CPU) are
# prediction targets, not calibration targets
MEASURE_REGISTRY = (
    "verdict_reduce", "packed_unpack", "msm", "finish_core", "ed_core",
)
MEASURE_REGISTRY_FULL = MEASURE_REGISTRY + ("kes_core", "vrf_core")


def _sds(shape, dtype="float32"):
    import jax
    from jax import numpy as jnp

    return jax.ShapeDtypeStruct(shape, getattr(jnp, dtype))


def _syn_chain(depth: int, fenced: bool):
    """An unrolled multiply chain of `depth` (the algebraic-simplifier
    pathology shape) or its fori_loop-fenced twin."""

    def unfenced(x):
        for _ in range(depth):
            x = x * x + x
        return x

    def fori(x):
        from jax import lax

        return lax.fori_loop(0, depth, lambda _, v: v * v + v, x)

    return (fori if fenced else unfenced), (_sds((32,)),)


def _syn_elementwise(n: int):
    def fn(x):
        for i in range(n):
            x = x + (x * 0.5 if i % 3 else x - 0.25)
        return x

    return fn, (_sds((64,)),)


def _syn_scan(body: int, length: int):
    def fn(x):
        from jax import lax

        def step(c, _):
            for i in range(body):
                c = c + c * 0.5 if i % 2 else c - 0.125
            return c, c

        out, _ = lax.scan(step, x, None, length=length)
        return out

    return fn, (_sds((32,)),)


def _syn_dots(n: int):
    def fn(x):
        from jax import numpy as jnp

        for _ in range(n):
            x = jnp.dot(x, x) / 17.0
        return x

    return fn, (_sds((16, 16)),)


def _syn_wide(fanout: int):
    def fn(x):
        parts = [x * (i + 1) for i in range(fanout)]
        return sum(parts)

    return fn, (_sds((64,)),)


def _syn_fences(n: int, body: int):
    """Many small fenced subcomputations (the split-stage shape)."""

    def fn(x):
        from jax import lax

        for _ in range(n):
            x = lax.fori_loop(0, 3, lambda _i, v: _chain_body(v, body), x)
        return x

    return fn, (_sds((32,)),)


def _chain_body(v, body):
    for i in range(body):
        v = v * 0.5 + v if i % 2 else v - 0.25
    return v


SYNTHETIC = {
    "syn_chain_64": _syn_chain(64, False),
    "syn_chain_256": _syn_chain(256, False),
    "syn_chain_640": _syn_chain(640, False),
    "syn_chain_640_fenced": _syn_chain(640, True),
    "syn_ew_512": _syn_elementwise(512),
    "syn_ew_2048": _syn_elementwise(2048),
    "syn_ew_8192": _syn_elementwise(8192),
    "syn_scan_200x8": _syn_scan(200, 8),
    "syn_scan_2000x4": _syn_scan(2000, 4),
    "syn_dots_64": _syn_dots(64),
    "syn_dots_256": _syn_dots(256),
    "syn_wide_256": _syn_wide(256),
    "syn_fences_48x16": _syn_fences(48, 16),
}


def _zeros_for(args):
    import numpy as np

    return [np.zeros(a.shape, dtype=a.dtype) for a in args]


def measure_one(name: str, fn, args) -> dict:
    """Trace (features) + compile-inclusive first-execute wall, timed
    exactly the way obs/warmup.py times a production stage."""
    import jax

    traced = jax.make_jaxpr(fn)(*args)
    feats = costmodel.extract_features(traced, name)
    concrete = _zeros_for(args)
    jitted = jax.jit(fn)
    t0 = time.monotonic()
    out = jitted(*concrete)
    jax.block_until_ready(out)
    wall = time.monotonic() - t0
    return {
        "stage": name,
        "graph": name if name in graphs.REGISTRY else None,
        "features": feats.to_dict(),
        "feature_hash": feats.hash(),
        "measured_s": round(wall, 3),
        "via": "local-calibration",
    }


def measure(full: bool = False) -> list[dict]:
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass
    rows = []
    targets = dict(SYNTHETIC)
    for g in (MEASURE_REGISTRY_FULL if full else MEASURE_REGISTRY):
        targets[g] = graphs.REGISTRY[g](None)
    for name, (fn, args) in targets.items():
        t0 = time.monotonic()
        row = measure_one(name, fn, args)
        rows.append(row)
        print(f"  {name:24s} eqns={row['features']['eqns']:>7d} "
              f"first-execute {row['measured_s']:7.2f}s "
              f"(total {time.monotonic()-t0:.1f}s)", flush=True)
    return rows


def bench_rows(pattern: str) -> tuple[list[dict], int]:
    """Joinable warmup-report stage walls from banked BENCH round JSONs;
    second result = rows seen but NOT joinable (no registered twin, or
    a carried hash that drifted from the current pin)."""
    rows, unjoined = [], 0
    for path in sorted(glob.glob(pattern)):
        try:
            with open(path, encoding="utf-8") as f:
                d = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        parsed = d.get("parsed") if isinstance(d.get("parsed"), dict) else d
        wr = (parsed or {}).get("warmup_report") or {}
        for stage, info in (wr.get("stages") or {}).items():
            if info.get("via") == "aot":
                continue  # an AOT load, not a compile
            # rounds banked before PR 34 carry the hash on the note (a
            # stale one fails to join); later notes join by their label
            h = (info.get("feature_hash")
                 or costmodel.stage_feature_hash(stage))
            g = costmodel.stage_graph(stage)
            pin = costmodel.pinned(g) if g else None
            if not h or not pin or pin.get("feature_hash") != h:
                unjoined += 1
                continue
            rows.append({
                "stage": f"{os.path.basename(path)}:{stage}",
                "graph": g,
                "features": pin["features"],
                "feature_hash": h,
                "measured_s": float(info["wall_s"]),
                "via": "bench-warmup",
            })
    return rows, unjoined


def check(rows: list[dict], model: dict | None) -> int:
    """Predicted-vs-measured: >= 80% of calibrated stages within 2x."""
    if not rows:
        print("no calibration rows to validate (run --measure first)")
        return 1
    if not model:
        print("no fitted model (run --measure or --fit first)")
        return 1
    n_ok = 0
    for r in rows:
        pred = costmodel.predict(r["features"], model)
        meas = max(1e-3, float(r["measured_s"]))
        ratio = pred / meas
        ok = 0.5 <= ratio <= 2.0
        n_ok += ok
        print(f"  {r['stage']:40s} measured {meas:8.2f}s "
              f"predicted {pred:8.2f}s x{ratio:5.2f} "
              f"{'ok' if ok else 'MISS'}")
    frac = n_ok / len(rows)
    print(f"check: {n_ok}/{len(rows)} within 2x ({frac:.0%}; need >= 80%)")
    return 0 if frac >= 0.8 else 1


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--measure", action="store_true",
                    help="compile the calibration set, fit, write")
    ap.add_argument("--full", action="store_true",
                    help="include the slower registry graphs in --measure")
    ap.add_argument("--fit", action="store_true",
                    help="re-fit from stored rows + bench reports")
    ap.add_argument("--check", action="store_true",
                    help="validate predicted-vs-measured (>=80% within 2x)")
    ap.add_argument("--bench-glob",
                    default=os.path.join(REPO, "BENCH_r*.json"))
    args = ap.parse_args(argv)

    try:
        stored = costmodel.load_cost()
    except (OSError, ValueError):
        stored = {}
    calibration = list(stored.get("calibration", []))
    joined, unjoined = bench_rows(args.bench_glob)
    print(f"bench warmup reports: {len(joined)} joinable stage wall(s), "
          f"{unjoined} unjoinable (pre-hash rounds / drifted features / "
          "aot loads)")

    if args.measure:
        print("measuring calibration set (compile-inclusive first "
              "executes, JAX_PLATFORMS=cpu):", flush=True)
        calibration = measure(full=args.full)

    all_rows = calibration + joined
    if args.measure or args.fit:
        import jax

        backend = f"cpu/jax-{jax.__version__}"
        model = costmodel.fit_model(
            [(r["features"], r["measured_s"]) for r in all_rows],
            backend=backend,
        )
        costmodel.write_cost(model=model, calibration=calibration)
        print(f"costmodel.json: model re-fit on {len(all_rows)} row(s) "
              f"({backend}); coeffs: "
              f"{ {k: v for k, v in model['coeffs'].items() if v} }")
        print("(predicted_s pins recomputed from stored features; run "
              "scripts/lint.py --update-costs after structural changes)")

    if args.check:
        try:
            model = costmodel.load_cost().get("model")
        except (OSError, ValueError):
            model = None
        return check(all_rows, model)
    return 0


if __name__ == "__main__":
    sys.exit(main())
