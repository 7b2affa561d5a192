"""CLI: `python -m ouroboros_consensus_tpu.analysis [subcommand] [options]`.

Default run = both static passes over the package + the registered
kernel graphs (AST rules, jaxpr budgets, point-op budgets).

Subcommands:
  range      octrange interval/overflow certification (analysis/absint)
  taint      octrange secret-taint certification
  pointops   per-lane point-op counts vs their budgets.json ceilings
  cost       octwall predicted cold-compile walls vs the budgets.json
             "compile_wall" ceilings (analysis/costmodel)
  resources  device-resource pins (FLOPs / bytes accessed / peak HBM,
             obs/resources.py) vs the budgets.json "device_resources"
             section: hash-freshness + ceiling compares only — traces
             for the fresh feature hashes, never compiles
  sync       octsync concurrency & durability-protocol sweep
             (analysis/concurrency.py): lock-order / guarded-attribute
             / thread-lifecycle / tmp-fsync-rename checkers vs the
             analysis/concurrency.json ratchet. Pure AST — never
             imports jax
  flow       octflow exception-routing & degradation-lattice sweep
             (analysis/flow.py): raise-classification / corruption-
             laundering / verdict-fabrication / lattice-completeness /
             kill-switch-integrity / re-dispatch-pinning checkers vs
             the analysis/flow.json ratchet. Pure AST — never imports
             jax

Shared options:
  --json            machine-readable report on stdout (keys sorted —
                    stable for CI diffing)
  --graphs G [G...] restrict to these graphs

Default-run options:
  --paths P [P...]  lint these packages/files instead of the package
  --no-graphs       skip Pass 2 (pure AST run, no jax import)
  --all             include suppressed findings in the report
  --baseline B      subtract baselined finding keys (ratchet mode —
                    scripts/lint.py drives this)

range/taint options:
  --tier {fast,full}  lane-sweep tier from shapes.json (default fast)
  --no-ratchet        report only; skip the certified.json comparison

sync options:
  --paths P [P...]  sweep these files/dirs instead of the default roots
                    (package + scripts/ + bench.py)
  --all             include suppressed findings in the report
  --no-ratchet      report only; skip the concurrency.json comparison

flow options:
  --paths P [P...]  sweep these files/dirs instead of the default roots
                    (package + scripts/ + bench.py); partial sweeps
                    skip the whole-tree FLOW305 lever audit
  --all             include suppressed findings in the report
  --no-ratchet      report only; skip the flow.json comparison

Exit codes (distinct so CI can tell WHY the gate failed):
  0  clean
  1  unsuppressed AST finding(s)
  2  usage error (argparse)
  3  jaxpr-metric or point-op budget violation
  4  certification failure (range proof lost / taint ratchet violation)
  5  compile-wall ratchet violation (predicted cold-compile wall over
     its budgets.json "compile_wall" ceiling)
  6  device-resource ratchet violation (a registry graph without a
     "device_resources" pin, a stale-structure pin — feature hash no
     longer matching the traced graph — or a pinned FLOP/byte/peak-HBM
     value over its ceiling)
  7  octsync concurrency ratchet violation (a new unsuppressed
     lock/thread/durability finding, lock-or-thread inventory drift,
     or a stale suppression)
  8  octflow failure-taxonomy ratchet violation (a new unsuppressed
     FLOW3xx exception-routing finding, raise-site/handler/rung-edge/
     lever inventory drift, or a stale suppression)
When several classes fire at once the lowest code wins
(1 < 3 < 4 < 5 < 6 < 7 < 8).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from . import astlint, graphs

EXIT_OK = 0
EXIT_FINDINGS = 1
EXIT_BUDGET = 3
EXIT_CERT = 4
EXIT_COST = 5
EXIT_RESOURCES = 6
EXIT_SYNC = 7
EXIT_FLOW = 8


def _package_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _pin_cpu() -> None:
    # abstract tracing never needs an accelerator — pin the platform
    # BEFORE the first backend touch so the lint gate neither waits on
    # nor takes the chip
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass  # already initialized (e.g. under pytest conftest)


def _emit(payload: dict, as_json: bool, lines: list[str]) -> None:
    if as_json:
        print(json.dumps(payload, indent=2, sort_keys=True))
    else:
        for ln in lines:
            print(ln)


def _cmd_certify(args, domain: str) -> int:
    from . import absint

    _pin_cpu()
    names = args.graphs or [
        n for n in absint.certifiable_graphs()
        if domain in absint._spec_of(n).get("domains", ["range", "taint"])
    ]
    shapes = absint.load_shapes()
    reports = []
    for name in names:
        if domain == "range":
            for lanes in (
                [args.lanes] if args.lanes is not None
                else absint.sweep_lanes(name, args.tier, shapes)
            ):
                reports.append(absint.certify_range(name, lanes, shapes))
        else:
            lanes = (args.lanes if args.lanes is not None
                     else absint.sweep_lanes(name, args.tier, shapes)[0])
            reports.append(absint.certify_taint(name, lanes, shapes))
    violations: list[str] = []
    if not args.no_ratchet:
        violations = absint.check_certified(reports)
    failed = [r for r in reports if not r.ok]
    lines = []
    for r in reports:
        lanes = "default" if r.lanes is None else r.lanes
        status = "ok" if r.ok else "FAIL"
        extra = (" lane-universal" if r.domain == "range"
                 and r.lane_universal else "")
        lines.append(
            f"{r.graph}@{lanes} [{r.domain}] {status}: "
            f"{len(r.findings)} finding(s), {r.eqns} eqns{extra}"
        )
        lines.extend(f"  {f.format()}" for f in r.findings)
    lines.extend(f"RATCHET: {v}" for v in violations)
    lines.append(
        f"octrange {domain}: {len(failed)} failing graph-sweep(s), "
        f"{len(violations)} ratchet violation(s)"
    )
    _emit(
        {
            "domain": domain,
            "reports": [r.to_dict() for r in reports],
            "ratchet_violations": violations,
            "ok": not (failed or violations),
        },
        args.json, lines,
    )
    return EXIT_CERT if (failed or violations) else EXIT_OK


def _cmd_cost(args) -> int:
    """octwall: per-graph compile-cost features + predicted walls vs
    the budgets.json compile_wall ceilings (sorted-keys --json is
    byte-stable for CI diffing)."""
    from . import absint, costmodel

    _pin_cpu()
    budgets = graphs.load_budgets(args.budgets)
    names = args.graphs or graphs.registered_graphs()
    shapes = absint.load_shapes()
    # trace at the fast-sweep lane counts — the SAME traces the lint
    # gate pins against, so the drift note below is meaningful
    feats = [
        costmodel.graph_features(
            n, absint.sweep_lanes(n, "fast", shapes)[0]
        )
        for n in names
    ]
    rows = []
    for f in feats:
        pred = costmodel.predict(f)
        pin = costmodel.pinned(f.name) or {}
        rows.append({
            "graph": f.name,
            "features": f.to_dict(),
            "feature_hash": f.hash(),
            "predicted_s": None if pred is None else round(pred, 1),
            "pinned_hash": pin.get("feature_hash"),
            "advisories": costmodel.advisories(f, budgets),
        })
    violations = costmodel.check_compile_wall(feats, budgets)
    lines = []
    for r in rows:
        pred = "?" if r["predicted_s"] is None else f"{r['predicted_s']}s"
        drift = ("" if r["pinned_hash"] in (None, r["feature_hash"])
                 else " [features drifted from pin]")
        lines.append(
            f"{r['graph']}: predicted {pred} "
            f"(eqns={r['features']['eqns']} "
            f"max_comp={r['features']['max_comp_eqns']} "
            f"chain={r['features']['mul_chain_depth']}){drift}"
        )
        # advisories stay in the JSON rows; the text report leaves them
        # to check_compile_wall's COST lines (single source, no dupes)
    lines.extend(f"COST: {v}" for v in violations)
    lines.append(f"octwall: {len(violations)} violation(s)")
    _emit({"cost": rows, "violations": violations,
           "ok": not violations}, args.json, lines)
    return EXIT_COST if violations else EXIT_OK


def _cmd_resources(args) -> int:
    """Device-resource ratchet status (sorted-keys --json is byte-stable
    for CI diffing). Traces each graph once for the fresh octwall
    feature hash — the staleness key — but never lowers or compiles;
    regeneration is scripts/lint.py --update-resources."""
    from ..obs import resources as obs_res
    from . import absint, costmodel

    _pin_cpu()
    budgets = graphs.load_budgets(args.budgets)
    names = args.graphs or graphs.registered_graphs()
    shapes = absint.load_shapes()
    feats = [
        costmodel.graph_features(
            n, absint.sweep_lanes(n, "fast", shapes)[0]
        )
        for n in names
    ]
    rows = obs_res.resources_payload(names, budgets, feats)
    violations = obs_res.check_device_resources(feats, budgets)
    lines = []
    for name in sorted(rows):
        r = rows[name]
        pin = r["pin"]
        if pin is None:
            lines.append(f"{name}: NO PIN")
            continue
        status = "fresh" if r["fresh"] else "STALE-STRUCTURE"
        lines.append(
            f"{name}@{pin.get('at_lanes')}: "
            f"flops={pin.get('flops')} "
            f"bytes={pin.get('bytes_accessed')} "
            f"peak_hbm={pin.get('peak_hbm_bytes')} [{status}]"
        )
    lines.extend(f"RESOURCES: {v}" for v in violations)
    lines.append(f"resources: {len(violations)} violation(s)")
    _emit({"resources": rows, "violations": violations,
           "ok": not violations}, args.json, lines)
    return EXIT_RESOURCES if violations else EXIT_OK


def _cmd_sync(args) -> int:
    """octsync: concurrency & durability-protocol sweep vs the
    concurrency.json ratchet (sorted-keys --json is byte-stable for CI
    diffing). Pure AST — jax is never imported on this route."""
    from . import concurrency

    repo = os.path.dirname(_package_root())
    paths = args.paths or concurrency.default_roots(repo)
    report = concurrency.sweep_paths(
        paths, repo, concurrency.load_roots()
    )
    violations: list[str] = []
    stale: list[str] = []
    if not args.no_ratchet:
        violations, stale = concurrency.check_sync(
            report, concurrency.load_baseline()
        )
    shown = (report.findings if args.all
             else [f for f in report.findings if not f.suppressed])
    lines = [f.format() for f in shown]
    lines.extend(f"SYNC: {v}" for v in violations)
    lines.extend(
        f"note: concurrency baseline entry no longer fires "
        f"(run scripts/lint.py --update-sync to ratchet): {k}"
        for k in stale
    )
    n_sup = sum(1 for f in report.findings if f.suppressed)
    lines.append(
        f"octsync: {len(shown)} finding(s), {n_sup} suppressed, "
        f"{len(violations)} ratchet violation(s), "
        f"{len(stale)} stale ratchet entr(y/ies)"
    )
    _emit(
        {
            "findings": [
                {
                    "rule": f.rule,
                    "path": f.path,
                    "line": f.line,
                    "col": f.col,
                    "message": f.message,
                    "suppressed": f.suppressed,
                    "key": f.key(),
                }
                for f in shown
            ],
            "inventory": report.inventory,
            "violations": violations,
            "stale": stale,
            "ok": not violations,
        },
        args.json, lines,
    )
    return EXIT_SYNC if violations else EXIT_OK


def _cmd_flow(args) -> int:
    """octflow: exception-routing & degradation-lattice sweep vs the
    flow.json ratchet (sorted-keys --json is byte-stable for CI
    diffing). Pure AST — jax is never imported on this route."""
    from . import flow

    repo = os.path.dirname(_package_root())
    paths = args.paths or flow.default_roots(repo)
    cfg = flow.load_roots()
    if args.paths:
        # FLOW305 lever integrity is a whole-tree property — a partial
        # --paths sweep would read none of the documented levers and
        # drown the report in dead-lever noise
        cfg["kill_switches"] = []
    report = flow.sweep_paths(paths, repo, cfg)
    violations: list[str] = []
    stale: list[str] = []
    if not args.no_ratchet:
        violations, stale = flow.check_flow(report, flow.load_baseline())
    shown = (report.findings if args.all
             else [f for f in report.findings if not f.suppressed])
    lines = [f.format() for f in shown]
    lines.extend(f"FLOW: {v}" for v in violations)
    lines.extend(
        f"note: flow baseline entry no longer fires "
        f"(run scripts/lint.py --update-flow to ratchet): {k}"
        for k in stale
    )
    n_sup = sum(1 for f in report.findings if f.suppressed)
    lines.append(
        f"octflow: {len(shown)} finding(s), {n_sup} suppressed, "
        f"{len(violations)} ratchet violation(s), "
        f"{len(stale)} stale ratchet entr(y/ies)"
    )
    _emit(
        {
            "findings": [
                {
                    "rule": f.rule,
                    "path": f.path,
                    "line": f.line,
                    "col": f.col,
                    "message": f.message,
                    "suppressed": f.suppressed,
                    "key": f.key(),
                }
                for f in shown
            ],
            "inventory": report.inventory,
            "violations": violations,
            "stale": stale,
            "ok": not violations,
        },
        args.json, lines,
    )
    return EXIT_FLOW if violations else EXIT_OK


def _cmd_pointops(args) -> int:
    _pin_cpu()
    budgets = graphs.load_budgets(args.budgets)
    sec = budgets.get("point_ops", {})
    names = args.graphs or sorted(sec)
    rows = []
    for name in names:
        cfg = sec.get(name)
        lanes = int(cfg["at_lanes"]) if cfg else None
        stats = graphs.point_ops(name, lanes)
        rows.append({
            "graph": name,
            "at_lanes": lanes,
            "ops": stats["ops"],
            "lane_ops": stats["lane_ops"],
            "lane_ops_per_lane": (
                stats["lane_ops"] / lanes if lanes else None
            ),
            "budget": cfg["lane_ops_per_lane"] if cfg else None,
        })
    violations = graphs.check_point_ops(budgets, names=names)
    lines = [
        f"{r['graph']}@{r['at_lanes']}: {r['lane_ops_per_lane']:.1f} "
        f"lane-ops/lane (budget {r['budget']})"
        for r in rows
    ]
    lines.extend(f"BUDGET: {v}" for v in violations)
    lines.append(f"pointops: {len(violations)} violation(s)")
    _emit({"point_ops": rows, "violations": violations,
           "ok": not violations}, args.json, lines)
    return EXIT_BUDGET if violations else EXIT_OK


def _cmd_default(args) -> int:
    paths = args.paths or [_package_root()]
    findings = astlint.lint_paths(paths)

    # default runs also report rule coverage over the purpose-built
    # fixtures (tests/lint_fixtures) — a self-check that every rule
    # still fires; fixture findings never affect the exit status
    fixture_rules: list[str] = []
    if not args.paths:
        fdir = os.path.join(
            os.path.dirname(_package_root()), "tests", "lint_fixtures"
        )
        if os.path.isdir(fdir):
            fixture_rules = sorted({
                f.rule for f in astlint.lint_paths([fdir])
            })

    baseline_keys: set[str] = set()
    if args.baseline:
        with open(args.baseline, encoding="utf-8") as f:
            baseline_keys = set(json.load(f).get("findings", []))

    active = [
        f for f in findings
        if not f.suppressed and f.key() not in baseline_keys
    ]
    shown = findings if args.all else active

    reports: list[graphs.GraphReport] = []
    violations: list[str] = []
    if not args.no_graphs:
        _pin_cpu()
        budgets = graphs.load_budgets(args.budgets)
        reports = graphs.analyze_registered(args.graphs)
        violations = graphs.check_budgets(reports, budgets)
        violations += graphs.check_point_ops(budgets, names=args.graphs)

    failed = bool(active or violations)

    if args.json:
        out = {
            "findings": [
                {
                    "rule": f.rule,
                    "path": f.path,
                    "line": f.line,
                    "col": f.col,
                    "message": f.message,
                    "suppressed": f.suppressed,
                    "key": f.key(),
                }
                for f in shown
            ],
            "rules_fired": sorted({f.rule for f in shown}),
            "fixture_rules_fired": fixture_rules,
            "graphs": [r.to_dict() for r in reports],
            "budget_violations": violations,
            "ok": not failed,
        }
        print(json.dumps(out, indent=2, sort_keys=True))
    else:
        for f in shown:
            print(f.format())
        for r in reports:
            print(
                f"graph {r.name}: eqns={r.eqns} muls={r.mul_count} "
                f"mul_chain_depth={r.mul_chain_depth} "
                f"fanout={r.op_fanout} remat_width={r.remat_width} "
                f"computations={r.computations}"
            )
        for v in violations:
            print(f"BUDGET: {v}")
        n_sup = sum(1 for f in findings if f.suppressed)
        extra = (
            f", fixture rules firing: {'/'.join(fixture_rules)}"
            if fixture_rules else ""
        )
        print(
            f"octlint: {len(active)} finding(s), {n_sup} suppressed, "
            f"{len(violations)} budget violation(s){extra}"
        )
    if active:
        return EXIT_FINDINGS
    return EXIT_BUDGET if violations else EXIT_OK


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="ouroboros_consensus_tpu.analysis")
    sub = ap.add_subparsers(dest="cmd")

    def common(p, with_choices=True):
        p.add_argument("--json", action="store_true")
        p.add_argument(
            "--graphs", nargs="+", default=None,
            choices=None if not with_choices else None,
        )
        p.add_argument("--budgets", default=None,
                       help="alternate budgets.json")

    common(ap)
    ap.add_argument("--paths", nargs="+", default=None)
    ap.add_argument("--no-graphs", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="include suppressed findings")
    ap.add_argument("--baseline", default=None,
                    help="baseline.json of grandfathered finding keys")

    for name in ("range", "taint"):
        p = sub.add_parser(name)
        common(p)
        p.add_argument("--tier", choices=("fast", "full"), default="fast")
        p.add_argument("--lanes", type=int, default=None,
                       help="override the swept lane count")
        p.add_argument("--no-ratchet", action="store_true",
                       help="skip the certified.json comparison")

    common(sub.add_parser("pointops"))
    common(sub.add_parser("cost"))
    common(sub.add_parser("resources"))

    p = sub.add_parser("sync")
    p.add_argument("--json", action="store_true")
    p.add_argument("--paths", nargs="+", default=None)
    p.add_argument("--all", action="store_true",
                   help="include suppressed findings")
    p.add_argument("--no-ratchet", action="store_true",
                   help="skip the concurrency.json comparison")

    p = sub.add_parser("flow")
    p.add_argument("--json", action="store_true")
    p.add_argument("--paths", nargs="+", default=None)
    p.add_argument("--all", action="store_true",
                   help="include suppressed findings")
    p.add_argument("--no-ratchet", action="store_true",
                   help="skip the flow.json comparison")

    args = ap.parse_args(argv)
    if args.cmd in ("range", "taint"):
        return _cmd_certify(args, args.cmd)
    if args.cmd == "pointops":
        return _cmd_pointops(args)
    if args.cmd == "cost":
        return _cmd_cost(args)
    if args.cmd == "resources":
        return _cmd_resources(args)
    if args.cmd == "sync":
        return _cmd_sync(args)
    if args.cmd == "flow":
        return _cmd_flow(args)
    # default-run graph names must be registered (certification targets
    # include aux graphs; the default run's budget pass does not)
    if args.graphs:
        bad = set(args.graphs) - set(graphs.registered_graphs())
        if bad:
            ap.error(f"unknown graphs: {sorted(bad)}")
    return _cmd_default(args)


if __name__ == "__main__":
    sys.exit(main())
