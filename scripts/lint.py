#!/usr/bin/env python
"""Repo-wide octlint + octrange gate: all static-analysis passes,
ratcheted.

    python scripts/lint.py                    # AST + budgets + point-ops
                                              #   + octrange certification
                                              #   + octwall compile costs
    python scripts/lint.py --no-graphs        # AST pass only (no jax)
    python scripts/lint.py --changed          # re-trace only graphs whose
                                              #   source modules differ from
                                              #   git HEAD (fast path)
    python scripts/lint.py --tier full        # full lane sweeps
    python scripts/lint.py --update-baseline  # re-grandfather AST keys
    python scripts/lint.py --update-certified # re-pin certification
    python scripts/lint.py --update-costs     # re-pin compile-cost features
                                              #   + compile_wall ceilings
    python scripts/lint.py --update-resources # re-measure + re-pin the
                                              #   device_resources section
                                              #   (lowers AND COMPILES every
                                              #   registry graph — slow)
    python scripts/lint.py --update-sync      # re-pin the octsync
                                              #   concurrency ratchet
                                              #   (analysis/concurrency.json)
    python scripts/lint.py --update-flow      # re-pin the octflow
                                              #   failure-taxonomy ratchet
                                              #   (analysis/flow.json)

Exit 0 = no NEW AST findings (anything in analysis/baseline.json is
grandfathered), every registered kernel graph within its
analysis/budgets.json ceilings (jaxpr metrics AND per-lane point-ops),
zero equation growth from telemetry on the instrumentation-purity
graphs (budgets.json "instrumentation_purity": the obs flight recorder
must stay host-side), every certification pin in
analysis/certified.json still holding (range proofs intact, no new
taint findings), and every graph's octwall predicted cold-compile wall
under its budgets.json "compile_wall" ceiling. Nonzero exits mirror
`python -m ouroboros_consensus_tpu.analysis`: 1 = new AST finding(s),
2 = registry drift (a REGISTRY/aux entry without a shapes.json spec or
source mapping — gate misconfiguration, checked before anything
traces), 3 = budget violation(s), 4 = certification ratchet
violation(s), 5 = compile-wall ratchet violation(s), 6 = device-resource
ratchet violation(s) (budgets.json "device_resources": a registry graph
without a pin, a pin whose octwall feature hash no longer matches the
traced structure, or a pinned FLOP/byte/peak-HBM value over its
ceiling — obs/resources.check_device_resources; the check is dict
compares only, the compiles run solely under --update-resources),
7 = octsync concurrency/durability ratchet violation(s) (Pass 5,
analysis/concurrency.py: a new unsuppressed SYNC2xx finding — lock-order
inversion, unguarded `# guarded-by:` attribute, silent thread death,
bare write to a protected store path — or drift in the pinned
lock/thread/guarded inventory vs analysis/concurrency.json; pure AST,
runs even under --no-graphs),
8 = octflow failure-taxonomy ratchet violation(s) (Pass 6,
analysis/flow.py: a new unsuppressed FLOW3xx finding — an unclassified
raise in the durable planes, a laundered REFUSE/REPAIR class inside the
recovery ladder, a silent broad handler on a verdict path, a device
dispatch unreachable from a host-reference protector, a dead or
re-entrant OCT_*=0 kill-switch lever, an unpinned anomaly re-dispatch —
drift in the pinned raise-site/handler/rung-edge/lever inventory vs
analysis/flow.json, or a README kill-switch row out of sync with the
pinned lever inventory (analysis/envlevers.check_kill_switches); pure
AST, runs even under --no-graphs). The
ratchet files only ever shrink in normal operation — fixing a
grandfathered finding makes its key stale, and the gate prints a
reminder to re-run the matching --update flag so the ratchet tightens.

One trace per graph feeds all four jaxpr passes: the gate traces each
graph at its fast-sweep lane count (production 8192 for the
lane-sensitive graphs, the registry tile otherwise) and the budget
metrics, point-op counts, certification AND compile-cost features all
read that cached trace.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from ouroboros_consensus_tpu.analysis import astlint, graphs  # noqa: E402

BASELINE = os.path.join(
    REPO, "ouroboros_consensus_tpu", "analysis", "baseline.json"
)
# a diff in any of these invalidates every certificate, not just one
# graph's — force the full sweep. scripts/fit_costmodel.py is costmodel
# machinery living outside analysis/ (a re-fit changes every predicted
# wall), so it is mapped into the fast path explicitly.
_MACHINERY_PREFIX = "ouroboros_consensus_tpu/analysis/"
_MACHINERY_FILES = {"scripts/fit_costmodel.py"}
# observability sources: an obs/ (or trajectory-report) edit cannot
# change any crypto graph, but it CAN leak telemetry into the traced
# programs — map these into the instrumentation-purity re-trace so an
# obs diff re-runs the zero-eqn differential instead of skipping every
# graph pass. The live-plane modules (obs/live.py, obs/server.py) and
# the recovery plane (obs/recovery.py) ride the prefix;
# parallel/spmd.py is mapped explicitly since round 11 — it emits
# per-shard ShardSpan telemetry beside the shard_map program, exactly
# the host/device boundary the purity differential fences — and
# testing/chaos.py since round 12: its injection seams sit beside the
# packed_unpack/verdict_reduce dispatch paths, so a chaos edit re-runs
# the zero-eqn differential proving the seams add no equations to the
# production jaxprs when disarmed. storage/ joined in round 13: the
# durable-store repair plane (immutable.py's write-fault seams +
# RepairEvent emission, guard.py's marker seam) emits telemetry beside
# the replay's staging inputs, so a storage edit re-runs the same
# zero-eqn differential.
_OBS_PREFIXES = ("ouroboros_consensus_tpu/obs/",
                 "ouroboros_consensus_tpu/storage/")
_OBS_FILES = {"scripts/perf_report.py",
              "ouroboros_consensus_tpu/parallel/spmd.py",
              "ouroboros_consensus_tpu/testing/chaos.py",
              "ouroboros_consensus_tpu/protocol/forge.py"}
# octsync (Pass 5) --changed trigger: the thread/lock/rename fabric
# lives in obs/ + storage/ + the chaos seams + the analysis machinery
# itself; protocol/batch.py and ops/pk/aot.py carry guarded-by
# annotations and bench.py hosts thread entries, so an edit to any of
# them re-runs the concurrency sweep too (pure AST — seconds, no jax)
_SYNC_PREFIXES = ("ouroboros_consensus_tpu/obs/",
                  "ouroboros_consensus_tpu/storage/",
                  "ouroboros_consensus_tpu/analysis/")
_SYNC_FILES = {"ouroboros_consensus_tpu/testing/chaos.py",
               "ouroboros_consensus_tpu/protocol/batch.py",
               "ouroboros_consensus_tpu/ops/pk/aot.py",
               "bench.py",
               # the serving plane (round 20): the scheduler's service
               # lock + checkpoint rename discipline, the lock-free
               # admission single-writer contract, and the seeded
               # traffic source the chaos matrix drives through it
               "ouroboros_consensus_tpu/node/serve.py",
               "ouroboros_consensus_tpu/protocol/admission.py",
               "ouroboros_consensus_tpu/testing/traffic.py"}


def _sync_selected(changed: set[str]) -> bool:
    """--changed: does the diff touch the concurrency plane? Empty
    diff/no git -> True (conservative: the sweep is cheap)."""
    if not changed:
        return True
    return any(f.startswith(_SYNC_PREFIXES) or f in _SYNC_FILES
               for f in changed)


# octflow (Pass 6) --changed trigger: the failure-routing fabric — the
# triage table (node/exit.py), the degradation ladder (obs/ prefix
# covers obs/recovery.py), the dispatch seams (protocol/batch.py,
# forge.py, tpraos.py), the REFUSE-classed storage planes, the chaos
# injection seams, and the analysis machinery itself. Any other diff
# skips the sweep under --changed (pure AST — seconds, no jax).
_FLOW_PREFIXES = ("ouroboros_consensus_tpu/storage/",
                  "ouroboros_consensus_tpu/obs/",
                  "ouroboros_consensus_tpu/analysis/")
_FLOW_FILES = {"ouroboros_consensus_tpu/node/exit.py",
               "ouroboros_consensus_tpu/protocol/batch.py",
               "ouroboros_consensus_tpu/protocol/forge.py",
               "ouroboros_consensus_tpu/protocol/tpraos.py",
               "ouroboros_consensus_tpu/testing/chaos.py",
               # the serving plane (round 20): its dispatch seam must
               # stay ladder-protected (FLOW304), AdmissionRefused is a
               # classified raise (FLOW301), and OCT_SERVE_DEVICE is a
               # documented lever (FLOW305)
               "ouroboros_consensus_tpu/node/serve.py",
               "ouroboros_consensus_tpu/protocol/admission.py",
               "ouroboros_consensus_tpu/testing/traffic.py"}


def _flow_selected(changed: set[str]) -> bool:
    """--changed: does the diff touch the failure-routing plane? Empty
    diff/no git -> True (conservative: the sweep is cheap)."""
    if not changed:
        return True
    return any(f.startswith(_FLOW_PREFIXES) or f in _FLOW_FILES
               for f in changed)


def _changed_files() -> set[str]:
    """Repo-relative paths that differ from HEAD (staged, unstaged and
    untracked)."""
    files: set[str] = set()
    for cmd in (
        ["git", "diff", "--name-only", "HEAD"],
        ["git", "ls-files", "--others", "--exclude-standard"],
    ):
        try:
            out = subprocess.run(
                cmd, capture_output=True, text=True, cwd=REPO, check=True
            ).stdout
        except (subprocess.CalledProcessError, FileNotFoundError):
            return set()  # not a git checkout: caller falls back to full
        files |= {ln.strip() for ln in out.splitlines() if ln.strip()}
    return files


def _select_graphs(changed: set[str]) -> list[str] | None:
    """Graphs whose traced source modules intersect the diff; None =
    run everything (machinery changed, or git unavailable)."""
    from ouroboros_consensus_tpu.analysis import absint

    if not changed:
        return []
    if any(f.startswith(_MACHINERY_PREFIX) or f in _MACHINERY_FILES
           for f in changed):
        return None
    sources = dict(graphs.GRAPH_SOURCES)
    sources.update(absint.AUX_SOURCES)
    names = [
        n for n in absint.certifiable_graphs()
        if changed & set(sources.get(n, []))
    ]
    if any(f.startswith(_OBS_PREFIXES) or f in _OBS_FILES for f in changed):
        purity = graphs.load_budgets().get(
            "instrumentation_purity", {}
        ).get("graphs", [])
        names.extend(n for n in purity if n not in names)
    return names


def _update_compile_wall_budgets(cost_features) -> None:
    """--update-costs: re-pin the budgets.json compile_wall ceilings at
    ~1.3x each graph's current predicted wall (same headroom philosophy
    as the jaxpr-metric budgets — drift toward the compile-wall
    pathology fails statically long before a TPU session burns on it).
    The advisory thresholds are hand-set policy and are preserved."""
    from ouroboros_consensus_tpu.analysis import costmodel

    path = graphs._BUDGET_PATH
    with open(path, encoding="utf-8") as f:
        budgets = json.load(f)
    sec = budgets.setdefault("compile_wall", {})
    sec.setdefault("advisory", {})
    per_graph = {}
    for feat in cost_features:
        pred = costmodel.predict(feat)
        if pred is None:
            continue
        per_graph[feat.name] = {
            "predicted_s_max": round(max(1.0, pred * 1.3), 1)
        }
    sec["graphs"] = per_graph
    with open(path, "w", encoding="utf-8") as f:
        json.dump(budgets, f, indent=2)
        f.write("\n")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--no-graphs", action="store_true")
    ap.add_argument("--changed", action="store_true",
                    help="re-trace only graphs whose sources changed")
    ap.add_argument("--tier", choices=("fast", "full"), default="fast")
    ap.add_argument("--update-baseline", action="store_true")
    ap.add_argument("--update-certified", action="store_true")
    ap.add_argument("--update-costs", action="store_true",
                    help="re-pin costmodel.json graph features and the "
                         "budgets.json compile_wall ceilings")
    ap.add_argument("--update-resources", action="store_true",
                    help="re-measure (lower + COMPILE every registry "
                         "graph — slow) and re-pin the budgets.json "
                         "device_resources section; missing ceilings "
                         "are created, existing ones preserved")
    ap.add_argument("--update-sync", action="store_true",
                    help="re-pin the octsync concurrency ratchet "
                         "(analysis/concurrency.json: grandfathered "
                         "finding keys + lock/thread/guarded inventory)")
    ap.add_argument("--update-flow", action="store_true",
                    help="re-pin the octflow failure-taxonomy ratchet "
                         "(analysis/flow.json: grandfathered finding "
                         "keys + raise-site/handler/rung-edge/lever "
                         "inventory)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    roots = [
        os.path.join(REPO, "ouroboros_consensus_tpu"),
        os.path.join(REPO, "bench.py"),
        os.path.join(REPO, "scripts"),
        os.path.join(REPO, "tutorials"),
    ]
    findings = astlint.lint_paths(
        [p for p in roots if os.path.exists(p)], rel_to=REPO
    )
    unsuppressed = [f for f in findings if not f.suppressed]

    with open(BASELINE, encoding="utf-8") as f:
        baseline = set(json.load(f).get("findings", []))

    if args.update_baseline:
        payload = {
            "comment": "Grandfathered octlint finding keys "
                       "(scripts/lint.py ratchet).",
            "findings": sorted({f.key() for f in unsuppressed}),
        }
        with open(BASELINE, "w", encoding="utf-8") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print(f"baseline updated: {len(payload['findings'])} finding(s)")
        return 0

    new = [f for f in unsuppressed if f.key() not in baseline]
    current_keys = {f.key() for f in unsuppressed}
    stale = sorted(baseline - current_keys)

    # Pass 5 (octsync): the concurrency/durability sweep is pure AST —
    # it runs with or without the graph passes, and under --changed only
    # when the diff touches the thread/lock/rename fabric
    from ouroboros_consensus_tpu.analysis import concurrency

    sync_violations: list[str] = []
    sync_stale: list[str] = []
    run_sync = (args.update_sync or not args.changed
                or _sync_selected(_changed_files()))
    if run_sync:
        sync_report = concurrency.sweep_paths(
            concurrency.default_roots(REPO), REPO, concurrency.load_roots()
        )
        if args.update_sync:
            payload = concurrency.write_baseline(sync_report)
            print(f"concurrency.json updated: "
                  f"{len(payload['findings'])} grandfathered finding(s), "
                  f"{sum(len(v) for v in payload['inventory'].values())} "
                  "inventory row(s)")
            return 0
        sync_violations, sync_stale = concurrency.check_sync(
            sync_report, concurrency.load_baseline()
        )

    # Pass 6 (octflow): the exception-routing/degradation-lattice sweep
    # is pure AST too — same run policy as Pass 5, own --changed map
    from ouroboros_consensus_tpu.analysis import envlevers, flow

    flow_violations: list[str] = []
    flow_stale: list[str] = []
    run_flow = (args.update_flow or not args.changed
                or _flow_selected(_changed_files()))
    if run_flow:
        flow_report = flow.sweep_paths(
            flow.default_roots(REPO), REPO
        )
        if args.update_flow:
            payload = flow.write_baseline(flow_report)
            print(f"flow.json updated: "
                  f"{len(payload['findings'])} grandfathered finding(s), "
                  f"{sum(len(v) for v in payload['inventory'].values())} "
                  "inventory row(s)")
            return 0
        flow_violations, flow_stale = flow.check_flow(
            flow_report, flow.load_baseline()
        )
        # the README kill-switch table and the pinned FLOW305 lever
        # inventory must name the same levers — a documented lever the
        # analyzer never proved guarded (or a proven lever the README
        # forgot) is a Pass-6 violation, not a docs nit
        flow_violations += envlevers.check_kill_switches(
            os.path.join(REPO, "ouroboros_consensus_tpu", "obs",
                         "README.md")
        )

    budget_violations: list[str] = []
    cert_violations: list[str] = []
    cost_violations: list[str] = []
    resource_violations: list[str] = []
    reports: list[graphs.GraphReport] = []
    cert_reports = []
    cost_features = []
    names: list[str] | None = None
    if not args.no_graphs:
        # abstract tracing needs no accelerator; pin the platform so
        # the lint gate neither waits on nor takes the chip
        import jax

        try:
            jax.config.update("jax_platforms", "cpu")
        except Exception:
            pass  # backend already initialized by the embedding process

        from ouroboros_consensus_tpu.analysis import absint, costmodel

        shapes = absint.load_shapes()
        # registry drift gate: a REGISTRY/aux entry without a
        # shapes.json spec or a source mapping is a gate
        # misconfiguration — fail loudly BEFORE anything traces
        drift = absint.check_registry_drift(shapes)
        if drift:
            if args.json:
                print(json.dumps(
                    {"drift_violations": drift, "ok": False},
                    indent=2, sort_keys=True,
                ))
            else:
                for v in drift:
                    print(f"DRIFT: {v}")
            return 2

        if args.changed:
            names = _select_graphs(_changed_files())
        todo = names if names is not None else absint.certifiable_graphs()
        budgets = graphs.load_budgets()
        for name in todo:
            # one trace per graph serves certification, jaxpr budgets,
            # point-op budgets and compile-cost features (trace_graph
            # LRU cache)
            cert_reports.extend(absint.certify_graph(name, args.tier,
                                                     shapes))
            if name in graphs.REGISTRY:
                lanes0 = absint.sweep_lanes(name, args.tier, shapes)[0]
                reports.append(graphs.analyze_jaxpr(
                    graphs.trace_graph(name, lanes0), name
                ))
                # cost features ALWAYS at the fast-sweep lane count —
                # the tile the costmodel.json pins are defined at, so
                # the pin-freshness check compares like with like even
                # under --tier full
                cost_lanes = absint.sweep_lanes(name, "fast", shapes)[0]
                cost_features.append(costmodel.extract_features(
                    graphs.trace_graph(name, cost_lanes), name
                ))
                budget_violations += graphs.check_point_ops(
                    budgets, names=[name]
                )
        budget_violations += graphs.check_budgets(reports, budgets)
        # instrumentation purity: the registry graphs built from the
        # telemetry-instrumented host modules must gain ZERO equations
        # with the obs flight recorder installed (observability is
        # host-side only — budgets.json "instrumentation_purity")
        budget_violations += graphs.check_instrumentation_purity(
            budgets, names=names
        )

        if args.update_certified:
            if names is not None:
                print("--update-certified requires the full sweep "
                      "(drop --changed)")
                return 2
            absint.write_certified(cert_reports)
            print(f"certified.json updated: "
                  f"{len(absint.load_certified()['graphs'])} graph(s)")
            return 0
        if args.update_costs:
            if names is not None:
                print("--update-costs requires the full sweep "
                      "(drop --changed)")
                return 2
            model = (costmodel._cached_cost() or {}).get("model")
            costmodel.write_cost(
                graphs_section=costmodel.pin_payload(cost_features, model)
            )
            _update_compile_wall_budgets(cost_features)
            print(f"costmodel.json pins updated: "
                  f"{len(cost_features)} graph(s)")
            return 0
        if args.update_resources:
            if names is not None:
                print("--update-resources requires the full sweep "
                      "(drop --changed)")
                return 2
            from ouroboros_consensus_tpu.obs import resources as obs_res

            measurements = {}
            hashes = {f.name: f.hash() for f in cost_features}
            for f in cost_features:
                lanes = absint.sweep_lanes(f.name, "fast", shapes)[0]
                print(f"# measuring {f.name}"
                      f"@{lanes if lanes is not None else 'tile'} "
                      "(lower + compile)...", flush=True)
                measurements[f.name] = graphs.measure_graph(
                    f.name, lanes, compile=True
                )
            path = graphs._BUDGET_PATH
            with open(path, encoding="utf-8") as fh:
                budgets_doc = json.load(fh)
            obs_res.update_budgets_section(
                budgets_doc, measurements, hashes,
                measured_at=obs_res.measured_at_string(),
            )
            with open(path, "w", encoding="utf-8") as fh:
                json.dump(budgets_doc, fh, indent=2)
                fh.write("\n")
            print(f"device_resources pins updated: "
                  f"{len(measurements)} graph(s)")
            return 0
        cert_violations = absint.check_certified(cert_reports)
        cost_violations = costmodel.check_compile_wall(cost_features, budgets)
        # pin freshness: stale pins would join calibration walls to an
        # old structure's features
        cost_violations += costmodel.check_pins(cost_features)
        # sixth ratchet: device-resource pins (hash-freshness + ceiling
        # compares only — no lowering, no compiling)
        from ouroboros_consensus_tpu.obs import resources as obs_res

        resource_violations = obs_res.check_device_resources(
            cost_features, budgets
        )

    if args.json:
        print(json.dumps({
            "new_findings": [f.format() for f in new],
            "stale_baseline": stale,
            "budget_violations": budget_violations,
            "certification_violations": cert_violations,
            "cost_violations": cost_violations,
            "resource_violations": resource_violations,
            "sync_violations": sync_violations,
            "stale_sync": sync_stale,
            "flow_violations": flow_violations,
            "stale_flow": flow_stale,
            "graphs": [r.to_dict() for r in reports],
            "certified": [r.to_dict() for r in cert_reports],
            "cost_features": [f.to_dict() | {"name": f.name}
                              for f in cost_features],
            "changed_selection": names,
            "ok": not (new or budget_violations or cert_violations
                       or cost_violations or resource_violations
                       or sync_violations or flow_violations),
        }, indent=2, sort_keys=True))
    else:
        for f in new:
            print(f.format())
        for v in budget_violations:
            print(f"BUDGET: {v}")
        for v in cert_violations:
            print(f"CERTIFIED: {v}")
        for v in cost_violations:
            print(f"COST: {v}")
        for v in resource_violations:
            print(f"RESOURCES: {v}")
        for v in sync_violations:
            print(f"SYNC: {v}")
        for v in flow_violations:
            print(f"FLOW: {v}")
        for k in stale:
            print(f"note: baseline entry no longer fires "
                  f"(run --update-baseline to ratchet): {k}")
        for k in sync_stale:
            print(f"note: concurrency baseline entry no longer fires "
                  f"(run --update-sync to ratchet): {k}")
        for k in flow_stale:
            print(f"note: flow baseline entry no longer fires "
                  f"(run --update-flow to ratchet): {k}")
        if names is not None:
            print(f"--changed: {len(names)} graph(s) selected: "
                  f"{', '.join(names) or '(none)'}")
        print(
            f"lint: {len(new)} new finding(s), "
            f"{len(budget_violations)} budget violation(s), "
            f"{len(cert_violations)} certification violation(s), "
            f"{len(cost_violations)} compile-wall violation(s), "
            f"{len(resource_violations)} device-resource violation(s), "
            f"{len(sync_violations)} concurrency violation(s), "
            f"{len(flow_violations)} flow violation(s), "
            f"{len(stale)} stale baseline entr(y/ies)"
        )
    if new:
        return 1
    if budget_violations:
        return 3
    if cert_violations:
        return 4
    if cost_violations:
        return 5
    if resource_violations:
        return 6
    if sync_violations:
        return 7
    return 8 if flow_violations else 0


if __name__ == "__main__":
    sys.exit(main())
