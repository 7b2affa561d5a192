#!/usr/bin/env python
"""Cross-round trajectory report: fold the run ledger plus every
BENCH_r*.json / MULTICHIP_r*.json into one markdown + JSON document
with explicit regression verdicts.

The single biggest fact about five rounds of benchmarking — r01 banked
3,986 headers/s on device, r02–r05 banked nothing — lived only in the
heads of people who hand-diffed the round files. This tool makes the
trajectory a build artifact: which rounds banked a device number, what
each dead round died of (classified from its own recorded output — the
probe timeouts, stored-executable rejections and compile walls are all
IN the tails), what the host/native ceilings did, how much warmup wall
each round burned, how many packed-qualification gate declines and
octwall pre-flight refusals the telemetry counted, and what env/build
facts changed at each transition (from the obs/ledger records when a
ledger exists).

Regression verdicts are configurable and exit non-zero so a CI perf
gate can consume this directly:

    python scripts/perf_report.py                      # report, exit 0
    python scripts/perf_report.py --threshold 0.8      # newest round
        # must be >= 0.8x the best previous round's headers/s: exit 1
    python scripts/perf_report.py --require-device     # newest round
        # must have banked a DEVICE number: exit 1 otherwise
    python scripts/perf_report.py --json out.json --out report.md

Round-file schema is deliberately treated as hostile: the five
checked-in rounds span three generations of bench.py output (r01 has
no warmup forensics, r05 has no metrics snapshot), so every field is
optional and classification falls back to the recorded tail text.

Since round 11 bench also banks the LIVE plane's evidence: a
`live_timeline` (the parent-tailed heartbeat classifications) and any
`stall_dump` the child's watchdog wrote. A dead round whose last
heartbeat says `phase=dispatch, age=600s` classifies as
`stalled@dispatch` — distinct from probe-timeout and compile-wall.

Since round 12 the RECOVERY plane's evidence rides too: the warmup
report's `recovery` rows (obs/recovery.py — every degradation-ladder
transition of every episode). A round that banked its device number
only because the supervisor walked failing windows down the ladder is
its own class, `recovered@<fault>` — priority-wise between `stalled@`
(it did not die) and clean (it did not run clean either) — rendered
with its per-action transition counts.

Since round 13 the durable-store REPAIR plane rides the same way: the
warmup report's `repairs` rows (storage/repair.py — every on-disk
repair the open-with-repair scan applied: truncated tails, rebuilt
indices, dropped chunks, dirty-open escalations). A round whose store
opened dirty or was repaired under it classifies `repaired@<action>`
— priority between `recovered@` (the replay itself never failed) and
clean (the store was not healthy either) — with per-action counts."""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# failure-mode classifiers, matched (all of them) against a dead
# round's recorded output — order is presentation priority, the FIRST
# match is the primary attribution
_FAILURE_PATTERNS = (
    ("aot-cache-rejected",
     re.compile(r"serialized executable is incompatible",
                re.IGNORECASE),
     "stale AOT/persistent-cache executables rejected by the runtime"),
    ("warmup-exceeded-wall",
     re.compile(r"exceeded\s+\d+s?\s*budget|warmup exceed",
                re.IGNORECASE),
     "device attempt ran past its wall budget (compile/warmup wall)"),
    ("backend-probe-timeout",
     re.compile(r"probe (?:timed out|failed)", re.IGNORECASE),
     "TPU backend probe timed out (backend unreachable / init hung)"),
    ("compile-wall-refused",
     re.compile(r"compile-wall-refused", re.IGNORECASE),
     "octwall pre-flight refused a cold compile against the deadline"),
)


def _round_of(path: str, doc: dict) -> int:
    m = re.search(r"_r(\d+)\.json$", path)
    if m:
        return int(m.group(1))
    return int(doc.get("n", 0))


def _first_float(pattern: str, text: str) -> float | None:
    m = re.search(pattern, text)
    return float(m.group(1)) if m else None


def _classify_failures(text: str, rc, parsed: dict | None = None) -> list[dict]:
    out = []
    # LIVE-PLANE classification first (round 11): a banked stall dump
    # or a heartbeat timeline whose last word is stalled/dead names the
    # wedged phase — a round whose last heartbeat said phase=dispatch,
    # age=600s is "stalled@dispatch", structurally distinct from a
    # probe timeout or a compile wall
    stall = (parsed or {}).get("stall_dump")
    if isinstance(stall, dict):
        out.append({
            "mode": f"stalled@{stall.get('phase') or '?'}",
            "detail": (
                f"stall watchdog tripped after {stall.get('age_s', '?')}s "
                f"without progress (budget {stall.get('budget_s', '?')}s; "
                "all-thread stacks in the banked stall_dump)"
            ),
        })
    timeline = (parsed or {}).get("live_timeline") or []
    last_live = timeline[-1] if timeline else None
    if (isinstance(last_live, dict)
            and last_live.get("state") in ("stalled", "dead") and not out):
        phase = last_live.get("phase") or "?"
        out.append({
            "mode": f"stalled@{phase}",
            "detail": (
                f"last heartbeat: state={last_live['state']}, "
                f"phase={phase}, headers={last_live.get('headers')}, "
                f"age={last_live.get('age_s', '?')}s (banked "
                "live_timeline)"
            ),
        })
    # STRUCTURED classification next (round 10): bench.py banks the
    # backend-probe verdict and a no_device_reason, so probe-timeout vs
    # driver-timeout vs run-death no longer rides regex archaeology
    probe = (parsed or {}).get("probe")
    if isinstance(probe, dict) and not probe.get("ok"):
        mode = probe.get("outcome") or "backend-probe"
        attempts = probe.get("attempts") or []
        out.append({
            "mode": mode,
            "detail": (f"backend probe verdict ({len(attempts)} "
                       "attempt(s), banked by bench.py)"),
        })
    reason = (parsed or {}).get("no_device_reason")
    if reason and not any(f["mode"] == reason for f in out):
        out.append({"mode": reason,
                    "detail": "bench.py's banked no-device reason"})
    for key, rx, desc in _FAILURE_PATTERNS:
        if rx.search(text) and not any(f["mode"] == key for f in out):
            out.append({"mode": key, "detail": desc})
    if rc not in (0, None):
        out.append({
            "mode": f"driver-timeout (rc={rc})",
            "detail": "the driver killed the run before the JSON line",
        })
    if not out:
        out.append({"mode": "unknown",
                    "detail": "no recognizable failure pattern in the "
                              "recorded output"})
    return out


def _recovery_counts(wr: dict | None) -> tuple[dict, str | None]:
    """({action: count}, fault-of-the-first-recovered-episode) out of a
    banked warmup report's `recovery` rows (obs/recovery.py). The fault
    is the exception class the supervisor recovered FROM — what
    `recovered@<fault>` names."""
    rows = (wr or {}).get("recovery") or []
    counts: dict = {}
    fault = None
    for row in rows:
        if not isinstance(row, dict):
            continue
        a = row.get("action", "?")
        counts[a] = counts.get(a, 0) + 1
        if fault is None and a == "recovered":
            fault = row.get("fault") or "?"
    return counts, fault


_REPAIR_PRIORITY = ("truncate-chunk", "drop-chunk", "rebuild-index",
                    "sweep-orphan-index", "dirty-open-escalated")


def _repair_counts(wr: dict | None) -> tuple[dict, str | None]:
    """({action: count}, primary-action) out of a banked warmup
    report's `repairs` rows (storage/repair.py). Only APPLIED rows
    count (dry-run scans are not repairs); the primary action — what
    `repaired@<action>` names — is the most disk-invasive one."""
    rows = (wr or {}).get("repairs") or []
    counts: dict = {}
    for row in rows:
        if not isinstance(row, dict) or not row.get("applied", True):
            continue
        a = row.get("action", "?")
        counts[a] = counts.get(a, 0) + 1
    primary = None
    for a in _REPAIR_PRIORITY:
        if counts.get(a):
            primary = a
            break
    if primary is None and counts:
        primary = sorted(counts)[0]
    return counts, primary


def _gate_counts(metrics: dict | None) -> dict:
    """{gate: count} out of a banked metrics snapshot (or {})."""
    if not isinstance(metrics, dict):
        return {}
    fam = metrics.get("oct_gate_declines_total") or {}
    out = {}
    for s in fam.get("samples", []):
        gate = (s.get("labels") or {}).get("gate", "?")
        out[gate] = out.get(gate, 0) + int(s.get("value", 0))
    return out


def analyze_bench_round(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    parsed = doc.get("parsed") if isinstance(doc.get("parsed"), dict) else None
    tail = str(doc.get("tail", "") or "")
    rc = doc.get("rc")
    metric_text = (parsed or {}).get("metric", "")
    headers = None
    m = re.search(r"(\d[\d_,]*)-header", metric_text)
    if m:
        headers = int(m.group(1).replace(",", "").replace("_", ""))
    device_banked = bool(
        parsed
        and not parsed.get("device_unavailable")
        and parsed.get("value")
    )
    wr = (parsed or {}).get("warmup_report")
    warmup = None
    ladder_events: list = []
    if isinstance(wr, dict):
        ladder_events = wr.get("ladder") or []
        warmup = {
            "compile_total_s": wr.get("compile_total_s"),
            "n_stages": wr.get("n_stages"),
            "aot": wr.get("aot"),
            "refusals": len(wr.get("refusals", [])),
            "ladder": len(ladder_events),
            "cache_probe": (wr.get("cache_probe") or {}).get("outcome"),
        }
    recovery_actions, recovered_fault = _recovery_counts(
        wr if isinstance(wr, dict) else None
    )
    repair_actions, repaired_action = _repair_counts(
        wr if isinstance(wr, dict) else None
    )
    row = {
        "round": _round_of(path, doc),
        "file": os.path.basename(path),
        "rc": rc,
        "headers": headers,
        "device_banked": device_banked,
        "value_per_s": (parsed or {}).get("value"),
        "vs_baseline": (parsed or {}).get("vs_baseline"),
        "native_baseline_per_s": _first_float(
            r"# native baseline (\d+(?:\.\d+)?) headers/s", tail)
            or ((parsed or {}).get("value")
                if parsed and parsed.get("device_unavailable") else None),
        "warmup_wall_s": _first_float(r"warmup=(\d+(?:\.\d+)?)s", tail),
        "warmup": warmup,
        # a LADDERED round banked its device number while the
        # production monolith compiled in the background — its own
        # class of round, not a warmup death (and for a dead round,
        # evidence the ladder engaged before the wall)
        "laddered": bool(ladder_events
                         or (parsed or {}).get("laddered")),
        "ladder_swapped": any(e.get("kind") == "swap"
                              for e in ladder_events),
        # the recovery plane's banked story (round 12): ladder-
        # transition counts per action, and — for a round that FINISHED
        # via recovery — the fault class it recovered from
        "recovery_actions": recovery_actions,
        "recovered_fault": recovered_fault,
        # the durable-store repair plane's banked story (round 13):
        # applied repair counts per action + whether the store opened
        # dirty (warmup `repairs` rows / the banked attribution)
        "repair_actions": repair_actions,
        "repaired_action": repaired_action,
        "opened_dirty": bool((parsed or {}).get("opened_dirty")
                             or repair_actions.get("dirty-open-escalated")),
        "resumed_headers": (parsed or {}).get("resumed_headers") or 0,
        # the live plane's banked story (round 11): timeline length +
        # last state, and whether a stall dump named a wedged phase
        "live_states": [e.get("state") for e in
                        ((parsed or {}).get("live_timeline") or [])
                        if isinstance(e, dict)],
        "stalled_phase": (
            ((parsed or {}).get("stall_dump") or {}).get("phase")
            if isinstance((parsed or {}).get("stall_dump"), dict)
            else None
        ),
        "gate_declines": _gate_counts((parsed or {}).get("metrics")),
        "failures": ([] if device_banked
                     else _classify_failures(tail, rc, parsed)),
    }
    return row


def analyze_multichip_round(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    tail = str(doc.get("tail", "") or "")
    rate = _first_float(r"\((\d+(?:\.\d+)?) headers/s", tail)
    return {
        "round": _round_of(path, doc),
        "file": os.path.basename(path),
        "ok": bool(doc.get("ok")),
        "skipped": bool(doc.get("skipped")),
        "n_devices": doc.get("n_devices"),
        "rate_per_s": rate,
        "failures": ([] if doc.get("ok")
                     else _classify_failures(tail, doc.get("rc"))),
    }


# ---------------------------------------------------------------------------
# Ledger fold: what actually changed between runs
# ---------------------------------------------------------------------------


def _env_diff(prev: dict, cur: dict) -> dict:
    """{key: [old, new]} over the banked OCT_*/BENCH_* env snapshots."""
    keys = set(prev) | set(cur)
    return {
        k: [prev.get(k), cur.get(k)]
        for k in sorted(keys) if prev.get(k) != cur.get(k)
    }


def ledger_section(ledger_dir: str | None) -> dict | None:
    from ouroboros_consensus_tpu.obs import ledger

    runs = ledger.read_runs(ledger_dir, kind=None)
    if not runs:
        return None
    bench_runs = [r for r in runs if r.get("kind") == "bench"]
    transitions = []
    for prev, cur in zip(bench_runs, bench_runs[1:]):
        delta: dict = {}
        if (prev.get("git") or {}).get("rev") != (cur.get("git") or {}).get("rev"):
            delta["git_rev"] = [(prev.get("git") or {}).get("rev"),
                                (cur.get("git") or {}).get("rev")]
        if prev.get("build_id") != cur.get("build_id"):
            delta["build_id"] = [prev.get("build_id"), cur.get("build_id")]
        env = _env_diff(prev.get("env") or {}, cur.get("env") or {})
        if env:
            delta["env"] = env
        transitions.append({
            "from_ts": prev.get("ts_iso"), "to_ts": cur.get("ts_iso"),
            "changed": delta,
        })
    kinds: dict = {}
    for r in runs:
        kinds[r.get("kind", "?")] = kinds.get(r.get("kind", "?"), 0) + 1
    return {
        "runs": len(runs),
        "by_kind": kinds,
        "bench_transitions": transitions,
    }


# ---------------------------------------------------------------------------
# Verdicts
# ---------------------------------------------------------------------------


def regression_verdicts(rounds: list[dict], threshold: float | None,
                        require_device: bool) -> list[dict]:
    """Explicit, configurable verdicts; any verdict with ok=False makes
    the process exit non-zero (the future CI perf gate)."""
    verdicts: list[dict] = []
    if not rounds:
        return [{"rule": "rounds-present", "ok": False,
                 "detail": "no BENCH_r*.json found"}]
    latest = rounds[-1]
    prev = rounds[:-1]
    if threshold is not None:
        best_prev = max(
            (r["value_per_s"] for r in prev if r.get("value_per_s")),
            default=None,
        )
        val = latest.get("value_per_s")
        if best_prev is None:
            # nothing to compare against — say so EXPLICITLY instead of
            # silently appending no verdict (a CI gate that goes green
            # without evaluating anything is the failure shape this
            # tool exists to kill). Not a regression: there is no prior
            # bar to fall below; pair with --require-device to gate on
            # banking itself.
            verdicts.append({
                "rule": f"latest >= {threshold:g} x best-previous",
                "ok": True,
                "detail": (
                    "no previous round banked a measurable headers/s — "
                    "threshold rule has nothing to compare (pair with "
                    "--require-device to gate on banking)"
                ),
            })
        elif val:
            ratio = val / best_prev
            verdicts.append({
                "rule": f"latest >= {threshold:g} x best-previous",
                "ok": ratio >= threshold,
                "detail": (
                    f"r{latest['round']:02d} banked {val:g} headers/s vs "
                    f"best previous {best_prev:g} (ratio {ratio:.2f})"
                ),
            })
        else:
            # the worst regression of all: the newest round produced NO
            # measurable number (driver kill before the JSON line). A
            # threshold gate that silently passes here would wave the
            # r02 failure shape through CI.
            verdicts.append({
                "rule": f"latest >= {threshold:g} x best-previous",
                "ok": False,
                "detail": (
                    f"r{latest['round']:02d} banked no measurable "
                    f"headers/s at all (best previous {best_prev:g}): "
                    + ", ".join(f["mode"]
                                for f in latest.get("failures", []))
                ),
            })
    if require_device:
        verdicts.append({
            "rule": "latest-round-banks-device",
            "ok": bool(latest.get("device_banked")),
            "detail": (
                f"r{latest['round']:02d} "
                + ("banked a device result" if latest.get("device_banked")
                   else "banked NO device result: "
                   + ", ".join(f["mode"] for f in latest.get("failures", [])))
            ),
        })
    return verdicts


# ---------------------------------------------------------------------------
# Rendering
# ---------------------------------------------------------------------------


def _md_escape(v) -> str:
    return str(v).replace("|", "\\|")


def render_markdown(report: dict) -> str:
    out = ["# Benchmark trajectory", ""]
    rounds = report["bench_rounds"]
    device_rounds = [r for r in rounds if r["device_banked"]]
    out.append(
        f"{len(rounds)} bench round(s); "
        f"{len(device_rounds)} banked a device result"
        + (" (" + ", ".join(f"r{r['round']:02d}" for r in device_rounds)
           + ")" if device_rounds else "")
        + "."
    )
    out += ["", "## Rounds", ""]
    out.append("| round | headers | device | headers/s | vs native | "
               "native/s | warmup s | declines | failure modes |")
    out.append("|---|---|---|---|---|---|---|---|---|")
    for r in rounds:
        declines = sum(r["gate_declines"].values()) or ""
        warm = r.get("warmup_wall_s")
        if warm is None and r.get("warmup"):
            warm = r["warmup"].get("compile_total_s")
        out.append("| r{:02d} | {} | {} | {} | {} | {} | {} | {} | {} |".format(
            r["round"],
            r["headers"] or "?",
            "YES" if r["device_banked"] else "no",
            r["value_per_s"] if r["device_banked"] else "—",
            r["vs_baseline"] if r["device_banked"] else "—",
            r["native_baseline_per_s"] or "?",
            warm if warm is not None else "?",
            declines,
            _md_escape(
                ", ".join(f["mode"] for f in r["failures"])
                or ", ".join(filter(None, [
                    # a banked round that finished VIA recovery is its
                    # own class — priority between stalled@ (it did not
                    # die) and clean (it did not run clean either);
                    # repaired@ sits between recovered@ and clean (the
                    # replay never failed, the STORE was not healthy)
                    (f"recovered@{r['recovered_fault']}"
                     if r.get("recovered_fault") else None),
                    (f"repaired@{r['repaired_action']}"
                     if r.get("repaired_action") else None),
                    ("laddered" + (" (swapped)" if r.get("ladder_swapped")
                                   else "")
                     if r.get("laddered") else None),
                ]))
                or "—"
            ),
        ))
    dead = [r for r in rounds if not r["device_banked"]]
    if dead:
        out += ["", "## Failure attribution", ""]
        for r in dead:
            modes = "; ".join(
                f"**{f['mode']}** ({f['detail']})" for f in r["failures"]
            )
            if r.get("laddered"):
                modes += " — warm ladder HAD engaged before the death"
            if r.get("recovery_actions"):
                acts = ", ".join(f"{k}={v}" for k, v in
                                 sorted(r["recovery_actions"].items()))
                modes += (" — recovery ladder HAD engaged before the "
                          f"death ({acts})")
            if r.get("repair_actions"):
                acts = ", ".join(f"{k}={v}" for k, v in
                                 sorted(r["repair_actions"].items()))
                modes += (" — store repairs HAD been applied before "
                          f"the death ({acts})")
            out.append(f"* r{r['round']:02d}: {modes}")
    recovered = [r for r in rounds
                 if r["device_banked"] and r.get("recovery_actions")]
    if recovered:
        out += ["", "## Recovered rounds", ""]
        for r in recovered:
            acts = ", ".join(f"{k}={v}" for k, v in
                             sorted(r["recovery_actions"].items()))
            resumed = (f"; resumed past {r['resumed_headers']} banked "
                       "headers" if r.get("resumed_headers") else "")
            out.append(
                f"* r{r['round']:02d}: recovered@"
                f"{r.get('recovered_fault') or '?'} — the supervisor "
                f"walked failing windows down the ladder ({acts})"
                f"{resumed}; the banked number is a RECOVERED replay's"
            )
    repaired = [r for r in rounds
                if r["device_banked"] and r.get("repair_actions")]
    if repaired:
        out += ["", "## Repaired rounds", ""]
        for r in repaired:
            acts = ", ".join(f"{k}={v}" for k, v in
                             sorted(r["repair_actions"].items()))
            out.append(
                f"* r{r['round']:02d}: repaired@"
                f"{r.get('repaired_action') or '?'} — the store "
                + ("opened dirty and " if r.get("opened_dirty") else "")
                + f"was repaired under the replay ({acts}); the banked "
                "number is a replay of the repaired store"
            )
    laddered = [r for r in rounds if r["device_banked"] and r.get("laddered")]
    if laddered:
        out += ["", "## Laddered rounds", ""]
        for r in laddered:
            out.append(
                f"* r{r['round']:02d}: banked {r['value_per_s']} headers/s "
                "while the production monolith compiled in the background"
                + (" (swapped to production mid-replay)"
                   if r.get("ladder_swapped") else " (no swap before end)")
            )
    po = report.get("point_ops")
    if po:
        out += ["", "## Static point-op ratchet (budgets.json)", ""]
        out.append(
            "Per-lane point-op ceilings pinned by lint exit 3 / "
            "`scripts/count_point_ops.py --check` — the device-free "
            "half of the perf story. Round 15 folded the Ed25519 and "
            "KES ladders into the one-RLC shared-bucket MSM, so the "
            "whole per-window pipeline now rides one aggregated "
            "program."
        )
        out.append("")
        out.append("| graph | pinned lane-ops/lane | at lanes |")
        out.append("|---|---|---|")
        for name, cfg in po["pins"]:
            out.append(f"| {name} | {cfg['lane_ops_per_lane']:g} | "
                       f"{cfg['at_lanes']} |")
        total = po.get("all_stage_total")
        if total:
            out.append(
                f"| **all_stage_total** ({'+'.join(total['graphs'])}) | "
                f"**{total['lane_ops_per_lane']:g}** | "
                f"{total['at_lanes']} |"
            )
    hc = report.get("host_ceiling")
    if hc:
        out += ["", "## Host ceiling trajectory", ""]
        out.append(
            "The best rate any device can be fed at "
            "(`profile_replay.py --host`). Round 17's columnar sidecar "
            "streams device-ready windows straight off disk — a warm "
            "sidecar replaces the native parse with an mmap."
        )
        out.append("")
        out.append("| round/run | pipeline | ceiling headers/s | "
                   "sidecar | mmap s | parse s |")
        out.append("|---|---|---|---|---|---|")
        for m in hc["milestones"]:
            out.append(f"| {m['round']} | {m['what']} | "
                       f"{m['ceiling_per_s']:,} | — | — | — |")
        for r in hc["runs"]:
            sc = r.get("sidecar") or {}
            sc_txt = (f"hit {sc.get('hit', 0)} / miss {sc.get('miss', 0)}"
                      if sc else "—")
            out.append("| {} | {} | {} | {} | {} | {} |".format(
                (r.get("ts") or "?")[:19],
                "sidecar" if sc.get("hit") else "parse",
                r.get("ceiling_per_s") or "?",
                sc_txt,
                r.get("stream_mmap_s") if r.get("stream_mmap_s")
                is not None else "—",
                r.get("stream_parse_s") if r.get("stream_parse_s")
                is not None else "—",
            ))
    fg = report.get("forge")
    if fg:
        out += ["", "## Forge trajectory", ""]
        out.append(
            "Chain-synthesis rates (`profile_forge.py`): the per-slot "
            "reference loop vs the batched host engine vs the packed "
            "device sweep (PR 18). Stub runs isolate the pipeline "
            "(crypto-independent per-slot costs); native runs are what "
            "a TPU session banks."
        )
        out.append("")
        out.append("| run | crypto | pools | engine | slots | blocks | "
                   "slots/s | vs loop |")
        out.append("|---|---|---|---|---|---|---|---|")
        for r in fg["runs"]:
            for e in r["engines"]:
                speed = r["speedups"].get(f"{e['engine']}_vs_loop")
                out.append("| {} | {} | {} | {} | {} | {} | {:,} | {} |".format(
                    (r.get("ts") or "?")[:19], r.get("crypto") or "?",
                    r.get("pools") or "?", e.get("engine") or "?",
                    e.get("slots") or "?", e.get("blocks") or "?",
                    e.get("slots_per_s") or 0,
                    f"{speed}x" if speed else "—",
                ))
    sv = report.get("serve")
    if sv:
        out += ["", "## Serving plane", ""]
        out.append(
            "Follow-the-tip serving rates (`profile_serve.py`): the "
            "same seeded multi-peer suffix traffic validated as one "
            "window per peer (the naive port) vs continuous-batched "
            "shared windows (PR 20), verdict-identical by assertion. "
            "The SLO columns are the live `/slo` document scraped "
            "during the batched run."
        )
        out.append("")
        out.append("| run | tenants | mode | headers | windows | "
                   "headers/s | speedup | p50 s | p99 s |")
        out.append("|---|---|---|---|---|---|---|---|---|")
        for r in sv["runs"]:
            slo = r.get("slo") or {}
            for m in r["modes"]:
                batched = m.get("mode") == "batched"
                p50 = slo.get("verdict_latency_p50_s")
                p99 = slo.get("verdict_latency_p99_s")
                out.append("| {} | {} | {} | {} | {} | {:,} | {} | {} | {} |".format(
                    (r.get("ts") or "?")[:19], r.get("tenants") or "?",
                    m.get("mode") or "?", m.get("headers") or "?",
                    m.get("windows") or "?",
                    m.get("headers_per_s") or 0,
                    (f"{r['speedup']}x" if batched and r.get("speedup")
                     else "—"),
                    (round(p50, 4) if batched and p50 is not None else "—"),
                    (round(p99, 4) if batched and p99 is not None else "—"),
                ))
    mc = report.get("multichip_rounds") or []
    if mc:
        out += ["", "## Multichip", ""]
        out.append("| round | devices | ok | headers/s | failure |")
        out.append("|---|---|---|---|---|")
        for r in mc:
            out.append("| r{:02d} | {} | {} | {} | {} |".format(
                r["round"], r.get("n_devices", "?"),
                "ok" if r["ok"] else ("skipped" if r["skipped"] else "FAIL"),
                r.get("rate_per_s") or "—",
                _md_escape(", ".join(f["mode"] for f in r["failures"]) or "—"),
            ))
    led = report.get("ledger")
    if led:
        out += ["", "## Run ledger", ""]
        out.append(f"{led['runs']} ledger run(s): " + ", ".join(
            f"{k}={v}" for k, v in sorted(led["by_kind"].items())))
        for t in led["bench_transitions"]:
            if t["changed"]:
                out.append(
                    f"* {t['from_ts']} → {t['to_ts']}: "
                    + "; ".join(f"{k} {v}" for k, v in t["changed"].items())
                )
    out += ["", "## Verdicts", ""]
    if not report["verdicts"]:
        out.append("(no regression rules configured — report only)")
    for v in report["verdicts"]:
        out.append(f"* {'OK ' if v['ok'] else 'REGRESSION'} "
                   f"[{v['rule']}]: {v['detail']}")
    return "\n".join(out) + "\n"


# the banked host-ceiling milestones (PERF.md): the parse ceiling's
# round-by-round trajectory the round-17 sidecar row appends to —
# static anchors so the section renders even on a box whose ledger
# only has the newest runs
_HOST_CEILING_MILESTONES = (
    ("r08", "columnar host pipeline", 26_800),
    ("r09", "threaded staging + native extract", 118_700),
    ("r16", "pass-5 host pipeline", 177_000),
    ("r17", "columnar sidecar: walked seals + PCLMUL CRC + native "
            "span hash", 419_000),
)


def host_ceiling_section(ledger_dir: str | None) -> dict | None:
    """The host-ceiling trajectory: the static PERF.md milestone
    anchors plus every `profile_replay --host` ledger record, with the
    round-17 sidecar evidence (hit/miss counts, mmap-vs-parse wall
    split) when the record carries it. Fail-soft like the ledger
    section."""
    rows = []
    try:
        from ouroboros_consensus_tpu.obs import ledger

        for r in ledger.read_runs(ledger_dir, kind="profile_replay"):
            cfg = r.get("config") or {}
            if cfg.get("mode") != "host":
                continue
            res = r.get("result") or {}
            phases = r.get("phases_s") or {}
            rows.append({
                "ts": r.get("ts_iso"),
                "headers": res.get("headers"),
                "ceiling_per_s": res.get("ceiling_per_s"),
                "sidecar": res.get("sidecar"),
                "stream_mmap_s": phases.get("stream-mmap"),
                "stream_parse_s": phases.get("stream-parse"),
            })
    except Exception:  # noqa: BLE001 — report survives a broken ledger
        pass
    if not rows and ledger_dir == "0":
        return None
    return {"milestones": [
        {"round": rd, "what": what, "ceiling_per_s": v}
        for rd, what, v in _HOST_CEILING_MILESTONES
    ], "runs": rows}


def forge_section(ledger_dir: str | None) -> dict | None:
    """The forging-rate trajectory: every `profile_forge` ledger record
    (engine table + speedups). Fail-soft like the ledger section — a
    broken or absent ledger just drops the section."""
    rows = []
    try:
        from ouroboros_consensus_tpu.obs import ledger

        for r in ledger.read_runs(ledger_dir, kind="profile_forge"):
            cfg = r.get("config") or {}
            res = r.get("result") or {}
            rows.append({
                "ts": r.get("ts_iso"),
                "n": cfg.get("n"),
                "pools": cfg.get("pools"),
                "crypto": cfg.get("crypto"),
                "engines": res.get("engines") or [],
                "speedups": res.get("speedups") or {},
            })
    except Exception:  # noqa: BLE001 — report survives a broken ledger
        pass
    if not rows:
        return None
    return {"runs": rows}


def serve_section(ledger_dir: str | None) -> dict | None:
    """The serving-plane trajectory: every `profile_serve` ledger
    record (continuous batching vs one-window-per-peer, with the
    scraped /slo document). Fail-soft like the ledger section."""
    rows = []
    try:
        from ouroboros_consensus_tpu.obs import ledger

        for r in ledger.read_runs(ledger_dir, kind="profile_serve"):
            cfg = r.get("config") or {}
            res = r.get("result") or {}
            rows.append({
                "ts": r.get("ts_iso"),
                "tenants": cfg.get("tenants"),
                "rounds": cfg.get("rounds"),
                "suffix_len": cfg.get("suffix_len"),
                "max_window": cfg.get("max_window"),
                "modes": res.get("modes") or [],
                "speedup": res.get("speedup_batched_vs_per_peer"),
                "slo": res.get("slo") or {},
            })
    except Exception:  # noqa: BLE001 — report survives a broken ledger
        pass
    if not rows:
        return None
    return {"runs": rows}


def point_ops_section() -> dict | None:
    """The ratcheted per-lane point-op pins from budgets.json — no
    tracing, a dict read: the STATIC perf trajectory (what the
    MSM/aggregate refactors banked) surfaced next to the device
    rounds. Fail-soft: a missing/odd budgets file just drops the
    section."""
    try:
        from ouroboros_consensus_tpu.analysis import graphs as an_graphs

        sec = an_graphs.load_budgets().get("point_ops", {})
    except Exception:  # noqa: BLE001 — report survives a broken budgets file
        return None
    if not sec:
        return None
    pins = [(n, cfg) for n, cfg in sorted(sec.items())
            if n != "all_stage_total" and cfg.get("lane_ops_per_lane")]
    return {
        "pins": pins,
        "all_stage_total": sec.get("all_stage_total"),
    }


def build_report(dir_: str, threshold: float | None,
                 require_device: bool, ledger_dir: str | None) -> dict:
    bench_rounds = sorted(
        (analyze_bench_round(p)
         for p in glob.glob(os.path.join(dir_, "BENCH_r*.json"))),
        key=lambda r: r["round"],
    )
    multichip = sorted(
        (analyze_multichip_round(p)
         for p in glob.glob(os.path.join(dir_, "MULTICHIP_r*.json"))),
        key=lambda r: r["round"],
    )
    led = None
    if ledger_dir != "0":
        try:
            led = ledger_section(ledger_dir)
        except Exception:  # noqa: BLE001 — a broken ledger never kills the report
            led = None
    verdicts = regression_verdicts(bench_rounds, threshold, require_device)
    return {
        "bench_rounds": bench_rounds,
        "multichip_rounds": multichip,
        "ledger": led,
        "point_ops": point_ops_section(),
        "host_ceiling": host_ceiling_section(ledger_dir),
        "forge": forge_section(ledger_dir),
        "serve": serve_section(ledger_dir),
        "verdicts": verdicts,
        "ok": all(v["ok"] for v in verdicts),
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dir", default=REPO,
                    help="where the BENCH_r*.json round files live")
    ap.add_argument("--ledger", default=None,
                    help="run-ledger dir (default: the repo ledger; "
                         "pass 0 to skip)")
    ap.add_argument("--out", default=None, help="write markdown here "
                    "(default: stdout)")
    ap.add_argument("--json", dest="json_out", default=None,
                    help="write the JSON report here")
    ap.add_argument("--threshold", type=float, default=None,
                    help="regression rule: newest round's headers/s "
                         "must be >= THRESHOLD x the best previous "
                         "round's (exit 1 otherwise)")
    ap.add_argument("--require-device", action="store_true",
                    help="regression rule: newest round must have "
                         "banked a device result")
    args = ap.parse_args(argv)

    report = build_report(args.dir, args.threshold, args.require_device,
                          args.ledger)
    md = render_markdown(report)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(md)
    else:
        sys.stdout.write(md)
    if args.json_out:
        with open(args.json_out, "w", encoding="utf-8") as f:
            json.dump(report, f, indent=2, sort_keys=True)
            f.write("\n")
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
