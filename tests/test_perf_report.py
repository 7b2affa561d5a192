"""scripts/perf_report.py over five recorded bench rounds (kept as
fixtures under tests/golden/rounds/): the
trajectory report must identify r01 as the only device-banking round,
attribute r02–r05 to their recorded failure modes, render valid
markdown + JSON, fold a run ledger when one exists, and exit non-zero
under a configurable regression threshold (the future CI perf gate)."""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROUNDS = os.path.join(REPO, "tests", "golden", "rounds")

_spec = importlib.util.spec_from_file_location(
    "perf_report", os.path.join(REPO, "scripts", "perf_report.py")
)
perf_report = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(perf_report)


@pytest.fixture(scope="module")
def report():
    return perf_report.build_report(ROUNDS, threshold=None,
                                    require_device=False, ledger_dir="0")


def test_r01_is_the_only_device_banking_round(report):
    rounds = report["bench_rounds"]
    assert [r["round"] for r in rounds] == [1, 2, 3, 4, 5]
    banked = [r["round"] for r in rounds if r["device_banked"]]
    assert banked == [1]
    r01 = rounds[0]
    assert r01["value_per_s"] == pytest.approx(3985.7)
    assert r01["vs_baseline"] == pytest.approx(2.93)
    assert r01["failures"] == []


def test_dead_rounds_attributed_to_recorded_failure_modes(report):
    by_round = {r["round"]: r for r in report["bench_rounds"]}
    modes = {
        n: {f["mode"] for f in by_round[n]["failures"]} for n in (2, 3, 4, 5)
    }
    # r02 died at the driver wall while the backend probe hung
    assert "backend-probe-timeout" in modes[2]
    assert any(m.startswith("driver-timeout") for m in modes[2])
    # r03/r04: probe timeouts, clean fallback to the native number
    assert modes[3] == {"backend-probe-timeout"}
    assert modes[4] == {"backend-probe-timeout"}
    assert by_round[3]["native_baseline_per_s"] == pytest.approx(2007.0)
    # r05: stored-executable rejections + the attempt exceeding its wall
    assert "aot-cache-rejected" in modes[5]
    assert "warmup-exceeded-wall" in modes[5]
    assert by_round[5]["headers"] == 1_000_000


def test_markdown_and_json_render(report, tmp_path):
    md = perf_report.render_markdown(report)
    assert "r01" in md and "YES" in md
    assert "backend-probe-timeout" in md
    assert "aot-cache-rejected" in md
    # JSON round-trips strictly
    json.loads(json.dumps(report, allow_nan=False))
    assert report["multichip_rounds"], "MULTICHIP files must fold in"


def test_threshold_regression_verdict(report):
    verdicts = perf_report.regression_verdicts(
        report["bench_rounds"], threshold=0.8, require_device=False
    )
    (v,) = verdicts
    assert not v["ok"]  # r05's 2484 native vs r01's 3985.7 device
    assert "r05" in v["detail"]
    ok = perf_report.regression_verdicts(
        report["bench_rounds"], threshold=0.5, require_device=False
    )
    assert ok[0]["ok"]
    dv = perf_report.regression_verdicts(
        report["bench_rounds"], threshold=None, require_device=True
    )
    assert not dv[0]["ok"]
    assert "banked NO device result" in dv[0]["detail"]


def test_threshold_fails_a_round_with_no_value_at_all(report):
    """The worst regression: the newest round produced NO measurable
    number (the r02 shape — driver kill before the JSON line). The
    threshold gate must fail it, not silently pass for lack of a
    number to compare."""
    rounds = [dict(r) for r in report["bench_rounds"]]
    rounds.append({
        "round": 6, "device_banked": False, "value_per_s": None,
        "failures": [{"mode": "driver-timeout (rc=137)",
                      "detail": "killed"}],
    })
    (v,) = perf_report.regression_verdicts(rounds, threshold=0.5,
                                           require_device=False)
    assert not v["ok"]
    assert "no measurable" in v["detail"]
    assert "driver-timeout" in v["detail"]


def test_threshold_with_no_prior_value_is_explicit_not_silent():
    """A configured threshold must always produce a verdict: with no
    previous round banking a value (or a single round), the rule says
    so explicitly instead of letting `all([])` go green unevaluated."""
    dead = {"round": 1, "device_banked": False, "value_per_s": None,
            "failures": []}
    live = {"round": 2, "device_banked": True, "value_per_s": 100.0,
            "failures": []}
    for rounds in ([live], [dead, dict(live, round=2)],
                   [dead, dict(dead, round=2)]):
        verdicts = perf_report.regression_verdicts(
            rounds, threshold=0.8, require_device=False
        )
        assert len(verdicts) == 1, rounds
        assert verdicts[0]["ok"]
        assert "nothing to compare" in verdicts[0]["detail"]


def test_cli_exit_codes_and_outputs(tmp_path):
    """The CI-gate contract: report-only exits 0; a tripped threshold
    exits 1; --json writes a parseable document."""
    jout = str(tmp_path / "report.json")
    mout = str(tmp_path / "report.md")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "perf_report.py"),
         "--dir", ROUNDS, "--ledger", "0", "--json", jout, "--out", mout],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert p.returncode == 0, p.stderr
    with open(jout, encoding="utf-8") as f:
        doc = json.load(f)
    assert doc["ok"] and len(doc["bench_rounds"]) == 5
    assert os.path.getsize(mout) > 200
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "perf_report.py"),
         "--dir", ROUNDS, "--ledger", "0", "--threshold", "0.8"],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert p.returncode == 1, "a tripped threshold must exit non-zero"
    assert "REGRESSION" in p.stdout


def test_ledger_fold_reports_env_and_build_transitions(tmp_path,
                                                      monkeypatch):
    """The r01→r02 question answered by the ledger: consecutive bench
    records with different env/build facts surface as transitions."""
    from ouroboros_consensus_tpu.obs import ledger

    led = str(tmp_path / "led")
    monkeypatch.setenv("OCT_LEDGER", led)
    monkeypatch.setenv("OCT_VRF_AGG", "1")
    ledger.record_run("bench", result={"value": 3985.7},
                      build_id="pjrt-v8")
    monkeypatch.setenv("OCT_VRF_AGG", "0")
    ledger.record_run("bench", result={"value": 2007.0,
                                       "device_unavailable": True},
                      build_id="pjrt-v9")
    sec = perf_report.ledger_section(led)
    assert sec["runs"] == 2 and sec["by_kind"] == {"bench": 2}
    (tr,) = sec["bench_transitions"]
    assert tr["changed"]["build_id"] == ["pjrt-v8", "pjrt-v9"]
    assert tr["changed"]["env"]["OCT_VRF_AGG"] == ["1", "0"]
    # and the full report folds it
    rep = perf_report.build_report(ROUNDS, None, False, led)
    assert rep["ledger"]["runs"] == 2
    md = perf_report.render_markdown(rep)
    assert "OCT_VRF_AGG" in md


# ---------------------------------------------------------------------------
# round 10: structured probe classification + laddered rounds
# ---------------------------------------------------------------------------


def _write_round(tmp_path, n, parsed, tail="", rc=0):
    doc = {"rc": rc, "tail": tail, "parsed": parsed}
    p = os.path.join(tmp_path, f"BENCH_r{n:02d}.json")
    with open(p, "w") as f:
        json.dump(doc, f)
    return p


def test_structured_probe_verdict_classifies_distinctly(tmp_path):
    """bench.py now BANKS the probe verdict: probe-timeout vs
    driver-timeout vs run-death are separated structurally, no regex
    archaeology on the tail."""
    p = _write_round(
        tmp_path, 6,
        {"value": 2100.0, "device_unavailable": True,
         "no_device_reason": "backend-probe-timeout",
         "probe": {"ok": False, "outcome": "backend-probe-timeout",
                   "attempts": [
                       {"outcome": "probe-timeout", "wall_s": 90.0},
                       {"outcome": "probe-timeout", "wall_s": 60.0},
                   ]}},
        tail="# device probe failed (attempt 2): probe timed out",
    )
    row = perf_report.analyze_bench_round(p)
    assert not row["device_banked"]
    modes = [f["mode"] for f in row["failures"]]
    assert modes[0] == "backend-probe-timeout"
    assert modes.count("backend-probe-timeout") == 1  # deduped vs regex
    # a probe that ANSWERED WRONGLY is a different failure class
    p2 = _write_round(
        tmp_path, 7,
        {"value": 2100.0, "device_unavailable": True,
         "no_device_reason": "backend-probe-error",
         "probe": {"ok": False, "outcome": "backend-probe-error",
                   "attempts": [{"outcome": "probe-error",
                                 "wall_s": 3.0, "detail": "boom"}]}},
    )
    row2 = perf_report.analyze_bench_round(p2)
    assert [f["mode"] for f in row2["failures"]][0] == "backend-probe-error"
    # run-death after a GOOD probe classifies as the banked reason
    p3 = _write_round(
        tmp_path, 8,
        {"value": 2100.0, "device_unavailable": True,
         "no_device_reason": "device-run-failed-or-wall",
         "probe": {"ok": True, "outcome": "ok", "attempts": []}},
    )
    row3 = perf_report.analyze_bench_round(p3)
    modes3 = [f["mode"] for f in row3["failures"]]
    assert "device-run-failed-or-wall" in modes3
    assert not any(m.startswith("backend-probe") for m in modes3)


def test_laddered_round_is_its_own_class(tmp_path):
    """A round that banked THROUGH the warm ladder renders as
    'laddered', not lumped with warmup deaths; a dead round with ladder
    events keeps its failure modes but notes the engagement."""
    ladder = [
        {"kind": "engaged", "rung": 1024, "target": 8192, "t": 1.0},
        {"kind": "bg-compile-started", "rung": 1024, "target": 8192,
         "t": 1.1},
        {"kind": "bg-compile-done", "rung": 1024, "target": 8192,
         "wall_s": 410.0, "t": 411.1},
        {"kind": "swap", "rung": 1024, "target": 8192, "t": 411.2},
    ]
    p = _write_round(
        tmp_path, 6,
        {"value": 4100.0, "vs_baseline": 2.1, "laddered": True,
         "metric": "end-to-end db-analyser revalidation of a "
                   "1000000-header synthetic Praos chain",
         "warmup_report": {"ladder": ladder, "stages": {},
                           "aot": {}, "refusals": []}},
    )
    row = perf_report.analyze_bench_round(p)
    assert row["device_banked"] and row["laddered"] and row["ladder_swapped"]
    assert row["failures"] == []
    assert row["warmup"]["ladder"] == 4
    report = {"bench_rounds": [row], "multichip_rounds": [],
              "ledger": None, "verdicts": [], "ok": True}
    md = perf_report.render_markdown(report)
    assert "laddered (swapped)" in md
    assert "## Laddered rounds" in md
    # dead-but-laddered: failure modes survive, engagement noted
    p2 = _write_round(
        tmp_path, 7,
        {"value": 2100.0, "device_unavailable": True,
         "no_device_reason": "device-run-failed-or-wall",
         "warmup_report": {"ladder": ladder[:2], "stages": {},
                           "aot": {}, "refusals": []}},
        rc=124,
    )
    row2 = perf_report.analyze_bench_round(p2)
    assert not row2["device_banked"] and row2["laddered"]
    md2 = perf_report.render_markdown(
        {"bench_rounds": [row2], "multichip_rounds": [], "ledger": None,
         "verdicts": [], "ok": False})
    assert "warm ladder HAD engaged" in md2


def test_stalled_round_classifies_by_live_plane(tmp_path):
    """Round 11: a dead round with a banked stall dump (or whose
    heartbeat timeline's last word is stalled/dead) classifies as
    stalled@<phase> — distinct from probe-timeout and compile-wall."""
    p = _write_round(
        tmp_path, 6,
        {"value": 2100.0, "device_unavailable": True,
         "no_device_reason": "device-run-failed-or-wall",
         "probe": {"ok": True, "outcome": "ok", "attempts": []},
         "stall_dump": {
             "phase": "dispatch", "age_s": 600.0, "budget_s": 240.0,
             "threads": {"MainThread-1": ["  File ...dispatch_batch"]},
         },
         "live_timeline": [
             {"t": 0.0, "attempt": 1, "state": "compiling"},
             {"t": 120.0, "attempt": 1, "state": "running",
              "phase": "dispatch", "headers": 81920, "age_s": 1.0},
             {"t": 700.0, "attempt": 1, "state": "stalled",
              "phase": "dispatch", "headers": 81920, "age_s": 600.0},
         ]},
        rc=124,
    )
    row = perf_report.analyze_bench_round(p)
    assert not row["device_banked"]
    modes = [f["mode"] for f in row["failures"]]
    assert modes[0] == "stalled@dispatch"
    assert row["stalled_phase"] == "dispatch"
    assert row["live_states"] == ["compiling", "running", "stalled"]
    assert not any(m.startswith("backend-probe") for m in modes)
    md = perf_report.render_markdown(
        {"bench_rounds": [row], "multichip_rounds": [], "ledger": None,
         "verdicts": [], "ok": False})
    assert "stalled@dispatch" in md

    # no dump, but the tailed timeline's last heartbeat says DEAD at
    # phase=materialize: still stalled@materialize, from the timeline
    p2 = _write_round(
        tmp_path, 7,
        {"value": 2100.0, "device_unavailable": True,
         "no_device_reason": "device-run-failed-or-wall",
         "live_timeline": [
             {"t": 0.0, "attempt": 1, "state": "running",
              "phase": "dispatch", "headers": 1000},
             {"t": 650.0, "attempt": 1, "state": "dead",
              "phase": "materialize", "headers": 81920, "age_s": 610.0},
         ]},
        rc=124,
    )
    row2 = perf_report.analyze_bench_round(p2)
    modes2 = [f["mode"] for f in row2["failures"]]
    assert modes2[0] == "stalled@materialize"
    # a HEALTHY banked round with a timeline gains no failure modes
    p3 = _write_round(
        tmp_path, 8,
        {"value": 4100.0, "vs_baseline": 2.1,
         "metric": "end-to-end db-analyser revalidation of a "
                   "1000000-header synthetic Praos chain",
         "live_timeline": [
             {"t": 0.0, "attempt": 1, "state": "compiling"},
             {"t": 400.0, "attempt": 1, "state": "running",
              "phase": "retired", "headers": 1000000},
         ]},
    )
    row3 = perf_report.analyze_bench_round(p3)
    assert row3["device_banked"] and row3["failures"] == []
    assert row3["live_states"] == ["compiling", "running"]
