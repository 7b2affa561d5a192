"""The run ledger (obs/ledger.py): record schema, append-only JSONL
semantics, the OCT_LEDGER override/kill-switch, corrupt-line tolerance,
and the bench-shaped acceptance path — bench.append_ledger_record (the
exact function bench.main calls) must append exactly one well-formed
record per run."""

from __future__ import annotations

import json
import os

import pytest

from ouroboros_consensus_tpu.obs import ledger


@pytest.fixture
def tmp_ledger(tmp_path, monkeypatch):
    d = str(tmp_path / "ledger")
    monkeypatch.setenv("OCT_LEDGER", d)
    return d


def _lines(d):
    out = []
    for name in sorted(os.listdir(d)):
        with open(os.path.join(d, name), encoding="utf-8") as f:
            out.extend(ln for ln in f.read().splitlines() if ln.strip())
    return out


def test_record_run_appends_exactly_one_valid_line(tmp_ledger):
    rec = ledger.record_run(
        "unit", config={"n": 7}, result={"ok": True}, wall_s=1.25,
    )
    assert rec is not None
    lines = _lines(tmp_ledger)
    assert len(lines) == 1
    on_disk = json.loads(lines[0])
    assert ledger.validate_record(on_disk) == []
    assert on_disk["kind"] == "unit"
    assert on_disk["config"] == {"n": 7}
    assert on_disk["result"] == {"ok": True}
    assert on_disk["wall_s"] == 1.25
    # provenance is complete at append time, not reconstructed later
    assert "rev" in on_disk["git"] and "dirty" in on_disk["git"]
    assert isinstance(on_disk["env"], dict)
    # this very test runs under OCT_LEDGER -> the kill-switch state is
    # IN the record
    assert on_disk["env"].get("OCT_LEDGER") == tmp_ledger
    assert on_disk["host"]["platform"]
    # day-keyed file name
    (fname,) = os.listdir(tmp_ledger)
    assert fname.startswith("runs-") and fname.endswith(".jsonl")


def test_git_provenance_matches_checkout():
    prov = ledger.git_provenance()
    # this repo IS a git checkout: the rev must resolve
    assert prov["rev"] and len(prov["rev"]) == 40
    assert prov["dirty"] in (True, False)


def test_kill_switch_and_override(tmp_path, monkeypatch):
    monkeypatch.setenv("OCT_LEDGER", "0")
    assert ledger.ledger_dir() is None
    assert ledger.record_run("unit") is None
    d = str(tmp_path / "elsewhere")
    monkeypatch.setenv("OCT_LEDGER", d)
    assert ledger.ledger_dir() == d
    assert ledger.record_run("unit") is not None
    assert len(_lines(d)) == 1


def test_append_only_and_corrupt_line_tolerance(tmp_ledger):
    ledger.record_run("a", result={"i": 1})
    # a torn append (crash mid-write) must be skipped, not fatal
    path = ledger.day_file(tmp_ledger)
    with open(path, "a", encoding="utf-8") as f:
        f.write('{"torn": \n')
    ledger.record_run("b", result={"i": 2})
    runs = ledger.read_runs(tmp_ledger)
    assert [r["kind"] for r in runs] == ["a", "b"]
    assert ledger.read_runs(tmp_ledger, kind="b")[0]["result"] == {"i": 2}


def test_validate_record_rejects_malformed():
    assert ledger.validate_record([]) != []
    assert ledger.validate_record({}) != []
    good = ledger.build_record("unit")
    assert ledger.validate_record(good) == []
    bad = dict(good)
    bad["schema"] = 99
    assert any("schema" in e for e in ledger.validate_record(bad))
    bad = dict(good)
    bad["metrics"] = "not-a-dict"
    assert any("metrics" in e for e in ledger.validate_record(bad))
    bad = dict(good)
    bad["wall_s"] = float("nan")
    assert any("JSON" in e for e in ledger.validate_record(bad))


def test_runtime_build_id_never_initializes_a_backend():
    """The parent bench process never touches the backend (the ledger
    must neither wait on nor take the chip): with no backend
    initialized the probe must answer None, not block."""
    import sys

    if "jax" not in sys.modules:
        assert ledger.runtime_build_id() is None
    else:
        # jax already imported by the test session: the probe may
        # answer a string (backend up — conftest pinned cpu) or None,
        # but must never raise
        v = ledger.runtime_build_id()
        assert v is None or isinstance(v, str)


# ---------------------------------------------------------------------------
# Acceptance: a bench.py-shaped run appends exactly one well-formed
# record through the SAME function bench.main calls
# ---------------------------------------------------------------------------


def test_bench_shaped_run_appends_one_record(tmp_ledger):
    import bench

    out = {
        "metric": "end-to-end db-analyser revalidation of a "
                  "100000-header synthetic Praos chain",
        "value": 3985.7, "unit": "headers/s", "vs_baseline": 2.93,
        "build_id": "test-build-v9",
        "phases_s": {"dispatch": 1.5, "materialize": 2.0},
        "warmup_report": {"stages": {"ed@b8192": {"wall_s": 12.0}},
                          "refusals": []},
        "metrics": {"oct_windows_total": {"type": "counter",
                                          "samples": []}},
        "metrics_summary": {"windows": 13},
        "device_resources": {
            "ed@b8192|8192|7": {"flops": 123, "via": "jit"},
        },
    }
    rec = bench.append_ledger_record(out, baseline=1359.0,
                                     native_wall_s=49.8)
    assert rec is not None
    lines = _lines(tmp_ledger)
    assert len(lines) == 1
    on_disk = json.loads(lines[0])
    assert ledger.validate_record(on_disk) == []
    assert on_disk["kind"] == "bench"
    # the obs blocks land in their dedicated sections, and the result
    # is the SLIM outcome (no double banking of the big blocks)
    assert on_disk["warmup_report"] == out["warmup_report"]
    assert on_disk["metrics_summary"] == {"windows": 13}
    assert on_disk["device_resources"] == out["device_resources"]
    assert "metrics" not in on_disk["result"]
    assert "warmup_report" not in on_disk["result"]
    assert on_disk["result"]["value"] == 3985.7
    assert on_disk["build_id"] == "test-build-v9"
    assert on_disk["config"]["headers"] == bench.BENCH_HEADERS
    assert on_disk["extra"]["native_baseline_per_s"] == 1359.0


def test_bench_ledger_failure_is_soft(tmp_path, monkeypatch):
    """The bench's one JSON line must survive a broken ledger: point
    OCT_LEDGER at a path that cannot be a directory."""
    import bench

    blocker = tmp_path / "not-a-dir"
    blocker.write_text("file, not dir")
    monkeypatch.setenv("OCT_LEDGER", str(blocker / "sub"))
    assert bench.append_ledger_record({"value": 1.0}) is None


def test_bench_suite_emit_appends_record(tmp_ledger, capsys):
    """The suite path: every _emit'd config row lands in the ledger as
    one kind="bench_suite" record."""
    import importlib.util

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_suite", os.path.join(repo, "scripts", "bench_suite.py")
    )
    bs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bs)
    bs._emit(2, "standalone Ed25519 verifies", 256, 0.5, 1.0,
             extra={"warmup_report": {"stages": {}}})
    runs = ledger.read_runs(tmp_ledger, kind="bench_suite")
    assert len(runs) == 1
    rec = runs[0]
    assert ledger.validate_record(rec) == []
    assert rec["config"] == {"config": 2, "n": 256}
    assert rec["result"]["vs_baseline"] == 2.0
    # the obs block moved to its dedicated section, out of the result
    assert "warmup_report" not in rec["result"]
    assert rec["warmup_report"] == {"stages": {}}


# ---------------------------------------------------------------------------
# the round-11 CLI: python -m ouroboros_consensus_tpu.obs.ledger tail
# ---------------------------------------------------------------------------


def test_cli_tail_last_and_build_id_filters(tmp_ledger, capsys):
    for i in range(5):
        ledger.record_run(
            "bench" if i % 2 == 0 else "profile_replay",
            config={"i": i},
            result={"value": 1000.0 + i, "unit": "headers/s"},
            wall_s=10.0 + i,
            build_id=f"libtpu-v{i % 2}",
        )
    # tail --last 2: the two NEWEST records, one line each
    rc = ledger.main(["tail", "--last", "2", "--dir", tmp_ledger])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0 and len(out) == 2
    assert "1003" in out[0] and "1004" in out[1]
    assert "headers/s" in out[1] and "bench" in out[1]
    # --build-id substring filter
    rc = ledger.main(
        ["tail", "--last", "10", "--build-id", "libtpu-v1",
         "--dir", tmp_ledger]
    )
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0 and len(out) == 2  # i in {1, 3}
    # --kind filter composes
    rc = ledger.main(
        ["tail", "--last", "10", "--kind", "bench", "--dir", tmp_ledger]
    )
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0 and len(out) == 3  # i in {0, 2, 4}
    # --json emits the full records as JSONL
    rc = ledger.main(
        ["tail", "--last", "1", "--json", "--dir", tmp_ledger]
    )
    out = capsys.readouterr().out.strip()
    rec = json.loads(out)
    assert ledger.validate_record(rec) == []
    assert rec["result"]["value"] == 1004.0
    # empty result set: non-zero exit, no traceback
    rc = ledger.main(
        ["tail", "--build-id", "nope", "--dir", tmp_ledger]
    )
    capsys.readouterr()
    assert rc == 1
    # --last 0 means NONE, not "the whole ledger" (runs[-0:] trap)
    rc = ledger.main(["tail", "--last", "0", "--dir", tmp_ledger])
    out = capsys.readouterr().out
    assert rc == 1 and "no matching" in out


def test_cli_blurb_surfaces_no_device_stalls_and_shards(tmp_ledger, capsys):
    """The one-liner answers "what did the last live session do": a
    no-device round shows its reason, stall trips and per-shard
    telemetry are called out."""
    ledger.record_run(
        "bench",
        result={"value": 2100.0, "unit": "headers/s",
                "device_unavailable": True,
                "no_device_reason": "backend-probe-timeout"},
        metrics={
            "oct_stalls_total": {"samples": [
                {"labels": {"phase": "dispatch"}, "value": 1},
            ]},
            "oct_shard_lanes_total": {"samples": [
                {"labels": {"shard": str(i)}, "value": 8} for i in range(8)
            ]},
        },
        wall_s=100.0,
    )
    rc = ledger.main(["tail", "--last", "1", "--dir", tmp_ledger])
    out = capsys.readouterr().out
    assert rc == 0
    assert "NO-DEVICE (backend-probe-timeout)" in out
    assert "1 STALL(s)" in out
    assert "per-shard telemetry x8" in out


def test_cli_module_entrypoint_runs(tmp_ledger):
    """python -m ouroboros_consensus_tpu.obs.ledger actually executes
    (the __main__ guard)."""
    import subprocess
    import sys

    ledger.record_run("unit", result={"value": 1.0, "unit": "x"})
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "ouroboros_consensus_tpu.obs.ledger",
         "tail", "--last", "1", "--dir", tmp_ledger],
        capture_output=True, text=True, timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "unit" in proc.stdout
