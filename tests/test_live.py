"""The live run plane (obs/live.py + obs/server.py): heartbeat
snapshots and crash safety, reader-side classification, the stubbed-
clock stall watchdog (a wedged dispatch_batch must be named in the
dump), the in-replay HTTP endpoint answering mid-replay, and the
bench-parent timeline machinery.

Crypto is the hash-only stub where a replay is needed (the test_obs
idiom): the live plumbing is what's under test."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request
from fractions import Fraction

import pytest

import jax  # noqa: F401 — backend pinned by conftest

from ouroboros_consensus_tpu import obs
from ouroboros_consensus_tpu.obs import live, server
from ouroboros_consensus_tpu.obs.registry import MetricsRegistry
from ouroboros_consensus_tpu.protocol import batch as pbatch
from ouroboros_consensus_tpu.protocol import praos
from ouroboros_consensus_tpu.testing import fixtures
from ouroboros_consensus_tpu.utils import trace as T

from tests.test_obs import _forge_chain, make_params
from tests.test_packed_batch import _stub_verify


@pytest.fixture(autouse=True)
def _fresh_obs():
    obs.reset_for_tests()
    yield
    obs.reset_for_tests()


@pytest.fixture(scope="module")
def pools():
    return [fixtures.make_pool(70 + i, kes_depth=3) for i in range(2)]


@pytest.fixture(scope="module")
def lview(pools):
    return fixtures.make_ledger_view(pools)


@pytest.fixture
def stubbed(monkeypatch):
    before = set(pbatch._JIT)
    monkeypatch.setenv("OCT_VRF_AGG", "0")
    monkeypatch.setattr(pbatch, "verify_praos", _stub_verify)
    monkeypatch.setattr(pbatch, "verify_praos_bc", _stub_verify)
    monkeypatch.setattr(pbatch, "verify_praos_any", _stub_verify)

    def patched_jv(bc=False):
        key = ("fn-stub-live", bc)
        if key not in pbatch._JIT:
            pbatch._JIT[key] = jax.jit(_stub_verify)
        return pbatch._JIT[key]

    monkeypatch.setattr(pbatch, "_jitted_verify", patched_jv)
    yield
    for k in set(pbatch._JIT) - before:
        del pbatch._JIT[k]


def _span(index=0, n_valid=8):
    return T.WindowSpan(
        index=index, lanes=8, outcome="packed", gate=None, stage_s=0.01,
        dispatch_s=0.02, materialize_s=0.03, epilogue_s=0.004,
        t_dispatch=1.0, t_materialized=2.0, t_done=3.0,
        n_valid=n_valid, failed=False,
    )


# ---------------------------------------------------------------------------
# snapshot + phase classification
# ---------------------------------------------------------------------------


def test_live_snapshot_phase_from_last_event():
    from ouroboros_consensus_tpu.obs.warmup import WARMUP

    # process-wide: a file that ran before this one in the worker and
    # noted a first execute would read as "warmup", not "idle"
    WARMUP.reset()
    rec = obs.recorder()
    doc = live.live_snapshot(rec)
    assert doc["phase"] == "idle" and doc["headers"] == 0
    rec(T.WindowStaged(0, 8, 16, "packed", None, 0.01, 0.02))
    assert live.live_snapshot(rec)["phase"] == "dispatch"
    rec(T.EncloseEvent("materialize", "start", 1.0))
    assert live.live_snapshot(rec)["phase"] == "materialize"
    rec(_span(0))
    doc = live.live_snapshot(rec)
    assert doc["phase"] == "retired"
    assert doc["headers"] == 8 and doc["window_index"] == 0
    json.dumps(doc, allow_nan=False)  # strict-JSON like every obs doc


def test_live_snapshot_warmup_side():
    from ouroboros_consensus_tpu.obs.warmup import WARMUP

    WARMUP.reset()
    try:
        WARMUP.note("aggregate_core@b8192 first execute starting")
        doc = live.live_snapshot(obs.recorder())
        assert doc["phase"] == "warmup"
        assert "first execute starting" in doc["warmup"]["last_note"]
        assert live.classify(doc) == "compiling"
    finally:
        WARMUP.reset()


def test_classify_compiling_overrides_frozen_dispatch_phase():
    """An in-flight FOREGROUND first-execute (the ~410 s wall): the
    dispatch loop's last event is stale, but the warmup's last note
    says '<stage> first execute starting' with no completion row — the
    live classification must say compiling, not running/stalled."""
    from ouroboros_consensus_tpu.obs.warmup import WARMUP

    WARMUP.reset()
    try:
        rec = obs.recorder()
        rec(T.WindowStaged(0, 8, 16, "packed", None, 0.01, 0.02))
        WARMUP.note("aggregate_core@b8192 first execute starting")
        doc = live.live_snapshot(rec)
        assert doc["phase"] == "dispatch"  # where the loop froze
        assert doc["warmup"]["compiling_now"]
        assert live.classify(doc) == "compiling"
        # the completion row flips it back to the loop's own phase
        WARMUP.note_stage("aggregate_core@b8192", 410.0)
        doc = live.live_snapshot(rec)
        assert not doc["warmup"]["compiling_now"]
        assert live.classify(doc) == "running"
    finally:
        WARMUP.reset()


def test_classify_vocabulary():
    assert live.classify(None) == "no-heartbeat"
    assert live.classify({"nope": 1}) == "no-heartbeat"
    now = time.time()
    base = {"ts_unix": now, "warmup": {}}
    assert live.classify({**base, "phase": "stage"}, now) == "staging"
    assert live.classify({**base, "phase": "stream"}, now) == "staging"
    for p in ("dispatch", "materialize", "retired", "epilogue"):
        assert live.classify({**base, "phase": p}, now) == "running"
    assert live.classify({**base, "phase": "warmup"}, now) == "compiling"
    assert live.classify({**base, "phase": "idle"}, now) == "idle"
    assert live.classify({**base, "phase": "idle", "stalled_now": True},
                         now) == "stalled"
    # the LIFETIME stall count is informational only: a run that
    # stalled once and recovered classifies by its live phase again
    assert live.classify(
        {**base, "phase": "retired", "stalls": 2, "stalled_now": False},
        now,
    ) == "running"
    # the file stopped being rewritten -> dead, whatever it says
    assert live.classify({**base, "phase": "dispatch"},
                         now + 1000) == "dead"


# ---------------------------------------------------------------------------
# heartbeat: rolling rate, atomic rewrite, SIGKILL crash safety
# ---------------------------------------------------------------------------


def test_heartbeat_beats_and_rolling_rate(tmp_path):
    rec = obs.recorder()
    clk = [100.0]
    path = str(tmp_path / "hb.json")
    hb = live.Heartbeat(path, rec=rec, clock=lambda: clk[0])
    hb.beat()
    doc0 = live.read_heartbeat(path)
    assert doc0["seq"] == 0 and doc0["headers_per_s"] is None
    rec(_span(0, n_valid=100))
    clk[0] = 110.0
    hb.beat()
    doc1 = live.read_heartbeat(path)
    assert doc1["seq"] == 1
    assert doc1["headers"] == 100
    assert doc1["headers_per_s"] == pytest.approx(10.0)
    # samples outside the rolling window age out
    clk[0] = 110.0 + live.RATE_WINDOW_S + 1
    hb.beat()
    assert live.read_heartbeat(path)["headers_per_s"] == pytest.approx(0.0)


def test_heartbeat_thread_start_stop(tmp_path):
    path = str(tmp_path / "hb.json")
    hb = live.Heartbeat(path, rec=obs.recorder(), interval_s=0.05)
    hb.start()
    time.sleep(0.25)
    hb.stop()
    doc = live.read_heartbeat(path)
    assert doc is not None and doc["seq"] >= 2
    assert doc["interval_s"] == 0.05


def test_heartbeat_thread_survives_beat_errors(tmp_path, monkeypatch):
    """A raising beat must not kill the heartbeat thread, must not be
    swallowed silently (the count surfaces as `beat_errors` in the next
    good document + ONE bounded warmup note), and stop() must still
    join and land a final beat."""
    from ouroboros_consensus_tpu.obs.warmup import WARMUP

    WARMUP.reset()
    try:
        path = str(tmp_path / "hb.json")
        hb = live.Heartbeat(path, rec=obs.recorder(), interval_s=0.02)
        boom = [True]
        real_beat = hb.beat

        def flaky_beat():
            if boom[0]:
                raise RuntimeError("snapshot source wedged")
            return real_beat()

        monkeypatch.setattr(hb, "beat", flaky_beat)
        hb.start()  # the immediate armed-plane beat raises too
        time.sleep(0.15)
        assert hb._thread is not None and hb._thread.is_alive()
        assert hb.beat_errors >= 2  # kept beating through the errors
        boom[0] = False
        time.sleep(0.1)
        hb.stop()  # joins cleanly; the final beat succeeds
        doc = live.read_heartbeat(path)
        assert doc is not None
        assert doc["beat_errors"] >= 2  # failures stay visible
        # one bounded forensic note, not one per failed interval
        notes = [n for n in WARMUP.report()["notes"]
                 if "heartbeat beat failed" in n]
        assert len(notes) == 1
        assert "RuntimeError" in notes[0]
    finally:
        WARMUP.reset()


def test_heartbeat_survives_a_kill_mid_rewrite(tmp_path):
    """Mirror of test_warmup_report_survives_a_kill: a child SIGKILLed
    mid-rewrite (a torn .tmp on disk) must leave the last COMPLETE beat
    readable — the parent's classification must never land on a torn
    file."""
    path = str(tmp_path / "hb.json")
    code = (
        "import os\n"
        "from ouroboros_consensus_tpu import obs\n"
        "from ouroboros_consensus_tpu.obs import live\n"
        "from ouroboros_consensus_tpu.utils import trace as T\n"
        "rec = obs.recorder()\n"
        "rec(T.WindowSpan(index=3, lanes=8, outcome='packed', gate=None,\n"
        "    stage_s=.01, dispatch_s=.02, materialize_s=.03,\n"
        "    epilogue_s=.004, t_dispatch=1., t_materialized=2., t_done=3.,\n"
        "    n_valid=8, failed=False))\n"
        f"hb = live.Heartbeat({path!r}, rec=rec)\n"
        "hb.beat()\n"
        "with open(hb.path + '.tmp', 'w') as f:\n"
        "    f.write('{\"torn\": tru')  # killed mid-rewrite\n"
        "os._exit(137)\n"
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, timeout=120,
    )
    assert proc.returncode == 137, proc.stderr.decode()[-2000:]
    doc = live.read_heartbeat(path)
    assert doc is not None, "a kill mid-rewrite must leave the last beat"
    assert doc["phase"] == "retired" and doc["headers"] == 8
    # and once the file goes stale the reader classifies the dead
    # child as dead, not running (fresh reads say running — correct,
    # the beat IS recent)
    assert live.classify(doc, now_unix=doc["ts_unix"] + 60) == "dead"


# ---------------------------------------------------------------------------
# stall watchdog: stubbed clock, wedged dispatch_batch named in the dump
# ---------------------------------------------------------------------------


def test_stall_watchdog_stubbed_clock_names_wedged_dispatch(tmp_path):
    """The forced-wedge harness: a thread wedged inside a frame named
    dispatch_batch, a recorder whose last event is the dispatch, and a
    stubbed clock driven past OCT_STALL_BUDGET_S. The dump must (a)
    name the wedged phase, (b) contain dispatch_batch in a thread
    stack, (c) increment oct_stalls_total{phase=}, and (d) emit a
    first-class StallEvent — and must NOT re-dump while the same stall
    persists."""
    rec = obs.recorder()
    # the last thing the replay did was dispatch a window
    rec(T.WindowStaged(7, 8, 16, "packed", None, 0.01, 0.02))

    wedged = threading.Event()
    release = threading.Event()

    def dispatch_batch(params, lview, eta0, hvs):
        wedged.set()
        release.wait(30)

    t = threading.Thread(
        target=dispatch_batch, args=(None,) * 4,
        name="oct-wedged-dispatch", daemon=True,
    )
    t.start()
    assert wedged.wait(10)

    clk = [1000.0]
    dump = str(tmp_path / "stall_dump.json")
    wd = live.StallWatchdog(
        budget_s=60.0, rec=rec, dump_path=dump, clock=lambda: clk[0]
    )
    assert wd.check() is None  # fresh fingerprint: armed, no trip
    clk[0] += 59.0
    assert wd.check() is None  # inside budget
    clk[0] += 2.0
    doc = wd.check()
    release.set()
    assert doc is not None, "61s without progress must trip a 60s budget"
    assert doc["phase"] == "dispatch"
    assert doc["age_s"] == pytest.approx(61.0)
    stacks = "\n".join(
        ln for frames in doc["threads"].values() for ln in frames
    )
    assert "dispatch_batch" in stacks, "the dump must name the wedged stage"
    assert "oct-wedged-dispatch" in "\n".join(doc["threads"])
    # on-disk twin (+ the raw faulthandler dump)
    on_disk = json.load(open(dump))
    assert on_disk["phase"] == "dispatch"
    assert os.path.exists(dump + ".txt")
    # countable + first-class
    snap = rec.registry.snapshot()
    row = snap["oct_stalls_total"]["samples"][0]
    assert row["labels"] == {"phase": "dispatch"} and row["value"] == 1
    stall_evs = [e for _t, e in rec.timed_events()
                 if isinstance(e, T.StallEvent)]
    assert len(stall_evs) == 1 and stall_evs[0].dump_path == dump
    # one dump per stall episode — the watchdog's OWN StallEvent must
    # not read as progress: a persistent multi-budget wedge stays ONE
    # dump and ONE counted trip, never a re-dump per budget window
    for _ in range(10):
        clk[0] += 100.0
        assert wd.check() is None
    assert wd.dumps == 1
    snap2 = rec.registry.snapshot()
    assert sum(s["value"] for s in
               snap2["oct_stalls_total"]["samples"]) == 1
    # progress re-arms
    rec(_span(8))
    assert wd.check() is None and not wd.tripped
    clk[0] += 61.0
    assert wd.check() is not None, "a NEW stall after progress trips again"


def test_heartbeat_stalled_now_recovers_with_progress(tmp_path):
    """The beat carries the watchdog's CURRENT trip state: stalled
    while wedged, back to the live phase once progress resumes — the
    cumulative stalls count alone must not pin classify() to stalled."""
    rec = obs.recorder()
    rec(_span(0))
    clk = [0.0]
    path = str(tmp_path / "hb.json")
    wd = live.StallWatchdog(budget_s=10.0, rec=rec,
                            dump_path=str(tmp_path / "dump.json"),
                            clock=lambda: clk[0])
    hb = live.Heartbeat(path, rec=rec, watchdog=wd, clock=lambda: clk[0])
    hb.beat()
    clk[0] = 20.0
    doc = hb.beat()
    assert doc["stalled_now"] and doc["stalls"] == 1
    assert live.classify(doc, now_unix=doc["ts_unix"]) == "stalled"
    rec(_span(1))  # the wedge clears
    clk[0] = 25.0
    doc = hb.beat()
    assert not doc["stalled_now"] and doc["stalls"] == 1
    assert live.classify(doc, now_unix=doc["ts_unix"]) == "running"


def test_stall_watchdog_warmup_notes_count_as_progress(tmp_path):
    """A 400 s compile is NOT a stall: warmup notes (first executes,
    AOT outcomes) advance the progress fingerprint."""
    from ouroboros_consensus_tpu.obs.warmup import WARMUP

    WARMUP.reset()
    try:
        clk = [0.0]
        wd = live.StallWatchdog(budget_s=10.0, rec=obs.recorder(),
                                dump_path=str(tmp_path / "dump.json"),
                                clock=lambda: clk[0])
        clk[0] = 9.0
        WARMUP.note_stage("agg@b8192", 123.0)
        assert wd.check() is None
        clk[0] = 18.0  # 9s since the note: inside budget again
        assert wd.check() is None and not wd.tripped
        clk[0] = 30.0
        assert wd.check() is not None  # silence past the budget trips
    finally:
        WARMUP.reset()


# ---------------------------------------------------------------------------
# acceptance: /metrics.json + /healthz answer MID-REPLAY
# ---------------------------------------------------------------------------


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_metrics_port_answers_mid_replay(pools, lview, stubbed,
                                         monkeypatch, tmp_path):
    """A stubbed-crypto replay with OCT_METRICS_PORT (+ heartbeat +
    watchdog) armed answers /metrics.json and /healthz from a second
    thread WHILE a window is materializing — the round-11 acceptance
    criterion, in tier-1."""
    port = _free_port()
    hb_path = str(tmp_path / "hb.json")
    monkeypatch.setenv("OCT_METRICS_PORT", str(port))
    monkeypatch.setenv("OCT_HEARTBEAT", hb_path)
    monkeypatch.setenv("OCT_STALL_BUDGET_S", "300")
    params = make_params()
    _, hvs = _forge_chain(params, pools, lview, 24)
    st0 = praos.PraosState(epoch_nonce=b"\x07" * 32)

    in_materialize = threading.Event()
    scraped = threading.Event()
    orig_mat = pbatch.materialize_verdicts

    def slow_materialize(tagged, b):
        in_materialize.set()
        scraped.wait(15)  # hold the window open until the scrape lands
        return orig_mat(tagged, b)

    monkeypatch.setattr(pbatch, "materialize_verdicts", slow_materialize)

    plane = live.maybe_arm()
    assert plane is not None and plane.server is not None
    assert plane.server.port == port
    results: dict = {}

    def replay():
        results["res"] = pbatch.validate_chain(
            params, lambda _e: lview, st0, hvs, max_batch=8
        )

    t = threading.Thread(target=replay, daemon=True)
    t.start()
    try:
        assert in_materialize.wait(30), "replay never reached materialize"
        # mid-replay, from this (second) thread:
        hz = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=10).read())
        assert hz["pid"] == os.getpid()
        assert "phase" in hz and "headers" in hz
        mj = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics.json", timeout=10).read())
        assert "oct_windows_total" in mj
        pg = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/progress", timeout=10).read())
        assert set(pg) <= set(server._PROGRESS_KEYS)
        scraped.set()
        t.join(timeout=60)
        assert not t.is_alive()
        assert results["res"].error is None
        assert results["res"].n_valid == 24
        # the scrapes counted themselves on the shared registry
        snap = obs.recorder().registry.snapshot()
        paths = {s["labels"]["path"]
                 for s in snap["oct_metrics_scrapes_total"]["samples"]}
        assert {"/healthz", "/metrics.json", "/progress"} <= paths
        # and the heartbeat file was written
        assert live.read_heartbeat(hb_path) is not None
    finally:
        scraped.set()
        plane.disarm()
    # disarm stopped the server: the port no longer answers
    with pytest.raises(Exception):
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=2)


def test_maybe_arm_is_refcounted_and_lever_gated(monkeypatch, tmp_path):
    for var in ("OCT_HEARTBEAT", "OCT_STALL_BUDGET_S", "OCT_METRICS_PORT"):
        monkeypatch.delenv(var, raising=False)
    assert live.maybe_arm() is None  # no levers -> no plane
    monkeypatch.setenv("OCT_HEARTBEAT", str(tmp_path / "hb.json"))
    p1 = live.maybe_arm()
    p2 = live.maybe_arm()  # nested replays share ONE plane
    assert p1 is p2 and p1 is not None
    assert obs.installed()  # the plane installed the recorder
    p2.disarm()
    assert obs.installed(), "inner disarm must not tear the plane down"
    p1.disarm()
    assert not obs.installed()


def test_revalidate_arms_the_live_plane(monkeypatch, tmp_path):
    """db_analyser.revalidate mounts obs/live when a lever is set: the
    heartbeat file exists after a (tiny, host-backend) replay."""
    from ouroboros_consensus_tpu.tools import db_analyser as ana
    from ouroboros_consensus_tpu.tools import db_synthesizer as synth

    hb_path = str(tmp_path / "hb.json")
    monkeypatch.setenv("OCT_HEARTBEAT", hb_path)
    params = make_params()
    pools_ = [fixtures.make_pool(0, kes_depth=3)]
    lview_ = fixtures.make_ledger_view(pools_)
    path = str(tmp_path / "db")
    res = synth.synthesize(
        path, params, pools_, lview_, synth.ForgeLimit(blocks=6),
    )
    assert res.n_blocks == 6
    out = ana.revalidate(path, params, lview_, backend="host")
    assert out.error is None and out.n_valid == 6
    doc = live.read_heartbeat(hb_path)
    assert doc is not None and doc["seq"] >= 0
    # and the plane was disarmed on the way out
    assert not obs.installed()


# ---------------------------------------------------------------------------
# bench parent machinery: heartbeat tail timeline + stall-dump slimming
# ---------------------------------------------------------------------------


def test_bench_heartbeat_tail_and_stall_dump_slim(tmp_path, monkeypatch):
    import bench

    hb_path = str(tmp_path / "hb.json")
    timeline: list = []
    tail = bench._HeartbeatTail(hb_path, timeline, attempt=1)
    try:
        # no file yet -> no-heartbeat
        tail._poll()
        assert timeline and timeline[0]["state"] == "no-heartbeat"
        # a live beat flips the classification ONCE (dedup on state)
        rec = obs.recorder()
        rec(_span(0))
        live.Heartbeat(hb_path, rec=rec).beat()
        tail._poll()
        tail._poll()
        assert [e["state"] for e in timeline] == ["no-heartbeat", "running"]
        assert timeline[1]["phase"] == "retired"
        assert timeline[1]["headers"] == 8
        assert timeline[1]["attempt"] == 1
    finally:
        tail.stop()
    json.dumps(timeline, allow_nan=False)

    # stall-dump slimming keeps the classification + trimmed stacks
    dump_path = str(tmp_path / "stall_dump.json")
    clk = [0.0]
    wd = live.StallWatchdog(budget_s=1.0, rec=obs.recorder(),
                            dump_path=dump_path, clock=lambda: clk[0])
    clk[0] = 5.0
    assert wd.check() is not None
    monkeypatch.setenv("OCT_STALL_DUMP", dump_path)
    slim = bench._read_stall_dump()
    assert slim is not None
    assert slim["phase"] == "retired"  # last event before the wedge
    assert slim["threads"] and all(
        len(frames) <= 6 for frames in slim["threads"].values()
    )
    json.dumps(slim, allow_nan=False)


# ---------------------------------------------------------------------------
# round 12 satellites: classify() edge states feeding the supervisor,
# watchdog episodes across a recovery, failed-replay plane lifecycle
# ---------------------------------------------------------------------------


def test_classify_clock_skewed_future_beat():
    """A beat timestamp IN THE FUTURE (writer/reader clock skew) must
    classify by its live phase — never as dead (staleness is 'too far
    in the past', a skewed-forward clock is not evidence of death)."""
    now = time.time()
    doc = {"ts_unix": now + 3600, "phase": "dispatch", "warmup": {}}
    assert live.classify(doc, now) == "running"
    doc = {"ts_unix": now + 3600, "phase": "stage", "warmup": {}}
    assert live.classify(doc, now) == "staging"
    doc = {"ts_unix": now + 3600, "phase": "idle", "warmup": {},
           "stalled_now": True}
    assert live.classify(doc, now) == "stalled"


def test_classify_zero_window_replay(tmp_path):
    """A replay that never retires a window (empty chain / all work
    ahead of it): armed and fresh it reads idle — not stalled, not
    dead — and the rolling rate stays None, never NaN."""
    from ouroboros_consensus_tpu.obs.warmup import WARMUP

    WARMUP.reset()
    try:
        rec = obs.recorder()
        clk = [100.0]
        hb = live.Heartbeat(str(tmp_path / "hb.json"), rec=rec,
                            clock=lambda: clk[0])
        doc = hb.beat()
        assert doc["headers"] == 0 and doc["phase"] == "idle"
        assert doc["headers_per_s"] is None
        assert live.classify(doc, now_unix=doc["ts_unix"]) == "idle"
        clk[0] = 130.0
        doc = hb.beat()
        assert doc["headers_per_s"] == pytest.approx(0.0)
        assert live.classify(doc, now_unix=doc["ts_unix"]) == "idle"
        json.dumps(doc, allow_nan=False)
    finally:
        WARMUP.reset()


def test_watchdog_one_dump_per_episode_across_recovery(tmp_path):
    """The episode contract across a RECOVERY: a wedge trips once; the
    supervisor's ladder transitions count as progress (re-arming the
    watchdog mid-recovery); a NEW wedge after the recovered episode is
    a new episode with its own dump — one dump per episode, not per
    process."""
    from ouroboros_consensus_tpu.obs.warmup import WARMUP

    WARMUP.reset()
    try:
        rec = obs.recorder()
        rec(_span(0))
        clk = [0.0]
        wd = live.StallWatchdog(budget_s=10.0, rec=rec,
                                dump_path=str(tmp_path / "d.json"),
                                clock=lambda: clk[0])
        clk[0] = 11.0
        assert wd.check() is not None  # episode 1 trips: one dump
        clk[0] = 25.0
        assert wd.check() is None  # SAME episode: no re-dump
        assert wd.dumps == 1
        # the supervisor starts walking the wedged window down the
        # ladder — recovery transitions ARE progress
        WARMUP.note_recovery("retry", window=3, attempt=1,
                             fault="DeviceChaosError")
        clk[0] = 26.0
        assert wd.check() is None and not wd.tripped  # re-armed
        WARMUP.note_recovery("recovered", window=3, attempt=1,
                             fault="DeviceChaosError", ok=True)
        clk[0] = 27.0
        assert wd.check() is None
        clk[0] = 45.0
        assert wd.check() is not None  # a NEW wedge = a new episode
        assert wd.dumps == 2
        snap = rec.registry.snapshot()
        assert sum(s["value"] for s in
                   snap["oct_stalls_total"]["samples"]) == 2
    finally:
        WARMUP.reset()


def test_failed_replay_leaves_no_orphan_listener(monkeypatch, tmp_path):
    """The round-12 lifecycle satellite: an exception escaping the
    replay mid-run must still release maybe_arm()'s ref-count and stop
    the OCT_METRICS_PORT server thread — the port answers mid-replay
    and is CLOSED after the failure, with the recorder uninstalled."""
    from ouroboros_consensus_tpu.tools import db_analyser as ana
    from ouroboros_consensus_tpu.tools import db_synthesizer as synth

    port = _free_port()
    monkeypatch.setenv("OCT_METRICS_PORT", str(port))
    params = make_params()
    pools_ = [fixtures.make_pool(1, kes_depth=3)]
    lview_ = fixtures.make_ledger_view(pools_)
    path = str(tmp_path / "db")
    res = synth.synthesize(
        path, params, pools_, lview_, synth.ForgeLimit(blocks=4),
    )
    assert res.n_blocks == 4
    calls = []
    orig_update = ana.praos.update

    def boom(params_, hv, slot, ticked):
        if calls:
            raise RuntimeError("device fell over mid-replay")
        calls.append(1)
        return orig_update(params_, hv, slot, ticked)

    monkeypatch.setattr(ana.praos, "update", boom)
    with pytest.raises(RuntimeError, match="fell over"):
        ana.revalidate(path, params, lview_, backend="host")
    assert calls, "the replay must have started before failing"
    # the plane unwound: recorder released, no orphan listener
    assert not obs.installed()
    with pytest.raises(Exception):
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=2)
