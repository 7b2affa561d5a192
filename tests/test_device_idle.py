"""The device's idle time of a replay, put down to what the main thread
was doing (obs/idle.py), the off-CPU time of `dispatch` and `stage`, and
the `gc` span.

The account is checked on a synthetic span timeline whose parts are known
exactly, and on one stubbed device-path replay of a small synthesized
chain through `db_analyser.revalidate(collect_phases=True)` behind the
flight recorder (testing/stubs: the pipeline, its threads and its spans
are the real ones)."""

import gc
import time
from fractions import Fraction

import pytest

from ouroboros_consensus_tpu import obs
from ouroboros_consensus_tpu.obs import idle as obs_idle
from ouroboros_consensus_tpu.obs.warmup import WARMUP
from ouroboros_consensus_tpu.protocol import batch as pbatch
from ouroboros_consensus_tpu.protocol import praos
from ouroboros_consensus_tpu.testing import fixtures, stubs
from ouroboros_consensus_tpu.tools import db_analyser as ana
from ouroboros_consensus_tpu.tools import db_synthesizer as synth
from ouroboros_consensus_tpu.utils import trace as T

PARAMS = praos.PraosParams(
    slots_per_kes_period=100, max_kes_evolutions=62, security_param=4,
    active_slot_coeff=Fraction(1, 2), epoch_length=50, kes_depth=3,
)

# -- the partition, on a timeline whose parts are known ---------------------

BASE = 86_400.0  # a monotonic clock a day up: the sums must still be exact


def _span(label, t0, t1, thread="MainThread", window=None, parent=None):
    return T.EncloseEvent(label, "end", BASE + t1, t1 - t0, 7, window,
                          parent, thread)


# a replay [0, 100]: two windows, the second launched (36) while the first
# is on the device (30 → 48), so the device is busy on [30, 60]; idle at
# the head [0, 30], the first window's dispatch whole, and at the tail
# [60, 100]
TIMELINE = [
    _span("replay", 0, 100),
    _span("open", 1, 10, parent="replay"),
    _span("open.index", 2, 4, parent="open"),
    _span("stream", 3, 40, thread="oct-prefetch", parent="replay"),
    _span("validate-chain", 12, 95, parent="replay"),
    _span("segment-wait", 13, 20, parent="validate-chain"),
    _span("enqueue", 20, 22, parent="validate-chain"),
    _span("stage", 20.5, 24, thread="oct-stage_0", window=0),
    _span("stage-wait", 22, 24, window=0, parent="validate-chain"),
    _span("dispatch", 24, 30, window=0, parent="validate-chain"),
    _span("dispatch.unpack", 24, 26, window=0, parent="dispatch"),
    _span("dispatch.ed", 26, 27, window=0, parent="dispatch"),
    _span("gc", 30, 31, parent="validate-chain"),  # the device is busy
    _span("enqueue", 31, 32, parent="validate-chain"),
    _span("dispatch", 32, 36, window=1, parent="validate-chain"),
    _span("dispatch.unpack", 32, 33, window=1, parent="dispatch"),
    _span("materialize", 36, 50, window=0, parent="validate-chain"),
    _span("materialize.wait", 36, 48, thread="oct-read_0", window=0),
    _span("materialize.copy", 48, 49.5, thread="oct-read_0", window=0),
    _span("tick", 50, 51, window=0, parent="validate-chain"),
    _span("epilogue", 51, 55, window=0, parent="validate-chain"),
    _span("epilogue.fold", 52, 54, window=0, parent="epilogue"),
    _span("materialize", 55, 70, window=1, parent="validate-chain"),
    _span("materialize.wait", 55, 60, thread="oct-read_0", window=1),
    _span("tick", 70, 71, window=1, parent="validate-chain"),
    _span("era-cross", 71, 72, parent="validate-chain"),
    _span("epilogue", 72, 80, window=1, parent="validate-chain"),
    _span("epilogue.counters", 73, 74, window=1, parent="epilogue"),
    _span("gc", 80, 81, thread="oct-prefetch"),  # not the main thread's
    _span("gc", 81, 82.5, parent="validate-chain"),
    # start edges are not read
    T.EncloseEvent("dispatch", "start", BASE + 24, None, 7, 0),
]

PARTS = {"open": 9, "segment-wait": 7, "enqueue": 2, "stage-wait": 2,
         "dispatch": 6, "materialize": 10, "era-cross": 1, "tick": 1,
         "epilogue": 8, "gc": 1.5,
         # replay's own [0, 1] and [10, 12] and [95, 100], validate-chain's
         # own [12, 13] and [80, 81] and [82.5, 95]
         "unspanned": 1 + 2 + 5 + 1 + 1 + 12.5}


def test_partition_of_a_known_timeline_is_exact():
    parts, pieces = obs_idle.account(TIMELINE)
    assert parts == pytest.approx(PARTS, abs=1e-9)
    assert set(parts) == set(obs_idle.CAUSES)
    idle = (30 - 0) + (100 - 60)
    assert obs_idle.total(parts) == pytest.approx(idle, abs=1e-6)
    assert abs(sum(parts.values()) - idle) < 1e-6
    # the pieces tile the two idle intervals, in time order
    assert pieces[0][0] == BASE and pieces[-1][1] == BASE + 100
    for (a0, a1, _), (b0, _b1, _) in zip(pieces, pieces[1:]):
        assert a1 <= b0
    joins = [(a1, b0) for (_, a1, _), (b0, _, _) in zip(pieces, pieces[1:])
             if b0 > a1]
    assert joins == [(BASE + 30, BASE + 60)]  # the one busy stretch
    assert sum(b - a for a, b, _ in pieces) == pytest.approx(idle, abs=1e-6)


def test_partition_order_of_events_does_not_matter():
    shuffled = TIMELINE[::-1]
    assert obs_idle.account(shuffled)[0] == \
        pytest.approx(obs_idle.account(TIMELINE)[0], abs=1e-9)


def test_a_window_without_its_unpack_or_wait_falls_back_to_its_spans():
    """A generic window has no `dispatch.<stage>` spans and no
    `materialize.wait`: `dispatch`'s end launches it, the main thread's
    `materialize` sees it done."""
    spans = [_span("replay", 0, 20),
             _span("dispatch", 2, 5, window=3, parent="replay"),
             _span("materialize", 5, 9, window=3, parent="replay"),
             _span("epilogue", 9, 12, window=3, parent="replay")]
    parts, _ = obs_idle.account(spans)
    assert parts["dispatch"] == pytest.approx(3)
    assert parts["materialize"] == 0
    assert parts["epilogue"] == pytest.approx(3)
    assert parts["unspanned"] == pytest.approx(2 + 8)
    assert obs_idle.total(parts) == pytest.approx(20 - (9 - 5))


def test_no_replay_span_no_idle():
    parts, pieces = obs_idle.account([_span("dispatch", 0, 1)])
    assert pieces == [] and set(parts.values()) == {0.0}


# -- a real replay, stubbed crypto -------------------------------------------


@pytest.fixture(scope="module")
def db(tmp_path_factory):
    pools = [fixtures.make_pool(60 + i, kes_depth=3) for i in range(2)]
    lview = fixtures.make_ledger_view(pools)
    path = str(tmp_path_factory.mktemp("idle") / "db")
    res = synth.synthesize(path, PARAMS, pools, lview,
                           synth.ForgeLimit(slots=160), chunk_size=32)
    assert res.n_blocks > 60
    return path, lview


def _idle_counters(rec) -> dict:
    return {s["labels"]["under"]: s["value"]
            for s in rec.registry.snapshot()[
                "oct_device_idle_seconds_total"]["samples"]}


def _replay_behind_the_recorder(db, install: bool, **kw):
    """One stubbed replay with the recorder riding it: installed here
    (`install`), or by `revalidate` itself under OCT_TRACE=1 as the
    db-analyser CLI runs. No automatic collection runs meanwhile: one
    that ends as the replay ends reaches the recorder after its account.
    -> (events, result, idle counters)."""
    mp = pytest.MonkeyPatch()
    before = set(pbatch._JIT)
    mp.delenv("OCT_STAGE_THREAD", raising=False)
    if install:
        mp.delenv("OCT_TRACE", raising=False)
    else:
        mp.setenv("OCT_TRACE", "1")
    mp.setattr(pbatch, "_WARM_SEEN", set())
    stubs.install_stub_crypto(mp)
    obs.reset_for_tests()
    rec = obs.install() if install else obs.recorder()
    automatic = gc.isenabled()
    gc.disable()
    path, lview = db
    try:
        res = ana.revalidate(path, PARAMS, lview, backend="device",
                             validate_all="stream", max_batch=16, **kw)
        events = [e for _, e in rec.timed_events()]
        counters = _idle_counters(rec)
    finally:
        if automatic:
            gc.enable()
        if install:
            obs.uninstall()
        obs.reset_for_tests()
        mp.undo()
        for k in set(pbatch._JIT) - before:
            del pbatch._JIT[k]
        WARMUP.reset()
    assert res.error is None and res.n_valid == res.n_blocks > 60
    return events, res, counters


@pytest.fixture(scope="module")
def replayed(db):
    """One replay behind the recorder, `collect_phases=True` ->
    (events, result, idle counters)."""
    return _replay_behind_the_recorder(db, True, collect_phases=True)


def test_replay_account_checks_itself(replayed):
    _, res, _ = replayed
    ph = res.phases
    for cause in obs_idle.CAUSES:
        assert ph["device-idle." + cause] >= 0, cause
    assert ph["device-idle"] == obs_idle.total(
        {c: ph["device-idle." + c] for c in obs_idle.CAUSES})
    # nothing is in flight while the store opens or the stream is waited
    # for: those parts are the spans' walls
    assert ph["device-idle.open"] == pytest.approx(ph["open"], abs=1e-3)
    assert ph["device-idle.segment-wait"] <= ph["segment-wait"] + 1e-9
    assert 0 < ph["device-idle"] < ph["replay"]
    assert "gc" in ph  # a replay with no collection reads 0.0
    # the intervals: inside the replay, in time order, summing to it
    gaps = res.idle_gaps
    assert gaps and all(a < b for a, b, _ in gaps)
    assert all(g0[1] <= g1[0] for g0, g1 in zip(gaps, gaps[1:]))
    assert sum(b - a for a, b, _ in gaps) == pytest.approx(
        ph["device-idle"], abs=1e-9)


def test_recorder_counts_the_account_by_cause(replayed):
    _, res, counters = replayed
    assert counters == pytest.approx(
        {c: res.phases["device-idle." + c] for c in obs_idle.CAUSES},
        abs=1e-12)


def test_recorder_counts_the_account_on_the_cli_path(db):
    """db-analyser's CLI replays under OCT_TRACE=1 without
    `collect_phases`: the recorder closes the account itself, as the
    replay's `replay` span ends, from the spans it was handed."""
    events, res, counters = _replay_behind_the_recorder(db, False)
    assert res.phases is None  # nothing collected the phases
    ends = [e for e in events
            if isinstance(e, T.EncloseEvent) and e.edge == "end"]
    parts, _ = obs_idle.account(ends)
    assert set(counters) == set(obs_idle.CAUSES)
    assert counters == pytest.approx(parts, abs=1e-12)
    walls = {lab: sum(e.duration for e in ends if e.label == lab)
             for lab in ("open", "replay")}
    assert counters["open"] == pytest.approx(walls["open"], abs=1e-3)
    assert 0 < sum(counters.values()) < walls["replay"]


def test_enqueue_spans_sit_beside_segment_wait(replayed):
    events, _, _ = replayed
    ends = [e for e in events
            if isinstance(e, T.EncloseEvent) and e.edge == "end"]
    enq = [e for e in ends if e.label == "enqueue"]
    assert enq and {e.parent for e in enq} == {"validate-chain"}
    assert {e.thread for e in enq} == {"MainThread"}
    # the blocking pull is never inside `enqueue`
    waits = [e for e in ends if e.label == "segment-wait"]
    assert waits and {e.parent for e in waits} == {"validate-chain"}


def test_off_cpu_of_dispatch_and_stage_is_within_their_walls(replayed):
    events, _, _ = replayed
    spans = [e for e in events if isinstance(e, T.WindowSpan)]
    assert spans
    for s in spans:
        assert s.dispatch_offcpu_s <= s.dispatch_s, s
        assert s.stage_offcpu_s <= s.stage_s, s


# -- the CPU clock of a span, and the `gc` span -------------------------------


def test_time_asleep_in_stage_and_dispatch_reads_off_the_cpu(db):
    """A window whose prechecks and launch each sleep 30 ms: the sleep is
    in `stage_offcpu_s` and `dispatch_offcpu_s`, not in the CPU time."""
    mp = pytest.MonkeyPatch()
    before = set(pbatch._JIT)
    mp.delenv("OCT_STAGE_THREAD", raising=False)
    mp.setattr(pbatch, "_WARM_SEEN", set())
    stubs.install_stub_crypto(mp)

    def sleeping(fn):
        def run(*a, **k):
            time.sleep(0.03)
            return fn(*a, **k)
        return run

    def sleeping_at_dispatch(fn):
        def run(phase, **k):
            if phase == "dispatch":
                time.sleep(0.03)
            return fn(phase, **k)
        return run

    mp.setattr(pbatch.PraosRules, "prechecks",
               sleeping(pbatch.PraosRules.prechecks))
    mp.setattr(pbatch, "_emit_transfer",
               sleeping_at_dispatch(pbatch._emit_transfer))
    obs.reset_for_tests()
    rec = obs.install()
    path, lview = db
    try:
        res = ana.revalidate(path, PARAMS, lview, backend="device",
                             validate_all="stream", max_batch=16,
                             max_headers=48, collect_phases=True)
        spans = [e for _, e in rec.timed_events()
                 if isinstance(e, T.WindowSpan)]
    finally:
        obs.uninstall()
        obs.reset_for_tests()
        mp.undo()
        for k in set(pbatch._JIT) - before:
            del pbatch._JIT[k]
        WARMUP.reset()
    assert res.error is None and spans
    for s in spans:
        assert s.stage_offcpu_s >= 0.02 and s.dispatch_offcpu_s >= 0.02, s


def test_a_collection_in_a_replay_is_a_gc_span(monkeypatch):
    lt = T.ListTracer()
    monkeypatch.setattr(pbatch, "_REPLAY", None)
    automatic = gc.isenabled()
    gc.disable()  # the two collections below are the only ones
    pbatch.set_batch_tracer(lt)
    try:
        assert any(cb is pbatch._GC_SPANS for cb in gc.callbacks)
        gc.collect()  # outside a replay: no span
        pbatch.begin_replay()
        with pbatch._enclose("dispatch", window=5):
            gc.collect()
        pbatch.end_replay()
    finally:
        pbatch.set_batch_tracer(None)
        if automatic:
            gc.enable()
    assert not any(cb is pbatch._GC_SPANS for cb in gc.callbacks)
    spans = [e for e in lt.events if e.label == "gc"]
    assert [e.edge for e in spans] == ["start", "end"]
    start, end = spans
    assert end.parent == "dispatch" and end.window is None
    assert end.thread == start.thread and end.replay is not None
    assert end.duration > 0
    assert start.t == pytest.approx(end.t - end.duration)
    # after the tracer is gone no collection is recorded or queued
    gc.collect()
    assert not pbatch._GC_SPANS.pending


def test_gc_edges_flushed_from_many_threads_at_once_arrive_once():
    """Spans on several threads hand the queued edges over at once: each
    edge reaches the tracer once, and no flush raises, also where two
    threads race for the queue's last edge (many short rounds)."""
    import sys
    import threading

    errors: list = []
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)  # hand the lock over between pops
    try:
        for _ in range(100):
            spans = T.GcSpans(lambda: 3)
            for _ in range(20):
                spans("start", {"generation": 0})
                spans("stop", {"generation": 0})
            got: list = []
            go = threading.Barrier(8)

            def flush():
                go.wait()
                try:
                    spans.flush(got.append)
                except Exception as exc:  # noqa: BLE001 — the finding
                    errors.append(exc)

            threads = [threading.Thread(target=flush) for _ in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
            assert not spans.pending and len(got) == 40
            assert len(set(map(id, got))) == 40
            assert sum(e.edge == "end" for e in got) == 20
    finally:
        sys.setswitchinterval(switch)
    assert errors == []
