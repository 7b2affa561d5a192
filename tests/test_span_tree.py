"""One span tree per replay (PR 28): every span of a window carries the
window's id, is recorded on the thread where the work happens, and the
self times add up; with no tracer installed nothing is built at all.

Crypto is the hash-only stub (testing/stubs): the pipeline, its threads
and its spans are the real ones. One stubbed device-path replay of a
small synthesized chain, run twice through `db_analyser.revalidate`
behind the flight recorder, feeds most of the tests here."""

import subprocess
import sys
from collections import defaultdict
from fractions import Fraction

import pytest

import jax
import numpy as np

from ouroboros_consensus_tpu import obs
from ouroboros_consensus_tpu.obs import spans as obs_spans
from ouroboros_consensus_tpu.obs.warmup import WARMUP
from ouroboros_consensus_tpu.ops.pk import kernels as K
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
MAX_BATCH = 16
PIPELINE_DEPTH = 3  # validate_chain's default
WINDOW_LABELS = {"stage", "stage.prechecks", "dispatch", "materialize.wait",
                 "materialize.copy", "materialize", "tick", "epilogue",
                 "epilogue.counters", "epilogue.fold"}


def _replay(db):
    path, lview = db
    return ana.revalidate(path, PARAMS, lview, backend="device",
                          validate_all="stream", max_batch=MAX_BATCH,
                          collect_phases=True)


@pytest.fixture(scope="module")
def db(tmp_path_factory):
    pools = [fixtures.make_pool(40 + i, kes_depth=3) for i in range(2)]
    lview = fixtures.make_ledger_view(pools)
    path = str(tmp_path_factory.mktemp("spans") / "db")
    res = synth.synthesize(path, PARAMS, pools, lview,
                           synth.ForgeLimit(slots=160), chunk_size=32)
    assert res.n_blocks > 60  # three epochs and more
    return path, lview


@pytest.fixture(scope="module")
def stub_crypto():
    """Stub crypto for the module, with the process-wide warm state the
    stub jits touch fenced (the test_warm_ladder `fresh_pipeline`
    idiom): a later test file in this worker finds it as it was."""
    mp = pytest.MonkeyPatch()
    before = set(pbatch._JIT)
    mp.delenv("OCT_STAGE_THREAD", raising=False)
    mp.delenv("OCT_TRACE", raising=False)
    mp.setattr(pbatch, "_WARM_SEEN", set())
    stubs.install_stub_crypto(mp)
    yield
    mp.undo()
    for k in set(pbatch._JIT) - before:
        del pbatch._JIT[k]
    WARMUP.reset()


@pytest.fixture(scope="module")
def traced(db, stub_crypto):
    """Two replays behind the recorder -> (events, [result, result])."""
    obs.reset_for_tests()
    rec = obs.install()
    try:
        results = [_replay(db), _replay(db)]
    finally:
        obs.uninstall()
    events = [e for _, e in rec.timed_events()]
    obs.reset_for_tests()
    assert pbatch.BATCH_TRACER is None
    for r in results:
        assert r.error is None and r.n_valid == r.n_blocks > 60
    return events, results


def _ends(events, **where):
    return [e for e in events if isinstance(e, T.EncloseEvent)
            and e.edge == "end"
            and all(getattr(e, k) == v for k, v in where.items())]


# -- (a) identity: one window id, on the thread where the work happens -----


def test_every_span_and_event_of_a_window_carries_its_id(traced):
    events, _ = traced
    spans = [e for e in events if isinstance(e, T.WindowSpan)]
    staged = {e.index for e in events if isinstance(e, T.WindowStaged)}
    assert len(spans) >= 8 and {s.index for s in spans} == staged
    by_window = defaultdict(list)
    for e in _ends(events):
        if e.window is not None:
            by_window[e.window].append(e)
    transfers = defaultdict(list)
    for e in events:
        if isinstance(e, T.TransferEvent):
            transfers[e.window].append(e.phase)
    for s in spans:
        labels = {e.label for e in by_window[s.index]}
        assert WINDOW_LABELS <= labels, (s.index, labels)
        assert labels <= WINDOW_LABELS | {"stage-wait"}
        assert sorted(transfers[s.index]) == ["dispatch", "materialize"]
        # one replay's window: every span of it names the same replay
        assert len({e.replay for e in by_window[s.index]}) == 1


def test_spans_name_the_thread_that_did_the_work(traced):
    events, _ = traced
    for e in _ends(events):
        if e.label == "stage":
            assert e.thread.startswith("oct-stage"), e
            assert e.parent is None  # joined to its window by the id
        elif e.label == "stage.prechecks":
            assert e.thread.startswith("oct-stage"), e
            assert e.parent == "stage"
        elif e.label.startswith("materialize."):
            assert e.thread.startswith("oct-read"), e
        elif e.label.startswith("stream"):
            assert e.thread == "oct-prefetch", e
        elif e.label == "gc":
            # a collection, on whichever thread ran it: no window's
            assert e.window is None and e.replay is not None, e
        else:
            assert e.thread == "MainThread", e
    # ONE pipeline a replay: the wait for the stream is the pipeline's
    # own (an empty one's), beside its windows' spans and the cutting
    # of its windows (`enqueue`)
    for label in ("segment-wait", "enqueue", "stage-wait", "dispatch",
                  "materialize", "tick", "epilogue"):
        assert {e.parent for e in _ends(events, label=label)} == \
            {"validate-chain"}
    for label in ("open", "validate-chain", "stream"):
        assert {e.parent for e in _ends(events, label=label)} == {"replay"}
    # the index of each chunk is read inside `open`, on the main thread
    assert {e.parent for e in _ends(events, label="open.index")} == {"open"}
    # the host's nonce fold: inside the window's `epilogue`, every window
    for label in ("epilogue.fold", "epilogue.counters"):
        assert {e.parent for e in _ends(events, label=label)} == {"epilogue"}
    assert {e.parent for e in _ends(events, label="stream-mmap")} == \
        {"stream"}
    spans = [e for e in events if isinstance(e, T.WindowSpan)]
    assert all(s.stage_thread.startswith("oct-stage") for s in spans)


def test_window_ids_are_allotted_in_staging_order(traced):
    events, _ = traced
    stage_starts = [e.window for e in events
                    if isinstance(e, T.EncloseEvent) and e.edge == "start"
                    and e.label == "stage"]
    dispatched = [e.index for e in events if isinstance(e, T.WindowStaged)]
    retired = [e.index for e in events if isinstance(e, T.WindowSpan)]
    assert stage_starts == sorted(stage_starts)
    assert len(set(stage_starts)) == len(stage_starts)
    # staging order is dispatch order is retire order
    assert dispatched == stage_starts == retired


def test_each_replay_has_one_id_and_one_root(traced):
    events, results = traced
    roots = _ends(events, label="replay")
    assert len(roots) == len(results) == 2
    assert roots[0].replay != roots[1].replay
    assert all(r.parent is None and r.window is None for r in roots)
    ids = {e.replay for e in _ends(events)}
    assert ids == {r.replay for r in roots}
    # and ONE `validate-chain` span, whatever the stream's segments
    pipelines = _ends(events, label="validate-chain")
    assert [p.replay for p in pipelines] == [r.replay for r in roots]
    assert pbatch._REPLAY is None  # no replay in progress any more


def test_stage_call_spans_sit_under_dispatch(monkeypatch):
    """`dispatch.<stage>` round the executable call: the window and the
    parent come from the enclosing `dispatch` span."""
    monkeypatch.setattr(K, "_FIRST_EXEC", set())
    monkeypatch.setenv("OCT_PK_AOT", "0")
    lt = T.ListTracer()
    monkeypatch.setattr(pbatch, "BATCH_TRACER", lt)
    fn = jax.jit(lambda x: x + 1)
    x = np.zeros((4,), np.int32)
    try:
        with pbatch._enclose("dispatch", window=77):
            K._stage_call("unpack_a1b2c3", fn, 4, 3, x)
            K._stage_call("vrf_bc", fn, 4, 3, x)
    finally:
        WARMUP.reset()  # the two first-execute notes
    got = [(e.label, e.window, e.parent) for e in _ends(lt.events)]
    assert got == [("dispatch.unpack", 77, "dispatch"),
                   ("dispatch.vrf_bc", 77, "dispatch"),
                   ("dispatch", 77, None)]


# -- (b) self times ----------------------------------------------------------


def _span(label, t0, t1, thread="MainThread"):
    return T.EncloseEvent(label, "end", t1, t1 - t0, thread=thread)


def test_self_time_is_the_span_less_its_children_on_its_own_thread():
    events = [
        _span("replay", 0.0, 10.0),
        _span("validate-chain", 1.0, 9.0),
        _span("dispatch", 2.0, 3.0),
        _span("dispatch.ed", 2.25, 2.5),
        _span("materialize", 3.0, 8.0),
        # the staging thread worked all through validate-chain: another
        # thread's span takes nothing from the main thread's
        _span("stage", 1.5, 8.5, thread="oct-stage_0"),
        T.EncloseEvent("ignored", "start", 0.0),
    ]
    got = obs_spans.self_times(events)
    assert got == pytest.approx({
        "replay": 2.0, "validate-chain": 2.0, "dispatch": 0.75,
        "dispatch.ed": 0.25, "materialize": 5.0, "stage": 7.0,
    })


def test_main_thread_self_times_sum_to_the_replays_duration(traced):
    events, results = traced
    for root, res in zip(_ends(events, label="replay"), results):
        mine = _ends(events, replay=root.replay)
        main = [e for e in mine if e.thread == "MainThread"]
        selfs = obs_spans.self_times(main)
        assert sum(selfs.values()) == pytest.approx(root.duration, abs=1e-3)
        # spans of the other threads take nothing from the main thread's
        with_all = obs_spans.self_times(mine)
        for label in ("replay", "validate-chain", "segment-wait"):
            assert with_all[label] == pytest.approx(selfs[label], abs=1e-9)
        # and the collector folds the same numbers into res.phases
        assert res.phases["replay"] == pytest.approx(root.duration)
        assert res.phases["replay.self"] == pytest.approx(selfs["replay"])
        assert res.phases["validate-chain.self"] < \
            res.phases["validate-chain"]
        # `stage` holds one child on its own thread, the prechecks
        assert res.phases["stage.self"] == pytest.approx(
            res.phases["stage"] - res.phases["stage.prechecks"])


def test_recorder_and_collector_share_the_one_function(traced):
    events, _ = traced
    rec = obs.recorder()
    for e in events:
        rec(e)
    try:
        assert rec.self_times() == pytest.approx(
            obs_spans.self_times(events))
    finally:
        obs.reset_for_tests()


# -- (c) the window span's stamps and counts ---------------------------------


def test_window_span_stamps_are_ordered_and_sums_fit_the_wall(traced):
    events, _ = traced
    spans = [e for e in events if isinstance(e, T.WindowSpan)]
    for s in spans:
        assert (s.t_stage_start <= s.t_stage_end <= s.t_dispatch_start
                <= s.t_dispatch <= s.t_materialized <= s.t_done), s
        assert s.stage_s == pytest.approx(s.t_stage_end - s.t_stage_start)
        assert 0 <= s.inflight_behind <= PIPELINE_DEPTH - 1
        assert 0 <= s.staged_ahead <= PIPELINE_DEPTH
        assert min(s.stage_wait_s, s.dispatch_s, s.materialize_s,
                   s.tick_s, s.epilogue_s) >= 0
        # the window's main-thread wall: from where it began to wait for
        # (or to dispatch) the staged window to the end of its epilogue
        wall = s.t_done - (s.t_dispatch_start - s.stage_wait_s)
        assert (s.stage_wait_s + s.dispatch_s + s.materialize_s + s.tick_s
                + s.epilogue_s) <= wall + 1e-3
    # a replay's last window drains the pipeline: nothing behind it
    assert any(s.inflight_behind == 0 for s in spans)
    assert any(s.stage_wait_s > 0 for s in spans)


# -- (d) the off path ---------------------------------------------------------


class _Counting:
    def __init__(self, real):
        self.real, self.n = real, 0

    def __call__(self, *a, **k):
        self.n += 1
        return self.real(*a, **k)


def test_no_tracer_no_event_no_annotation(db, stub_crypto, traced,
                                           monkeypatch):
    events = _Counting(T.EncloseEvent)
    annotations = _Counting(jax.profiler.TraceAnnotation)
    monkeypatch.setattr(T, "EncloseEvent", events)
    monkeypatch.setattr(T, "_ANNOTATION", annotations)
    assert pbatch.BATCH_TRACER is None
    path, lview = db
    res = ana.revalidate(path, PARAMS, lview, backend="device",
                         validate_all="stream", max_batch=MAX_BATCH)
    assert res.error is None and res.phases is None
    assert events.n == 0 and annotations.n == 0
    assert pbatch._enclose("stage") is pbatch._NULL
    # the same replay with a tracer installed builds both (the counters
    # do count): two events and one annotation a span
    lt = T.ListTracer()
    monkeypatch.setattr(pbatch, "BATCH_TRACER", lt)
    ana.revalidate(path, PARAMS, lview, backend="device",
                   validate_all="stream", max_batch=MAX_BATCH)
    n_spans = sum(e.edge == "end" for e in lt.events
                  if isinstance(e, events.real))
    assert n_spans > 50
    assert events.n == 2 * n_spans and annotations.n == n_spans


def test_trace_module_imports_and_encloses_without_jax():
    code = (
        "import sys; sys.modules['jax'] = None\n"
        "from ouroboros_consensus_tpu.utils import trace as T\n"
        "lt = T.ListTracer()\n"
        "with T.Enclose(lt, 'outer', replay=1):\n"
        "    with T.Enclose(lt, 'inner', window=2):\n"
        "        pass\n"
        "inner = lt.events[2]\n"
        "assert (inner.parent, inner.replay, inner.window) == "
        "('outer', 1, 2), inner\n"
        "assert 'jax.profiler' not in sys.modules\n"
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


# -- (e) stable device names --------------------------------------------------


def test_stage_modules_and_pallas_kernels_have_stable_names(monkeypatch):
    """The names a device trace is read by: `jit_<stage function>` on
    the `XLA Modules` line (benchmark/trace_modules.json) and the Pallas
    kernel's `name`. The kernel bodies are not traced here (minutes):
    `pallas_call` is stubbed to record its `name` and return zeros."""
    seen = []

    def fake_pallas_call(kernel, out_shape, *, name=None, **kw):
        seen.append(name)
        return lambda *args: tuple(
            jax.numpy.zeros(s.shape, s.dtype) for s in out_shape)

    monkeypatch.setattr(K.pl, "pallas_call", fake_pallas_call)
    monkeypatch.setattr(K, "_SPLIT_JIT", {})
    b, depth, nb = 128, 3, 5
    i32 = np.int32

    def sds(*prefixes):
        return [jax.ShapeDtypeStruct((*p, b), i32) for p in prefixes]

    args = {
        "ed": sds((32,), (32,), (nb, 128), (1,)),
        "kes": sds((32,), (1,), (32,), (32,), (depth, 32), (nb, 128), (1,)),
        "vrf": sds((32,), (32,), (16,), (32,), (32,)),
        "vrf_bc": sds((32,), (32,), (32,), (32,), (32,), (32,)),
        "finish": sds((1,), (80,), (32,), (1,), (80,), (32,), (1,), (400,),
                      (16,), (64,), (32,), (32,)),
    }
    want = {
        "ed": ("jit_ed_points", ["ed_points"]),
        "kes": ("jit_kes_points", ["kes_points"]),
        "vrf": ("jit_vrf_points", ["vrf_prep", "vrf_ladder"]),
        "vrf_bc": ("jit_vrf_points_bc", ["vrf_bc_prep", "vrf_ladder"]),
        "finish": ("jit_finish", ["finish"]),
    }
    stages = dict(K.split_stage_fns(depth))
    n_live = jax.ShapeDtypeStruct((1,), i32)  # every stage's last operand
    for stage, (module, kernels) in want.items():
        del seen[:]
        text = stages[stage].lower(*args[stage], n_live).as_text()
        assert text.startswith(f"module @{module} "), text[:80]
        assert seen == kernels
