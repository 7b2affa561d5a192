"""ONE window pipeline a replay (PR 35): `protocol/batch.validate_stream`
takes the stream of segments `db_analyser._epoch_window_segments`
yields and runs one pipeline over it, so the next segment's first
windows are staged and dispatched while this one's last are in flight.

What is held here: the streamed pipeline is verdict for verdict, error
for error, nonce for nonce the per-segment `validate_chain` calls and
the sequential fold; an invalid header discards whatever is staged or
in flight behind it, in whatever segment, closes the stream and leaves
no thread; the nonce looked ahead across two segment OBJECTS is the
retire-time tick's; a checkpoint resume mid-stream is verdict
identical; the loop holds the stated number of segments at most; and
the pipeline does fill across a segment boundary.

Crypto is the hash-only stub (testing/stubs): the loop, its threads,
its stream and its spans are the real ones."""

import threading
import time
from dataclasses import replace

import pytest

from ouroboros_consensus_tpu.obs import recovery
from ouroboros_consensus_tpu.obs.warmup import WARMUP
from ouroboros_consensus_tpu.protocol import batch as pbatch
from ouroboros_consensus_tpu.protocol import praos
from ouroboros_consensus_tpu.protocol.views import ViewColumns
from ouroboros_consensus_tpu.testing import chaos, stubs
from ouroboros_consensus_tpu.tools import db_analyser as ana
from ouroboros_consensus_tpu.utils import trace as T
# the span tree suite's chain: three epochs and more from genesis, a
# row-width step inside an epoch (one segment holds both widths), 16-lane
# windows
from tests.test_span_tree import MAX_BATCH, PARAMS, db  # noqa: F401

DEPTH = 3  # validate_stream's default pipeline_depth
LOOKAHEAD_BATCH = 8  # three windows an epoch and more
PIPELINE_THREADS = ("oct-read", "oct-stage", "oct-prefetch")


def _views(seg):
    return list(seg.views()) if isinstance(seg, ViewColumns) else list(seg)


@pytest.fixture(scope="module")
def segments(db):
    """The chain as the replay's own stream cuts it (one segment an
    epoch), with the first segment of more than two headers cut once
    more into one-header segments and its rest."""
    path, _ = db
    imm = ana.open_immutable(path, validate_all=False)
    segs, cut = [], False
    for seg in ana._epoch_window_segments(
            PARAMS, ana._stream_windows(imm, ana.ValidationResult())):
        if len(seg) > 2 and not cut:
            segs += [seg[0:1], seg[1:2], seg[2:]]
            cut = True
        else:
            segs.append(seg)
    epochs = [PARAMS.epoch_of(_views(s)[0].slot) for s in segs]
    assert len(set(epochs)) >= 3 and epochs == sorted(epochs)
    # a row-width step inside an epoch: ONE ViewColumns segment whose
    # bodies differ in length; the only two segments of one epoch are
    # this fixture's own cut
    assert any(isinstance(s, ViewColumns)
               and len(set(s.signed_len.tolist())) > 1 for s in segs)
    assert sum(e0 == e1 for e0, e1 in zip(epochs, epochs[1:])) == 2
    assert sum(len(s) == 1 for s in segs) >= 2
    return segs


def test_stream_cuts_one_segment_an_epoch(db):
    """From genesis the bodies step width (no previous hash, then block
    numbers and slots crossing 24 and 256): the stream cuts at epoch
    boundaries only, each segment holding every body layout of its
    epoch."""
    path, _ = db
    imm = ana.open_immutable(path, validate_all=False)
    segs = list(ana._epoch_window_segments(
        PARAMS, ana._stream_windows(imm, ana.ValidationResult())))
    epochs = [PARAMS.epoch_of(int(s.slot[0])) for s in segs]
    assert all(isinstance(s, ViewColumns) for s in segs)
    assert epochs == sorted(set(epochs)) and len(epochs) >= 3
    assert all(len(set(PARAMS.epoch_of(s.slot).tolist())) == 1 for s in segs)
    assert len(set(segs[0].signed_len.tolist())) > 1  # genesis, widths
    assert sum(len(s) for s in segs) == imm.n_blocks()



def test_stream_hands_whole_windows_over_as_it_reads(db):
    """With `cut` (the replay's window size) an epoch's segments are its
    whole windows from its start, then its rest: the rows and their
    order are the uncut stream's, so the windows cut from them are too,
    and the first is there before the epoch has been read."""
    path, _ = db
    imm = ana.open_immutable(path, validate_all=False)

    def stream(**kw):
        return list(ana._epoch_window_segments(
            PARAMS, ana._stream_windows(imm, ana.ValidationResult()), **kw))

    whole, cut = stream(), stream(cut=MAX_BATCH)
    assert len(cut) > len(whole)
    for seg in whole:
        e = PARAMS.epoch_of(int(seg.slot[0]))
        mine = [s for s in cut if PARAMS.epoch_of(int(s.slot[0])) == e]
        assert [len(s) for s in mine[:-1]] == [MAX_BATCH] * (len(mine) - 1)
        assert 0 < len(mine[-1]) <= MAX_BATCH
        assert [hv.signed_bytes for s in mine for hv in s.views()] == [
            hv.signed_bytes for hv in seg.views()]

@pytest.fixture(scope="module")
def reference(db, segments):
    """The sequential fold over every header: the state after each."""
    _, lview = db
    st = praos.PraosState()
    states = []
    for seg in segments:
        for hv in _views(seg):
            st = praos.reupdate(PARAMS, hv, hv.slot,
                                praos.tick(PARAMS, lview, hv.slot, st))
            states.append(st)
    return states


@pytest.fixture
def stubbed(monkeypatch):
    """Stub crypto with the process-wide warm state fenced (the
    test_staging_thread `fresh_pipeline` idiom)."""
    WARMUP.reset()
    monkeypatch.delenv("OCT_STAGE_THREAD", raising=False)
    monkeypatch.setattr(pbatch, "_WARM_SEEN", set())
    before = set(pbatch._JIT)
    stubs.install_stub_crypto(monkeypatch)
    yield
    for k in set(pbatch._JIT) - before:
        del pbatch._JIT[k]
    WARMUP.reset()


def _stream(db, segs, st0=None, max_batch=MAX_BATCH):
    _, lview = db
    return pbatch.validate_stream(
        PARAMS, lambda _e: lview, st0 or praos.PraosState(), segs,
        max_batch=max_batch)


def _per_segment(db, segs):
    """What the replay did before: one `validate_chain` call a segment."""
    _, lview = db
    st, n_valid = praos.PraosState(), 0
    for seg in segs:
        res = pbatch.validate_chain(PARAMS, lambda _e: lview, st, seg,
                                    max_batch=MAX_BATCH)
        st, n_valid = res.state, n_valid + res.n_valid
        if res.error is not None:
            return st, n_valid, res.error
    return st, n_valid, None


def _no_pipeline_thread_left():
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        left = [t.name for t in threading.enumerate()
                if t.name.startswith(PIPELINE_THREADS)]
        if not left:
            return True
        time.sleep(0.05)
    return False


def _bad_counter(segs, k, i):
    """Segment k as HeaderViews with header i's OCert counter over-
    incremented (a check the hash-only stub leaves real)."""
    hvs = _views(segs[k])
    hvs[i] = replace(hvs[i], ocert=replace(
        hvs[i].ocert, counter=hvs[i].ocert.counter + 5))
    return [*segs[:k], hvs, *segs[k + 1:]]


# -- (a) one streamed pipeline = per-segment calls = the fold ---------------


@pytest.mark.parametrize("prefetch", [False, True],
                         ids=["inline-stream", "prefetched"])
@pytest.mark.parametrize("thread", ["1", "0"])
def test_stream_equals_per_segment_calls_and_the_fold(
        db, segments, reference, stubbed, monkeypatch, thread, prefetch):
    monkeypatch.setenv("OCT_STAGE_THREAD", thread)
    n = sum(len(s) for s in segments)
    stream = iter(segments)
    if prefetch:
        stream = ana._prefetch_iter(stream, depth=2)
    res = _stream(db, stream)
    assert (res.n_valid, res.error) == (n, None)
    assert res.state == reference[-1]
    st, n_valid, err = _per_segment(db, segments)
    assert (n_valid, err) == (n, None) and st == res.state
    assert _no_pipeline_thread_left()


@pytest.mark.parametrize("thread", ["1", "0"])
def test_the_one_piece_stream_is_validate_chain(db, segments, reference,
                                                stubbed, monkeypatch,
                                                thread):
    """`validate_chain` is the degenerate stream: a whole run of
    headers (three epochs, one object) gives what the segments give."""
    monkeypatch.setenv("OCT_STAGE_THREAD", thread)
    _, lview = db
    hvs = [hv for seg in segments for hv in _views(seg)]
    res = pbatch.validate_chain(PARAMS, lambda _e: lview,
                                praos.PraosState(), hvs,
                                max_batch=MAX_BATCH)
    assert (res.n_valid, res.error) == (len(hvs), None)
    assert res.state == reference[-1]
    empty = pbatch.validate_chain(PARAMS, lambda _e: lview,
                                  praos.PraosState(), [])
    assert (empty.n_valid, empty.error) == (0, None)
    assert empty.state == praos.PraosState()


# -- (b) the first invalid header, with its successors staged or in flight --


@pytest.mark.parametrize("thread", ["1", "0"])
def test_invalid_header_discards_the_segments_behind_it(
        db, segments, reference, stubbed, monkeypatch, thread):
    monkeypatch.setenv("OCT_STAGE_THREAD", thread)
    # the LAST header of a segment that is not the stream's last: every
    # window behind it belongs to a later segment, of the same epoch (one
    # staged without waiting for a nonce: the fixture's own cut)
    epochs = [PARAMS.epoch_of(_views(s)[0].slot) for s in segments]
    k = next(i for i in range(1, len(segments) - 1)
             if epochs[i + 1] == epochs[i])
    bad_i = len(segments[k]) - 1
    segs = _bad_counter(segments, k, bad_i)
    n_before = sum(len(s) for s in segs[:k]) + bad_i

    prepared = []
    orig_prep = pbatch.prepare_window

    def spy_prep(params, lview, eta0, hvs, *a, **kw):
        prepared.append(hvs)
        return orig_prep(params, lview, eta0, hvs, *a, **kw)

    monkeypatch.setattr(pbatch, "prepare_window", spy_prep)
    orig_mat = pbatch.materialize_verdicts

    def slow_mat(tagged, b):
        time.sleep(0.05)  # the device wait: the pipeline fills behind it
        return orig_mat(tagged, b)

    monkeypatch.setattr(pbatch, "materialize_verdicts", slow_mat)

    closed = []

    def gen():
        try:
            yield from segs
        finally:
            closed.append(True)

    stream = gen()
    if thread == "1":
        stream = ana._prefetch_iter(stream, depth=2)
    res = _stream(db, stream)
    assert res.n_valid == n_before
    assert isinstance(res.error, praos.CounterOverIncrementedOCERT)
    assert res.state == reference[n_before - 1]
    # windows of the segments BEHIND the invalid header were staged (and
    # dispatched) before its verdict was known, and are discarded
    first_behind = _views(segs[k + 1])[0].slot
    assert any(pbatch._slot_at(w, 0) >= first_behind for w in prepared)
    # the same verdict as the per-segment calls give
    st, n_valid, err = _per_segment(db, segs)
    assert (n_valid, repr(err)) == (res.n_valid, repr(res.error))
    assert st == res.state
    # the stream's generator closed, and no thread left
    if thread == "1":
        stream.thread.join(timeout=10.0)
        assert not stream.thread.is_alive()
    assert closed == [True]
    assert _no_pipeline_thread_left()


def test_a_failing_stream_raises_through_and_leaves_no_thread(
        db, segments, stubbed):
    def gen():
        yield from segments[:4]
        raise OSError("chunk unreadable")

    with pytest.raises(OSError, match="chunk unreadable"):
        _stream(db, ana._prefetch_iter(gen(), depth=2))
    assert _no_pipeline_thread_left()


# -- (c) the nonce looked ahead across two segment objects -------------------


def _epoch_run(segments, reference):
    """(epoch 0 as ONE segment object, epoch 1 as another, the state
    before epoch 0, the fold's states) for the first two WHOLE epochs
    after the genesis segments."""
    hvs = [hv for seg in segments for hv in _views(seg)]
    e0 = PARAMS.epoch_of(hvs[0].slot) + 1
    a = [hv for hv in hvs if PARAMS.epoch_of(hv.slot) == e0]
    b = [hv for hv in hvs if PARAMS.epoch_of(hv.slot) == e0 + 1]
    first = hvs.index(a[0])
    # at LOOKAHEAD_BATCH lanes a window of epoch 0 lies wholly behind
    # the freeze slot: the one before it retires with the candidate
    # nonce frozen and a successor left
    freeze = PARAMS.first_slot_of(e0 + 1) - PARAMS.stability_window
    assert sum(hv.slot >= freeze for hv in a) > LOOKAHEAD_BATCH
    assert len(b) > LOOKAHEAD_BATCH
    return a, b, reference[first - 1], reference[first:]


def test_lookahead_nonce_across_segment_objects_is_the_ticks(
        db, segments, reference, stubbed, monkeypatch):
    """Inline staging and an inline stream, so the order is exact: the
    next epoch's first window is staged, with the rotated nonce, while
    the tail of this epoch is in flight, and the retire-time tick finds
    the same nonce."""
    monkeypatch.setenv("OCT_STAGE_THREAD", "0")
    a, b, st0, states = _epoch_run(segments, reference)
    order = []
    orig_prep = pbatch.prepare_window

    def spy_prep(params, lview, eta0, hvs, *args, **kw):
        order.append(("stage", pbatch._slot_at(hvs, 0), eta0))
        return orig_prep(params, lview, eta0, hvs, *args, **kw)

    monkeypatch.setattr(pbatch, "prepare_window", spy_prep)
    orig_note = recovery.note_window

    def spy_note(state, n_valid):
        order.append(("retire", state.last_slot, None))
        return orig_note(state, n_valid)

    monkeypatch.setattr(recovery, "note_window", spy_note)
    res = _stream(db, iter([a, b]), st0=st0, max_batch=LOOKAHEAD_BATCH)
    assert (res.n_valid, res.error) == (len(a) + len(b), None)
    assert res.state == states[len(a) + len(b) - 1]
    # the nonce epoch 1's windows staged with is the one the fold's
    # tick rotates to at the boundary
    eta1 = states[len(a)].epoch_nonce
    assert eta1 != states[0].epoch_nonce
    staged_b = [e for e in order if e[0] == "stage" and e[1] >= b[0].slot]
    assert staged_b and all(e[2] == eta1 for e in staged_b)
    # and it was looked AHEAD: staged before epoch 0's last window
    # retired
    i_stage = order.index(staged_b[0])
    i_drained = order.index(("retire", a[-1].slot, None))
    assert i_stage < i_drained


def test_a_wrong_lookahead_nonce_trips_the_retire_time_assertion(
        db, segments, reference, stubbed, monkeypatch):
    monkeypatch.setenv("OCT_STAGE_THREAD", "0")
    a, b, st0, _ = _epoch_run(segments, reference)
    # latched as last_epoch_block_nonce by the tick into the first
    # epoch: it names the rotation's call among the fold's
    marker = b"\x55" * 32
    st0 = replace(st0, lab_nonce=marker)
    real = pbatch.nonces

    class Planted:
        def __getattr__(self, name):
            return getattr(real, name)

        @staticmethod
        def combine(x, y):
            return b"\xee" * 32 if y == marker else real.combine(x, y)

    monkeypatch.setattr(pbatch, "nonces", Planted())
    with pytest.raises(AssertionError, match="lookahead epoch nonce"):
        _stream(db, iter([a, b]), st0=st0, max_batch=LOOKAHEAD_BATCH)
    assert _no_pipeline_thread_left()


# -- (d) checkpoint resume mid-stream -----------------------------------------


@pytest.fixture
def fresh_recovery(monkeypatch):
    recovery.reset_for_tests()
    for var in ("OCT_CHAOS", "OCT_CHAOS_SEED", "OCT_CHECKPOINT",
                "OCT_RESUME", "OCT_RECOVERY"):
        monkeypatch.delenv(var, raising=False)
    chaos.reset()
    yield
    monkeypatch.delenv("OCT_CHAOS", raising=False)
    recovery.reset_for_tests()
    chaos.reset()


def _revalidate(db, **kw):
    path, lview = db
    return ana.revalidate(path, PARAMS, lview, backend="device",
                          validate_all=False, max_batch=MAX_BATCH, **kw)


# the chain's replay is seven windows (two an epoch, one for its last)
@pytest.mark.parametrize("fault_at", [4, 5])
def test_checkpoint_resume_mid_stream_is_verdict_identical(
        db, reference, stubbed, fresh_recovery, monkeypatch, tmp_path,
        fault_at):
    """tests/test_selfheal.py's shape over the streamed pipeline: the
    attempt dies at a dispatch with windows of later segments staged;
    the record holds what RETIRED, and the resumed replay lands there."""
    base = _revalidate(db)
    assert base.error is None and base.final_state == reference[-1]
    ck = str(tmp_path / "ckpt.json")
    monkeypatch.setenv("OCT_CHECKPOINT", ck)
    monkeypatch.setenv("OCT_RECOVERY", "0")  # die, don't degrade
    monkeypatch.setenv("OCT_CHAOS", f"device-error@dispatch:{fault_at}")
    chaos.reset()
    with pytest.raises(chaos.DeviceChaosError):
        _revalidate(db)
    monkeypatch.delenv("OCT_CHAOS")
    chaos.reset()
    doc = recovery.read_checkpoint(ck)
    assert doc is not None and not doc["complete"]
    assert 0 < doc["headers"] < base.n_valid
    # the record is a retired window's: the fold's state at that header
    assert recovery.decode_state(doc["state"]) == \
        reference[doc["headers"] - 1]
    monkeypatch.setenv("OCT_RECOVERY", "1")
    res = _revalidate(db, resume=True)
    assert res.resumed_headers == doc["headers"]
    assert (res.n_valid, res.error) == (base.n_valid, None)
    assert res.final_state == base.final_state
    assert recovery.read_checkpoint(ck)["complete"]
    assert _no_pipeline_thread_left()


# -- (e) the bound on what the loop holds --------------------------------------


@pytest.mark.parametrize("thread", ["1", "0"])
def test_loop_holds_at_most_twice_the_depth_in_segments(
        db, segments, reference, stubbed, monkeypatch, thread):
    """One-header segments, the worst case: every window is a segment.
    A segment is held from the pull to the retire of its last window."""
    monkeypatch.setenv("OCT_STAGE_THREAD", thread)
    hvs = [hv for seg in segments for hv in _views(seg)][:40]
    pulled = retired = held_most = 0
    orig_note = recovery.note_window

    def spy_note(state, n_valid):
        nonlocal retired
        retired += 1
        return orig_note(state, n_valid)

    monkeypatch.setattr(recovery, "note_window", spy_note)

    def counting():
        nonlocal pulled, held_most
        for hv in hvs:
            pulled += 1
            held_most = max(held_most, pulled - retired)
            yield [hv]

    res = _stream(db, counting())
    assert (res.n_valid, res.error) == (len(hvs), None)
    assert res.state == reference[len(hvs) - 1]
    assert pulled == retired == len(hvs)
    assert DEPTH <= held_most <= 2 * DEPTH


# -- (f) the pipeline fills across a segment boundary --------------------------


def test_window_behind_a_segments_last_is_in_flight(db, segments,
                                                    reference, stubbed,
                                                    monkeypatch):
    """Inline staging, inline stream: exact. Every window but the
    stream's last retires with its successor in flight behind it,
    whichever segment the successor belongs to, wherever the successor's
    nonce was known: inside an epoch always."""
    monkeypatch.setenv("OCT_STAGE_THREAD", "0")
    lt = T.ListTracer()
    monkeypatch.setattr(pbatch, "BATCH_TRACER", lt)
    res = _stream(db, iter(segments))
    assert res.error is None and res.state == reference[-1]
    ends = [e for e in lt.events
            if isinstance(e, T.EncloseEvent) and e.edge == "end"]
    assert sum(e.label == "validate-chain" for e in ends) == 1
    spans = [e for e in lt.events if isinstance(e, T.WindowSpan)]
    # the windows in chain order, each with its segment's number
    seg_of = [k for k, seg in enumerate(segments)
              for _ in range(-(-len(seg) // MAX_BATCH))]
    assert len(spans) == len(seg_of) > len(segments)
    assert [s.lanes for s in spans] == [
        min(MAX_BATCH, len(seg) - w)
        for seg in segments for w in range(0, len(seg), MAX_BATCH)]
    epochs = [PARAMS.epoch_of(_views(s)[0].slot) for s in segments]
    boundaries = 0
    for i, s in enumerate(spans[:-1]):
        assert 0 <= s.inflight_behind <= DEPTH - 1
        if seg_of[i + 1] != seg_of[i] and \
                epochs[seg_of[i + 1]] == epochs[seg_of[i]]:
            # a segment's last window, its successor another segment's
            # first (the fixture's one-header segments): in flight
            boundaries += 1
            assert s.inflight_behind >= 1, (i, seg_of[i])
    assert boundaries == 2
    # across an epoch boundary the successor waits for the freeze slot
    # only: somewhere a new epoch's first window was in flight behind
    # the old epoch's last
    assert any(s.inflight_behind >= 1 for i, s in enumerate(spans[:-1])
               if epochs[seg_of[i + 1]] != epochs[seg_of[i]])
    assert spans[-1].inflight_behind == 0
    # an inline stream is pulled with next(), under `segment-wait`:
    # one span a segment and the end's
    assert sum(e.label == "segment-wait" for e in ends) == \
        len(segments) + 1
