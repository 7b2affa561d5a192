"""Threaded staging pipeline: the differential suite.

The invariants under test:

  * validate_chain's device loop, with the staging producer thread on
    or off, produces byte-identical final state, identical verdicts,
    the exact reference error object and the same first-failure
    truncation as the sequential reupdate fold, across an epoch
    boundary.
  * With the thread on, staging runs on the producer thread and
    overlaps the main thread's device wait.

Crypto is the hash-only stub (ouroboros_consensus_tpu/testing/stubs)
with the AGGREGATE path active (the XLA twin's default), so the stub
agg program rides the real `_warm_timed` machinery."""

import os
import time
from dataclasses import replace
from fractions import Fraction

import pytest

from ouroboros_consensus_tpu.block.forge import forge_block
from ouroboros_consensus_tpu.obs.warmup import WARMUP
from ouroboros_consensus_tpu.protocol import batch as pbatch
from ouroboros_consensus_tpu.protocol import praos
from ouroboros_consensus_tpu.testing import fixtures, stubs

pytestmark = pytest.mark.skipif(
    os.environ.get("JAX_PLATFORMS", "") not in ("", "cpu"),
    reason="CPU differential suite",
)

PARAMS = praos.PraosParams(
    slots_per_kes_period=100,
    max_kes_evolutions=62,
    security_param=4,
    active_slot_coeff=Fraction(1, 2),
    epoch_length=100,
    kes_depth=3,
)


@pytest.fixture(scope="module")
def pools():
    return [fixtures.make_pool(60 + i, kes_depth=3) for i in range(2)]


@pytest.fixture(scope="module")
def lview(pools):
    return fixtures.make_ledger_view(pools)


def forge_chain(pools, lview, n, first_slot=100):
    """Real-codec bc-proof chain crossing an epoch boundary, with the
    reupdate-fold reference state computed alongside. Slots stay in one
    CBOR width class so every window stages packed (the agg path)."""
    st0 = praos.PraosState(epoch_nonce=b"\x07" * 32)
    st = st0
    hvs, prev = [], b"\xaa" * 32
    slot, blkno = first_slot, 40
    while len(hvs) < n:
        ticked = praos.tick(PARAMS, lview, slot, st)
        blk = forge_block(
            PARAMS, pools[len(hvs) % 2], slot=slot, block_no=blkno,
            prev_hash=prev, epoch_nonce=ticked.state.epoch_nonce,
            txs=(b"t",),
        )
        hv = blk.header.to_view()
        st = praos.reupdate(PARAMS, hv, slot, ticked)
        hvs.append(hv)
        prev = blk.header.hash_
        slot += 1
        blkno += 1
    return st0, hvs, st


@pytest.fixture(scope="module")
def chain(pools, lview):
    st0, hvs, st = forge_chain(pools, lview, 120)
    assert len(hvs[0].vrf_proof) == 128  # batch-compatible (agg path)
    assert PARAMS.epoch_of(hvs[-1].slot) > PARAMS.epoch_of(hvs[0].slot)
    return st0, hvs, st


@pytest.fixture
def fresh_pipeline(monkeypatch):
    """Isolate the process-wide warm state a replay mutates: warmup
    recorder, first-execute label sets and any stub jit entries."""
    WARMUP.reset()
    monkeypatch.setattr(pbatch, "_WARM_SEEN", set())
    before = set(pbatch._JIT)
    yield
    for k in set(pbatch._JIT) - before:
        del pbatch._JIT[k]
    WARMUP.reset()


def _run_chain(st0, hvs, max_batch=16):
    return pbatch.validate_chain(
        PARAMS, lambda _e: _LVIEW[0], st0, hvs, max_batch=max_batch
    )


_LVIEW = [None]  # set per test (validate_chain takes a callable)


@pytest.mark.parametrize("thread", ["1", "0"])
def test_thread_matrix_equals_fold(pools, lview, chain, monkeypatch,
                                   fresh_pipeline, thread):
    """Staging thread on and off: byte-identical final state vs the
    sequential reupdate fold, across an epoch boundary, with the host
    nonce fold threaded throughout."""
    st0, hvs, st_ref = chain
    _LVIEW[0] = lview
    monkeypatch.setenv("OCT_STAGE_THREAD", thread)
    stubs.install_stub_crypto(monkeypatch)
    res = _run_chain(st0, hvs)
    assert res.error is None and res.n_valid == len(hvs)
    assert res.state == st_ref


@pytest.mark.parametrize("thread", ["1", "0"])
def test_matrix_first_failure_truncation(pools, lview, monkeypatch,
                                         fresh_pipeline, thread):
    """A tampered lane (OCert counter over-increment — a check the
    hash-only stub leaves real) truncates at the SAME position with the
    SAME exact error object with the thread on and off."""
    st0, hvs, _ = forge_chain(pools, lview, 40)
    bad = 23
    hvs[bad] = replace(
        hvs[bad], ocert=replace(hvs[bad].ocert,
                                counter=hvs[bad].ocert.counter + 5)
    )
    _LVIEW[0] = lview
    monkeypatch.setenv("OCT_STAGE_THREAD", thread)
    stubs.install_stub_crypto(monkeypatch)
    res = _run_chain(st0, hvs, max_batch=8)
    assert res.n_valid == bad
    assert isinstance(res.error, praos.CounterOverIncrementedOCERT)
    assert res.error == praos.CounterOverIncrementedOCERT(0, 5)


def test_staging_thread_overlaps_device_wait(pools, lview, chain,
                                             monkeypatch, fresh_pipeline):
    """The mechanism itself, timestamp-proven (ratio-free — a 1-core
    box can't show wall-clock speedup): with OCT_STAGE_THREAD=1,
    prepare_window runs on the producer thread and at least one
    staging call STARTS while the main thread is blocked inside a
    device wait; with =0 every prepare runs inline on the main
    thread."""
    import threading

    st0, hvs, st_ref = chain
    _LVIEW[0] = lview
    stubs.install_stub_crypto(monkeypatch)

    prep_calls: list = []
    orig_prep = pbatch.prepare_window

    def traced_prep(*a, **k):
        t0 = time.monotonic()
        out = orig_prep(*a, **k)
        prep_calls.append(
            (threading.current_thread().name, t0, time.monotonic())
        )
        return out

    monkeypatch.setattr(pbatch, "prepare_window", traced_prep)
    waits: list = []
    orig_mat = pbatch.materialize_verdicts

    def slow_mat(tagged, b):
        t0 = time.monotonic()
        time.sleep(0.05)  # the simulated device wait (GIL released)
        out = orig_mat(tagged, b)
        waits.append((t0, time.monotonic()))
        return out

    monkeypatch.setattr(pbatch, "materialize_verdicts", slow_mat)

    monkeypatch.setenv("OCT_STAGE_THREAD", "1")
    res = _run_chain(st0, hvs, max_batch=16)
    assert res.error is None and res.state == st_ref
    assert all(name.startswith("oct-stage") for name, _, _ in prep_calls)
    overlapped = [
        1 for _name, p0, p1 in prep_calls
        for w0, w1 in waits
        if max(p0, w0) < min(p1, w1)
    ]
    assert overlapped, "no staging call overlapped a device wait"

    prep_calls.clear()
    waits.clear()
    monkeypatch.setenv("OCT_STAGE_THREAD", "0")
    res = _run_chain(st0, hvs, max_batch=16)
    assert res.error is None and res.state == st_ref
    assert prep_calls
    assert all(name == "MainThread" for name, _, _ in prep_calls)
