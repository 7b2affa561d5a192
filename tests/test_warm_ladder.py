"""Warm-while-serving compile ladder + threaded staging pipeline
(round-10 tentpole): the differential suite.

The invariants under test:

  * Window RE-TILING never changes semantics — validate_chain with the
    ladder capping windows at a rung, with the staging producer thread
    on or off (all four combinations), produces byte-identical final
    state, identical verdicts, the exact reference error object and the
    same first-failure truncation as the sequential reupdate fold.
  * A MID-CHAIN rung swap (slow-compile stub: the production-bucket
    program's first execute sleeps like a compile wall) changes no
    verdicts, and the swap/bg-compile trajectory is first-class warmup
    forensics.
  * The simulated cold-cache bench harness (stubbed clock via
    OCT_WALL_DEADLINE, as in test_costmodel.py): the replay makes
    progress CONCURRENT with the background production compile, and a
    second run against the same artifact store loads the monolith warm
    with zero doomed deserializes.

Crypto is the hash-only stub (ouroboros_consensus_tpu/testing/stubs)
with the AGGREGATE path active — the ladder only engages on the
aggregate monolith, so the stub agg program rides the real
`_warm_timed` machinery (first-execute labels, store write-back)."""

import os
import time
from dataclasses import replace
from fractions import Fraction

import numpy as np
import pytest

from ouroboros_consensus_tpu.analysis import costmodel
from ouroboros_consensus_tpu.block.forge import forge_block
from ouroboros_consensus_tpu.obs.warmup import WARMUP
from ouroboros_consensus_tpu.ops.pk import aot
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
    """Isolate the process-wide warm state a ladder test mutates:
    warmup recorder, first-execute label sets, the ladder singleton and
    any stub jit entries."""
    WARMUP.reset()
    pbatch.reset_warm_ladder()
    monkeypatch.setattr(pbatch, "_WARM_SEEN", set())
    before = set(pbatch._JIT)
    yield
    for k in set(pbatch._JIT) - before:
        del pbatch._JIT[k]
    # a ladder's background warm-up outlives its replay: wait for it, or
    # its `bg-compile-done` lands in the next test's report (seen under
    # a loaded tier-1 run: `[1-0]` and `[0-0]` after their `force` twins)
    ladder = pbatch._LADDER
    if ladder is not None and ladder._bg is not None:
        ladder._bg.join(timeout=120)
    pbatch.reset_warm_ladder()
    WARMUP.reset()


def _run_chain(st0, hvs, max_batch=16):
    return pbatch.validate_chain(
        PARAMS, lambda _e: _LVIEW[0], st0, hvs, max_batch=max_batch
    )


_LVIEW = [None]  # set per test (validate_chain takes a callable)


def _rungs(monkeypatch, *rungs):
    monkeypatch.setattr(costmodel, "LADDER_RUNGS", tuple(rungs))


@pytest.mark.parametrize("ladder", ["force", "0"])
@pytest.mark.parametrize("thread", ["1", "0"])
def test_ladder_thread_matrix_equals_fold(pools, lview, chain, monkeypatch,
                                          fresh_pipeline, ladder, thread):
    """All four (ladder x staging-thread) combinations: byte-identical
    final state vs the sequential reupdate fold, across an epoch
    boundary, with the host nonce fold threaded throughout."""
    st0, hvs, st_ref = chain
    _LVIEW[0] = lview
    monkeypatch.setenv("OCT_WARM_LADDER", ladder)
    monkeypatch.setenv("OCT_STAGE_THREAD", thread)
    _rungs(monkeypatch, 4)
    stubs.install_stub_crypto(monkeypatch)
    res = _run_chain(st0, hvs)
    assert res.error is None and res.n_valid == len(hvs)
    assert res.state == st_ref
    evs = [e["kind"] for e in WARMUP.report()["ladder"]]
    if ladder == "force":
        assert "engaged" in evs and "bg-compile-started" in evs
    else:
        assert evs == []


@pytest.mark.parametrize("ladder", ["force", "0"])
@pytest.mark.parametrize("thread", ["1", "0"])
def test_matrix_first_failure_truncation(pools, lview, monkeypatch,
                                         fresh_pipeline, ladder, thread):
    """A tampered lane (OCert counter over-increment — a check the
    hash-only stub leaves real) truncates at the SAME position with the
    SAME exact error object in every combination."""
    st0, hvs, _ = forge_chain(pools, lview, 40)
    bad = 23
    hvs[bad] = replace(
        hvs[bad], ocert=replace(hvs[bad].ocert,
                                counter=hvs[bad].ocert.counter + 5)
    )
    _LVIEW[0] = lview
    monkeypatch.setenv("OCT_WARM_LADDER", ladder)
    monkeypatch.setenv("OCT_STAGE_THREAD", thread)
    _rungs(monkeypatch, 4)
    stubs.install_stub_crypto(monkeypatch)
    res = _run_chain(st0, hvs, max_batch=8)
    assert res.n_valid == bad
    assert isinstance(res.error, praos.CounterOverIncrementedOCERT)
    assert res.error == praos.CounterOverIncrementedOCERT(0, 5)


def test_mid_chain_rung_swap_changes_no_verdicts(pools, lview, chain,
                                                 monkeypatch,
                                                 fresh_pipeline):
    """Slow-compile stub: the production-bucket program's first execute
    sleeps (simulated compile wall) while rung windows serve; after the
    background 'compile' lands, the loop swaps to production-sized
    windows mid-replay — final state still byte-identical to the fold,
    and the swap is recorded in the warmup report."""
    from ouroboros_consensus_tpu.utils.trace import (
        LadderEvent, WindowStaged,
    )

    st0, hvs, st_ref = chain
    _LVIEW[0] = lview
    monkeypatch.setenv("OCT_WARM_LADDER", "force")
    monkeypatch.setenv("OCT_STAGE_THREAD", "1")
    _rungs(monkeypatch, 4)
    # target-bucket (16-lane) first execute sleeps 0.4 s — rung windows
    # (padded to 8 lanes) compile instantly
    stubs.install_stub_crypto(
        monkeypatch, agg_delay_s=lambda lanes: 0.4 if lanes >= 16 else 0.0
    )
    events = []
    prev_tracer = pbatch.BATCH_TRACER
    pbatch.set_batch_tracer(lambda ev: events.append(ev))
    try:
        res1 = _run_chain(st0, hvs[:60])
        assert res1.error is None and res1.n_valid == 60
        lad = pbatch._LADDER
        assert lad is not None
        assert lad._done.wait(5.0)  # background compile lands
        res2 = _run_chain(res1.state, hvs[60:])
        assert res2.error is None and res2.n_valid == 60
        assert res2.state == st_ref
    finally:
        pbatch.set_batch_tracer(prev_tracer)
    kinds = [e.kind for e in events if isinstance(e, LadderEvent)]
    assert "engaged" in kinds and "bg-compile-started" in kinds
    assert "swap" in kinds
    report = WARMUP.report()["ladder"]
    assert any(e["kind"] == "swap" for e in report)
    assert any(e["kind"] == "bg-compile-done" for e in report)
    # the re-tiling is VISIBLE: rung-capped windows before the swap,
    # production-sized windows after it
    staged = [e for e in events if isinstance(e, WindowStaged)]
    assert any(e.lanes <= 4 for e in staged), "no rung-sized window"
    assert any(e.lanes > 4 for e in staged), "never re-tiled to production"


def test_cold_cache_harness_overlaps_and_reloads_warm(
        pools, lview, chain, monkeypatch, fresh_pipeline, tmp_path):
    """The simulated cold-cache bench harness (stubbed clock +
    slow-compile stub, as in test_costmodel.py):

      1. auto-mode ladder engages because the aggregate monolith is
         predicted over the remaining $OCT_WALL_DEADLINE;
      2. replay progress is CONCURRENT with the background compile —
         a rung window's first execute lands before bg-compile-done;
      3. the run completes well inside the wall (the provisional
         checkpoint would have banked);
      4. a SECOND run against the same artifact store loads the
         production program warm: via=xla-aot, zero doomed
         deserializes (no failed/rejected/wrong_build outcomes)."""
    st0, hvs, st_ref = chain
    _LVIEW[0] = lview
    monkeypatch.delenv("OCT_WARM_LADDER", raising=False)  # auto mode
    monkeypatch.setenv("OCT_STAGE_THREAD", "1")
    monkeypatch.setenv("OCT_PK_AOT_DIR", str(tmp_path))
    monkeypatch.setenv("OCT_PK_AOT_WRITEBACK", "1")
    # XLA:CPU cannot round-trip serialized executables for large fused
    # programs ("Symbols not found" at deserialize — a backend
    # limitation; TPU PJRT serialization is the production path, and
    # test_aot_latch covers the REAL roundtrip with small executables).
    # Fake ONLY the PJRT serialization layer; every store mechanism —
    # manifest, provenance, markers, memoization — stays real.
    from jax.experimental import serialize_executable as se

    exec_reg: dict = {}

    def fake_serialize(compiled):
        token = b"tok%d" % len(exec_reg)
        exec_reg[token] = compiled
        return token, None, None

    monkeypatch.setattr(se, "serialize", fake_serialize)
    monkeypatch.setattr(se, "deserialize_and_load",
                        lambda ser, it, ot: exec_reg[ser])
    monkeypatch.setattr(aot, "_LOADED", {})
    monkeypatch.setattr(aot, "_MANIFEST_CACHE", {})
    _rungs(monkeypatch, 4, 8)
    # stubbed clock: 300 s of wall; the monolith predicted 500 s (does
    # not fit -> ladder engages), rung programs predicted cheap (fit ->
    # choose_rung picks the LARGEST rung)
    monkeypatch.setenv("OCT_WALL_DEADLINE", str(time.time() + 300.0))
    pred = {"aggregate_core": 500.0, "verify_praos_core_bc": 400.0}
    monkeypatch.setattr(costmodel, "predicted_wall",
                        lambda g: pred.get(g, 1.0))
    real_pinned = costmodel.pinned
    monkeypatch.setattr(
        costmodel, "pinned",
        lambda n: ({"feature_hash": "rungpin"} if "@" in n
                   else real_pinned(n)),
    )
    stubs.install_stub_crypto(
        monkeypatch, agg_delay_s=lambda lanes: 0.4 if lanes >= 16 else 0.0
    )
    t0 = time.monotonic()
    res = _run_chain(st0, hvs)
    wall = time.monotonic() - t0
    assert res.error is None and res.n_valid == len(hvs)
    assert res.state == st_ref
    assert wall < 60.0  # trivially inside the 300 s stubbed wall
    lad = pbatch._LADDER
    assert lad is not None and lad._done.wait(10.0)
    report = WARMUP.report()
    lad_evs = {e["kind"]: e for e in report["ladder"]}
    assert "engaged" in lad_evs
    assert lad_evs["engaged"]["rung"] == 8  # largest rung that fits
    assert "bg-compile-done" in lad_evs
    # replay progress concurrent with the background compile: a RUNG
    # window's first execute landed before the bg compile did
    rung_stages = [
        v for k, v in report["stages"].items()
        if k.startswith("agg-packed:") and ":16l" not in k
    ]
    assert rung_stages, report["stages"]
    assert min(s["t"] for s in rung_stages) < lad_evs["bg-compile-done"]["t"]
    # the write-back banked the production program: a fresh process
    # (fresh warm/label state) loads it from the store
    saved = [e for e in report["aot_events"] if e["outcome"] == "saved"]
    assert saved, report["aot_events"]
    WARMUP.reset()
    pbatch.reset_warm_ladder()
    monkeypatch.setattr(pbatch, "_WARM_SEEN", set())
    monkeypatch.setattr(aot, "_LOADED", {})
    monkeypatch.setattr(aot, "_MANIFEST_CACHE", {})
    monkeypatch.delenv("OCT_WALL_DEADLINE", raising=False)
    res2 = _run_chain(st0, hvs)
    assert res2.error is None and res2.state == st_ref
    rep2 = WARMUP.report()
    outcomes = rep2["aot"]
    assert outcomes.get("loaded", 0) >= 1
    for bad in ("failed", "rejected", "wrong_build", "marker_skip"):
        assert outcomes.get(bad, 0) == 0, rep2["aot_events"]
    assert any(v.get("via") == "xla-aot" for v in rep2["stages"].values())


def test_choose_rung_against_deadline(monkeypatch):
    """costmodel.choose_rung: largest pinned rung that fits the
    remaining deadline with margin; smallest when none fit; largest
    when no deadline is exported."""
    monkeypatch.setattr(
        costmodel, "predicted_wall",
        lambda g: {"aggregate_core@1024": 10.0,
                   "aggregate_core@2048": 200.0}.get(g),
    )
    monkeypatch.delenv("OCT_WALL_DEADLINE", raising=False)
    assert costmodel.choose_rung("aggregate_core",
                                 rungs=(1024, 2048)) == 2048
    monkeypatch.setenv("OCT_WALL_DEADLINE", str(1000.0))
    # 100 s left: 10+30 fits, 200+30 does not
    assert costmodel.choose_rung("aggregate_core", now=900.0,
                                 rungs=(1024, 2048)) == 1024
    # 10 s left: nothing fits -> smallest rung
    assert costmodel.choose_rung("aggregate_core", now=990.0,
                                 rungs=(1024, 2048)) == 1024
    # 400 s left: both fit -> largest
    assert costmodel.choose_rung("aggregate_core", now=600.0,
                                 rungs=(1024, 2048)) == 2048


def test_ladder_pins_are_shipped():
    """Every rung program the ladder may compile is pinned in
    costmodel.json AND fenced by a budgets.json compile_wall ceiling
    (lint exit 5 enforces the ratchet; this pins the shipped state)."""
    from ouroboros_consensus_tpu.analysis import graphs

    cost = costmodel.load_cost()
    budgets = graphs.load_budgets()
    wall = budgets["compile_wall"]["graphs"]
    for pin_name, base, lanes in costmodel.ladder_pins():
        assert pin_name in cost["graphs"], pin_name
        assert pin_name in wall, pin_name
        assert cost["graphs"][pin_name]["predicted_s"] > 0
    # the honest structural fact the pins record on this snapshot: the
    # composed graphs are lane-invariant, so a rung pin hashes equal to
    # its base graph's — if a kernel change ever makes the structure
    # lane-sensitive, THIS is where it shows up first
    for pin_name, base, lanes in costmodel.ladder_pins():
        assert "feature_hash" in cost["graphs"][pin_name]


def test_stage_pin_graph_resolution(monkeypatch):
    real_pinned = costmodel.pinned
    monkeypatch.setattr(
        costmodel, "pinned",
        lambda n: ({"feature_hash": "x"} if n == "aggregate_core@1024"
                   else real_pinned(n)),
    )
    s = "agg-packed:410b:1024l"
    assert costmodel.stage_graph(s) == "aggregate_core"
    assert costmodel.stage_pin_graph(s, 1024) == "aggregate_core@1024"
    assert costmodel.stage_pin_graph(s, 512) == "aggregate_core"
    assert costmodel.stage_pin_graph(s, None) == "aggregate_core"


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
    monkeypatch.setenv("OCT_WARM_LADDER", "0")
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
