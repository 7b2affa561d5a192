"""The chaos matrix (PR 12 acceptance): for EVERY fault kind in the
OCT_CHAOS grammar, a seeded injection ends in a COMPLETED,
verdict-correct replay — resumed or degraded — differentially equal
(verdicts, exact error taxonomy, final nonce carry) to the
uninterrupted run. Includes a real SIGKILL-mid-window child resumed by
the parent and a sharded (parallel/spmd) shard-fault case.

Crypto is the hash-only stub (test_packed_batch idiom): the recovery
plumbing is what's under test; the rungs' crypto semantics are pinned
by the existing differential suites. probe-timeout is covered in
tests/test_bench_probe.py (it injects into bench's probe, not a
replay); the per-stage `stage-call` seam is unit-covered in
tests/test_chaos.py (the pk dispatch path it sits on is TPU-only)."""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
from fractions import Fraction

import pytest

import jax

from ouroboros_consensus_tpu import obs
from ouroboros_consensus_tpu.obs import recovery
from ouroboros_consensus_tpu.obs.warmup import WARMUP
from ouroboros_consensus_tpu.protocol import batch as pbatch
from ouroboros_consensus_tpu.protocol import praos
from ouroboros_consensus_tpu.testing import chaos, fixtures
from ouroboros_consensus_tpu.utils import trace as T

from tests.test_obs import _forge_chain, make_params
from tests.test_packed_batch import _stub_verify

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    WARMUP.reset()
    obs.reset_for_tests()
    recovery.reset_for_tests()
    for var in ("OCT_CHAOS", "OCT_CHAOS_SEED", "OCT_CHECKPOINT",
                "OCT_RESUME", "OCT_RECOVERY"):
        monkeypatch.delenv(var, raising=False)
    chaos.reset()
    yield
    WARMUP.reset()
    obs.reset_for_tests()
    recovery.reset_for_tests()
    chaos.reset()


@pytest.fixture(scope="module")
def pools():
    return [fixtures.make_pool(110 + i, kes_depth=3) for i in range(2)]


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
        key = ("fn-stub-selfheal", bc)
        if key not in pbatch._JIT:
            pbatch._JIT[key] = jax.jit(_stub_verify)
        return pbatch._JIT[key]

    monkeypatch.setattr(pbatch, "_jitted_verify", patched_jv)
    yield
    for k in set(pbatch._JIT) - before:
        del pbatch._JIT[k]


def _arm(monkeypatch, spec: str, **env):
    monkeypatch.setenv("OCT_CHAOS", spec)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    chaos.reset()


def _run_chain(params, lview, hvs, max_batch=8, backend="device"):
    st0 = praos.PraosState(epoch_nonce=b"\x07" * 32)
    return pbatch.validate_chain(
        params, lambda _e: lview, st0, hvs, max_batch=max_batch,
        backend=backend,
    )


def _same_result(a, b):
    assert a.n_valid == b.n_valid
    assert repr(a.error) == repr(b.error)  # exact error taxonomy
    assert a.state == b.state  # final nonce carry + counters + slots


def _recovery_events(lt):
    return [e for e in lt.events if isinstance(e, T.RecoveryEvent)]


# ---------------------------------------------------------------------------
# in-process matrix: validate_chain survives every injected pipeline fault
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", [
    # a fake XlaRuntimeError-class failure at the 2nd window dispatch
    "device-error@dispatch:1",
    # TWO consecutive dispatch faults (x2): retry absorbs each episode
    "device-error@dispatch:1x2",
    # the staging producer thread dies mid-prepare_window
    "staging-thread-death@window:1",
    # faults in BOTH halves of the pipeline in one replay
    "staging-thread-death@window:0,device-error@dispatch:3",
])
def test_chaos_matrix_pipeline_faults(pools, lview, stubbed, monkeypatch,
                                      spec):
    params = make_params(epoch_length=60)
    # slots 100.. with epoch_length=60: the chain crosses an epoch
    # boundary mid-replay, so recovery and the carry re-seed are
    # exercised against the nonce rotation too
    _, hvs = _forge_chain(params, pools, lview, 60)
    base = _run_chain(params, lview, hvs)
    assert base.error is None and base.n_valid == 60

    _arm(monkeypatch, spec)
    lt = T.ListTracer()
    pbatch.set_batch_tracer(lt)
    try:
        res = _run_chain(params, lview, hvs)
    finally:
        pbatch.set_batch_tracer(None)
    _same_result(res, base)
    assert chaos.plan().fired(), "the injection must actually fire"
    evs = _recovery_events(lt)
    assert evs and evs[-1].action == "recovered" and evs[-1].ok
    # every episode recovered on the retry rung (chaos faults are
    # transient by contract)
    assert {e.action for e in evs} == {"retry", "recovered"}


def test_chaos_compile_stall_is_survived_not_recovered(pools, lview,
                                                       stubbed,
                                                       monkeypatch):
    """compile-stall models a WALL, not an error: the replay simply
    takes longer and completes identically — no recovery episode."""
    params = make_params(epoch_length=60)
    _, hvs = _forge_chain(params, pools, lview, 24)
    base = _run_chain(params, lview, hvs)
    _arm(monkeypatch, "compile-stall@window:1", OCT_CHAOS_STALL_S="0.01")
    lt = T.ListTracer()
    pbatch.set_batch_tracer(lt)
    try:
        res = _run_chain(params, lview, hvs)
    finally:
        pbatch.set_batch_tracer(None)
    _same_result(res, base)
    assert chaos.plan().fired() == ["compile-stall@window:1"]
    assert not _recovery_events(lt)


def test_chaos_disabled_supervisor_raises_through(pools, lview, stubbed,
                                                  monkeypatch):
    """OCT_RECOVERY=0 restores the pre-PR-12 behavior: the fault
    propagates raw out of validate_chain."""
    params = make_params()
    _, hvs = _forge_chain(params, pools, lview, 24)
    monkeypatch.setenv("OCT_RECOVERY", "0")
    _arm(monkeypatch, "device-error@dispatch:1")
    with pytest.raises(chaos.DeviceChaosError):
        _run_chain(params, lview, hvs)


def test_shard_fault_recovers_on_sharded_backend(pools, lview, stubbed,
                                                 monkeypatch):
    """The sharded (parallel/spmd) shard-fault case: device-error at
    the 0th sharded dispatch; the supervisor's "sharded" ladder's retry
    re-runs the window through the mesh once the injection is spent."""
    from ouroboros_consensus_tpu.parallel import spmd

    from tests.test_parallel import _fake_sharded_verify

    monkeypatch.setattr(spmd, "_sharded_verify", _fake_sharded_verify)
    params = make_params()
    _, hvs = _forge_chain(params, pools, lview, 24)
    base = _run_chain(params, lview, hvs, backend="sharded")
    assert base.error is None and base.n_valid == 24

    _arm(monkeypatch, "device-error@shard:0")
    lt = T.ListTracer()
    pbatch.set_batch_tracer(lt)
    try:
        res = _run_chain(params, lview, hvs, backend="sharded")
    finally:
        pbatch.set_batch_tracer(None)
    _same_result(res, base)
    assert chaos.plan().fired() == ["device-error@shard:0"]
    evs = _recovery_events(lt)
    assert [e.action for e in evs] == ["retry", "recovered"]


# ---------------------------------------------------------------------------
# db_analyser-level matrix: chunk corruption, AOT rejection, resume
# ---------------------------------------------------------------------------


def _synth_params():
    # small epochs (stability window 24 < 60) so the chain spans
    # SEVERAL epochs and — chunk_size == epoch_length — several chunks:
    # chunk index stands in for the epoch, exactly the chaos grammar
    return praos.PraosParams(
        slots_per_kes_period=100,
        max_kes_evolutions=62,
        security_param=4,
        active_slot_coeff=Fraction(1, 2),
        epoch_length=60,
        kes_depth=3,
    )


@pytest.fixture(scope="module")
def synth_db(tmp_path_factory):
    from ouroboros_consensus_tpu.tools import db_synthesizer as synth

    params = _synth_params()
    pool = fixtures.make_pool(11, kes_depth=3)
    lv = fixtures.make_ledger_view([pool])
    path = str(tmp_path_factory.mktemp("selfheal") / "db")
    res = synth.synthesize(
        path, params, [pool], lv, synth.ForgeLimit(blocks=80),
        chunk_size=params.epoch_length,
    )
    assert res.n_blocks == 80
    return path, params, lv


def _revalidate(synth, **kw):
    from ouroboros_consensus_tpu.tools import db_analyser as ana

    path, params, lv = synth
    return ana.revalidate(path, params, lv, backend="device",
                          validate_all=False, max_batch=8, **kw)


def test_chunk_corrupt_rereads_and_matches(synth_db, stubbed, monkeypatch):
    base = _revalidate(synth_db)
    assert base.error is None and base.n_valid == 80

    _arm(monkeypatch, "chunk-corrupt@epoch:1")
    res = _revalidate(synth_db)
    # (describe() renders the NORMALIZED trigger: epoch -> chunk)
    assert chaos.plan().fired() == ["chunk-corrupt@chunk:1"]
    assert res.error is None and res.n_valid == base.n_valid
    assert res.final_state == base.final_state
    rows = WARMUP.report()["recovery"]
    assert [r["action"] for r in rows] == ["chunk-reread", "recovered"]
    assert rows[0]["fault"] == "ChunkChaosError"


def test_aot_reject_falls_back_and_matches(synth_db, stubbed, monkeypatch):
    """aot-reject@stage: the store reports the r04 'incompatible'
    class; the stage falls back to the jit path and the replay is
    byte-identical — no latch, no marker, nothing condemned."""
    base = _revalidate(synth_db)
    # fence the process-wide first-execute memo so THIS replay consults
    # the AOT store again (other suites may have warmed the label)
    monkeypatch.setattr(pbatch, "_WARM_SEEN", set())
    from ouroboros_consensus_tpu.ops.pk import aot

    monkeypatch.setattr(aot, "_LOADED", {})
    _arm(monkeypatch, "aot-reject@stage:packed")
    res = _revalidate(synth_db)
    assert chaos.plan().fired() == ["aot-reject@stage:packed"]
    assert res.error is None and res.n_valid == base.n_valid
    assert res.final_state == base.final_state
    # the real outcome vocabulary banked the rejection...
    assert WARMUP.report()["aot"].get("rejected", 0) >= 1
    # ...and the transient injection latched NOTHING process-wide
    assert not aot._RUNTIME_REJECTED


def test_checkpoint_resume_differential(synth_db, stubbed, monkeypatch,
                                        tmp_path):
    """The crash-consistent resume contract, differentially: a killed
    attempt (fault with the supervisor disabled) leaves a progress
    record; the resumed replay — including one resuming PAST an epoch
    boundary and one re-tiled onto a different max_batch — is
    verdict-identical to the uninterrupted run."""
    base = _revalidate(synth_db)
    assert base.error is None and base.n_valid == 80

    # the pipeline fills to its depth (3 windows in flight, across
    # segments) before the first window retires: a fault at one of the
    # first three dispatches leaves no record to resume from
    for fault_at, resume_batch in ((3, 8), (5, 16)):
        ck = str(tmp_path / f"ckpt_{fault_at}.json")
        monkeypatch.setenv("OCT_CHECKPOINT", ck)
        monkeypatch.setenv("OCT_RECOVERY", "0")  # die, don't degrade
        _arm(monkeypatch, f"device-error@dispatch:{fault_at}")
        with pytest.raises(chaos.DeviceChaosError):
            _revalidate(synth_db)
        monkeypatch.delenv("OCT_CHAOS")
        chaos.reset()
        doc = recovery.read_checkpoint(ck)
        assert doc is not None and not doc["complete"]
        assert 0 < doc["headers"] < 80
        # the resumed run: supervisor back on, fresh tiling allowed —
        # resume is window-slicing invariant (the mid-ladder-swap
        # analog: the killed attempt retired 8-lane windows, the
        # resumed one re-tiles at 16)
        monkeypatch.setenv("OCT_RECOVERY", "1")
        monkeypatch.setenv("OCT_RESUME", "1")
        from ouroboros_consensus_tpu.tools import db_analyser as ana

        path, params, lv = synth_db
        res = ana.revalidate(path, params, lv, backend="device",
                             validate_all=False, max_batch=resume_batch)
        monkeypatch.delenv("OCT_RESUME")
        assert res.resumed_headers == doc["headers"]
        assert res.error is None and res.n_valid == base.n_valid
        assert res.final_state == base.final_state
        # the finished record is COMPLETE: a further "resume" starts
        # fresh instead of trusting a finished run's position
        done = recovery.read_checkpoint(ck)
        assert done["complete"] and done["headers"] == 80


def test_resume_ignores_other_chains_record(synth_db, stubbed,
                                            monkeypatch, tmp_path):
    """A record tagged for ANOTHER chain (bench warms on the 100k
    chain, measures the 1M one) must not seed a resume: the replay
    silently starts fresh and still matches."""
    base = _revalidate(synth_db)
    ck = str(tmp_path / "ckpt.json")
    # a record for a different chain tag, valid in every other way
    w = recovery.ProgressWriter(ck, "someone-elses-chain")
    w.note(praos.PraosState(epoch_nonce=b"\x01" * 32), 48)
    monkeypatch.setenv("OCT_CHECKPOINT", ck)
    monkeypatch.setenv("OCT_RESUME", "1")
    res = _revalidate(synth_db)
    assert res.resumed_headers == 0  # fresh start, not a wrong re-seed
    assert res.error is None and res.n_valid == base.n_valid
    assert res.final_state == base.final_state


# ---------------------------------------------------------------------------
# the real thing: SIGKILL mid-window, child resumed by the parent
# ---------------------------------------------------------------------------

_CHILD = r"""
import json, os, sys
sys.path.insert(0, os.environ["OCT_REPO"])
import jax
from jax import numpy as jnp
from fractions import Fraction
from ouroboros_consensus_tpu.obs import recovery
from ouroboros_consensus_tpu.ops import blake2b
from ouroboros_consensus_tpu.protocol import batch as pbatch
from ouroboros_consensus_tpu.protocol import praos
from ouroboros_consensus_tpu.testing import fixtures
from ouroboros_consensus_tpu.tools import db_analyser as ana


def _stub_verify(*cols):
    beta_decl = cols[-3]
    bd = jnp.asarray(beta_decl).astype(jnp.int32)
    b = bd.shape[0]
    tag_l = jnp.broadcast_to(jnp.asarray([ord("L")], jnp.int32), (b, 1))
    lv = blake2b.blake2b_fixed(jnp.concatenate([tag_l, bd], -1), 65, 32)
    tag_n = jnp.broadcast_to(jnp.asarray([ord("N")], jnp.int32), (b, 1))
    eta1 = blake2b.blake2b_fixed(jnp.concatenate([tag_n, bd], -1), 65, 32)
    eta = blake2b.blake2b_fixed(eta1, 32, 32)
    ones = jnp.ones((b,), bool)
    return pbatch.Verdicts(ones, ones, ones, ones,
                           jnp.zeros((b,), bool), eta, lv)


pbatch.verify_praos = _stub_verify
pbatch.verify_praos_bc = _stub_verify
pbatch.verify_praos_any = _stub_verify
_stub_jit = {}


def _patched(bc=False):
    if bc not in _stub_jit:
        _stub_jit[bc] = jax.jit(_stub_verify)
    return _stub_jit[bc]


pbatch._jitted_verify = _patched
os.environ["OCT_VRF_AGG"] = "0"

params = praos.PraosParams(
    slots_per_kes_period=100, max_kes_evolutions=62, security_param=4,
    active_slot_coeff=Fraction(1, 2), epoch_length=60, kes_depth=3,
)
pool = fixtures.make_pool(11, kes_depth=3)
lv = fixtures.make_ledger_view([pool])
res = ana.revalidate(os.environ["OCT_TEST_DB"], params, lv,
                     backend="device", validate_all=False, max_batch=8)
out = {
    "n_valid": res.n_valid,
    "resumed": res.resumed_headers,
    "error": repr(res.error) if res.error is not None else None,
    "state": recovery.encode_state(res.final_state),
}
with open(os.environ["OCT_TEST_OUT"], "w") as f:
    json.dump(out, f)
"""


def test_sigkill_mid_window_child_resumed_by_parent(synth_db, tmp_path):
    """A REAL SIGKILL between a window's checkpoint and the next: the
    child dies rc=-9 having banked a progress record; the parent
    relaunches it with OCT_RESUME=1 and the resumed child's verdicts,
    error taxonomy and final nonce carry equal an uninterrupted
    child's."""
    path, _params, _lv = synth_db

    def run_child(extra_env):
        out = str(tmp_path / f"out_{len(os.listdir(tmp_path))}.json")
        env = dict(os.environ)
        env.pop("OCT_CHAOS", None)
        env.pop("OCT_CHECKPOINT", None)
        env.pop("OCT_RESUME", None)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "OCT_REPO": REPO,
            "OCT_TEST_DB": path,
            "OCT_TEST_OUT": out,
        })
        env.update(extra_env)
        proc = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                              cwd=REPO, capture_output=True, timeout=300)
        return proc, out

    ck = str(tmp_path / "ckpt.json")
    # 1. the uninterrupted reference child
    proc, ref_out = run_child({})
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    ref = json.load(open(ref_out))
    assert ref["error"] is None and ref["n_valid"] == 80

    # 2. the killed child: SIGKILL fires the moment window 2 retires
    # (AFTER its checkpoint landed — the exactly-once boundary)
    proc, _ = run_child({
        "OCT_CHECKPOINT": ck,
        "OCT_CHAOS": "sigkill@window:2",
    })
    assert proc.returncode == -signal.SIGKILL, (
        proc.returncode, proc.stderr.decode()[-2000:]
    )
    doc = recovery.read_checkpoint(ck)
    assert doc is not None and not doc["complete"]
    assert 0 < doc["headers"] < 80

    # 3. the parent relaunches with resume: verdict-identical
    proc, res_out = run_child({
        "OCT_CHECKPOINT": ck,
        "OCT_RESUME": "1",
    })
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    res = json.load(open(res_out))
    assert res["resumed"] == doc["headers"] > 0
    assert res["n_valid"] == ref["n_valid"]
    assert res["error"] is None
    assert res["state"] == ref["state"]  # the full nonce carry
    assert recovery.read_checkpoint(ck)["complete"]
