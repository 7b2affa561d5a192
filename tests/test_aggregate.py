"""Aggregated (RLC + MSM) window verification — dispatch plumbing and
full differentials.

Fast tier: the aggregate DISPATCH path with a stubbed aggregate core —
clean windows ride the bitmask fast path end to end, a nonzero
aggregate re-dispatches the per-lane packed program and the result is
byte-identical to the sequential fold (the crypto itself is stubbed
hash-only, PR-2 pattern, so the default tier never pays the XLA:CPU
curve compile).

Slow tier: the REAL thing on CPU — the bench-chain shape validated
through the aggregated path vs the per-lane path (OCT_VRF_AGG=0) vs the
host sequential fold, byte-identical on clean chains; and the
corrupted-lane matrix (ocert / kes / vrf proof / beta) where the
poisoned aggregate must fall back and isolate exactly the bad lane with
the exact reference error. Plus the 256-bit MSM differential.
"""

import os
import random
from dataclasses import replace
from fractions import Fraction

import numpy as np
import pytest

import jax
from jax import numpy as jnp

from ouroboros_consensus_tpu.block.forge import forge_block
from ouroboros_consensus_tpu.ops import blake2b
from ouroboros_consensus_tpu.protocol import batch as pbatch
from ouroboros_consensus_tpu.protocol import praos
from ouroboros_consensus_tpu.protocol.praos import PraosIsLeader
from ouroboros_consensus_tpu.testing import fixtures


def make_params(kes_depth=3, epoch_length=100_000):
    return praos.PraosParams(
        slots_per_kes_period=100,
        max_kes_evolutions=62,
        security_param=4,
        active_slot_coeff=Fraction(1, 2),
        epoch_length=epoch_length,
        kes_depth=kes_depth,
    )


@pytest.fixture(scope="module")
def pools():
    return [fixtures.make_pool(50 + i, kes_depth=3) for i in range(2)]


@pytest.fixture(scope="module")
def lview(pools):
    return fixtures.make_ledger_view(pools)


def real_chain(params, pools, lview, n, tamper=None, first_slot=100,
               vrf_batch=None):
    """Real-codec batch-compatible chain forged on WINNING slots only
    (the leader lottery is consulted per slot, db-synthesizer style, so
    a clean chain validates end to end); `tamper(i, pool, is_leader,
    ocert) -> (is_leader, ocert, kes_flip)` lets a lane be corrupted
    BEFORE the body is built, so the window still qualifies for packed
    staging (the corruption is inside the signed body, exactly like a
    forged-on-chain attack). `vrf_batch(i) -> bool` selects the proof
    format per header (True = 128-byte batch-compatible, False =
    80-byte draft-03) so mixed-format chains stay real-codec."""
    from ouroboros_consensus_tpu.block.forge import evaluate_vrf
    from ouroboros_consensus_tpu.protocol import nonces as nonces_mod
    from ouroboros_consensus_tpu.protocol.leader import check_leader_value

    nonce = b"\x07" * 32
    hvs, prev = [], b"\xaa" * 32
    slot = first_slot
    prev_fmt = os.environ.get("OCT_VRF_BATCH")
    while len(hvs) < n:
        if vrf_batch is not None:
            os.environ["OCT_VRF_BATCH"] = "1" if vrf_batch(len(hvs)) else "0"
        winner = None
        for pool in pools:
            cand = evaluate_vrf(pool, slot, nonce)
            stake = lview.pool_distr[pool.pool_id].stake
            if check_leader_value(
                nonces_mod.vrf_leader_value(cand.vrf_output), stake,
                params.active_slot_coeff,
            ):
                winner, is_leader = pool, cand
                break
        if winner is None:
            slot += 1
            continue
        i = len(hvs)
        kp = params.kes_period_of(slot)
        c0 = max(0, kp - (kp % params.max_kes_evolutions))
        ocert = winner.make_ocert(0, c0)
        kes_flip = False
        if tamper is not None:
            is_leader, ocert, kes_flip = tamper(i, winner, is_leader, ocert)
        blk = _forge_raw(
            params, winner, slot, 30 + i, prev, nonce, (b"tx-%d" % i,),
            is_leader, ocert,
        )
        hv = blk.header.to_view()
        if kes_flip:
            if callable(kes_flip):
                hv = replace(hv, kes_sig=kes_flip(hv.kes_sig))
            else:
                sig = bytearray(hv.kes_sig)
                sig[1] ^= 1
                hv = replace(hv, kes_sig=bytes(sig))
        hvs.append(hv)
        prev = blk.header.hash_
        slot += 1
    if vrf_batch is not None:
        if prev_fmt is None:
            os.environ.pop("OCT_VRF_BATCH", None)
        else:
            os.environ["OCT_VRF_BATCH"] = prev_fmt
    return nonce, hvs


def _forge_raw(params, pool, slot, block_no, prev, nonce, txs, is_leader,
               ocert):
    """forge_block with an explicit (possibly tampered) OCert but the
    synthesizer-style static KES signing."""
    from ouroboros_consensus_tpu.block.praos_block import (
        Block, Header, HeaderBody, body_hash,
    )
    from ouroboros_consensus_tpu.ops.host import kes as host_kes

    kp = params.kes_period_of(slot)
    body = HeaderBody(
        block_no=block_no, slot=slot, prev_hash=prev,
        issuer_vk=pool.vk_cold, vrf_vk=pool.vrf_vk,
        vrf_output=is_leader.vrf_output, vrf_proof=is_leader.vrf_proof,
        body_size=sum(len(t) for t in txs), body_hash=body_hash(txs),
        ocert=ocert, protocol_version=(9, 0),
    )
    t = kp - ocert.kes_period
    kes_sig = host_kes.sign(pool.kes_seed, pool.kes_depth, t,
                            body.signed_bytes)
    return Block(Header(body, kes_sig), tuple(txs))


def host_fold(params, lview, nonce, hvs):
    """The sequential reference: (n_valid, error-or-None, final state)."""
    st = replace(praos.PraosState(), epoch_nonce=nonce)
    for i, hv in enumerate(hvs):
        ticked = praos.tick(params, lview, hv.slot, st)
        try:
            st = praos.update(params, hv, hv.slot, ticked)
        except praos.PraosValidationError as e:
            return i, e, st
    return len(hvs), None, st


def _results_match_host(res, params, lview, nonce, hvs):
    n, err, st = host_fold(params, lview, nonce, hvs)
    assert res.n_valid == n, (res.n_valid, n, repr(res.error))
    assert (res.error is None) == (err is None), (res.error, err)
    if err is not None:
        assert type(res.error) is type(err), (res.error, err)
        assert vars(res.error) == vars(err)
    else:
        assert res.state == st


# ---------------------------------------------------------------------------
# Fast tier: dispatch plumbing with a stubbed aggregate core
# ---------------------------------------------------------------------------


def _hash_tail(beta_decl_bt):
    bd = jnp.asarray(beta_decl_bt).astype(jnp.int32)
    b = bd.shape[0]
    tag_l = jnp.broadcast_to(jnp.asarray([ord("L")], jnp.int32), (b, 1))
    lv = blake2b.blake2b_fixed(jnp.concatenate([tag_l, bd], axis=-1), 65, 32)
    tag_n = jnp.broadcast_to(jnp.asarray([ord("N")], jnp.int32), (b, 1))
    eta1 = blake2b.blake2b_fixed(jnp.concatenate([tag_n, bd], axis=-1), 65, 32)
    eta = blake2b.blake2b_fixed(eta1, 32, 32)
    return eta, lv


def _stub_aggregate(agg_ok: bool):
    """aggregate_window stand-in: real eta/leader hashes (the fold must
    stay byte-exact), all-pass cheap checks, forced aggregate verdict."""
    from ouroboros_consensus_tpu.ops.pk import aggregate as agg_mod

    def fn(*limb, kes_depth):
        beta_decl = limb[-3]  # [64, T] limb-first
        eta, lv = _hash_tail(jnp.transpose(beta_decl))
        eta, lv = jnp.transpose(eta), jnp.transpose(lv)
        t = beta_decl.shape[-1]
        ok = jnp.full((t,), bool(agg_ok))
        flags = jnp.stack([
            ok.astype(jnp.int32), ok.astype(jnp.int32),
            ok.astype(jnp.int32),
            jnp.ones((t,), jnp.int32), jnp.zeros((t,), jnp.int32),
        ])
        return agg_mod.AggregateVerdicts(
            flags, eta, lv, jnp.asarray(bool(agg_ok)),
            jnp.asarray(bool(agg_ok)),
        )

    return fn


@pytest.fixture
def fenced_jits(monkeypatch):
    before = set(pbatch._JIT)
    yield
    for k in set(pbatch._JIT) - before:
        del pbatch._JIT[k]


@pytest.mark.parametrize("clean", [True, False])
def test_agg_dispatch_clean_vs_fallback(pools, lview, clean, monkeypatch,
                                        fenced_jits):
    """Clean windows ride the aggregate bitmask fast path; a nonzero
    aggregate re-dispatches the per-lane packed program (stubbed
    hash-only here) and the chain result still equals the fold."""
    from ouroboros_consensus_tpu.ops.pk import aggregate as agg_mod

    params = make_params()
    nonce, hvs = real_chain(params, pools, lview, 12)
    assert len(hvs[0].vrf_proof) == 128
    monkeypatch.setattr(agg_mod, "aggregate_window", _stub_aggregate(clean))

    calls = {"fallback": 0}
    orig_xla = pbatch._jitted_packed_xla

    def counting_xla(layout):
        calls["fallback"] += 1
        return orig_xla(layout)

    monkeypatch.setattr(pbatch, "_jitted_packed_xla", counting_xla)
    # the per-lane fallback would compile real crypto: stub it too
    monkeypatch.setattr(pbatch, "verify_praos_any",
                        lambda *cols: _stub_verdicts(cols))

    st0 = replace(praos.PraosState(), epoch_nonce=nonce)
    res = pbatch.validate_chain(
        params, lambda _e: lview, st0, hvs, max_batch=len(hvs)
    )
    assert res.error is None and res.n_valid == len(hvs)
    # byte-exact state against the reupdate fold
    st = st0
    for hv in hvs:
        ticked = praos.tick(params, lview, hv.slot, st)
        st = praos.reupdate(params, hv, hv.slot, ticked)
    assert res.state == st
    assert calls["fallback"] == (0 if clean else 1)


def _stub_verdicts(cols):
    beta_decl = cols[-3]
    eta, lv = _hash_tail(beta_decl)
    b = jnp.asarray(beta_decl).shape[0]
    ones = jnp.ones((b,), bool)
    return pbatch.Verdicts(ones, ones, ones, ones,
                           jnp.zeros((b,), bool), eta, lv)


# ---------------------------------------------------------------------------
# Slow tier: the real aggregated crypto, differentially
# ---------------------------------------------------------------------------


def _validate(params, lview, nonce, hvs, agg: bool, monkeypatch):
    monkeypatch.setenv("OCT_VRF_AGG", "1" if agg else "0")
    st0 = replace(praos.PraosState(), epoch_nonce=nonce)
    return pbatch.validate_chain(
        params, lambda _e: lview, st0, hvs, max_batch=len(hvs)
    )


@pytest.mark.slow
def test_aggregate_clean_chain_matches_per_lane_and_host(
    pools, lview, monkeypatch
):
    """Acceptance: aggregated window verification produces verdicts
    byte-identical to the per-lane path on a clean bench-shaped chain,
    and both equal the host sequential fold."""
    params = make_params()
    nonce, hvs = real_chain(params, pools, lview, 16)
    res_agg = _validate(params, lview, nonce, hvs, True, monkeypatch)
    res_lane = _validate(params, lview, nonce, hvs, False, monkeypatch)
    _results_match_host(res_agg, params, lview, nonce, hvs)
    _results_match_host(res_lane, params, lview, nonce, hvs)
    assert res_agg.n_valid == res_lane.n_valid
    assert res_agg.state == res_lane.state


def _torsion8():
    """A point of EXACT order 8 (host representation): [L]Q for the
    first decompressable encoding Q whose torsion component has full
    order. Adding it to a wire point encoding keeps the encoding
    canonical but moves the point off the prime-order subgroup."""
    from ouroboros_consensus_tpu.ops.host import ed25519 as he

    for b0 in range(256):
        q = he.point_decompress(bytes([b0]) + bytes(31))
        if q is None:
            continue
        t = he.point_mul(he.L, q)
        if (not he.point_equal(t, he.IDENT)
                and not he.point_equal(he.point_mul(4, t), he.IDENT)):
            return t
    raise AssertionError("no order-8 point found")


def _add_torsion(enc32: bytes) -> bytes:
    from ouroboros_consensus_tpu.ops.host import ed25519 as he

    p = he.point_decompress(enc32)
    assert p is not None
    return he.point_compress(he.point_add(p, _torsion8()))


def _tamper_factory(kind, bad_lane):
    def tamper(i, pool, is_leader, ocert):
        if i != bad_lane:
            return is_leader, ocert, False
        if kind == "ed_torsion":
            # torsion-grind the announced Ed25519 R of the OCert
            # signature: still a canonical encoding, but off the
            # prime-order subgroup — the odd (cofactor-coprime) z1
            # keeps the z1·T term alive in the aggregate, so the
            # unified identity check must reject exactly like the
            # cofactorless host reference
            sig = _add_torsion(ocert.sigma[:32]) + ocert.sigma[32:]
            return is_leader, replace(ocert, sigma=sig), False
        if kind == "kes_torsion":
            # same grind on the KES leaf signature's R (first 32 bytes
            # of the CompactSum signature) — the z2 lane of the fold
            return is_leader, ocert, (
                lambda ks: _add_torsion(ks[:32]) + ks[32:]
            )
        if kind == "ocert":
            sig = bytearray(ocert.sigma)
            sig[3] ^= 1
            return is_leader, replace(ocert, sigma=bytes(sig)), False
        if kind == "kes":
            return is_leader, ocert, True
        if kind == "vrf":
            pi = bytearray(is_leader.vrf_proof)
            pi[40] ^= 1  # announced U point
            return (PraosIsLeader(is_leader.vrf_output, bytes(pi)),
                    ocert, False)
        if kind == "beta":
            out = bytearray(is_leader.vrf_output)
            out[0] ^= 1
            return (PraosIsLeader(bytes(out), is_leader.vrf_proof),
                    ocert, False)
        raise AssertionError(kind)

    return tamper


@pytest.mark.slow
@pytest.mark.parametrize("kind", ["ocert", "kes", "vrf", "beta"])
def test_corrupted_lane_falls_back_and_isolates(pools, lview, kind,
                                                monkeypatch):
    """Acceptance: a poisoned aggregate triggers the per-lane fallback
    and reproduces the exact reference error at exactly the bad lane —
    for each crypto family."""
    params = make_params()
    bad = 5
    nonce, hvs = real_chain(
        params, pools, lview, 9, tamper=_tamper_factory(kind, bad)
    )
    assert len(hvs[0].vrf_proof) == 128
    res = _validate(params, lview, nonce, hvs, True, monkeypatch)
    assert res.n_valid == bad
    _results_match_host(res, params, lview, nonce, hvs)
    expect = {
        "ocert": praos.InvalidSignatureOCERT,
        "kes": praos.InvalidKesSignatureOCERT,
        "vrf": praos.VRFKeyBadProof,
        "beta": praos.VRFKeyBadProof,
    }[kind]
    assert isinstance(res.error, expect), res.error


@pytest.mark.slow
@pytest.mark.parametrize("kind", ["ed_torsion", "kes_torsion"])
def test_single_lane_torsion_grinding_rejected(pools, lview, kind,
                                               monkeypatch):
    """Round-15 regression: an adversary who grinds an 8-torsion offset
    onto a single lane's Ed25519 R (OCert sigma) or KES leaf R must be
    rejected by the UNIFIED aggregate exactly like the cofactorless
    host reference — the odd Fiat–Shamir coefficients keep the z·T
    torsion term alive in the folded identity, so the shared-bucket
    MSM cannot be talked into accepting what the per-lane path
    refuses. Same 9-lane window shape as the corruption matrix (shares
    the compiled programs)."""
    params = make_params()
    bad = 5
    nonce, hvs = real_chain(
        params, pools, lview, 9, tamper=_tamper_factory(kind, bad)
    )
    res = _validate(params, lview, nonce, hvs, True, monkeypatch)
    assert res.n_valid == bad
    _results_match_host(res, params, lview, nonce, hvs)
    expect = {
        "ed_torsion": praos.InvalidSignatureOCERT,
        "kes_torsion": praos.InvalidKesSignatureOCERT,
    }[kind]
    assert isinstance(res.error, expect), res.error


@pytest.mark.slow
@pytest.mark.parametrize("combo,first_err", [
    ((("ocert", 2), ("vrf", 6)), "ocert"),
    ((("kes", 1), ("beta", 7)), "kes"),
])
def test_multiple_dirty_stages_one_window(pools, lview, combo, first_err,
                                          monkeypatch):
    """Two DIFFERENT crypto families corrupted in the same window: the
    single aggregated identity check must go dirty, and the per-lane
    re-dispatch must reproduce the FIRST reference error at the first
    bad lane (later corruption stays behind the first-error horizon,
    exactly like the sequential fold)."""
    params = make_params()
    tampers = [_tamper_factory(kind, lane) for kind, lane in combo]

    def tamper(i, pool, is_leader, ocert):
        flip = False
        for t in tampers:
            is_leader, ocert, f = t(i, pool, is_leader, ocert)
            flip = flip or f
        return is_leader, ocert, flip

    nonce, hvs = real_chain(params, pools, lview, 9, tamper=tamper)
    res = _validate(params, lview, nonce, hvs, True, monkeypatch)
    assert res.n_valid == min(lane for _, lane in combo)
    _results_match_host(res, params, lview, nonce, hvs)
    expect = {
        "ocert": praos.InvalidSignatureOCERT,
        "kes": praos.InvalidKesSignatureOCERT,
    }[first_err]
    assert isinstance(res.error, expect), res.error


def test_mixed_format_chain_segments_before_aggregate(pools, lview,
                                                      monkeypatch,
                                                      fenced_jits):
    """A chain mixing 80-byte draft-03 and 128-byte batch-compatible
    proofs must SEGMENT at format boundaries rather than enter the
    unified one-RLC path: every window the aggregate builder sees is
    batch-compatible, draft-03 segments ride the per-lane packed
    program, and the chain result still equals the sequential
    reupdate fold (crypto stubbed hash-only — dispatch plumbing
    only)."""
    from ouroboros_consensus_tpu.ops.pk import aggregate as agg_mod

    params = make_params()
    # alternating 2-header format runs: [bc, bc][d3, d3][bc, bc][d3, d3]
    nonce, hvs = real_chain(
        params, pools, lview, 8, vrf_batch=lambda i: (i // 2) % 2 == 0
    )
    assert {len(hv.vrf_proof) for hv in hvs} == {80, 128}

    monkeypatch.setattr(agg_mod, "aggregate_window", _stub_aggregate(True))
    monkeypatch.setattr(pbatch, "verify_praos_any",
                        lambda *cols: _stub_verdicts(cols))
    seen_plens = []
    orig_agg = pbatch._jitted_packed_agg

    def counting_agg(layout, mode="all"):
        seen_plens.append(layout.vrf_proof_len)
        return orig_agg(layout, mode)

    monkeypatch.setattr(pbatch, "_jitted_packed_agg", counting_agg)

    st0 = replace(praos.PraosState(), epoch_nonce=nonce)
    res = pbatch.validate_chain(
        params, lambda _e: lview, st0, hvs, max_batch=len(hvs)
    )
    assert res.error is None and res.n_valid == len(hvs)
    st = st0
    for hv in hvs:
        ticked = praos.tick(params, lview, hv.slot, st)
        st = praos.reupdate(params, hv, hv.slot, ticked)
    assert res.state == st
    assert seen_plens, "no batch-compatible segment reached the aggregate"
    assert set(seen_plens) == {128}


@pytest.mark.slow
def test_msm_matches_host_256bit():
    from ouroboros_consensus_tpu.ops import bigint as bi
    from ouroboros_consensus_tpu.ops.host import ed25519 as he
    from ouroboros_consensus_tpu.ops.pk import curve as pc
    from ouroboros_consensus_tpu.ops.pk import msm

    random.seed(3)
    n = 11
    ks = [random.randrange(he.L) for _ in range(n)]
    pts = [he.point_mul(random.randrange(1, he.L), he.B) for _ in range(n)]
    acc = he.IDENT
    for k, p in zip(ks, pts):
        acc = he.point_add(acc, he.point_mul(k, p))
    enc = np.stack(
        [np.frombuffer(he.point_compress(p), np.uint8) for p in pts]
    ).astype(np.int32).T
    ok, P = pc.decompress(jnp.asarray(enc))
    assert bool(jnp.all(ok))
    scal = jnp.asarray(np.stack([bi.int_to_limbs_np(k, 20) for k in ks],
                                axis=-1))
    got = np.asarray(pc.compress(msm.msm(scal, P, 256)))[:, 0]
    assert got.astype(np.uint8).tobytes() == he.point_compress(acc)
