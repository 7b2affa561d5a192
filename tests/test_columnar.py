"""Columnar-vs-per-object differential suite (the round-8 host
pipeline).

The tentpole invariant: a `ViewColumns` window flowing the columnar
path — vectorized host_prechecks, columnar packed/generic staging, the
columnar all-clean epilogue, the native leader bracket — must be
BYTE-IDENTICAL to the same window flowing as a `Sequence[HeaderView]`:
identical verdicts, identical EXACT reference-error objects, identical
first-failure truncation, identical final PraosState. Corruption,
mixed 80/128-byte proof segments and generic-fallback windows are all
exercised; random chains ride hypothesis when installed, a seeded
sweep otherwise (the repo's test_absint precedent).

Crypto runs through the NATIVE backend (C++, fast on CPU) for the
differential folds and through the hash-only stub for the pipelined
device loop — the real-crypto device end-to-end lives in the slow tier
(test_tools.test_device_revalidation_matches_host).
"""

import os
import random
from dataclasses import replace
from fractions import Fraction

import numpy as np
import pytest

from ouroboros_consensus_tpu.block.forge import forge_block
from ouroboros_consensus_tpu.ops import sha512
from ouroboros_consensus_tpu.protocol import batch as pbatch
from ouroboros_consensus_tpu.protocol import praos
from ouroboros_consensus_tpu.protocol.views import ViewColumns
from ouroboros_consensus_tpu.testing import fixtures

pytestmark = pytest.mark.skipif(
    os.environ.get("JAX_PLATFORMS", "") not in ("", "cpu"),
    reason="CPU differential suite",
)


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
    return [fixtures.make_pool(i, kes_depth=3) for i in range(2)]


@pytest.fixture(scope="module")
def lview(pools):
    return fixtures.make_ledger_view(pools)


def real_chain(params, pools, n, first_slot=100, first_block=30,
               epoch_nonce=b"\x07" * 32, counter=0):
    hvs, prev = [], b"\xaa" * 32
    for i in range(n):
        blk = forge_block(
            params, pools[i % len(pools)], slot=first_slot + i,
            block_no=first_block + i, prev_hash=prev,
            epoch_nonce=epoch_nonce, txs=(b"tx-%d" % i,),
            ocert_counter=counter,
        )
        hvs.append(blk.header.to_view())
        prev = blk.header.hash_
    return hvs


def leader_chain(params, pools, lview, n, first_slot=100,
                 epoch_nonce=b"\x07" * 32):
    """Real-codec chain where every forged slot PASSES the leader check
    (clean end-to-end validation). Slots stay in one CBOR width class
    so the bodies stay rectangular."""
    hvs, prev = [], b"\xaa" * 32
    slot, blkno = first_slot, 30
    while len(hvs) < n:
        pool = fixtures.find_leader(params, pools, lview, slot, epoch_nonce)
        if pool is None:
            slot += 1
            continue
        blk = forge_block(
            params, pool, slot=slot, block_no=blkno, prev_hash=prev,
            epoch_nonce=epoch_nonce, txs=(b"tx-%03d" % len(hvs),),
            ocert_counter=0,
        )
        hvs.append(blk.header.to_view())
        prev = blk.header.hash_
        slot += 1
        blkno += 1
    return hvs


def columns_of(hvs) -> ViewColumns:
    vc = ViewColumns.from_views(hvs)
    assert vc is not None
    return vc


# ---------------------------------------------------------------------------
# representation round-trips
# ---------------------------------------------------------------------------


def test_viewcolumns_views_roundtrip(pools, lview):
    """from_views -> views() is the identity, per field — including a
    genesis lane (prev_hash None) and both proof formats."""
    params = make_params()
    hvs = real_chain(params, pools, 7)
    blk0 = forge_block(params, pools[0], slot=99, block_no=29,
                       prev_hash=None, epoch_nonce=b"\x07" * 32,
                       txs=(b"tx-x",))
    hvs = [blk0.header.to_view()] + hvs
    vc = ViewColumns.from_views(hvs)
    if vc is None:
        # genesis body width differs: drop it and round-trip the rest
        hvs = hvs[1:]
        vc = columns_of(hvs)
    assert len(vc) == len(hvs)
    assert vc.views() == hvs
    # single-lane lazy view + int indexing agree
    assert vc[3] == hvs[3]
    # slicing composes
    assert vc[2:5].views() == hvs[2:5]


def test_dedup_rows_matches_np_unique():
    rng = np.random.default_rng(11)
    for n, w, k in ((1, 64, 1), (50, 64, 3), (257, 288, 5), (64, 7, 2)):
        base = rng.integers(0, 256, (k, w), np.uint8)
        rows = base[rng.integers(0, k, n)]
        uniq, inv = pbatch._dedup_rows(rows)
        ref_u, ref_inv = np.unique(rows, axis=0, return_inverse=True)
        assert uniq.shape == ref_u.shape
        # same unique SET (ordering may differ) and exact reconstruction
        assert {r.tobytes() for r in uniq} == {r.tobytes() for r in ref_u}
        assert np.array_equal(uniq[inv], rows)


def test_pad_matrix_np_equals_pad_messages():
    rng = np.random.default_rng(3)
    for n, ln in ((1, 1), (5, 111), (9, 112), (4, 240), (3, 300)):
        mat = rng.integers(0, 256, (n, ln), np.uint8)
        msgs = [mat[i].tobytes() for i in range(n)]
        hb_a, nb_a = sha512.pad_matrix_np(mat)
        hb_b, nb_b = sha512.pad_messages_np(msgs)
        assert np.array_equal(hb_a, hb_b) and np.array_equal(nb_a, nb_b)


# ---------------------------------------------------------------------------
# prechecks + staging equivalence
# ---------------------------------------------------------------------------


def test_prechecks_columnar_equals_perview(pools, lview):
    """Same evolution column and the SAME error objects per lane —
    including KES-window violations, an unknown pool and a wrong VRF
    key registration."""
    params = make_params()
    hvs = real_chain(params, pools, 8)
    # KES window violations: c0 > kp (before start), kp >= c0+max (after)
    hvs[2] = replace(hvs[2], ocert=replace(hvs[2].ocert, kes_period=7))
    hvs[5] = replace(hvs[5], slot=hvs[5].slot + 100 * 80)
    # unknown pool: a cold key outside the distribution
    hvs[3] = replace(hvs[3], vk_cold=b"\x99" * 32)
    # wrong VRF key for a registered pool
    hvs[6] = replace(hvs[6], vrf_vk=b"\x77" * 32)
    vc = columns_of(hvs)
    a = pbatch.host_prechecks(params, lview, hvs)
    b = pbatch.host_prechecks(params, lview, vc)
    assert isinstance(b, pbatch.ColumnChecks)
    assert a.kes_window_errors == b.kes_window_errors
    assert a.vrf_lookup_errors == b.vrf_lookup_errors
    assert np.array_equal(a.kes_evolution, b.kes_evolution)
    assert not b.clean and b.any_errors()


@pytest.mark.parametrize("bc", [True, False])
def test_stage_columns_equals_stage(pools, lview, monkeypatch, bc):
    """The generic columnar staging is byte-identical to `stage` over
    the materialized views, for both proof formats."""
    monkeypatch.setenv("OCT_VRF_BATCH", "1" if bc else "0")
    params = make_params()
    hvs = real_chain(params, pools, 9)
    assert len(hvs[0].vrf_proof) == (128 if bc else 80)
    vc = columns_of(hvs)
    nonce = b"\x07" * 32
    pre = pbatch.host_prechecks(params, lview, vc)
    ref = pbatch.stage(params, lview, nonce, hvs, pre.kes_evolution)
    got = pbatch.stage_columns(params, lview, nonce, vc, pre.kes_evolution, pre)
    for name, a, b in zip(
        ["ed", "kes", "vrf"], (ref.ed, ref.kes, ref.vrf),
        (got.ed, got.kes, got.vrf),
    ):
        assert type(a) is type(b), name
        for f, x, y in zip(type(a)._fields, a, b):
            assert np.array_equal(np.asarray(x), np.asarray(y)), (name, f)
    assert np.array_equal(ref.beta, got.beta)
    assert np.array_equal(ref.thr_lo, got.thr_lo)
    assert np.array_equal(ref.thr_hi, got.thr_hi)


def test_stage_packed_columns_equals_stage_packed(pools, lview):
    """Same layout; per-lane equality of every packed column (the dedup
    tables may be PERMUTED — the gather indices compensate, so compare
    the gathered per-lane rows)."""
    params = make_params()
    nonce = b"\x07" * 32
    hvs = real_chain(params, pools, 11)
    vc = columns_of(hvs)
    pre = pbatch.host_prechecks(params, lview, vc)
    ref = pbatch.stage_packed(params, lview, nonce, hvs)
    got = pbatch.stage_packed_columns(params, lview, nonce, vc, pre)
    assert ref is not None and got is not None
    (rl, rp), (gl, gp) = ref, got
    assert rl == gl
    assert np.array_equal(rp.body, gp.body)
    assert np.array_equal(rp.kes_rs, gp.kes_rs)
    assert np.array_equal(
        rp.kes_tail_tab[rp.kes_tail_idx], gp.kes_tail_tab[gp.kes_tail_idx]
    )
    assert np.array_equal(
        rp.thr_tab[rp.thr_idx], gp.thr_tab[gp.thr_idx]
    )
    for f in ("slot", "counter", "c0", "nonce"):
        assert np.array_equal(getattr(rp, f), getattr(gp, f)), f


def test_stage_packed_columns_fallback_gates(pools, lview):
    """Non-qualifying columnar windows fall back exactly like the
    per-view stager: synthetic bodies that do not embed the fields, and
    out-of-int32-range integers."""
    params = make_params()
    nonce = b"\x07" * 32
    fv = [
        fixtures.forge_header_view(params, pools[0], slot=s,
                                   epoch_nonce=nonce, prev_hash=b"x" * 32,
                                   body_bytes=b"body-%03d" % s)
        for s in range(1, 5)
    ]
    vc = columns_of(fv)
    pre = pbatch.host_prechecks(params, lview, vc)
    assert pbatch.stage_packed_columns(params, lview, nonce, vc, pre) is None
    hvs = real_chain(params, pools, 4)
    big = columns_of([replace(hvs[0], slot=2**31)] + hvs[1:])
    pre = pbatch.host_prechecks(params, lview, big)
    assert pbatch.stage_packed_columns(params, lview, nonce, big, pre) is None


# ---------------------------------------------------------------------------
# validate_batch differential (native backend, real C crypto)
# ---------------------------------------------------------------------------


def _corrupt(hvs, i, kind):
    hv = hvs[i]
    if kind == "ocert_sig":
        sig = hv.ocert.sigma
        return replace(hv, ocert=replace(
            hv.ocert, sigma=sig[:1] + bytes([sig[1] ^ 1]) + sig[2:]
        ))
    if kind == "kes_sig":
        ks = hv.kes_sig
        return replace(hv, kes_sig=ks[:1] + bytes([ks[1] ^ 1]) + ks[2:])
    if kind == "vrf_proof":
        pf = hv.vrf_proof
        return replace(hv, vrf_proof=pf[:-1] + bytes([pf[-1] ^ 1]))
    if kind == "counter_jump":
        return replace(hv, ocert=replace(
            hv.ocert, counter=hv.ocert.counter + 5
        ))
    if kind == "kes_window":
        return replace(hv, ocert=replace(hv.ocert, kes_period=900))
    raise AssertionError(kind)


def _assert_same_result(a: pbatch.BatchResult, b: pbatch.BatchResult):
    assert a.n_valid == b.n_valid
    assert type(a.error) is type(b.error)
    assert a.error == b.error
    assert a.state == b.state


def _ticked(params, lview, hvs):
    st = praos.PraosState(epoch_nonce=b"\x07" * 32)
    slot = hvs[0].slot if not isinstance(hvs, ViewColumns) else int(hvs.slot[0])
    return praos.tick(params, lview, slot, st)


def test_validate_batch_native_columnar_clean(pools, lview):
    params = make_params()
    hvs = leader_chain(params, pools, lview, 12)
    t = _ticked(params, lview, hvs)
    a = pbatch.validate_batch(params, t, hvs, backend="native")
    b = pbatch.validate_batch(params, t, columns_of(hvs), backend="native")
    assert a.error is None and a.n_valid == 12
    _assert_same_result(a, b)


@pytest.mark.parametrize(
    "kind,where",
    [
        ("ocert_sig", 0), ("kes_sig", 5), ("vrf_proof", 11),
        ("counter_jump", 3), ("kes_window", 7),
    ],
)
def test_validate_batch_native_columnar_corrupted(pools, lview, kind, where):
    """Corrupted lanes — first lane, interior, last lane; every error
    family — truncate at the SAME position with the SAME exact error
    object through both representations."""
    params = make_params()
    hvs = leader_chain(params, pools, lview, 12)
    hvs[where] = _corrupt(hvs, where, kind)
    t = _ticked(params, lview, hvs)
    a = pbatch.validate_batch(params, t, hvs, backend="native")
    b = pbatch.validate_batch(params, t, columns_of(hvs), backend="native")
    assert a.n_valid == where and a.error is not None
    _assert_same_result(a, b)


def test_validate_batch_mixed_proof_formats(pools, lview, monkeypatch):
    """Mixed 80/128-byte proof chains segment at format boundaries in
    BOTH representations and agree lane-for-lane, clean and tampered."""
    params = make_params()
    eta = b"\x07" * 32
    hvs, prev, slot = [], None, 1
    while len(hvs) < 8:
        pool = fixtures.find_leader(params, pools, lview, slot, eta)
        if pool is not None:
            monkeypatch.setenv("OCT_VRF_BATCH", "0" if len(hvs) % 2 else "1")
            hv = fixtures.forge_header_view(
                params, pool, slot=slot, epoch_nonce=eta,
                prev_hash=prev, body_bytes=b"body-%d" % len(hvs),
            )
            hvs.append(hv)
            prev = (b"%032d" % len(hvs))[:32]
        slot += 1
    monkeypatch.delenv("OCT_VRF_BATCH", raising=False)
    assert {len(hv.vrf_proof) for hv in hvs} == {80, 128}
    t = _ticked(params, lview, hvs)
    a = pbatch.validate_batch(params, t, hvs, backend="native")
    vc = columns_of(hvs)
    assert not pbatch._proof_len_uniform(vc)
    b = pbatch.validate_batch(params, t, vc, backend="native")
    assert a.error is None and a.n_valid == 8
    _assert_same_result(a, b)
    # tampered mixed-format lane: same truncation, same exact error
    bad = hvs[5]
    hvs[5] = replace(bad, vrf_proof=bad.vrf_proof[:-1]
                     + bytes([bad.vrf_proof[-1] ^ 1]))
    a = pbatch.validate_batch(params, t, hvs, backend="native")
    b = pbatch.validate_batch(params, t, columns_of(hvs), backend="native")
    assert a.n_valid == 5 and isinstance(a.error, praos.VRFKeyBadProof)
    _assert_same_result(a, b)


def test_validate_batch_generic_fallback_window(pools, lview):
    """Synthetic views whose bodies do not embed the fields cannot
    stage packed; the columnar window still flows (columnar generic
    staging) and agrees with the per-view fold."""
    params = make_params()
    eta = b"\x07" * 32
    hvs, prev, slot = [], None, 1
    while len(hvs) < 6:
        pool = fixtures.find_leader(params, pools, lview, slot, eta)
        if pool is not None:
            hv = fixtures.forge_header_view(
                params, pool, slot=slot, epoch_nonce=eta,
                prev_hash=prev, body_bytes=b"body-%d" % len(hvs),
            )
            hvs.append(hv)
            prev = (b"%032d" % len(hvs))[:32]
        slot += 1
    t = _ticked(params, lview, hvs)
    a = pbatch.validate_batch(params, t, hvs, backend="native")
    b = pbatch.validate_batch(params, t, columns_of(hvs), backend="native")
    assert a.error is None and a.n_valid == 6
    _assert_same_result(a, b)


# ---------------------------------------------------------------------------
# randomized chains: hypothesis when installed, seeded sweep otherwise
# ---------------------------------------------------------------------------

_KINDS = ("ocert_sig", "kes_sig", "vrf_proof", "counter_jump", "kes_window")


def _random_trial(params, pools, lview, seed: int):
    rng = random.Random(seed)
    n = rng.randint(2, 14)
    hvs = real_chain(params, pools, n, first_slot=100 + rng.randint(0, 50))
    n_bad = rng.randint(0, 2)
    for _ in range(n_bad):
        i = rng.randrange(n)
        hvs[i] = _corrupt(hvs, i, rng.choice(_KINDS))
    t = _ticked(params, lview, hvs)
    a = pbatch.validate_batch(params, t, hvs, backend="native")
    b = pbatch.validate_batch(params, t, columns_of(hvs), backend="native")
    _assert_same_result(a, b)


try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(max_examples=12, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2**32 - 1))
    def test_columnar_differential_property(pools, lview, seed):
        _random_trial(make_params(), pools, lview, seed)

except ImportError:  # seeded fallback: same property, fixed sweep

    @pytest.mark.parametrize("seed", range(12))
    def test_columnar_differential_property(pools, lview, seed):
        _random_trial(make_params(), pools, lview, seed)


# ---------------------------------------------------------------------------
# the pipelined device loop with ViewColumns (crypto stubbed)
# ---------------------------------------------------------------------------


def test_validate_chain_columnar_pipeline_equals_fold(pools, lview,
                                                      monkeypatch):
    """The full pipelined device path fed a ViewColumns chain — packed
    columnar staging, device unpack, bitmask verdicts, the host nonce
    fold across windows AND epoch boundaries — agrees with the
    sequential reupdate fold and with the same chain fed as a list.
    Crypto is the hash-only stub (test_packed_batch idiom); the columnar
    epilogue fast path is what's under test."""
    import jax

    from tests.test_packed_batch import _stub_verify

    before = set(pbatch._JIT)
    monkeypatch.setenv("OCT_VRF_AGG", "0")
    monkeypatch.setattr(pbatch, "verify_praos", _stub_verify)
    monkeypatch.setattr(pbatch, "verify_praos_bc", _stub_verify)
    monkeypatch.setattr(pbatch, "verify_praos_any", _stub_verify)

    def patched_jv(bc=False):
        key = ("fn-stub", bc)
        if key not in pbatch._JIT:
            pbatch._JIT[key] = jax.jit(_stub_verify)
        return pbatch._JIT[key]

    monkeypatch.setattr(pbatch, "_jitted_verify", patched_jv)
    try:
        params = make_params(epoch_length=60)
        st0 = praos.PraosState(epoch_nonce=b"\x07" * 32)
        st = st0
        hvs, prev = [], b"\xaa" * 32
        slot, blkno = 18, 40  # crosses the CBOR 1->2-byte slot boundary
        while len(hvs) < 60:
            ticked = praos.tick(params, lview, slot, st)
            blk = forge_block(
                params, pools[len(hvs) % 2], slot=slot, block_no=blkno,
                prev_hash=prev, epoch_nonce=ticked.state.epoch_nonce,
                txs=(b"t",),
            )
            hv = blk.header.to_view()
            st = praos.reupdate(params, hv, slot, ticked)
            hvs.append(hv)
            prev = blk.header.hash_
            slot += 1
            blkno += 1
        assert params.epoch_of(hvs[-1].slot) >= 1

        # the forged bodies change width at the CBOR boundary: feed the
        # chain as width-uniform columnar runs, state threading through
        widths = {}
        runs: list = []
        for hv in hvs:
            w = len(hv.signed_bytes)
            if runs and runs[-1][0] == w:
                runs[-1][1].append(hv)
            else:
                runs.append((w, [hv]))
            widths[w] = widths.get(w, 0) + 1
        res_list = pbatch.validate_chain(
            params, lambda _e: lview, st0, hvs, max_batch=8,
        )
        assert res_list.error is None and res_list.n_valid == 60
        assert res_list.state == st

        state = st0
        total = 0
        for _w, run in runs:
            vc = columns_of(run)
            res = pbatch.validate_chain(
                params, lambda _e: lview, state, vc, max_batch=8,
            )
            assert res.error is None
            total += res.n_valid
            state = res.state
        assert total == 60
        assert state == st
    finally:
        for k in set(pbatch._JIT) - before:
            del pbatch._JIT[k]


def test_revalidate_columnar_equals_perview_on_disk(tmp_path, monkeypatch):
    """End-to-end on-disk differential: synthesize a chain, revalidate
    with the native backend through the columnar window stream and the
    per-object stream (OCT_COLUMNAR=0) — identical verdicts and final
    state; then corrupt a block on disk and check identical truncation."""
    from ouroboros_consensus_tpu.tools import db_analyser, db_synthesizer

    params = praos.PraosParams(
        slots_per_kes_period=100, max_kes_evolutions=62, security_param=4,
        active_slot_coeff=Fraction(1, 2), epoch_length=50, kes_depth=3,
    )
    pools = [fixtures.make_pool(40 + i, kes_depth=3) for i in range(2)]
    lv = fixtures.make_ledger_view(pools)
    path = str(tmp_path / "db")
    res = db_synthesizer.synthesize(
        path, params, pools, lv, db_synthesizer.ForgeLimit(slots=120),
        chunk_size=32,
    )
    assert res.n_blocks > 30

    def run():
        return db_analyser.revalidate(
            path, params, lv, backend="native", validate_all="stream",
        )

    monkeypatch.delenv("OCT_COLUMNAR", raising=False)
    a = run()
    monkeypatch.setenv("OCT_COLUMNAR", "0")
    b = run()
    assert a.error is None and a.n_valid == res.n_blocks
    assert b.n_valid == a.n_valid and b.n_blocks == a.n_blocks
    assert a.final_state == b.final_state

    # corrupt one byte of a mid-chain block body on disk
    import glob

    chunk = sorted(glob.glob(os.path.join(path, "immutable", "*.chunk")))[1]
    with open(chunk, "r+b") as f:
        f.seek(40)
        c = f.read(1)
        f.seek(40)
        f.write(bytes([c[0] ^ 0xFF]))
    monkeypatch.delenv("OCT_COLUMNAR", raising=False)
    a = run()
    monkeypatch.setenv("OCT_COLUMNAR", "0")
    b = run()
    assert a.n_valid == b.n_valid and a.n_blocks == b.n_blocks
    assert repr(a.error) == repr(b.error)
    assert a.final_state == b.final_state
    assert a.n_valid < res.n_blocks  # the corruption truncated the chain
