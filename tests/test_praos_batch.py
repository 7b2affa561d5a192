"""Batched Praos validation == sequential reference fold.

The contract (SURVEY.md §7.3 item 2): `validate_batch` must produce the
same resulting PraosState, the same valid-prefix length, and the same
first-error class as folding `praos.update` header by header.
"""

import os
from dataclasses import replace
from fractions import Fraction

import pytest

from ouroboros_consensus_tpu.ops.host import kes as host_kes
from ouroboros_consensus_tpu.protocol import batch as pbatch
from ouroboros_consensus_tpu.protocol import nonces, praos
from ouroboros_consensus_tpu.testing import fixtures

PARAMS = praos.PraosParams(
    slots_per_kes_period=100,
    max_kes_evolutions=62,
    security_param=4,
    active_slot_coeff=Fraction(1, 2),
    epoch_length=50,
    kes_depth=3,
)


def make_chain(n, pools, params=PARAMS, epoch_nonce=b"\x07" * 32, lview=None):
    """Leader-aware forging: only slots some pool actually wins."""
    if lview is None:
        lview = fixtures.make_ledger_view(pools)
    hvs = []
    prev = None
    slot = 1
    while len(hvs) < n:
        pool = fixtures.find_leader(params, pools, lview, slot, epoch_nonce)
        if pool is not None:
            hv = fixtures.forge_header_view(
                params, pool, slot=slot, epoch_nonce=epoch_nonce,
                prev_hash=prev, body_bytes=b"body-%d" % len(hvs),
            )
            hvs.append(hv)
            prev = (b"%032d" % len(hvs))[:32]
        slot += 1
    return hvs


def sequential_fold(params, ticked, hvs):
    """Reference semantics: fold praos.update, stop at first error."""
    st = ticked.state
    for i, hv in enumerate(hvs):
        try:
            st = praos.update(params, hv, hv.slot, praos.TickedPraosState(st, ticked.ledger_view))
        except praos.PraosValidationError as e:
            return st, i, e
    return st, len(hvs), None


@pytest.fixture(scope="module")
def pools():
    return [fixtures.make_pool(i, kes_depth=PARAMS.kes_depth) for i in range(3)]


@pytest.fixture(scope="module")
def lview(pools):
    return fixtures.make_ledger_view(pools)


def ticked_state(lview, epoch_nonce=b"\x07" * 32):
    st = praos.PraosState(epoch_nonce=epoch_nonce)
    return praos.TickedPraosState(st, lview)


def assert_same(params, ticked, hvs):
    st_seq, n_seq, err_seq = sequential_fold(params, ticked, hvs)
    res = pbatch.validate_batch(params, ticked, hvs)
    assert res.n_valid == n_seq
    if err_seq is None:
        assert res.error is None
    else:
        assert type(res.error) is type(err_seq)
    assert res.state == replace(
        st_seq, ocert_counters=dict(st_seq.ocert_counters)
    ) or (
        res.state.evolving_nonce == st_seq.evolving_nonce
        and res.state.candidate_nonce == st_seq.candidate_nonce
        and res.state.lab_nonce == st_seq.lab_nonce
        and res.state.last_slot == st_seq.last_slot
        and dict(res.state.ocert_counters) == dict(st_seq.ocert_counters)
    )


@pytest.mark.slow
def test_all_valid(pools, lview):
    hvs = make_chain(8, pools)
    t = ticked_state(lview)
    assert_same(PARAMS, t, hvs)
    res = pbatch.validate_batch(PARAMS, t, hvs)
    assert res.n_valid == 8 and res.error is None


def test_mixed_proof_format_chain_validates(pools, lview, monkeypatch):
    """A chain mixing 80-byte draft-03 and 128-byte batch-compatible
    proofs (e.g. synthesized across an OCT_VRF_BATCH flip) validates
    header-by-header like the reference fold instead of crashing the
    uniform-proof-column staging: validate_batch segments the run at
    format boundaries. Native backend — no device compile, fast tier."""
    eta = b"\x07" * 32
    hvs, prev, slot = [], None, 1
    while len(hvs) < 6:
        pool = fixtures.find_leader(PARAMS, pools, lview, slot, eta)
        if pool is not None:
            monkeypatch.setenv("OCT_VRF_BATCH",
                               "0" if len(hvs) % 2 else "1")
            hv = fixtures.forge_header_view(
                PARAMS, pool, slot=slot, epoch_nonce=eta,
                prev_hash=prev, body_bytes=b"body-%d" % len(hvs),
            )
            hvs.append(hv)
            prev = (b"%032d" % len(hvs))[:32]
        slot += 1
    monkeypatch.delenv("OCT_VRF_BATCH", raising=False)
    assert {len(hv.vrf_proof) for hv in hvs} == {80, 128}
    t = ticked_state(lview)
    st_seq, n_seq, err_seq = sequential_fold(PARAMS, t, hvs)
    assert err_seq is None and n_seq == len(hvs)
    res = pbatch.validate_batch(PARAMS, t, hvs, backend="native")
    assert res.error is None and res.n_valid == len(hvs)
    assert res.state.evolving_nonce == st_seq.evolving_nonce
    assert dict(res.state.ocert_counters) == dict(st_seq.ocert_counters)
    # a tampered mixed-format lane still isolates with the exact error
    bad = hvs[4]
    hvs[4] = replace(
        bad,
        vrf_proof=bad.vrf_proof[:-1] + bytes([bad.vrf_proof[-1] ^ 1]),
    )
    res = pbatch.validate_batch(PARAMS, t, hvs, backend="native")
    assert res.n_valid == 4
    assert isinstance(res.error, praos.VRFKeyBadProof)


@pytest.mark.slow
def test_bad_kes_sig_midway(pools, lview):
    hvs = make_chain(6, pools)
    bad = hvs[3]
    hvs[3] = replace(bad, kes_sig=b"\x01" + bad.kes_sig[1:])
    assert_same(PARAMS, ticked_state(lview), hvs)


@pytest.mark.slow
def test_bad_vrf_proof(pools, lview):
    hvs = make_chain(5, pools)
    bad = hvs[2]
    hvs[2] = replace(bad, vrf_proof=bad.vrf_proof[:-1] + bytes([bad.vrf_proof[-1] ^ 1]))
    assert_same(PARAMS, ticked_state(lview), hvs)


@pytest.mark.slow
def test_bad_ocert_sigma(pools, lview):
    hvs = make_chain(4, pools)
    bad = hvs[1]
    hvs[1] = replace(bad, ocert=replace(bad.ocert, sigma=bytes(64)))
    assert_same(PARAMS, ticked_state(lview), hvs)


@pytest.mark.slow
def test_unknown_pool(pools, lview):
    stranger = fixtures.make_pool(99, kes_depth=PARAMS.kes_depth)
    hvs = make_chain(3, pools)
    hvs[1] = fixtures.forge_header_view(
        PARAMS, stranger, slot=hvs[1].slot, epoch_nonce=b"\x07" * 32,
        prev_hash=hvs[1].prev_hash,
    )
    assert_same(PARAMS, ticked_state(lview), hvs)


@pytest.mark.slow
def test_counter_regression(pools, lview):
    # same pool twice: second header reuses a LOWER ocert counter; pick
    # slots the pool actually wins so the counter check is what fires
    p = pools[0]
    eta = b"\x07" * 32
    slots = [
        s for s in range(1, 2000)
        if fixtures.find_leader(PARAMS, [p], lview, s, eta) is not None
    ][:2]
    assert len(slots) == 2
    hv1 = fixtures.forge_header_view(
        PARAMS, p, slot=slots[0], epoch_nonce=eta, prev_hash=None,
        ocert_counter=5,
    )
    hv2 = fixtures.forge_header_view(
        PARAMS, p, slot=slots[1], epoch_nonce=eta, prev_hash=b"x" * 32,
        ocert_counter=3,
    )
    assert_same(PARAMS, ticked_state(lview), [hv1, hv2])


@pytest.mark.slow
def test_leader_threshold_losers(pools):
    # tiny stake for pool 0 => its VRF values should mostly lose the slot
    lv = fixtures.make_ledger_view(
        pools, stakes=[Fraction(1, 10**12)] + [Fraction(1, 2)] * (len(pools) - 1)
    )
    hvs = make_chain(6, pools)
    t = ticked_state(lv)
    assert_same(PARAMS, t, hvs)


@pytest.mark.slow
def test_validate_chain_epoch_segmentation(pools, lview):
    # headers crossing an epoch boundary (epoch_length=50): nonce rotation
    # between segments must match the sequential tick-per-header fold
    params = PARAMS
    hvs = []
    prev = None
    st0 = praos.PraosState(epoch_nonce=b"\x07" * 32)

    # build chain with correct per-epoch nonces by running the fold as forge
    st = st0
    slot = 44  # will cross slot 50 (epoch 0 -> 1)
    while len(hvs) < 8:
        ticked = praos.tick(params, lview, slot, st)
        pool = fixtures.find_leader(
            params, pools, lview, slot, ticked.state.epoch_nonce
        )
        if pool is None:
            slot += 1
            continue
        hv = fixtures.forge_header_view(
            params, pool, slot=slot,
            epoch_nonce=ticked.state.epoch_nonce, prev_hash=prev,
            body_bytes=b"b%d" % len(hvs),
        )
        st = praos.update(params, hv, slot, ticked)
        hvs.append(hv)
        prev = (b"%032d" % len(hvs))[:32]
        slot += 1

    res = pbatch.validate_chain(
        params, lambda epoch: lview, st0, hvs
    )
    assert res.error is None and res.n_valid == len(hvs)
    assert res.state.evolving_nonce == st.evolving_nonce
    assert res.state.epoch_nonce == st.epoch_nonce
    assert res.state.candidate_nonce == st.candidate_nonce


def test_leader_threshold_bracket_sane():
    lo, hi = pbatch.leader_threshold_bracket(Fraction(1, 3), Fraction(1, 20))
    assert 0 < lo <= hi < pbatch.leader.LEADER_VALUE_MAX
    assert hi - lo <= 1 << 200  # tight bracket (width << 2^256)
    assert pbatch.leader_threshold_bracket(Fraction(0), Fraction(1, 20)) == (0, 0)


def test_staged_relayout_matches_pk_arrays(monkeypatch):
    """verify_praos_staged (the PRODUCTION dispatch marshalling) must
    hand verify_praos_tiles EXACTLY what the host-side pk_arrays built —
    column for column, dtype for dtype. Captures the tiles call's real
    arguments instead of re-implementing the relayout, so a swapped
    argument in the staged entry fails here."""
    import functools

    import numpy as np

    from ouroboros_consensus_tpu.ops.pk import kernels as K

    # this test pins the DRAFT-03 (80-byte proof) staged wiring; the
    # batch-compatible twin is test_split_dispatch_bc below
    monkeypatch.setenv("OCT_VRF_BATCH", "0")
    pools = [fixtures.make_pool(i, kes_depth=PARAMS.kes_depth)
             for i in range(3)]
    lview = fixtures.make_ledger_view(pools)
    hvs = make_chain(24, pools, lview=lview)
    pre = pbatch.host_prechecks(PARAMS, lview, hvs)
    staged = pbatch.stage(PARAMS, lview, b"\x07" * 32, hvs, pre.kes_evolution)
    assert not pbatch.batch_is_bc(staged)
    ref = pbatch.pk_arrays(staged)

    captured = {}

    def capture(*args, kes_depth):
        captured["args"] = args
        captured["kes_depth"] = kes_depth
        return None

    monkeypatch.setattr(K, "verify_praos_tiles", capture)
    ed, kes, vrf = staged.ed, staged.kes, staged.vrf
    K.verify_praos_staged(
        ed.pk, ed.r, ed.s, ed.hblocks, ed.hnblocks,
        kes.vk, kes.period, kes.r, kes.s, kes.vk_leaf, kes.siblings,
        kes.hblocks, kes.hnblocks,
        vrf.pk, vrf.gamma, vrf.c, vrf.s, vrf.alpha,
        staged.beta, staged.thr_lo, staged.thr_hi,
        kes_depth=PARAMS.kes_depth,
    )
    got = captured["args"]
    assert captured["kes_depth"] == PARAMS.kes_depth
    assert len(ref) == len(got) == 21
    for i, (a, b) in enumerate(zip(ref, got)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and a.dtype == b.dtype == np.int32, i
        assert (a == b).all(), i


def test_split_dispatch_threads_stages_correctly(monkeypatch):
    """verify_praos_split (the per-stage-jit production dispatch,
    VERDICT r3 item 2) must hand each STAGE exactly the columns the
    fused composition would: the real relayout jit runs, the crypto
    stages are capture stubs returning shaped dummies, and every
    captured argument is checked against pk_arrays — so a swapped
    argument in the split wiring fails here without a multi-minute
    XLA:CPU crypto compile."""
    import numpy as np
    from jax import numpy as jnp

    from ouroboros_consensus_tpu.ops.pk import kernels as K

    monkeypatch.setenv("OCT_VRF_BATCH", "0")  # draft-03 wiring pin
    pools = [fixtures.make_pool(i, kes_depth=PARAMS.kes_depth)
             for i in range(3)]
    lview = fixtures.make_ledger_view(pools)
    hvs = make_chain(8, pools, lview=lview)
    pre = pbatch.host_prechecks(PARAMS, lview, hvs)
    staged = pbatch.stage(PARAMS, lview, b"\x07" * 32, hvs, pre.kes_evolution)
    assert not pbatch.batch_is_bc(staged)
    ref = [np.asarray(a) for a in pbatch.pk_arrays(staged)]
    b = staged.beta.shape[0]
    depth = PARAMS.kes_depth

    captured = {}

    def stub(name, outs):
        def fn(*args):
            captured[name] = [np.asarray(a) for a in args]
            return tuple(jnp.zeros((*p, b), jnp.int32) for p in outs)
        return fn

    monkeypatch.setitem(K._SPLIT_JIT, "ed", stub("ed", [(1,), (80,)]))
    monkeypatch.setitem(
        K._SPLIT_JIT, ("kes", depth), stub("kes", [(1,), (80,)])
    )
    monkeypatch.setitem(K._SPLIT_JIT, "vrf", stub("vrf", [(1,), (400,)]))
    monkeypatch.setitem(
        K._SPLIT_JIT, "finish", stub("finish", [(5,), (32,), (32,)])
    )

    ed, kes, vrf = staged.ed, staged.kes, staged.vrf
    out = K.verify_praos_split(
        ed.pk, ed.r, ed.s, ed.hblocks, ed.hnblocks,
        kes.vk, kes.period, kes.r, kes.s, kes.vk_leaf, kes.siblings,
        kes.hblocks, kes.hnblocks,
        vrf.pk, vrf.gamma, vrf.c, vrf.s, vrf.alpha,
        staged.beta, staged.thr_lo, staged.thr_hi,
        kes_depth=depth,
    )
    assert len(out) == 3  # finish's (flags, eta, leader_value)

    # ref index map (pk_arrays order):
    # 0 ed_pk 1 ed_r 2 ed_s 3 ed_hb 4 ed_hnb 5 kes_vk 6 kes_per 7 kes_r
    # 8 kes_s 9 kes_leaf 10 kes_sib 11 kes_hb 12 kes_hnb 13 vrf_pk
    # 14 vrf_g 15 vrf_c 16 vrf_s 17 vrf_al 18 beta 19 tlo 20 thi
    def eq(got, want_ix):
        assert (got == ref[want_ix]).all(), want_ix

    g = captured["ed"]
    eq(g[0], 0); eq(g[1], 2); eq(g[2], 3); eq(g[3], 4)
    g = captured["kes"]
    eq(g[0], 5); eq(g[1], 6); eq(g[2], 8); eq(g[3], 9); eq(g[4], 10)
    eq(g[5], 11); eq(g[6], 12)
    g = captured["vrf"]
    eq(g[0], 13); eq(g[1], 14); eq(g[2], 15); eq(g[3], 16); eq(g[4], 17)
    g = captured["finish"]
    # finish(ed_ok, ed_pt, ed_r, kes_ok, kes_pt, kes_r, vrf_ok, vrf_pts,
    #        c, beta, thr_lo, thr_hi)
    eq(g[2], 1); eq(g[5], 7); eq(g[8], 15); eq(g[9], 18)
    eq(g[10], 19); eq(g[11], 20)
    assert g[0].shape == (1, b) and g[1].shape == (80, b)
    assert g[6].shape == (1, b) and g[7].shape == (400, b)
    # a generic window hands every stage the count of all its tiles
    for g in captured.values():
        assert g[-1].tolist() == [K.live_tiles(b)]


def test_split_dispatch_bc_threads_stages_correctly(monkeypatch):
    """The batch-compatible split wiring (relayout_bc -> ed/kes ->
    vrf_bc -> finish): announced u/v columns reach the vrf_bc stage, and
    the finish stage receives the DERIVED challenge (the vrf_bc stage's
    second output), not a staged column."""
    import numpy as np
    from jax import numpy as jnp

    from ouroboros_consensus_tpu.ops.pk import kernels as K

    pools = [fixtures.make_pool(i, kes_depth=PARAMS.kes_depth)
             for i in range(3)]
    lview = fixtures.make_ledger_view(pools)
    hvs = make_chain(8, pools, lview=lview)
    assert len(hvs[0].vrf_proof) == 128  # forge default is bc
    pre = pbatch.host_prechecks(PARAMS, lview, hvs)
    staged = pbatch.stage(PARAMS, lview, b"\x07" * 32, hvs, pre.kes_evolution)
    assert pbatch.batch_is_bc(staged)
    ref = [np.asarray(a) for a in pbatch.pk_arrays(staged)]
    b = staged.beta.shape[0]
    depth = PARAMS.kes_depth

    captured = {}

    def stub(name, outs):
        def fn(*args):
            captured[name] = [np.asarray(a) for a in args]
            return tuple(jnp.zeros((*p, b), jnp.int32) for p in outs)
        return fn

    monkeypatch.setitem(K._SPLIT_JIT, "ed", stub("ed", [(1,), (80,)]))
    monkeypatch.setitem(
        K._SPLIT_JIT, ("kes", depth), stub("kes", [(1,), (80,)])
    )
    monkeypatch.setitem(
        K._SPLIT_JIT, "vrf_bc", stub("vrf_bc", [(1,), (16,), (400,)])
    )
    monkeypatch.setitem(
        K._SPLIT_JIT, "finish", stub("finish", [(5,), (32,), (32,)])
    )

    ed, kes, vrf = staged.ed, staged.kes, staged.vrf
    out = K.verify_praos_split_bc(
        ed.pk, ed.r, ed.s, ed.hblocks, ed.hnblocks,
        kes.vk, kes.period, kes.r, kes.s, kes.vk_leaf, kes.siblings,
        kes.hblocks, kes.hnblocks,
        vrf.pk, vrf.gamma, vrf.u, vrf.v, vrf.s, vrf.alpha,
        staged.beta, staged.thr_lo, staged.thr_hi,
        kes_depth=depth,
    )
    assert len(out) == 3

    # bc pk_arrays index map: 0-12 as draft-03, then 13 vrf_pk 14 vrf_g
    # 15 vrf_u 16 vrf_v 17 vrf_s 18 vrf_al 19 beta 20 tlo 21 thi
    def eq(got, want_ix):
        assert (got == ref[want_ix]).all(), want_ix

    g = captured["vrf_bc"]
    eq(g[0], 13); eq(g[1], 14); eq(g[2], 15); eq(g[3], 16); eq(g[4], 17)
    eq(g[5], 18)
    g = captured["finish"]
    eq(g[2], 1); eq(g[5], 7); eq(g[9], 19); eq(g[10], 20); eq(g[11], 21)
    # the challenge column handed to finish is the vrf_bc stage's c16
    # output (a stub zero array here), NOT any staged column
    assert g[8].shape == (16, b) and (g[8] == 0).all()
    assert g[0].shape == (1, b) and g[1].shape == (80, b)
    assert g[6].shape == (1, b) and g[7].shape == (400, b)
    # a generic window hands every stage the count of all its tiles
    for g in captured.values():
        assert g[-1].tolist() == [K.live_tiles(b)]


@pytest.mark.slow
def test_validate_chain_cross_epoch_pipelining(pools, lview):
    # THREE epoch boundaries with several small batches per epoch and
    # pipeline depth 3: the next epoch's first windows must stage with
    # the LOOKAHEAD nonce (combine(candidate, last_epoch_block_nonce)
    # once the fold passes the freeze slot) while the current epoch's
    # tail is still in flight — the retire-time tick asserts the staged
    # nonce, and the final state must equal the per-header fold.
    params = PARAMS
    hvs = []
    prev = None
    st0 = praos.PraosState(epoch_nonce=b"\x07" * 32)

    st = st0
    slot = 2
    while len(hvs) < 70:
        ticked = praos.tick(params, lview, slot, st)
        pool = fixtures.find_leader(
            params, pools, lview, slot, ticked.state.epoch_nonce
        )
        if pool is None:
            slot += 1
            continue
        hv = fixtures.forge_header_view(
            params, pool, slot=slot,
            epoch_nonce=ticked.state.epoch_nonce, prev_hash=prev,
            body_bytes=b"c%d" % len(hvs),
        )
        st = praos.update(params, hv, slot, ticked)
        hvs.append(hv)
        prev = (b"%032d" % len(hvs))[:32]
        slot += 1
    assert params.epoch_of(hvs[-1].slot) >= 3  # crossed >= 3 boundaries

    res = pbatch.validate_chain(
        params, lambda epoch: lview, st0, hvs, max_batch=4,
        pipeline_depth=3,
    )
    assert res.error is None and res.n_valid == len(hvs)
    assert res.state == st


@pytest.mark.parametrize("asked,impl,want", [
    (None, "pk", False),  # the chip's default: per-lane stage kernels
    (None, "xla", True),  # the twin's default: the aggregate, as before
    ("1", "pk", True), ("0", "pk", False),  # an explicit lever decides
    ("1", "xla", True), ("0", "xla", False),
])
def test_agg_default_follows_the_implementation(monkeypatch, asked, impl,
                                                want):
    if asked is None:
        monkeypatch.delenv("OCT_VRF_AGG", raising=False)
    else:
        monkeypatch.setenv("OCT_VRF_AGG", asked)
    with pbatch.recovery_overrides(impl=impl):
        assert pbatch._agg_enabled() is want
        # the recovery rungs still pin either path over the default
        with pbatch.recovery_overrides(agg=not want, impl=impl):
            assert pbatch._agg_enabled() is (not want)


def test_one_lane_shape_per_replay_on_pk_only(monkeypatch):
    """On `pk` every window pads to the caller's max_batch bucket; on
    the XLA twin windows keep their own buckets."""
    monkeypatch.delenv("OCT_DEVICE_IMPL", raising=False)
    with pbatch.recovery_overrides(impl="pk"):
        assert pbatch.window_lanes(8192) == 8192
        assert pbatch.window_lanes(5000) == pbatch.bucket_size(5000) == 6144
    with pbatch.recovery_overrides(impl="xla"):
        assert pbatch.window_lanes(8192) is None
    pools = [fixtures.make_pool(i, kes_depth=3) for i in range(2)]
    lview = fixtures.make_ledger_view(pools)
    hvs = make_chain(5, pools, lview=lview)
    own = pbatch.prepare_window(PARAMS, lview, b"\x07" * 32, hvs)
    fixed = pbatch.prepare_window(PARAMS, lview, b"\x07" * 32, hvs, 64)
    assert (own.b, own.lanes) == (5, 8) and (fixed.b, fixed.lanes) == (5, 64)


@pytest.mark.parametrize("placed", [True, False], ids=["env-set", "env-unset"])
def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path, placed):
    """With JAX_COMPILATION_CACHE_DIR set the function sets no directory
    in code; without it, the one fixed in-tree path."""
    import jax

    from ouroboros_consensus_tpu import compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    if placed:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.configure()
    dirs = [v for k, v in updates if k.endswith("_cache_dir")]
    if placed:
        assert got == str(tmp_path) and dirs == []
    else:
        assert got == compile_cache.DEFAULT_DIR and dirs == [got]
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert got == os.path.join(repo, ".jax_cache")
    assert ("jax_persistent_cache_min_compile_time_secs", 1.0) in updates
