"""Packed staging + on-device verdict reduction (the PR-2 "cut the
wire" path).

Three layers:
  1. the packed round-trip property — packed u8 staging -> device unpack
     must be BYTE-IDENTICAL to the host `stage` SoA columns for all
     three column families (ed / kes / vrf), across randomized chains,
     nonces and KES depths; and the limb-first decomposition must equal
     `pk_arrays` of the staged batch;
  2. the D2H reduction — verdict bitmask packing and the eta column
     (`verdict_pack`, what every dispatch runs), and the retired
     on-device nonce scan (`verdict_reduce`, reference only) against
     the host `nonces.combine` fold, including neutral carries and
     bucket-pad masking;
  3. epilogue equivalence — windows with invalid lanes at the edges
     (first lane, last lane, epoch-tail boundary) produce identical
     `BatchResult` through the packed-verdict fast path and the
     per-lane slow path; and the full pipelined `validate_chain` with
     packed staging agrees with the sequential fold (crypto stubbed so
     the default tier never pays a fused XLA:CPU crypto compile — the
     real-crypto end-to-end runs in the slow tier via
     test_tools.test_device_revalidation_matches_host).
"""

from dataclasses import replace
from fractions import Fraction

import numpy as np
import pytest

import jax
from jax import numpy as jnp

from ouroboros_consensus_tpu.block.forge import forge_block
from ouroboros_consensus_tpu.ops import blake2b
from ouroboros_consensus_tpu.protocol import batch as pbatch
from ouroboros_consensus_tpu.protocol import nonces, praos
from ouroboros_consensus_tpu.protocol.views import ViewColumns
from ouroboros_consensus_tpu.testing import fixtures

_COLS_HEAD = [
    "ed.pk", "ed.r", "ed.s", "ed.hblocks", "ed.hnblocks",
    "kes.vk", "kes.period", "kes.r", "kes.s", "kes.vk_leaf",
    "kes.siblings", "kes.hblocks", "kes.hnblocks",
]
_COLS_TAIL = ["beta", "thr_lo", "thr_hi"]


def cols_of(staged):
    """Column names in flatten_batch order — the vrf block depends on
    the staged proof format (draft-03: c; batch-compatible: u, v)."""
    vrf = ["vrf." + f for f in type(staged.vrf)._fields]
    return _COLS_HEAD + vrf + _COLS_TAIL


def make_params(kes_depth=3, epoch_length=100_000):
    return praos.PraosParams(
        slots_per_kes_period=100,
        max_kes_evolutions=62,
        security_param=4,
        active_slot_coeff=Fraction(1, 2),
        epoch_length=epoch_length,
        kes_depth=kes_depth,
    )


def real_chain(params, pools, n, first_slot=100, first_block=30,
               epoch_nonce=b"\x07" * 32, counter=0):
    """Real-codec headers (block/praos_block CBOR bodies): the packed
    staging extracts fields from these bodies. Slot/block_no ranges are
    chosen inside one CBOR width class so the window stays uniform."""
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


@pytest.fixture(scope="module")
def pools():
    return [fixtures.make_pool(i, kes_depth=3) for i in range(2)]


@pytest.fixture(scope="module")
def lview(pools):
    return fixtures.make_ledger_view(pools)


# ---------------------------------------------------------------------------
# 1. the packed round-trip property
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "nonce,depth,first_slot",
    [
        (b"\x07" * 32, 3, 100),
        (None, 3, 300),  # neutral epoch nonce: alpha has no nonce tail
        # different depth + wider (4-byte CBOR) slots; 68200 = KES
        # period 682 = 11*62, so the forged evolution index stays 0
        (b"\x55" * 32, 2, 68_200),
    ],
)
def test_packed_unpack_roundtrips_all_families(nonce, depth, first_slot):
    """Property: for any qualifying window, the device unpack of the
    packed columns equals the host-staged SoA columns byte for byte —
    every ed / kes / vrf column, plus beta and the threshold rows."""
    params = make_params(kes_depth=depth)
    pls = [fixtures.make_pool(10 + i, kes_depth=depth) for i in range(2)]
    lv = fixtures.make_ledger_view(pls)
    hvs = real_chain(params, pls, 9, first_slot=first_slot,
                     epoch_nonce=nonce)
    pre = pbatch.host_prechecks(params, lv, hvs)
    res = pbatch.stage_packed(params, lv, nonce, hvs)
    assert res is not None, "real-codec window must qualify for packing"
    layout, parr = res
    staged = pbatch.stage(params, lv, nonce, hvs, pre.kes_evolution)
    ref = pbatch.flatten_batch(staged)
    got = jax.jit(lambda *a: pbatch.unpack_packed(layout, *a))(*parr)
    # batch-compatible proofs (the forge default) stage 22 columns
    assert len(ref) == len(got) == (22 if layout.vrf_proof_len == 128 else 21)
    for name, a, b in zip(cols_of(staged), ref, got):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert (a == b).all(), name


def test_packed_limb_first_matches_pk_arrays(pools, lview):
    """The packed `unpack` STAGE (unpack + staged_to_limb_first in one
    jit — ops/pk/kernels._mk_packed_unpack) must hand the crypto stages
    exactly what the host-side pk_arrays marshalling builds."""
    from ouroboros_consensus_tpu.ops.pk import kernels as K

    params = make_params()
    nonce = b"\x07" * 32
    hvs = real_chain(params, pools, 8)
    pre = pbatch.host_prechecks(params, lview, hvs)
    layout, parr = pbatch.stage_packed(params, lview, nonce, hvs)
    staged = pbatch.stage(params, lview, nonce, hvs, pre.kes_evolution)
    ref = pbatch.pk_arrays(staged)
    got = jax.jit(K._mk_packed_unpack(layout))(*parr)
    assert len(ref) == len(got) == 22  # bc-staged: u, v replace c
    for i, (a, b) in enumerate(zip(ref, got)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and a.dtype == b.dtype == np.int32, i
        assert (a == b).all(), i


def test_packed_unpack_pads_kes_hash_column_with_headroom(pools, lview,
                                                          monkeypatch):
    """One `kes` program per replay on the chip: the packed unpack
    stage hands the kes stage the block count a body up to 64 bytes
    longer would need, so a chain's first header (no prev-hash: 410
    bytes where the rest are 443-449) shares the others' program. The
    spare block is zero and each lane keeps its own count."""
    from ouroboros_consensus_tpu.ops.pk import kernels as K

    assert K.kes_hash_blocks(410) == K.kes_hash_blocks(449) == 5
    assert K.kes_hash_blocks(304) == 4  # the pinned packed_unpack graph
    params = make_params()
    nonce = b"\x07" * 32
    hvs = real_chain(params, pools, 8)
    pre = pbatch.host_prechecks(params, lview, hvs)
    layout, parr = pbatch.stage_packed(params, lview, nonce, hvs)
    ref = pbatch.pk_arrays(
        pbatch.stage(params, lview, nonce, hvs, pre.kes_evolution)
    )
    k = ref[11].shape[0]
    monkeypatch.setattr(K, "kes_hash_blocks", lambda body_len: k + 1)
    got = [np.asarray(x) for x in
           jax.jit(K._mk_packed_unpack(layout))(*parr)]
    assert got[11].shape == (k + 1, *ref[11].shape[1:])
    assert (got[11][:k] == ref[11]).all() and not got[11][k:].any()
    assert (got[12] == ref[12]).all() and (got[12] == k).all()
    for i in (j for j in range(22) if j != 11):
        assert (got[i] == np.asarray(ref[i])).all(), i


def test_packed_h2d_bytes_shrink(pools, lview):
    """The wire contract: the packed columns must ship at most HALF the
    staged bytes per lane of the generic SoA path on a real window."""
    params = make_params(kes_depth=7)
    pls = [fixtures.make_pool(20 + i, kes_depth=7) for i in range(2)]
    lv = fixtures.make_ledger_view(pls)
    hvs = real_chain(params, pls, 16)
    pre = pbatch.host_prechecks(params, lv, hvs)
    _, parr = pbatch.stage_packed(params, lv, b"\x07" * 32, hvs)
    staged = pbatch.stage(params, lv, b"\x07" * 32, hvs, pre.kes_evolution)
    packed_b = sum(np.asarray(c).nbytes for c in parr)
    staged_b = sum(np.asarray(c).nbytes for c in pbatch.flatten_batch(staged))
    assert packed_b * 2 <= staged_b, (packed_b, staged_b)


def test_stage_packed_fallback_gates(pools, lview):
    params = make_params()
    nonce = b"\x07" * 32
    # mixed body lengths (genesis prev=None header) stage packed: one
    # body layout each, every lane at its own
    hvs = real_chain(params, pools, 4)
    blk0 = forge_block(params, pools[0], slot=99, block_no=29,
                       prev_hash=None, epoch_nonce=nonce)
    layout, parr = pbatch.stage_packed(
        params, lview, nonce, [blk0.header.to_view()] + hvs)
    # the most common length first: its pass takes most lanes whole
    assert parr.body_layout.tolist() == [1, 0, 0, 0, 0]
    assert layout.body_len == parr.body_tab[:2, 0].max()
    # the rows past the window's layouts replicate its first
    assert (parr.body_tab[2:] == parr.body_tab[0]).all()
    # synthetic views whose signed bytes do not embed the fields
    fv = [
        fixtures.forge_header_view(params, pools[0], slot=s,
                                   epoch_nonce=nonce, prev_hash=b"x" * 32,
                                   body_bytes=b"body-%d" % s)
        for s in range(1, 5)
    ]
    assert pbatch.stage_packed(params, lview, nonce, fv) is None
    # out-of-range integers -> generic fallback
    big = [replace(hvs[0], slot=2**31)] + hvs[1:]
    assert pbatch.stage_packed(params, lview, nonce, big) is None
    # empty window
    assert pbatch.stage_packed(params, lview, nonce, []) is None


def test_kes_tail_table_dedupes(pools, lview):
    """Lanes sharing a (pool, KES period) share one Merkle-tail row —
    the column that used to cost 32 + depth*32 bytes per lane."""
    params = make_params()
    hvs = real_chain(params, pools, 12)
    _, parr = pbatch.stage_packed(params, lview, b"\x07" * 32, hvs)
    n_rows = len({hv.kes_sig[64:] for hv in hvs})
    assert n_rows <= 2  # 2 pools, one period each
    assert parr.kes_tail_idx.max() == n_rows - 1
    # gather reproduces every lane's tail
    for i, hv in enumerate(hvs):
        row = parr.kes_tail_tab[parr.kes_tail_idx[i]]
        assert row.tobytes() == hv.kes_sig[64:]


# -- windows of several body layouts ---------------------------------------

# every CBOR width step a window meets, from genesis: (slot, block_no,
# body bytes). The first header has no previous hash; block numbers cross
# 24 and 256; slots cross 24, 256 and 65,536; bodies straddle 256 and
# 65,536 bytes, so the body size's own width alternates
_RAGGED = [
    (0, 0, 0), (7, 1, 300), (14, 2, 10), (21, 3, 70_000),
    (22, 22, 0), (23, 23, 300), (24, 24, 70_000), (25, 25, 10),
    (254, 254, 300), (255, 255, 0), (256, 256, 70_000), (257, 257, 10),
    (65_534, 258, 70_000), (65_535, 259, 300), (65_536, 260, 0),
    (65_537, 261, 300),
]


def ragged_params(kes_depth=3):
    """f = 1 (every pool leads every slot it forges) and KES periods of
    10,000 slots, so that slot 65,537 is evolution 6 of a depth-3 key."""
    return replace(make_params(kes_depth=kes_depth),
                   active_slot_coeff=Fraction(1),
                   slots_per_kes_period=10_000)


def ragged_chain(params, pools, epoch_nonce=b"\x07" * 32):
    hvs, prev = [], None
    for i, (slot, block_no, size) in enumerate(_RAGGED):
        blk = forge_block(
            params, pools[i % len(pools)], slot=slot, block_no=block_no,
            prev_hash=prev, epoch_nonce=epoch_nonce,
            txs=(bytes([i]) * size,) if size else (),
        )
        hvs.append(blk.header.to_view())
        prev = blk.header.hash_
    return hvs


@pytest.fixture(scope="module")
def ragged():
    params = ragged_params()
    pls = [fixtures.make_pool(40 + i, kes_depth=3) for i in range(2)]
    lv = fixtures.make_ledger_view(pls)
    return params, pls, lv, ragged_chain(params, pls)


@pytest.mark.parametrize("lo,hi", [(0, 16), (4, 12), (11, 16)])
def test_packed_unpack_roundtrips_ragged_windows(ragged, lo, hi):
    """The packed round trip over windows of several body layouts: each
    lane's fields come out of its own layout's offsets, its KES message
    is padded at its own length, and the whole equals the host staging
    (per view, and columnar) byte for byte, limb-first too."""
    from ouroboros_consensus_tpu.ops.pk import kernels as K

    params, _, lv, hvs = ragged
    hvs = hvs[lo:hi]
    nonce = b"\x07" * 32
    assert len({len(hv.signed_bytes) for hv in hvs}) > 1
    layout, parr = pbatch.stage_packed(params, lv, nonce, hvs)
    assert parr.body_layout.max() > 0
    assert layout.body_len == max(len(hv.signed_bytes) for hv in hvs)
    for i, hv in enumerate(hvs):
        assert parr.body_tab[parr.body_layout[i], 0] == len(hv.signed_bytes)
    pre = pbatch.host_prechecks(params, lv, hvs)
    staged = pbatch.stage(params, lv, nonce, hvs, pre.kes_evolution)
    vc = ViewColumns.from_views(hvs)
    cstaged = pbatch.stage_columns(
        params, lv, nonce, vc, pre.kes_evolution,
        pbatch.host_prechecks_columns(params, lv, vc))
    got = jax.jit(lambda *a: pbatch.unpack_packed(layout, *a))(*parr)
    ref = pbatch.flatten_batch(staged)
    for name, a, c, b in zip(cols_of(staged), ref,
                             pbatch.flatten_batch(cstaged), got):
        a, b, c = np.asarray(a), np.asarray(b), np.asarray(c)
        assert a.shape == b.shape == c.shape and a.dtype == b.dtype, name
        assert (a == b).all() and (a == c).all(), name
    limb = jax.jit(K._mk_packed_unpack(layout))(*parr)
    for i, (a, b) in enumerate(zip(pbatch.pk_arrays(staged), limb)):
        a, b = np.asarray(a), np.asarray(b)
        if i == 11:  # the kes hash column carries its headroom block
            assert (b[: a.shape[0]] == a).all() and not b[a.shape[0]:].any()
        else:
            assert (a == b).all(), i



def test_one_unpack_program_whatever_the_layouts(ragged):
    """The body layouts ride the wire: two windows of one widest body
    and proof format get ONE layout descriptor (the jit key and the
    stored program's name) whatever layouts each holds."""
    from ouroboros_consensus_tpu.ops.pk import kernels as K

    params, _, lv, hvs = ragged
    nonce = b"\x07" * 32
    la, pa = pbatch.stage_packed(params, lv, nonce, hvs)
    lb, pb = pbatch.stage_packed(params, lv, nonce, hvs[1:])
    assert pa.body_layout.max() > pb.body_layout.max()
    assert la == lb and K.packed_unpack_name(la) == K.packed_unpack_name(lb)


@pytest.mark.parametrize("at,ok", [(127, True), (128, False)])
def test_body_layouts_bound_a_fields_spread(at, ok):
    """`unpack` shifts a lane's field by under `_MAX_BODY_SHIFT` bytes
    from the window's least offset: a window whose layouts spread a
    field further stages generic."""
    field = np.arange(1, 33, dtype=np.uint8)
    body = np.zeros((2, 200), np.uint8)
    body[0, :32] = field
    body[1, at:at + 32] = field
    lens = np.array([200, 200])
    got = pbatch._body_layouts(body, lens, (np.stack([field, field]),))
    if ok:
        tab, lane = got
        assert tab[:, :2].tolist() == [[200, 0], [200, at]]
        assert lane.tolist() == [0, 1]
    else:
        assert got is None and pbatch._LAST_DECLINE == "body-layouts"

def _words_to_bytes(words: np.ndarray) -> bytes:
    """[NB, 16, 2] uint32 SHA-512 words -> the padded message bytes."""
    return np.asarray(words, ">u4").tobytes()


def _padded_message(blocks, nblocks: int) -> bytes:
    """The message of a lane's padded SHA-512 blocks (its bit length
    sits in the last 16 bytes of its last block)."""
    raw = _words_to_bytes(blocks[:nblocks])
    return raw[: int.from_bytes(raw[-16:], "big") // 8]


def exact_host_verify(*cols):
    """`verify_praos_any` with the curve arithmetic left to the native
    verifier (`jax.pure_callback`): EXACT verdicts from the staged
    columns the packed `unpack` built — the OCert and KES messages read
    back out of their padded hash blocks — and the real eta / leader
    value extensions of `stubs.stub_verify`."""
    from ouroboros_consensus_tpu import native_loader as nl
    from ouroboros_consensus_tpu.testing import stubs

    bc = len(cols) == 22
    proof_cols = cols[14:18] if bc else cols[14:17]
    alpha, beta = cols[-4], cols[-3]

    def host(issuer, ed_r, ed_s, ed_hb, ed_hnb, vk_hot, period, kes_r,
             kes_s, vk_leaf, sib, kes_hb, kes_hnb, vrf_vk, alpha, beta,
             *proof):
        a = [np.asarray(x) for x in (issuer, ed_r, ed_s, ed_hb, ed_hnb,
                                     vk_hot, period, kes_r, kes_s, vk_leaf,
                                     sib, kes_hb, kes_hnb, vrf_vk, alpha,
                                     beta)]
        proof = [np.asarray(p) for p in proof]
        u8 = [x.astype(np.uint8) if x.dtype != np.uint32 else x for x in a]
        (issuer, ed_r, ed_s, ed_hb, ed_hnb, vk_hot, period, kes_r, kes_s,
         vk_leaf, sib, kes_hb, kes_hnb, vrf_vk, alpha, beta) = u8
        b = issuer.shape[0]
        ok = np.zeros((3, b), np.bool_)
        depth = sib.shape[1]
        for i in range(b):
            ok[0, i] = nl.native_ed25519_verify(
                issuer[i].tobytes(), ed_r[i].tobytes() + ed_s[i].tobytes(),
                _padded_message(ed_hb[i], int(ed_hnb[i]))[64:])
            ok[1, i] = nl.native_kes_verify(
                vk_hot[i].tobytes(), depth, int(period[i]),
                _padded_message(kes_hb[i], int(kes_hnb[i]))[64:],
                b"".join(x[i].tobytes() for x in (kes_r, kes_s, vk_leaf))
                + sib[i].astype(np.uint8).tobytes())
            out = nl.native_ecvrf_verify(
                vrf_vk[i].tobytes(),
                b"".join(p[i].astype(np.uint8).tobytes() for p in proof),
                alpha[i].tobytes())
            ok[2, i] = out is not None and out == beta[i].tobytes()
        return ok

    b = jnp.asarray(cols[0]).shape[0]
    ok = jax.pure_callback(
        host, jax.ShapeDtypeStruct((3, b), jnp.bool_),
        *cols[:14], alpha, beta, *proof_cols)
    v = stubs.stub_verify(*cols)
    lv = v.leader_value
    thr_lo = jnp.asarray(cols[-2]).astype(jnp.int32)
    thr_hi = jnp.asarray(cols[-1]).astype(jnp.int32)
    win = pbatch._lt_be(lv, thr_lo)
    return pbatch.Verdicts(ok[0], ok[1], ok[2], win,
                           ~win & pbatch._lt_be(lv, thr_hi), v.eta, lv)


@pytest.fixture
def exact_packed(monkeypatch):
    """The per-lane XLA programs, packed and staged, with exact host
    verdicts (their programs fenced off the process-wide jit table)."""
    before = set(pbatch._JIT)
    monkeypatch.setenv("OCT_VRF_AGG", "0")
    for name in ("verify_praos", "verify_praos_bc", "verify_praos_any"):
        monkeypatch.setattr(pbatch, name, exact_host_verify)
    fused = jax.jit(exact_host_verify)
    monkeypatch.setattr(pbatch, "_jitted_verify", lambda bc=False: fused)
    yield
    for k in set(pbatch._JIT) - before:
        del pbatch._JIT[k]


def _flip(b: bytes, i: int) -> bytes:
    return b[:i] + bytes([b[i] ^ 1]) + b[i + 1:]


def _corrupt(params, pools, hvs, i, what):
    """Header i wrong as an attacker would send it: the OCert signature
    (in the view and the body it is signed in), the KES signature, or
    the VRF proof (the body signed again, so the proof is what is
    wrong)."""
    from ouroboros_consensus_tpu.ops.host import kes as host_kes
    from ouroboros_consensus_tpu.protocol.views import OCert

    hv = hvs[i]
    if what == "kes-signature":
        bad = replace(hv, kes_sig=_flip(hv.kes_sig, 32))
    elif what == "ocert-signature":
        sigma = _flip(hv.ocert.sigma, 32)
        o = hv.signed_bytes.index(hv.ocert.sigma)
        bad = replace(hv, ocert=OCert(hv.ocert.vk_hot, hv.ocert.counter,
                                      hv.ocert.kes_period, sigma),
                      signed_bytes=hv.signed_bytes[:o] + sigma
                      + hv.signed_bytes[o + 64:])
    else:
        proof = _flip(hv.vrf_proof, len(hv.vrf_proof) - 32)
        o = hv.signed_bytes.index(hv.vrf_proof)
        body = (hv.signed_bytes[:o] + proof
                + hv.signed_bytes[o + len(proof):])
        pool = next(p for p in pools if p.kes_vk == hv.ocert.vk_hot)
        t = params.kes_period_of(hv.slot) - hv.ocert.kes_period
        bad = replace(hv, vrf_proof=proof, signed_bytes=body,
                      kes_sig=host_kes.sign(pool.kes_seed, pool.kes_depth,
                                            t, body))
    return [*hvs[:i], bad, *hvs[i + 1:]]


@pytest.mark.parametrize("what,at", [
    (None, None), ("ocert-signature", 3), ("kes-signature", 12),
    ("vrf-proof", 9),
])
def test_ragged_window_validates_as_the_fold(ragged, exact_packed, what,
                                             at):
    """A window from genesis holding every body layout of `_RAGGED`
    goes through the packed path (the XLA twin's program, exact
    verdicts) to the sequential reference's valid count, first error and
    final state; one wrong lane of each kind is refused at its own
    index."""
    from ouroboros_consensus_tpu.obs import recovery

    params, pls, lv, hvs = ragged
    if what is not None:
        hvs = _corrupt(params, pls, hvs, at, what)
    st0 = praos.PraosState(epoch_nonce=b"\x07" * 32)
    lt = []
    pbatch.set_batch_tracer(lt.append)
    try:
        got = pbatch.validate_chain(params, lambda _e: lv, st0,
                                    ViewColumns.from_views(hvs),
                                    max_batch=16)
    finally:
        pbatch.set_batch_tracer(None)
    want = recovery.host_reference_fold(
        params, praos.tick(params, lv, hvs[0].slot, st0), hvs)
    assert got.n_valid == want.n_valid == (len(hvs) if at is None else at)
    assert repr(got.error) == repr(want.error)
    assert got.state == want.state
    from ouroboros_consensus_tpu.utils.trace import WindowSpan

    (span,) = [e for e in lt if isinstance(e, WindowSpan)]
    assert span.outcome == "packed" and span.layouts > 4


@pytest.mark.parametrize(
    "rung", ("retry", "stage-split", "xla-twin", "host-reference"))
def test_recovery_rung_revalidates_a_ragged_window(ragged, exact_packed,
                                                   rung):
    """Each rung of the device ladder re-validates a window of several
    body layouts from its views to the same verdict."""
    from ouroboros_consensus_tpu.obs import recovery

    params, pls, lv, hvs = ragged
    hvs = _corrupt(params, pls, hvs, 10, "kes-signature")
    st0 = praos.PraosState(epoch_nonce=b"\x07" * 32)
    ticked = praos.tick(params, lv, hvs[0].slot, st0)
    want = recovery.host_reference_fold(params, ticked, hvs)
    got = recovery.supervisor()._run_rung(
        rung, params, ticked, ViewColumns.from_views(hvs), "device", None)
    assert (got.n_valid, repr(got.error), got.state) == (
        want.n_valid, repr(want.error), want.state) and got.n_valid == 10


# ---------------------------------------------------------------------------
# 2. the D2H reduction: bitmasks + eta column; the reference scan
# ---------------------------------------------------------------------------


def test_pack_bits_roundtrip():
    rng = np.random.default_rng(7)
    for b in (1, 8, 31, 32, 33, 64, 100):
        bits = rng.integers(0, 2, b).astype(bool)
        words = np.asarray(jax.jit(pbatch._pack_bits_u32)(jnp.asarray(bits)))
        assert (pbatch._mask_bits(words, b) == bits).all(), b


def _state_carry(state):
    """The reference scan's carry-in from a PraosState."""

    def arr(n):
        return np.frombuffer(n or bytes(32), np.uint8).astype(np.int32)

    return (
        arr(state.evolving_nonce), np.bool_(state.evolving_nonce is not None),
        arr(state.candidate_nonce), np.bool_(state.candidate_nonce is not None),
    )


@pytest.mark.parametrize("seed_state", ["set", "neutral"])
def test_verdict_reduce_scan_matches_host_fold(seed_state):
    """The reference comparison: the retired on-device scan
    (`verdict_reduce`, which no dispatch path reaches) equals the host
    fold, and `verdict_pack`, which every packed dispatch runs, ships
    the same masks and the eta column that fold consumes."""
    rng = np.random.default_rng(3)
    b, n_real = 11, 9
    flags = np.ones((5, b), np.int32)
    flags[4] = 0
    flags[2, 9:] = 0  # pad lanes may carry garbage verdicts
    etas = rng.integers(0, 256, (b, 32)).astype(np.int32)
    within = np.ones(b, np.uint8)
    within[6:] = 0
    st = (
        praos.PraosState(evolving_nonce=b"\x01" * 32)
        if seed_state == "set" else praos.PraosState()
    )
    red = jax.jit(pbatch.verdict_reduce)(
        flags, etas, within, np.int32(n_real), *_state_carry(st)
    )
    masks, ev, evs, cand, cands = (np.asarray(x) for x in red)
    evolving, candidate = st.evolving_nonce, st.candidate_nonce
    for i in range(n_real):
        evolving = nonces.combine(evolving, etas[i].astype(np.uint8).tobytes())
        if within[i]:
            candidate = evolving
    assert bool(evs) == (evolving is not None)
    assert ev.astype(np.uint8).tobytes() == evolving
    assert bool(cands) == (candidate is not None)
    if candidate is not None:
        assert cand.astype(np.uint8).tobytes() == candidate
    # masks reflect the raw flags, pad lanes included
    for r in range(5):
        assert (pbatch._mask_bits(masks[r], b) == (flags[r] != 0)).all(), r
    # what the dispatch paths run ships the uint8 eta column instead
    m2, eta_u8 = jax.jit(pbatch.verdict_pack)(flags, etas)
    assert np.asarray(eta_u8).dtype == np.uint8
    assert (np.asarray(eta_u8) == etas.astype(np.uint8)).all()
    assert (np.asarray(m2) == masks).all()


# ---------------------------------------------------------------------------
# 3. epilogue equivalence: packed fast path vs per-lane slow path
# ---------------------------------------------------------------------------


def _fab_verdicts(hvs, bad=(), ambiguous=()):
    """Fabricated device outputs: all lanes valid except `bad` (KES bit
    cleared) / `ambiguous` (leader undecided). Etas are arbitrary —
    equivalence is about identical FOLDS, not crypto."""
    b = len(hvs)
    rng = np.random.default_rng(b)
    ok = np.ones(b, bool)
    kes_ok = ok.copy()
    for i in bad:
        kes_ok[i] = False
    amb = np.zeros(b, bool)
    for i in ambiguous:
        amb[i] = True
    eta = rng.integers(0, 256, (b, 32)).astype(np.uint8)
    lv = np.zeros((b, 32), np.uint8)  # certainly-below any threshold
    return pbatch.Verdicts(ok, kes_ok.copy(), ok.copy(), ok.copy(), amb,
                           eta, lv)


def _as_packed(v, hvs):
    """Wrap fabricated Verdicts as the PackedVerdicts materialize would
    produce (numpy mask packing + the uint8 eta column)."""
    b = len(hvs)
    rows = [v.ok_ocert_sig, v.ok_kes_sig, v.ok_vrf, v.ok_leader,
            v.leader_ambiguous]
    w = -(-b // 32)
    masks = np.zeros((5, w), np.uint32)
    for r, bits in enumerate(rows):
        for i, x in enumerate(np.asarray(bits)):
            if x:
                masks[r, i // 32] |= np.uint32(1 << (i % 32))
    flags = np.stack([np.asarray(r).astype(np.int32) for r in rows])
    return pbatch.PackedVerdicts(
        masks, b, "xla", np.asarray(v.eta).astype(np.uint8),
        (flags, np.asarray(v.eta).astype(np.int32),
         np.asarray(v.leader_value).astype(np.int32)),
    )


def _results_equal(a, b):
    assert a.n_valid == b.n_valid
    assert (a.error is None) == (b.error is None)
    if a.error is not None:
        assert type(a.error) is type(b.error)
        assert vars(a.error) == vars(b.error)
    assert a.state == b.state


@pytest.mark.parametrize("window", ["views", "columns"])
@pytest.mark.parametrize("bad_at", ["none", "first", "last", "tail-edge"])
def test_epilogue_packed_fast_equals_slow(pools, lview, bad_at, window):
    """Satellite: invalid lanes at window edges (first lane, last lane,
    epoch-tail boundary) give identical BatchResult.error and nonce
    state through the packed fast path and the per-lane slow path,
    whether the window is a list of views (`_epilogue_packed_fast`) or
    a ViewColumns (`_epilogue_columns_fast`): one host fold behind
    both."""
    params = make_params(epoch_length=160)
    nonce = b"\x07" * 32
    if bad_at == "tail-edge":
        # last lane sits at the epoch tail: slots run up to the final
        # slot of epoch 0 (epoch_length 160, first_slot 140 + 19 = 159)
        hvs = real_chain(params, pools, 20, first_slot=140)
    else:
        hvs = real_chain(params, pools, 20)
    bad = {"none": (), "first": (0,), "last": (len(hvs) - 1,),
           "tail-edge": (len(hvs) - 1,)}[bad_at]
    v = _fab_verdicts(hvs, bad=bad)
    st = praos.PraosState(epoch_nonce=nonce, evolving_nonce=b"\x02" * 32)
    ticked = praos.TickedPraosState(st, lview)
    whvs = hvs
    if window == "columns":
        whvs = ViewColumns.from_views(hvs)
        assert whvs is not None
    pre = pbatch.host_prechecks(params, lview, whvs)
    pv = _as_packed(v, hvs)
    res_packed = pbatch._epilogue(params, ticked, whvs, pre, pv)
    res_slow = pbatch._epilogue(
        params, ticked, hvs, pbatch.host_prechecks(params, lview, hvs), v
    )
    _results_equal(res_packed, res_slow)
    if bad_at == "none":
        # the all-clean window must have taken the fast path (the slow
        # Verdicts were never materialized from the handles)
        assert pv._full is None
    else:
        assert isinstance(res_packed.error, praos.InvalidKesSignatureOCERT)


def test_epilogue_counter_gate_routes_to_slow_path(pools, lview):
    """A counter regression is only detectable by the stateful host
    gate: the packed mask is all-clean, yet the fast path must decline
    and the slow path must produce the exact reference error."""
    params = make_params()
    nonce = b"\x07" * 32
    hvs = real_chain(params, pools, 6)
    # pool 1 appears at lanes 1 and 3: counter 1 then a REGRESSION to 0
    # (the view's ocert is edited without re-signing — fine here, the
    # fabricated verdicts stand in for the crypto)
    hvs[1] = replace(hvs[1], ocert=replace(hvs[1].ocert, counter=1))
    hvs[3] = replace(hvs[3], ocert=replace(hvs[3].ocert, counter=0))
    v = _fab_verdicts(hvs)
    st = praos.PraosState(epoch_nonce=nonce)
    ticked = praos.TickedPraosState(st, lview)
    pre = pbatch.host_prechecks(params, lview, hvs)
    pv = _as_packed(v, hvs)
    res_packed = pbatch._epilogue(params, ticked, hvs, pre, pv)
    res_slow = pbatch._epilogue(params, ticked, hvs, pre, v)
    _results_equal(res_packed, res_slow)
    assert isinstance(res_packed.error, praos.CounterTooSmallOCERT)
    assert res_packed.n_valid == 3


# ---------------------------------------------------------------------------
# 3b. the pipelined loop end-to-end (crypto stubbed, everything else real)
# ---------------------------------------------------------------------------


def _stub_verify(*cols):
    """All-valid crypto stub with the REAL eta / leader-value range
    extensions (hash-only: compiles in seconds on XLA:CPU where the
    full curve graphs take minutes). Keeps every non-crypto part of the
    packed pipeline — staging, unpack, masks, eta column, epilogue —
    byte-exact against the reupdate fold. Arity-generic
    (21 draft-03 / 22 batch-compatible columns): beta_decl is always
    the third-from-last column."""
    beta_decl = cols[-3]
    bd = jnp.asarray(beta_decl).astype(jnp.int32)
    b = bd.shape[0]
    tag_l = jnp.broadcast_to(jnp.asarray([ord("L")], jnp.int32), (b, 1))
    lv = blake2b.blake2b_fixed(jnp.concatenate([tag_l, bd], axis=-1), 65, 32)
    tag_n = jnp.broadcast_to(jnp.asarray([ord("N")], jnp.int32), (b, 1))
    eta1 = blake2b.blake2b_fixed(jnp.concatenate([tag_n, bd], axis=-1), 65, 32)
    eta = blake2b.blake2b_fixed(eta1, 32, 32)
    ones = jnp.ones((b,), bool)
    return pbatch.Verdicts(ones, ones, ones, ones, jnp.zeros((b,), bool),
                           eta, lv)


@pytest.fixture
def stubbed_crypto(monkeypatch):
    """Patch the fused verifiers (both proof formats) with the hash-only
    stub, disable the aggregated fast path (its RLC/MSM program is real
    crypto — covered stubbed by test_aggregate.py and for real in the
    slow tier), and fence the jit caches so stub-compiled programs never
    leak into other tests."""
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
    yield
    for k in set(pbatch._JIT) - before:
        del pbatch._JIT[k]


def test_validate_chain_packed_pipeline_equals_fold(
    pools, lview, stubbed_crypto, monkeypatch
):
    """The full pipelined device path — packed staging, device unpack,
    bitmask verdicts and the eta column, the host nonce fold across
    windows AND epoch boundaries, fallback windows (CBOR width changes)
    in between — against the sequential reupdate fold. Covers packed-on
    and packed-off."""
    params = make_params(epoch_length=60)
    st0 = praos.PraosState(epoch_nonce=b"\x07" * 32)
    st = st0
    hvs, prev = [], b"\xaa" * 32
    slot, blkno = 18, 40  # slots cross the CBOR 1->2-byte boundary at 24
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
    assert params.epoch_of(hvs[-1].slot) >= 1  # crossed an epoch boundary

    for packed in (True, False):
        monkeypatch.setattr(pbatch, "PACKED_STAGE", packed)
        res = pbatch.validate_chain(
            params, lambda _e: lview, st0, hvs, max_batch=8,
            pipeline_depth=3,
        )
        assert res.error is None, (packed, repr(res.error))
        assert res.n_valid == len(hvs)
        assert res.state == st, packed


def _traced_chain(params, lview, st0, hvs, **kw):
    """validate_chain under a batch tracer -> (result, events)."""
    events = []
    pbatch.set_batch_tracer(events.append)
    try:
        res = pbatch.validate_chain(params, lambda _e: lview, st0, hvs, **kw)
    finally:
        pbatch.set_batch_tracer(None)
    return res, events


def test_transfer_events_report_packed_bytes(
    pools, lview, stubbed_crypto, monkeypatch
):
    """The tracer byte accounting: packed windows must report ≥2x fewer
    H2D bytes than the generic path, and on the way back exactly the
    five mask rows plus 32 B a lane against the generic path's five
    bool rows and two int32 [B, 32] columns: 261 B a lane against
    32.6, 7.8x at 16 lanes (8.0x less the mask words' rounding). It
    read "≥8x" while the device folded the nonces and a window shipped
    two of them (64 B) in place of the eta column."""
    from ouroboros_consensus_tpu.utils.trace import TransferEvent

    params = make_params()
    st0 = praos.PraosState(epoch_nonce=b"\x07" * 32)
    b = 16
    hvs = real_chain(params, pools, b)

    def run(packed):
        monkeypatch.setattr(pbatch, "PACKED_STAGE", packed)
        res, events = _traced_chain(params, lview, st0, hvs, max_batch=b)
        assert res.error is None and res.n_valid == len(hvs)
        h2d = sum(e.h2d_bytes for e in events
                  if isinstance(e, TransferEvent))
        d2h = sum(e.d2h_bytes for e in events
                  if isinstance(e, TransferEvent))
        return h2d, d2h

    h2d_packed, d2h_packed = run(True)
    h2d_generic, d2h_generic = run(False)
    assert h2d_packed * 2 <= h2d_generic, (h2d_packed, h2d_generic)
    assert d2h_packed == 5 * 4 * -(-b // 32) + b * 32
    assert d2h_generic == b * (5 + 2 * 32 * 4)
    assert d2h_packed * 7.8 <= d2h_generic, (d2h_packed, d2h_generic)


class _SpyNp:
    """numpy, counting the bytes of every device array `asarray` pulls."""

    def __init__(self):
        self.pulled = 0

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, a, *args, **kw):
        if isinstance(a, jax.Array):
            self.pulled += a.nbytes
        return np.asarray(a, *args, **kw)


def test_packed_d2h_bytes_are_the_bytes_pulled(
    pools, lview, stubbed_crypto, monkeypatch
):
    """A packed window's `TransferEvent.d2h_bytes` is what crossed the
    wire: the five mask rows and the eta column AS COPIED, padded lanes
    and all (11 lanes ride a 16-lane bucket), not the slice the
    epilogue keeps."""
    from ouroboros_consensus_tpu.utils.trace import TransferEvent

    params = make_params()
    st0 = praos.PraosState(epoch_nonce=b"\x07" * 32)
    hvs = real_chain(params, pools, 11)
    spy = _SpyNp()
    monkeypatch.setattr(pbatch, "np", spy)
    res, events = _traced_chain(params, lview, st0, hvs, max_batch=16)
    assert res.error is None and res.n_valid == 11
    (mat,) = [e for e in events
              if isinstance(e, TransferEvent) and e.phase == "materialize"]
    assert mat.packed and mat.lanes == 11
    assert mat.d2h_bytes == spy.pulled == 5 * 4 + 16 * 32


# ---------------------------------------------------------------------------
# 4. the nonce fold is the host's: no loop over lanes in any dispatched
#    program, no carry between dispatches, no lever
# ---------------------------------------------------------------------------


def _loops(jaxpr):
    """[(primitive, trip count or None)] of every loop in a jaxpr,
    nested computations included."""
    out = []
    for e in jaxpr.eqns:
        if e.primitive.name in ("scan", "while"):
            out.append((e.primitive.name, e.params.get("length")))
        for v in e.params.values():
            for x in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(x, "jaxpr", x)
                if hasattr(sub, "eqns"):
                    out += _loops(sub)
    return out


def test_pk_reduce_stage_holds_no_loop():
    """The pk `reduce` stage is bit packing and a cast: its jaxpr holds
    no `scan` and no `while` (the round-6 stage folded the nonces in a
    `lax.scan` of one trip a lane, 3.3 s a window on a v5e)."""
    from ouroboros_consensus_tpu.ops.pk import kernels as K

    b = 24
    flags = np.ones((5, b), np.int32)
    eta = np.arange(32 * b, dtype=np.int32).reshape(32, b) % 256
    assert _loops(jax.make_jaxpr(K.reduce_fn)(flags, eta).jaxpr) == []
    masks, eta_u8 = jax.jit(K.reduce_fn)(flags, eta)
    assert np.asarray(masks).shape == (5, 1)
    assert (np.asarray(eta_u8) == eta.T.astype(np.uint8)).all()


def test_xla_twin_packed_program_holds_no_loop_over_lanes(
    pools, lview, stubbed_crypto
):
    """The XLA twin's packed program folds nothing either: with the
    verifiers stubbed (hash-only), what loops are left are the hash
    rounds of unpack and the stub, none of them `while` and none with
    a trip a lane; and it returns (masks, uint8 eta column) and no
    nonce."""
    params = make_params()
    b = 11  # no hash has 11 rounds
    layout, parr = pbatch.stage_packed(
        params, lview, b"\x07" * 32, real_chain(params, pools, b)
    )
    closed = jax.make_jaxpr(pbatch._packed_xla_fn(layout))(*parr)
    loops = _loops(closed.jaxpr)
    assert loops and all(p == "scan" and n != b for p, n in loops), loops
    masks, eta_u8 = closed.out_avals[:2]
    assert masks.shape == (5, 1) and masks.dtype == np.uint32
    assert eta_u8.shape == (b, 32) and eta_u8.dtype == np.uint8
    assert len(closed.out_avals) == 5


def _epoch_crossing_chain(params, pools, lview, st0, first_slot, last_slot):
    """One header a slot through the sequential fold -> (views, the
    reference's state after each)."""
    st, hvs, states, prev = st0, [], [], b"\xaa" * 32
    for k, slot in enumerate(range(first_slot, last_slot + 1)):
        ticked = praos.tick(params, lview, slot, st)
        blk = forge_block(
            params, pools[k % 2], slot=slot, block_no=40 + k,
            prev_hash=prev, epoch_nonce=ticked.state.epoch_nonce,
            txs=(b"t",),
        )
        hv = blk.header.to_view()
        st = praos.reupdate(params, hv, slot, ticked)
        hvs.append(hv)
        states.append(st)
        prev = blk.header.hash_
    return hvs, states


@pytest.mark.parametrize("pipeline_depth", [1, 3])
@pytest.mark.parametrize("first_slot", [59, 50],
                         ids=["one-lane-first-window", "ten-lane-epoch"])
def test_host_fold_freezes_mid_window_and_crosses_epochs(
    pools, lview, stubbed_crypto, first_slot, pipeline_depth
):
    """The host fold, window by window in retire order, against the
    sequential reference's five nonces: epochs of 60 slots, stability
    window 24, windows of 8 lanes. Epoch 1 (slots 60..119) freezes its
    candidate at slot 96, the fifth lane of its fifth window; the chain
    starts in epoch 0 (one lane when it starts at slot 59) and ends in
    epoch 2, so two rotations consume what the fold left. All slots and
    block numbers sit in one CBOR width class: every window is packed."""
    from ouroboros_consensus_tpu.utils.trace import WindowStaged

    params = make_params(epoch_length=60)
    assert params.stability_window == 24
    st0 = praos.PraosState(epoch_nonce=b"\x07" * 32)
    hvs, states = _epoch_crossing_chain(params, pools, lview, st0,
                                        first_slot, 127)
    want, end_of_epoch_1 = states[-1], states[119 - first_slot]
    # the candidate did freeze, so the rotation into epoch 2 tells a
    # fold that froze at slot 96 from one that did not
    assert end_of_epoch_1.candidate_nonce != end_of_epoch_1.evolving_nonce
    res, events = _traced_chain(params, lview, st0, hvs, max_batch=8,
                                pipeline_depth=pipeline_depth)
    assert res.error is None and res.n_valid == len(hvs)
    staged = [e for e in events if isinstance(e, WindowStaged)]
    assert {e.outcome for e in staged} == {"packed"}
    lanes = [e.lanes for e in staged]
    assert lanes[0] == (1 if first_slot == 59 else 8)
    assert sum(lanes) == len(hvs)
    for f in ("evolving_nonce", "candidate_nonce", "epoch_nonce",
              "lab_nonce", "last_epoch_block_nonce"):
        assert getattr(res.state, f) == getattr(want, f), f
    assert res.state == want


@pytest.mark.parametrize("value", ["0", "1"])
def test_nonce_scan_variable_is_read_by_nothing(
    pools, lview, stubbed_crypto, monkeypatch, value
):
    """`OCT_NONCE_SCAN` was round 6's lever between the on-device fold
    and this path. It is gone: setting it changes neither the programs
    dispatched nor the bytes a window ships back, no module keeps a
    name for it and no file of the package reads it."""
    from ouroboros_consensus_tpu.analysis import envlevers
    from ouroboros_consensus_tpu.obs.warmup import WARMUP
    from ouroboros_consensus_tpu.utils.trace import (TransferEvent,
                                                     WindowStaged)

    params = make_params()
    st0 = praos.PraosState(epoch_nonce=b"\x07" * 32)
    hvs = real_chain(params, pools, 16)

    def run():
        res, events = _traced_chain(params, lview, st0, hvs, max_batch=16)
        assert res.error is None and res.n_valid == len(hvs)
        return (
            [(e.outcome, e.gate) for e in events
             if isinstance(e, WindowStaged)],
            [e.d2h_bytes for e in events if isinstance(e, TransferEvent)],
            sorted(k for k in WARMUP.report()["stages"]
                   if k.startswith(("xla-packed", "agg-", "reduce"))),
            sorted(repr(k) for k in pbatch._JIT),
        )

    monkeypatch.delenv("OCT_NONCE_SCAN", raising=False)
    unset = run()
    monkeypatch.setenv("OCT_NONCE_SCAN", value)
    assert run() == unset
    assert unset[0] == [("packed", None)]
    assert not hasattr(pbatch, "NONCE_SCAN")
    assert "OCT_NONCE_SCAN" not in envlevers.scan_reads(
        envlevers.default_roots()
    )
