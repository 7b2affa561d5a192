"""TPraos on the device path: `finish_tp` and the window that runs it.

  * `finish_tp_core` (the real core, jitted as the XLA twin of the Pallas
    kernel's body) against the plain reference lane by lane: both proofs'
    challenge and output checks, the 512-bit leader rule on the raw
    leader output with an exact rational threshold, the overlay bit in its
    place, eta = Blake2b-256(beta_eta); each lane breaks one thing;
  * the `finish_tp` kernel (its twenty-one references, its block shapes)
    interpreted, with a light core: live tiles equal the all-live run bit
    for bit and garbage planted behind them changes nothing (the plant of
    tests/test_live_tiles.py);
  * one packed TPraos window of real two-certificate headers through
    `validate_chain` on the `pk` path (stand-in cores, as
    tests/test_live_tiles.py: an interpreted kernel that hashes is half an
    hour of XLA:CPU compile), a wrong header in the last live lane: the
    `vrf` stage traced once and run twice (spans `dispatch.vrf_eta`,
    `dispatch.vrf_leader`), `finish_tp` under the span `dispatch.finish`,
    `vrf_proofs` two a lane, `overlay_lanes` counted;
  * both `vrf` runs of a TPraos window ask the store for the program a
    draft-03 window's one run asks for.
"""

import dataclasses
import functools
from fractions import Fraction

import numpy as np
import pytest

import jax
from jax import numpy as jnp

from benchmark.reference import ecvrf as recvrf
from benchmark.reference import ed25519 as red
from benchmark.reference import tpraos as ref
from ouroboros_consensus_tpu.block.forge import forge_block
from ouroboros_consensus_tpu.ops.pk import curve as pc
from ouroboros_consensus_tpu.ops.pk import kernels as K
from ouroboros_consensus_tpu.ops.pk import limbs as fe
from ouroboros_consensus_tpu.ops.pk import verify as pv
from ouroboros_consensus_tpu.protocol import batch as pbatch
from ouroboros_consensus_tpu.protocol import praos, tpraos
from ouroboros_consensus_tpu.protocol.views import ViewColumns
from ouroboros_consensus_tpu.testing import fixtures
from ouroboros_consensus_tpu.tools import db_synthesizer as synth

TILE = K.TILE
NONCE = b"\x07" * 32
PP = praos.PraosParams(
    slots_per_kes_period=3600, max_kes_evolutions=62, security_param=2160,
    active_slot_coeff=Fraction(1, 2), epoch_length=43200, kes_depth=3,
)


@functools.cache
def _deployment():
    pool = fixtures.make_pool(0, kes_depth=3)
    return synth.make_tpraos(PP, [pool], fixtures.make_ledger_view([pool]),
                             3, Fraction(1, 2))


def _forge(n, first_slot=1000):
    """n real-codec two-certificate headers on slots that have a leader
    (the overlay's delegate, or the pool where it wins the lottery)."""
    from ouroboros_consensus_tpu.protocol import forge as forge_mod

    params, creds, lview = _deployment()
    hvs, prev, slot = [], b"\xaa" * 32, first_slot
    while len(hvs) < n:
        el = forge_mod.elect_slot_tpraos(params, lview, creds, slot, NONCE)
        if el is not None:
            blk = forge_block(params, creds[el.pool], slot=slot,
                              block_no=500 + len(hvs), prev_hash=prev,
                              epoch_nonce=NONCE, is_leader=el.is_leader)
            hvs.append(blk.header.to_view())
            prev = blk.header.hash_
        slot += 1
    return hvs


# ---------------------------------------------------------------------------
# finish_tp_core against the plain reference
# ---------------------------------------------------------------------------


def _limbs(x: int) -> list[int]:
    return [(x >> (fe.BITS * i)) & ((1 << fe.BITS) - 1)
            for i in range(fe.NLIMBS)]


def _point_rows(p) -> np.ndarray:
    """A reference point (x, y, z, t ints) as the kernel's [80] rows,
    with a Z that is not 1 (what the ladders hand over is projective)."""
    k = 0x1234567
    return np.asarray([v for c in p for v in _limbs(c * k % red.P)],
                      np.int32)


def _proof_points(pk: bytes, proof: bytes, alpha: bytes):
    """(H, Γ, U, V, 8Γ) as the `vrf` stage leaves them, by the reference's
    big-integer arithmetic."""
    y = red.point_decompress(pk)
    gamma, c, s = recvrf.decode_proof(proof)
    h = recvrf.hash_to_curve(pk, alpha)
    u = red.point_add(red.point_mul(s, red.B),
                      red.point_neg(red.point_mul(c, y)))
    v = red.point_add(red.point_mul(s, h),
                      red.point_neg(red.point_mul(c, gamma)))
    return [h, gamma, u, v, red.point_mul(8, gamma)]


LANES = 8
# what each lane breaks: (name, the flag row that must read 0)
BROKEN = [None, "eta-output", "eta-challenge", "leader-output",
          "leader-challenge", "ed-r", None, None]


@functools.cache
def _finish_inputs():
    """Eight lanes of real headers' `finish_tp` operands, lane by lane
    one thing wrong, and what the reference says of each lane."""
    hvs = _forge(LANES)
    params, _creds, _lview = _deployment()
    cols = {k: [] for k in ("ed_pt", "ed_r", "kes_pt", "kes_r", "e_pts",
                            "c_e", "l_pts", "c_l", "b_e", "b_l", "lo", "hi",
                            "over")}
    want = []
    half = (1 << 511).to_bytes(64, "big")  # f = 1/2, sigma = 1: exact
    for i, hv in enumerate(hvs):
        broke = BROKEN[i]
        a_e = ref.mk_seed(ref.SEED_ETA, hv.slot, NONCE)
        a_l = ref.mk_seed(ref.SEED_L, hv.slot, NONCE)
        assert recvrf.verify(hv.vrf_vk, hv.vrf_proof, a_e) == hv.vrf_output
        assert recvrf.verify(hv.vrf_vk, hv.vrf_leader_proof,
                             a_l) == hv.vrf_leader_output
        ed_p = red.point_mul(1000 + i, red.B)
        kes_p = red.point_mul(2000 + i, red.B)
        ed_r = bytearray(red.point_compress(ed_p))
        b_e, b_l = bytearray(hv.vrf_output), bytearray(hv.vrf_leader_output)
        c_e, c_l = bytearray(hv.vrf_proof[32:48]), bytearray(
            hv.vrf_leader_proof[32:48])
        for name, buf in (("ed-r", ed_r), ("eta-output", b_e),
                          ("eta-challenge", c_e), ("leader-output", b_l),
                          ("leader-challenge", c_l)):
            if broke == name:
                buf[3] ^= 1
        over = tpraos.overlay_position(params, hv.slot) is not None
        if i == 6:
            over = True  # the overlay bit over a value that would lose
        cols["ed_pt"].append(_point_rows(ed_p))
        cols["ed_r"].append(np.frombuffer(bytes(ed_r), np.uint8))
        cols["kes_pt"].append(_point_rows(kes_p))
        cols["kes_r"].append(
            np.frombuffer(red.point_compress(kes_p), np.uint8))
        cols["e_pts"].append(np.concatenate([
            _point_rows(p) for p in _proof_points(
                hv.vrf_vk, hv.vrf_proof, a_e)]))
        cols["l_pts"].append(np.concatenate([
            _point_rows(p) for p in _proof_points(
                hv.vrf_vk, hv.vrf_leader_proof, a_l)]))
        for k, buf in (("c_e", c_e), ("c_l", c_l), ("b_e", b_e),
                       ("b_l", b_l)):
            cols[k].append(np.frombuffer(bytes(buf), np.uint8))
        # lane 6 and 7: a threshold the value certainly misses (lo = hi =
        # 0); lane 7 has no overlay bit to save it
        row = bytes(64) if i >= 6 else half
        cols["lo"].append(np.frombuffer(row, np.uint8))
        cols["hi"].append(np.frombuffer(row, np.uint8))
        if i == 7:
            over = False
        cols["over"].append(np.asarray([int(over)], np.int32))
        lv = int.from_bytes(bytes(b_l), "big")
        wins = ref.wins(lv, Fraction(1), Fraction(1, 2)) and i < 6
        want.append({
            "ok_ed": broke != "ed-r",
            "ok_e": broke not in ("eta-output", "eta-challenge"),
            "ok_l": broke not in ("leader-output", "leader-challenge"),
            "ok_leader": over or wins,
            "eta": ref.blake2b_256(bytes(b_e)),
            "lv": bytes(b_l),
        })
    arr = {k: np.stack(v).astype(np.int32).T for k, v in cols.items()}
    return arr, want


def test_finish_tp_core_against_the_reference():
    arr, want = _finish_inputs()
    ones = jnp.ones((LANES,), bool)

    def core(ed_pt, ed_r, kes_pt, kes_r, e_pts, c_e, l_pts, c_l, b_e, b_l,
             lo, hi, over):
        unstack = K._unstack_point
        with fe.kernel_consts(LANES):
            return pv.finish_tp_core(
                ones, unstack(ed_pt), ed_r, ones, unstack(kes_pt), kes_r,
                ones, [unstack(e_pts[80 * i:80 * (i + 1)]) for i in range(5)],
                c_e,
                ones, [unstack(l_pts[80 * i:80 * (i + 1)]) for i in range(5)],
                c_l, b_e, b_l, lo, hi, over[0],
            )

    v = jax.tree.map(np.asarray, jax.jit(core)(
        *(arr[k] for k in ("ed_pt", "ed_r", "kes_pt", "kes_r", "e_pts",
                           "c_e", "l_pts", "c_l", "b_e", "b_l", "lo", "hi",
                           "over"))))
    for i, w in enumerate(want):
        assert bool(v.ok_ocert_sig[i]) == w["ok_ed"], i
        assert bool(v.ok_kes_sig[i]), i
        assert bool(v.ok_vrf_nonce[i]) == w["ok_e"], i
        assert bool(v.ok_vrf_leader[i]) == w["ok_l"], i
        assert bool(v.ok_vrf[i]) == (w["ok_e"] and w["ok_l"]), i
        assert bool(v.ok_leader[i]) == w["ok_leader"], i
        assert not v.leader_ambiguous[i], i
        assert bytes(v.eta[:, i].astype(np.uint8)) == w["eta"], i
        assert bytes(v.leader_value[:, i].astype(np.uint8)) == w["lv"], i
    # the two ways a lane passes the leader rule, and the one it fails
    assert [bool(x) for x in v.ok_leader[5:]] == [want[5]["ok_leader"],
                                                  True, False]

    # a bracket the value falls inside is neither a win nor a loss: the
    # host decides it exactly; the overlay bit clears that too
    lo = arr["b_l"].copy()  # lo = value: not below it
    hi = np.full_like(lo, 255)
    v2 = jax.tree.map(np.asarray, jax.jit(core)(
        *(arr[k] for k in ("ed_pt", "ed_r", "kes_pt", "kes_r", "e_pts",
                           "c_e", "l_pts", "c_l", "b_e", "b_l")),
        lo, hi, arr["over"]))
    over = arr["over"][0] != 0
    assert (v2.leader_ambiguous == ~over).all()
    assert (v2.ok_leader == over).all()


# ---------------------------------------------------------------------------
# the kernel's wrapper, interpreted, with garbage behind the live tiles
# ---------------------------------------------------------------------------

_FINISH_TP_IN = [(1,), (80,), (32,), (1,), (80,), (32,), (1,), (400,),
                 (16,), (1,), (400,), (16,), (64,), (64,), (64,), (64,),
                 (1,)]


def _light_finish_tp_core(ok_ed, ed_pt, ed_r, ok_kes, kes_pt, kes_r, ok_e,
                          e_pts, c_e, ok_l, l_pts, c_l, b_e, b_l, lo, hi,
                          over):
    """Reads every operand of `finish_tp_core`, lane by lane."""
    mix = (ed_pt.x[0] + kes_pt.y[1] + sum(p.t[2] for p in e_pts)
           + sum(p.z[3] for p in l_pts) + c_e[0] + c_l[1])
    win = ((mix & 1) == 0) | (over != 0)
    return pv.TPraosCoreVerdicts(
        ok_ed & (ed_r[0] < 128), ok_kes & (kes_r[1] < 128), ok_e & ok_l,
        win, ~win & (hi[0] < 128), b_e[:32] ^ lo[:32], b_l + hi, ok_e, ok_l,
    )


@pytest.mark.parametrize("tiles,live", [
    (t, live) for t in (2, 3) for live in (1, TILE - 1, TILE, TILE + 1, None)
], ids=lambda x: str(x))
def test_finish_tp_kernel_runs_the_live_tiles_alone(monkeypatch, tiles, live):
    monkeypatch.setattr(pv, "finish_tp_core", _light_finish_tp_core)
    fn = jax.jit(lambda *a: K.finish_tp(*a))
    lanes = tiles * TILE
    live = lanes if live is None else live
    n = K.live_tiles(live)

    def inputs(bound, seed):
        rng = np.random.default_rng(seed)
        return [rng.integers(0, bound, (*p, lanes), dtype=np.int32)
                for p in _FINISH_TP_IN]

    args = inputs(256, tiles)
    full = [np.asarray(o) for o in
            fn(*args, np.full((1,), tiles, np.int32))]
    assert [f.shape[0] for f in full] == [5, 32, 64, 2]
    junk = inputs(1 << 30, 99)
    planted = [np.concatenate([a[..., :live], j[..., live:]], axis=-1)
               for a, j in zip(args, junk)]
    n_live = np.full((1,), n, np.int32)
    for got in (fn(*args, n_live), fn(*planted, n_live)):
        for g, f in zip(got, full):
            g = np.asarray(g)
            assert np.array_equal(g[..., :live], f[..., :live])
            assert n == tiles or not np.array_equal(
                g[..., n * TILE:], f[..., n * TILE:])
    assert any(f[..., :live].any() for f in full)


# ---------------------------------------------------------------------------
# one window of real headers on the `pk` path
# ---------------------------------------------------------------------------

LIVE, MAX_BATCH = TILE + 2, 4 * TILE


def _stand_in_cores(monkeypatch):
    """Cheap cores with the real ones' shapes (tests/test_live_tiles.py):
    a signature whose scalar is all zeros is refused, every lane wins its
    slot, the nonces are not the chain's."""

    def ok_of(s):
        return jnp.any(s != 0, axis=0)

    def ed_core(pk, s, hblocks, hnblocks):
        return ok_of(s), pc.identity(s.shape[-1])

    def kes_core(vk, period, s, vk_leaf, siblings, hblocks, hnblocks, depth):
        return ok_of(s), pc.identity(s.shape[-1])

    def vrf_core_prep(pk, gamma, c, s, alpha):
        pt = pc.identity(s.shape[-1])
        return ok_of(s), pt, pt, pt

    def vrf_core_ladders(c, s, h_pt, y_pt, g_pt):
        return h_pt, g_pt, h_pt, y_pt, g_pt

    def finish_tp_core(ok_ed, _edp, _edr, ok_kes, _kp, _kr, ok_e, _ep, _ce,
                       ok_l, _lp, _cl, b_e, b_l, lo, _hi, over):
        win = jnp.ones_like(ok_ed)
        return pv.TPraosCoreVerdicts(ok_ed, ok_kes, ok_e & ok_l, win, ~win,
                                     b_e[:32], b_l, ok_e, ok_l)

    for fn in (ed_core, kes_core, vrf_core_prep, vrf_core_ladders,
               finish_tp_core):
        monkeypatch.setattr(pv, fn.__name__, fn)
    monkeypatch.setattr(
        K, "_jit1", lambda key, fn: jax.jit(lambda *a: fn(*a)))
    monkeypatch.setattr(K, "_FIRST_EXEC", set())
    monkeypatch.setenv("OCT_PK_AOT", "0")
    monkeypatch.setattr(pbatch, "DEVICE_IMPL", "pk")


@functools.cache
def _chain():
    return _forge(LIVE)


def _fold(params, lview, st0, hvs):
    st = st0
    for i, hv in enumerate(hvs):
        try:
            st = tpraos.update(params, hv, hv.slot,
                               tpraos.tick(params, lview, hv.slot, st))
        except praos.PraosValidationError as e:
            return st, i, e
    return st, len(hvs), None


@pytest.mark.parametrize("wrong", ["kes", "leader-proof", None])
def test_a_tpraos_window_on_the_pk_path(monkeypatch, wrong):
    """`wrong` zeroes the KES signature's scalar, or the leader proof's
    (with the body signed again): the last live lane is refused with the
    sequential fold's error."""
    params, creds, lview = _deployment()
    hvs = list(_chain())
    if wrong == "kes":
        bad = hvs[-1]
        hvs[-1] = dataclasses.replace(
            bad, kes_sig=bad.kes_sig[:32] + bytes(32) + bad.kes_sig[64:])
    elif wrong == "leader-proof":
        from ouroboros_consensus_tpu.ops.host import kes as host_kes

        bad = hvs[-1]
        proof = bad.vrf_leader_proof[:48] + bytes(32)
        o = bad.signed_bytes.index(bad.vrf_leader_proof)
        body = bad.signed_bytes[:o] + proof + bad.signed_bytes[o + 80:]
        cred = next(c for c in creds if c.vk_cold == bad.vk_cold)
        hvs[-1] = dataclasses.replace(
            bad, vrf_leader_proof=proof, signed_bytes=body,
            kes_sig=host_kes.sign(
                cred.kes_seed, 3, PP.kes_period_of(bad.slot), body))
    st0 = tpraos.TPraosState(epoch_nonce=NONCE)
    want_st, want_n, want_err = _fold(params, lview, st0, hvs)
    assert (want_err is None) == (wrong is None)
    assert want_n == (LIVE if wrong is None else LIVE - 1)
    if wrong == "leader-proof":
        assert isinstance(want_err, tpraos.VRFKeyBadLeaderValue)

    _stand_in_cores(monkeypatch)
    seen = []
    real_call = K._call

    def spy(kernel, name, *a, n_live, **kw):
        seen.append(name)
        return real_call(kernel, name, *a, n_live=n_live, **kw)

    monkeypatch.setattr(K, "_call", spy)
    events = []
    pbatch.set_batch_tracer(events.append)
    try:
        res = pbatch.validate_chain(params, lambda _e: lview, st0, hvs,
                                    max_batch=MAX_BATCH)
    finally:
        pbatch.set_batch_tracer(None)
    assert res.n_valid == want_n
    assert res.error == want_err
    assert type(res.state) is tpraos.TPraosState
    assert res.state.last_slot == want_st.last_slot
    assert res.state.ocert_counters == want_st.ocert_counters
    # the `vrf` stage's two kernels are traced ONCE and run twice (the
    # spans below count the runs): one program for both proofs
    assert seen == ["ed_points", "kes_points", "vrf_prep", "vrf_ladder",
                    "finish_tp"]
    spans = [e for e in events if type(e).__name__ == "WindowSpan"]
    assert [(s.lanes, s.tiles_live, s.outcome, s.vrf_proofs) for s in
            spans] == [(LIVE, 2, "packed", 2 * LIVE)]
    n_overlay = sum(tpraos.overlay_position(params, hv.slot) is not None
                    for hv in hvs)
    assert 0 < spans[0].overlay_lanes == n_overlay < LIVE
    assert spans[0].issuers == 4 and spans[0].overlay_s > 0
    labels = [e.label for e in events
              if type(e).__name__ == "EncloseEvent" and e.edge == "start"]
    for label in ("stage.overlay", "dispatch.vrf_eta", "dispatch.vrf_leader",
                  "dispatch.finish"):
        assert labels.count(label) == 1, label
    assert "dispatch.vrf" not in labels


def test_both_vrf_runs_ask_for_the_draft03_program(monkeypatch):
    """The store is asked for `vrf` twice under ONE key, and it is the
    key a draft-03 Praos window of the same lanes asks for; `finish_tp`
    and the TPraos `unpack` go under their own names."""
    from ouroboros_consensus_tpu.ops.pk import aot

    asked = []
    outs = {"ed": [(1,), (80,)], "kes": [(1,), (80,)],
            "vrf": [(1,), (400,)], "finish": [(5,), (32,), (32,)],
            "finish_tp": [(5,), (32,), (64,), (2,)]}

    def run_stage(name, fn, b, kes_depth, *args):
        asked.append((name, aot.sig_of(args)))
        if name in outs:
            return tuple(jnp.zeros((*p, b), jnp.int32) for p in outs[name])
        return fn(*args)  # unpack, reduce: the real ones

    monkeypatch.setattr(K, "_run_stage", run_stage)
    params, _creds, lview = _deployment()
    sw = pbatch.prepare_window(params, lview, NONCE,
                               ViewColumns.from_views(_forge(3)), 2 * TILE)
    layout, parr = sw.packed
    assert layout.proofs == 2 and type(parr).__name__ == "TPraosPacked"
    out = K.verify_praos_packed_split(layout, *parr, tiles_live=1)
    assert len(out) == 5  # reduce's pair, flags, eta, leader value, vrf_ok
    tp = asked[:]
    assert [n for n, _ in tp] == [K.packed_unpack_name(layout), "ed", "kes",
                                  "vrf", "vrf", "finish_tp", "reduce"]
    assert tp[3][1] == tp[4][1]

    asked.clear()
    monkeypatch.setenv("OCT_VRF_BATCH", "0")
    pool = fixtures.make_pool(0, kes_depth=3)
    pv_lview = fixtures.make_ledger_view([pool])
    hvs, prev = [], b"\xaa" * 32
    for i in range(3):
        blk = forge_block(PP, pool, slot=1000 + i, block_no=500 + i,
                          prev_hash=prev, epoch_nonce=NONCE)
        hvs.append(blk.header.to_view())
        prev = blk.header.hash_
    sw = pbatch.prepare_window(PP, pv_lview, NONCE, hvs, 2 * TILE)
    layout3, parr3 = sw.packed
    assert layout3.proofs == 1 and layout3.vrf_proof_len == 80
    K.verify_praos_packed_split(layout3, *parr3, tiles_live=1)
    d3 = dict(asked)
    assert d3["vrf"] == tp[3][1]
    assert d3["ed"] == dict(tp)["ed"]
    assert K.packed_unpack_name(layout3) != K.packed_unpack_name(layout)
