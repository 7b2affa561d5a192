"""Dead lane tiles do no work, and nothing reads them.

Every stage kernel of the chip path is one `pallas_call` over lane tiles
(ops/pk/kernels._call) and takes the window's live-tile count as a
run-time operand, the bound of its grid: a tile at or past it is not
run, and what the outputs hold there is whatever the buffer held (the
interpreter plants a pattern, the chip leaves what was in memory). One
program whatever the count. Here, in interpret mode:

  * `_call` with a cheap kernel and `finish` (its fifteen references,
    its block shapes) at two and three tiles, live lane counts on and
    off a tile edge: live tiles equal the all-live run bit for bit, and
    garbage planted in the dead lanes of the inputs changes nothing;
  * one packed window of real headers through `validate_chain` on the
    `pk` path (130 of 512 lanes, two of four tiles) with a wrong header
    in the LAST live lane: the sequential fold's error and index, with
    the interpreter's pattern in every dead tile of every stage;
  * a window's `WindowSpan.tiles_live` is ceil(lanes / TILE) where the
    count bounded its kernels, and the count the kernels were handed;
  * the generic dispatch hands every stage the full count, and so asks
    the store for the programs the packed dispatch asks for; the
    deviceless builder (scripts/aot_precompile.py) cuts its operands
    with the same `stage_operands` / `finish_operands`.

The cores of verify.py are stood in for by cheap ones: an interpreted
kernel that hashes (the unrolled rounds, as on the chip) is over half an
hour of XLA:CPU compile, so tier-1 runs no real core anywhere. Staging,
`unpack`, the operand's way into every kernel, `reduce`, materialize and
the epilogue are the real ones; the real cores with the operand compile
for the v5e in tests/test_chip_compile.py and run in the benchmark.
"""

import dataclasses
import functools
from fractions import Fraction
from typing import NamedTuple

import numpy as np
import pytest

import jax
from jax import numpy as jnp

from ouroboros_consensus_tpu.block.forge import forge_block
from ouroboros_consensus_tpu.ops.pk import curve as pc
from ouroboros_consensus_tpu.ops.pk import kernels as K
from ouroboros_consensus_tpu.ops.pk import verify as pv
from ouroboros_consensus_tpu.protocol import batch as pbatch
from ouroboros_consensus_tpu.protocol import praos
from ouroboros_consensus_tpu.testing import fixtures

TILE = K.TILE

# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

_CHEAP_IN, _CHEAP_OUT = [(4,), (1,)], [(4,), (1,)]
_FINISH_IN = [(1,), (80,), (32,), (1,), (80,), (32,), (1,), (400,), (16,),
              (64,), (32,), (32,)]


def _cheap_kernel(a_ref, b_ref, o_ref, p_ref):
    o_ref[:] = a_ref[:] * 3 + b_ref[:]
    p_ref[:] = jnp.sum(a_ref[:], axis=0, keepdims=True) ^ b_ref[:]


@jax.jit
def _cheap(a, b, n_live):
    return K._call(_cheap_kernel, "cheap", a.shape[-1], _CHEAP_IN,
                   _CHEAP_OUT, (a, b), with_base8=False, n_live=n_live)


def _light_finish_core(ok_ed, ed_pt, ed_r, ok_kes, kes_pt, kes_r, ok_vrf,
                       vrf_pts, c, beta_decl, thr_lo, thr_hi):
    """Reads every operand of `finish_core`, lane by lane."""
    mix = ed_pt.x[0] + kes_pt.y[1] + sum(p.t[2] for p in vrf_pts) + c[0]
    win = (mix & 1) == 0
    return pv.CoreVerdicts(
        ok_ed & (ed_r[0] < 128), ok_kes & (kes_r[1] < 128), ok_vrf, win,
        ~win & (thr_hi[0] < 128), beta_decl[:32] ^ thr_lo, thr_lo + thr_hi,
    )


_KERNELS = {
    "cheap": (_cheap, _CHEAP_IN, 1 << 20),
    "finish": (jax.jit(lambda *a: K.finish(*a)), _FINISH_IN, 256),
}


def _inputs(prefixes, lanes, bound, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, bound, (*p, lanes), dtype=np.int32)
            for p in prefixes]


class KernelCase(NamedTuple):
    kernel: str
    tiles: int
    live: int | None  # live lanes; None: all

    @property
    def id(self):
        return f"{self.kernel}-{self.tiles}tiles-{self.live or 'all'}live"

    def check(self, monkeypatch):
        monkeypatch.setattr(pv, "finish_core", _light_finish_core)
        fn, prefixes, bound = _KERNELS[self.kernel]
        lanes = self.tiles * TILE
        live = lanes if self.live is None else self.live
        n = K.live_tiles(live)
        assert n == -(-live // TILE) <= self.tiles
        args = _inputs(prefixes, lanes, bound, seed=self.tiles)
        full = [np.asarray(o) for o in
                fn(*args, np.full((1,), self.tiles, np.int32))]
        # what a dead lane holds is nobody's business: replicas of lane 0
        # in production, garbage here
        junk = _inputs(prefixes, lanes, 1 << 30, seed=99)
        planted = [np.concatenate([a[..., :live], j[..., live:]], axis=-1)
                   for a, j in zip(args, junk)]
        n_live = np.full((1,), n, np.int32)
        for got in (fn(*args, n_live), fn(*planted, n_live)):
            for g, f in zip(got, full):
                g = np.asarray(g)
                assert np.array_equal(g[..., :live], f[..., :live])
                # and the dead tiles were not run
                assert n == self.tiles or not np.array_equal(
                    g[..., n * TILE:], f[..., n * TILE:])
        assert any(f[..., :live].any() for f in full)  # not zeros all over


# ---------------------------------------------------------------------------
# one window of real headers on the `pk` path
# ---------------------------------------------------------------------------

PARAMS = praos.PraosParams(
    slots_per_kes_period=3600, max_kes_evolutions=62, security_param=2160,
    active_slot_coeff=Fraction(1, 2), epoch_length=43200, kes_depth=3,
)
NONCE = b"\x07" * 32
LIVE, MAX_BATCH = TILE + 2, 4 * TILE  # 130 headers in 512 lanes


def _stand_in_cores(monkeypatch):
    """Cheap cores with the real ones' shapes: a signature whose scalar
    is all zeros is refused (what the planted fault looks like), every
    lane wins its slot, and the nonces are not the chain's."""

    def ok_of(s):
        return jnp.any(s != 0, axis=0)

    def ed_core(pk, s, hblocks, hnblocks):
        return ok_of(s), pc.identity(s.shape[-1])

    def kes_core(vk, period, s, vk_leaf, siblings, hblocks, hnblocks, depth):
        return ok_of(s), pc.identity(s.shape[-1])

    def vrf_core_bc_prep(pk, gamma, u, v, s, alpha):
        pt = pc.identity(s.shape[-1])
        return ok_of(s), u[:16], pt, pt, pt

    def vrf_core_ladders(c, s, h_pt, y_pt, g_pt):
        return h_pt, g_pt, h_pt, y_pt, g_pt

    def finish_core(ok_ed, _edp, _edr, ok_kes, _kp, _kr, ok_vrf, _pts, c,
                    beta_decl, thr_lo, thr_hi):
        win = jnp.ones_like(ok_ed)
        return pv.CoreVerdicts(ok_ed, ok_kes, ok_vrf, win, ~win,
                               beta_decl[:32], thr_lo)

    for fn in (ed_core, kes_core, vrf_core_bc_prep, vrf_core_ladders,
               finish_core):
        monkeypatch.setattr(pv, fn.__name__, fn)
    # no program traced over the stand-ins outlives the test: JAX keeps
    # traces by the identity of the function that was jitted
    monkeypatch.setattr(
        K, "_jit1", lambda key, fn: jax.jit(lambda *a: fn(*a)))
    monkeypatch.setattr(K, "_FIRST_EXEC", set())
    monkeypatch.setenv("OCT_PK_AOT", "0")
    monkeypatch.setattr(pbatch, "DEVICE_IMPL", "pk")


def _forge(n):
    """(ledger view, n real-codec headers forged on winning slots)."""
    pool = fixtures.make_pool(0, kes_depth=PARAMS.kes_depth)
    lview = fixtures.make_ledger_view([pool])
    hvs, prev, slot = [], b"\xaa" * 32, 1000
    while len(hvs) < n:
        if fixtures.find_leader(PARAMS, [pool], lview, slot, NONCE):
            blk = forge_block(PARAMS, pool, slot=slot,
                              block_no=500 + len(hvs), prev_hash=prev,
                              epoch_nonce=NONCE)
            hvs.append(blk.header.to_view())
            prev = blk.header.hash_
        slot += 1
    return lview, hvs


@functools.cache
def _chain():
    return _forge(LIVE)


def _fold(lview, st0, hvs):
    """The sequential reference -> (state, n_valid, first error)."""
    st = st0
    for i, hv in enumerate(hvs):
        try:
            ticked = praos.tick(PARAMS, lview, hv.slot, st)
            st = praos.update(PARAMS, hv, hv.slot, ticked)
        except praos.PraosValidationError as e:
            return st, i, e
    return st, len(hvs), None


class WindowCase(NamedTuple):
    wrong_lane: int | None  # whose KES signature is zeroed

    @property
    def id(self):
        if self.wrong_lane is None:
            return "window-clean"
        return f"window-wrong-lane-{self.wrong_lane}"

    def check(self, monkeypatch):
        lview, hvs = _chain()
        hvs = list(hvs)
        if self.wrong_lane is not None:
            bad = hvs[self.wrong_lane]
            hvs[self.wrong_lane] = dataclasses.replace(
                bad, kes_sig=bytes(64) + bad.kes_sig[64:])
        st0 = praos.PraosState(epoch_nonce=NONCE)
        want_st, want_n, want_err = _fold(lview, st0, hvs)
        assert (want_err is None) == (self.wrong_lane is None)
        assert want_n == (LIVE if want_err is None else self.wrong_lane)

        _stand_in_cores(monkeypatch)
        seen = []
        real_call = K._call

        def spy(*a, n_live, **kw):
            seen.append(n_live)
            return real_call(*a, n_live=n_live, **kw)

        monkeypatch.setattr(K, "_call", spy)
        events = []
        pbatch.set_batch_tracer(events.append)
        try:
            res = pbatch.validate_chain(PARAMS, lambda _e: lview, st0, hvs,
                                        max_batch=MAX_BATCH)
        finally:
            pbatch.set_batch_tracer(None)
        assert res.n_valid == want_n
        assert res.error == want_err
        # (the nonces are the stand-in's: what the fold counted is not)
        assert res.state.last_slot == want_st.last_slot
        assert res.state.ocert_counters == want_st.ocert_counters

        spans = [e for e in events if type(e).__name__ == "WindowSpan"]
        staged = [e for e in events if type(e).__name__ == "WindowStaged"]
        assert [(s.lanes, s.tiles_live, s.outcome) for s in spans] == [
            (LIVE, 2, "packed")]
        assert staged[0].lanes_padded == MAX_BATCH  # one shape, as before
        # all five kernels of the window were traced with the operand
        assert len(seen) == 5


class SpanCase(NamedTuple):
    lanes: int

    @property
    def id(self):
        return f"span-{self.lanes}lanes"

    def check(self, monkeypatch):
        lview, hvs = _chain()
        sw = pbatch.prepare_window(PARAMS, lview, NONCE, hvs[:self.lanes],
                                   MAX_BATCH)
        assert sw.packed is not None and sw.lanes == MAX_BATCH
        layout, parr = sw.packed
        monkeypatch.setattr(  # the XLA twin has no tiles
            pbatch, "_jitted_packed_xla", lambda _l: lambda *a: len(a))
        assert pbatch._dispatch_packed_lanes(layout, parr, sw.b) == (
            "xla", len(parr), 0)
        # on `pk` the kernels are handed the count the span reports
        monkeypatch.setattr(pbatch, "DEVICE_IMPL", "pk")
        monkeypatch.setattr(
            K, "verify_praos_packed_split",
            lambda _l, *a, tiles_live: ("handed", tiles_live))
        events = []
        pbatch.set_batch_tracer(events.append)
        try:
            _pre, d, b = pbatch.dispatch_prepared(sw)
        finally:
            pbatch.set_batch_tracer(None)
        n = -(-self.lanes // TILE)
        assert (b, d.out, d.meta.tiles_live) == (self.lanes, ("handed", n), n)


_POINT = [(1,), (80,)]
_STAGE_OUTS = {"ed": _POINT, "kes": _POINT, "vrf": [(1,), (400,)],
               "vrf_bc": [(1,), (16,), (400,)],
               "finish": [(5,), (32,), (32,)]}


class SharedProgramsCase(NamedTuple):
    bc: bool  # batch-compatible proofs (vrf_bc) or draft-03 (vrf)

    @property
    def id(self):
        return "shared-programs-" + ("bc" if self.bc else "draft03")

    def check(self, monkeypatch):
        """One form of every stage: what the generic dispatch asks the
        store for (`aot.sig_of` of a stage's operands) is what the
        packed dispatch of the same lanes asks for, whatever its live
        count, and what `stage_operands` / `finish_operands` give the
        deviceless builder."""
        from ouroboros_consensus_tpu.ops.pk import aot

        asked = []

        def run_stage(name, fn, b, kes_depth, *args):
            asked.append((name, aot.sig_of(args), args[-1]))
            if name in _STAGE_OUTS:
                return tuple(jnp.zeros((*p, b), jnp.int32)
                             for p in _STAGE_OUTS[name])
            return fn(*args)  # relayout, unpack, reduce: the real ones

        monkeypatch.setattr(K, "_run_stage", run_stage)
        if not self.bc:
            monkeypatch.setenv("OCT_VRF_BATCH", "0")
        lview, hvs = _forge(3)  # draft-03 proofs under OCT_VRF_BATCH=0
        sw = pbatch.prepare_window(PARAMS, lview, NONCE, hvs, 2 * TILE)
        layout, parr = sw.packed
        assert (layout.vrf_proof_len == 128) == self.bc
        K.verify_praos_packed_split(layout, *parr, tiles_live=1)
        packed = {n: (sig, int(np.asarray(live)[0]))
                  for n, sig, live in asked if n in _STAGE_OUTS}
        assert set(packed) == {"ed", "kes", "finish",
                               "vrf_bc" if self.bc else "vrf"}
        assert {live for _, live in packed.values()} == {1}

        asked.clear()
        pre = pbatch.host_prechecks(PARAMS, lview, hvs)
        staged = pbatch.pad_batch_to(
            pbatch.stage(PARAMS, lview, NONCE, hvs, pre.kes_evolution),
            2 * TILE)
        assert pbatch.batch_is_bc(staged) == self.bc
        monkeypatch.setattr(pbatch, "_JIT", {})
        pbatch._pk_dispatch(staged)
        generic = {n: (sig, int(np.asarray(live)[0]))
                   for n, sig, live in asked if n in _STAGE_OUTS}
        assert {live for _, live in generic.values()} == {2}  # every tile
        # the KES hash column of the packed path has a spare block
        # (`kes_hash_blocks`), so its `kes` is another shape's program
        for name in set(packed) - {"kes"}:
            assert generic[name][0] == packed[name][0], name

        # the builder's cut: shapes only, as scripts/aot_precompile.py
        unpack = K._mk_packed_unpack(layout)
        limb = jax.eval_shape(unpack, *parr)
        n_live = jax.ShapeDtypeStruct((1,), jnp.int32)
        fns = dict(K.split_stage_fns(PARAMS.kes_depth))
        outs = []
        for name, ops in K.stage_operands(limb, n_live):
            assert aot.sig_of(ops) == packed[name][0], name
            outs.append(tuple(jax.ShapeDtypeStruct((*p, 2 * TILE), jnp.int32)
                              for p in _STAGE_OUTS[name]))
        fin = K.finish_operands(limb, *outs, n_live)
        assert aot.sig_of(fin) == packed["finish"][0]
        assert fns.keys() >= packed.keys()


CASES = [
    *(KernelCase(k, t, live) for k in _KERNELS for t in (2, 3)
      for live in (1, TILE - 1, TILE, TILE + 1, None)),
    WindowCase(LIVE - 1), WindowCase(TILE), WindowCase(None),
    SpanCase(1), SpanCase(TILE), SpanCase(TILE + 1),
    SharedProgramsCase(True), SharedProgramsCase(False),
]


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.id)
def test_live_tiles(case, monkeypatch):
    case.check(monkeypatch)
