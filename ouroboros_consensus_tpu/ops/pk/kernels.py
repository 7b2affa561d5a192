"""Pallas TPU kernel wrappers for the Praos verifier cores.

Each stage of ops/pk/verify.py runs as ONE `pallas_call` with a 1-D grid
over batch tiles: inputs arrive [*, B] (limb-first), each program sees a
[*, TILE] block in VMEM and runs the full core — ladders, hash rounds,
inversion chains — with every intermediate in VMEM/registers. The four
stages chain inside a single jit, so a verification batch is one host
dispatch regardless of tile count.

Kernels cannot close over array constants (jax requires them as
inputs): small field/Barrett constants are materialized inside the
kernel from Python-int scalar fills (limbs.kernel_consts), and the one
genuinely large constant — the [32, 80, 256] f32 fixed-base table
(curve.BASE8_NP) — is passed as a grid-invariant VMEM input where
fixed-base muls occur (curve.kernel_base8).

On non-TPU backends the same kernels run under `interpret=True`
(functionally identical, used by the CPU test suite), so correctness is
established once by the differential tests for both execution modes.
"""

from __future__ import annotations

import functools
import os
import time

import jax
import numpy as np
from jax import numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import curve as pc
from . import limbs as fe
from . import verify as pv

# 128 lanes/tile: the ed/kes/vrf cores peak ~17MB of scoped VMEM at 256
# lanes on v5e (16MB limit) — measured OOM on hardware; 128 fits with
# headroom and matches the lane register width.
TILE = int(os.environ.get("OCT_PK_TILE", "128"))

_BASE8_SHAPE = pc.BASE8_NP.shape  # [32, 80, 256] f32


def _interpret() -> bool:
    # OCT_PK_INTERPRET=0 forces real Mosaic lowering even when the
    # default backend is CPU — required for deviceless AOT compilation
    # against a TPU TopologyDescription (scripts/aot_precompile.py);
    # =1 forces interpret mode (the ≤60s composed smoke test).
    force = os.environ.get("OCT_PK_INTERPRET", "")
    if force in ("0", "1"):
        return force == "1"
    return jax.devices()[0].platform != "tpu"


def _tile_spec(shape_prefix, tile):
    """BlockSpec for an array [*shape_prefix, B] tiled on the last axis."""
    nd = len(shape_prefix)
    return pl.BlockSpec(
        (*shape_prefix, tile),
        lambda i, _nd=nd: (*(0,) * _nd, i),
        memory_space=pltpu.VMEM,
    )


def _full_spec(shape):
    """BlockSpec for a grid-invariant input (consts pack, base table)."""
    nd = len(shape)
    return pl.BlockSpec(
        tuple(shape), lambda i, _nd=nd: (0,) * _nd, memory_space=pltpu.VMEM
    )


def live_tiles(lanes: int) -> int:
    """Lane tiles that hold the first `lanes` lanes of a window."""
    return -(-lanes // TILE)


def all_tiles(b: int):
    """`_call`'s `n_live` for a caller whose `b` lanes are all live."""
    return np.full((1,), live_tiles(b), np.int32)


def _call(kernel, name: str, b, in_prefixes, out_prefixes, args,
          with_base8: bool, n_live):
    """One pallas_call over lane tiles. `name` is the kernel's name in
    the lowered program and in a device trace (a kernel that is a
    functools.partial has none of its own). `n_live` ([1] int32, a
    run-time operand) is how many of the leading tiles hold live lanes
    and is the grid's bound: the kernel runs on those and on no other,
    so the device's time follows it and the program does not change
    with it. What the outputs hold behind the live tiles is whatever
    the buffer held: no caller reads it."""
    tile = min(TILE, b)
    assert b % tile == 0
    grid = jnp.minimum(n_live[0], b // tile)
    const_args = []
    const_specs = []
    if with_base8:
        const_args.append(jnp.asarray(pc.BASE8_NP))
        const_specs.append(_full_spec(_BASE8_SHAPE))
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=const_specs + [_tile_spec(p, tile) for p in in_prefixes],
        out_specs=tuple(_tile_spec(p, tile) for p in out_prefixes),
        out_shape=tuple(
            jax.ShapeDtypeStruct((*p, b), jnp.int32) for p in out_prefixes
        ),
        interpret=_interpret(),
        name=name,
    )(*const_args, *args)


# ---------------------------------------------------------------------------
# Stage kernels
# ---------------------------------------------------------------------------


def _ed_kernel(base8_ref, pk_ref, s_ref, hb_ref, hnb_ref, ok_ref, pt_ref):
    tile = pk_ref.shape[-1]
    with fe.kernel_consts(tile), pc.kernel_base8(base8_ref[:]):
        ok, p = pv.ed_core(pk_ref[:], s_ref[:], hb_ref[:], hnb_ref[:][0])
        ok_ref[:] = ok.astype(jnp.int32)[None, :]
        pt_ref[:] = jnp.concatenate([p.x, p.y, p.z, p.t], axis=0)


def ed_points(pk, s, hblocks, hnblocks, n_live):
    """pk, s: [32, B]; hblocks [NB, 128, B]; hnblocks [1, B] ->
    (ok [1, B] int32, point [80, B] int32). `n_live`, here and in every
    stage below: `_call`'s live-tile count."""
    nb = hblocks.shape[0]
    b = pk.shape[-1]
    return _call(
        _ed_kernel, "ed_points", b,
        [(32,), (32,), (nb, 128), (1,)],
        [(1,), (80,)],
        (pk, s, hblocks, hnblocks),
        with_base8=True, n_live=n_live,
    )


def _kes_kernel(depth, base8_ref, vk_ref, per_ref, s_ref,
                leaf_ref, sib_ref, hb_ref, hnb_ref, ok_ref, pt_ref):
    tile = vk_ref.shape[-1]
    with fe.kernel_consts(tile), pc.kernel_base8(base8_ref[:]):
        ok, p = pv.kes_core(
            vk_ref[:], per_ref[:][0], s_ref[:], leaf_ref[:], sib_ref[:],
            hb_ref[:], hnb_ref[:][0], depth,
        )
        ok_ref[:] = ok.astype(jnp.int32)[None, :]
        pt_ref[:] = jnp.concatenate([p.x, p.y, p.z, p.t], axis=0)


def kes_points(vk, period, s, vk_leaf, siblings, hblocks, hnblocks,
               n_live, *, depth):
    nb = hblocks.shape[0]
    b = vk.shape[-1]
    return _call(
        functools.partial(_kes_kernel, depth), "kes_points", b,
        [(32,), (1,), (32,), (32,), (depth, 32), (nb, 128), (1,)],
        [(1,), (80,)],
        (vk, period, s, vk_leaf, siblings, hblocks, hnblocks),
        with_base8=True, n_live=n_live,
    )


def _vrf_prep_kernel(pk_ref, g_ref, c_ref, s_ref, al_ref,
                     ok_ref, pts_ref):
    # stage A: decompress + hash-to-curve — field ops only, no base
    # table, roughly half the monolithic vrf module's op count
    tile = pk_ref.shape[-1]
    with fe.kernel_consts(tile):
        ok, h_pt, y_pt, g_pt = pv.vrf_core_prep(
            pk_ref[:], g_ref[:], c_ref[:], s_ref[:], al_ref[:]
        )
        ok_ref[:] = ok.astype(jnp.int32)[None, :]
        pts_ref[:] = jnp.concatenate(
            [jnp.concatenate([p.x, p.y, p.z, p.t], axis=0)
             for p in (h_pt, y_pt, g_pt)],
            axis=0,
        )


def _vrf_ladder_kernel(base8_ref, c_ref, s_ref, prep_ref, pts_ref):
    # stage B: the three ladders over the stage-A points
    tile = c_ref.shape[-1]
    with fe.kernel_consts(tile), pc.kernel_base8(base8_ref[:]):
        flat = prep_ref[:]
        h_pt, y_pt, g_pt = (
            _unstack_point(flat[80 * i: 80 * (i + 1)]) for i in range(3)
        )
        pts = pv.vrf_core_ladders(c_ref[:], s_ref[:], h_pt, y_pt, g_pt)
        pts_ref[:] = jnp.concatenate(
            [jnp.concatenate([p.x, p.y, p.z, p.t], axis=0) for p in pts],
            axis=0,
        )


def vrf_points(pk, gamma, c, s, alpha, n_live):
    """Two chained pallas_calls (split compile — module docstring and
    verify.vrf_core_prep rationale); same (ok [1, B], points [400, B])
    contract as the former single kernel."""
    b = pk.shape[-1]
    ok, prep = _call(
        _vrf_prep_kernel, "vrf_prep", b,
        [(32,), (32,), (16,), (32,), (32,)],
        [(1,), (240,)],
        (pk, gamma, c, s, alpha),
        with_base8=False, n_live=n_live,
    )
    (pts,) = _call(
        _vrf_ladder_kernel, "vrf_ladder", b,
        [(16,), (32,), (240,)],
        [(400,)],
        (c, s, prep),
        with_base8=True, n_live=n_live,
    )
    return ok, pts


def _unstack_point(flat):
    return pc.Point(flat[0:20], flat[20:40], flat[40:60], flat[60:80])


def _vrf_bc_prep_kernel(pk_ref, g_ref, u_ref, v_ref, s_ref, al_ref,
                        ok_ref, c_ref, pts_ref):
    # batch-compatible stage A: decompress + hash-to-curve + DERIVED
    # challenge from the announced U, V bytes (verify.vrf_core_bc_prep);
    # one extra inversion (compress H) vs the draft-03 prep, no ladders
    tile = pk_ref.shape[-1]
    with fe.kernel_consts(tile):
        ok, c16, h_pt, y_pt, g_pt = pv.vrf_core_bc_prep(
            pk_ref[:], g_ref[:], u_ref[:], v_ref[:], s_ref[:], al_ref[:]
        )
        ok_ref[:] = ok.astype(jnp.int32)[None, :]
        c_ref[:] = c16
        pts_ref[:] = jnp.concatenate(
            [jnp.concatenate([p.x, p.y, p.z, p.t], axis=0)
             for p in (h_pt, y_pt, g_pt)],
            axis=0,
        )


def vrf_points_bc(pk, gamma, u, v, s, alpha, n_live):
    """Batch-compatible vrf stage: prep (derived challenge) chained into
    the UNCHANGED ladder kernel. -> (ok [1, B], c16 [16, B],
    points [400, B]); the derived c16 feeds the unchanged finish stage."""
    b = pk.shape[-1]
    ok, c16, prep = _call(
        _vrf_bc_prep_kernel, "vrf_bc_prep", b,
        [(32,), (32,), (32,), (32,), (32,), (32,)],
        [(1,), (16,), (240,)],
        (pk, gamma, u, v, s, alpha),
        with_base8=False, n_live=n_live,
    )
    (pts,) = _call(
        _vrf_ladder_kernel, "vrf_ladder", b,
        [(16,), (32,), (240,)],
        [(400,)],
        (c16, s, prep),
        with_base8=True, n_live=n_live,
    )
    return ok, c16, pts


def _finish_kernel(edok_ref, edpt_ref, edr_ref, kesok_ref,
                   kespt_ref, kesr_ref, vrfok_ref, vrfpts_ref, c_ref,
                   beta_ref, tlo_ref, thi_ref, out_ref, eta_ref, lv_ref):
    tile = c_ref.shape[-1]
    with fe.kernel_consts(tile):
        vrf_flat = vrfpts_ref[:]
        pts = [_unstack_point(vrf_flat[80 * i : 80 * (i + 1)]) for i in range(5)]
        v = pv.finish_core(
            edok_ref[:][0] != 0, _unstack_point(edpt_ref[:]), edr_ref[:],
            kesok_ref[:][0] != 0, _unstack_point(kespt_ref[:]), kesr_ref[:],
            vrfok_ref[:][0] != 0, pts, c_ref[:],
            beta_ref[:], tlo_ref[:], thi_ref[:],
        )
        out_ref[:] = jnp.stack(
            [
                v.ok_ocert_sig.astype(jnp.int32),
                v.ok_kes_sig.astype(jnp.int32),
                v.ok_vrf.astype(jnp.int32),
                v.ok_leader.astype(jnp.int32),
                v.leader_ambiguous.astype(jnp.int32),
            ],
            axis=0,
        )
        eta_ref[:] = v.eta
        lv_ref[:] = v.leader_value


def finish(ed_ok, ed_pt, ed_r, kes_ok, kes_pt, kes_r, vrf_ok, vrf_pts,
           c, beta_decl, thr_lo, thr_hi, n_live):
    b = c.shape[-1]
    return _call(
        _finish_kernel, "finish", b,
        [(1,), (80,), (32,), (1,), (80,), (32,), (1,), (400,), (16,),
         (64,), (32,), (32,)],
        [(5,), (32,), (32,)],
        (ed_ok, ed_pt, ed_r, kes_ok, kes_pt, kes_r, vrf_ok, vrf_pts,
         c, beta_decl, thr_lo, thr_hi),
        with_base8=False, n_live=n_live,
    )


def _finish_tp_kernel(edok_ref, edpt_ref, edr_ref, kesok_ref, kespt_ref,
                      kesr_ref, eok_ref, epts_ref, ce_ref, lok_ref,
                      lpts_ref, cl_ref, be_ref, bl_ref, tlo_ref, thi_ref,
                      over_ref, out_ref, eta_ref, lv_ref, vrf_ref):
    tile = ce_ref.shape[-1]
    with fe.kernel_consts(tile):
        e_flat, l_flat = epts_ref[:], lpts_ref[:]
        v = pv.finish_tp_core(
            edok_ref[:][0] != 0, _unstack_point(edpt_ref[:]), edr_ref[:],
            kesok_ref[:][0] != 0, _unstack_point(kespt_ref[:]), kesr_ref[:],
            eok_ref[:][0] != 0,
            [_unstack_point(e_flat[80 * i: 80 * (i + 1)]) for i in range(5)],
            ce_ref[:],
            lok_ref[:][0] != 0,
            [_unstack_point(l_flat[80 * i: 80 * (i + 1)]) for i in range(5)],
            cl_ref[:],
            be_ref[:], bl_ref[:], tlo_ref[:], thi_ref[:], over_ref[:][0],
        )
        out_ref[:] = jnp.stack(
            [
                v.ok_ocert_sig.astype(jnp.int32),
                v.ok_kes_sig.astype(jnp.int32),
                v.ok_vrf.astype(jnp.int32),
                v.ok_leader.astype(jnp.int32),
                v.leader_ambiguous.astype(jnp.int32),
            ],
            axis=0,
        )
        eta_ref[:] = v.eta
        lv_ref[:] = v.leader_value
        vrf_ref[:] = jnp.stack(
            [v.ok_vrf_nonce.astype(jnp.int32),
             v.ok_vrf_leader.astype(jnp.int32)], axis=0)


def finish_tp(ed_ok, ed_pt, ed_r, kes_ok, kes_pt, kes_r,
              eta_ok, eta_pts, c_eta, l_ok, l_pts, c_l,
              beta_eta, beta_l, thr_lo, thr_hi, overlay, n_live):
    """The TPraos `finish` stage (a header's two VRF certificates; the
    `vrf` stage ran once for each) -> (flags [5, B] with `finish`'s
    rows, eta [32, B], the raw leader value [64, B], and which proof
    held [2, B]: nonce, leader)."""
    b = c_eta.shape[-1]
    return _call(
        _finish_tp_kernel, "finish_tp", b,
        [(1,), (80,), (32,), (1,), (80,), (32,),
         (1,), (400,), (16,), (1,), (400,), (16,),
         (64,), (64,), (64,), (64,), (1,)],
        [(5,), (32,), (64,), (2,)],
        (ed_ok, ed_pt, ed_r, kes_ok, kes_pt, kes_r, eta_ok, eta_pts, c_eta,
         l_ok, l_pts, c_l, beta_eta, beta_l, thr_lo, thr_hi, overlay),
        with_base8=False, n_live=n_live,
    )


def _finish_pbft_kernel(edok_ref, edpt_ref, edr_ref, out_ref, eta_ref):
    tile = edr_ref.shape[-1]
    with fe.kernel_consts(tile):
        ok = pv.finish_pbft_core(edok_ref[:][0] != 0,
                                 _unstack_point(edpt_ref[:]), edr_ref[:])
        one = jnp.ones(ok.shape, jnp.int32)
        out_ref[:] = jnp.stack(
            [ok.astype(jnp.int32), one, one, one, 0 * one], axis=0)
        eta_ref[:] = jnp.zeros(eta_ref.shape, jnp.int32)


def finish_pbft(ed_ok, ed_pt, ed_r, n_live):
    """The `finish` of a Byron window, whose lanes hold an Ed25519 check
    and nothing else: the `ed` point compressed and compared with R ->
    (flags [5, B] with `finish`'s rows: the signature's verdict in row
    0, rows 1-3 set, row 4 clear; eta [32, B] zeros), what `reduce`
    takes unchanged."""
    b = ed_r.shape[-1]
    return _call(
        _finish_pbft_kernel, "finish_pbft", b,
        [(1,), (80,), (32,)],
        [(5,), (32,)],
        (ed_ok, ed_pt, ed_r),
        with_base8=False, n_live=n_live,
    )


# ---------------------------------------------------------------------------
# Fused driver (one jit = one host dispatch)
# ---------------------------------------------------------------------------


def verify_praos_tiles(
    ed_pk, ed_r, ed_s, ed_hblocks, ed_hnblocks,
    kes_vk, kes_period, kes_r, kes_s, kes_vk_leaf, kes_siblings,
    kes_hblocks, kes_hnblocks,
    vrf_pk, vrf_gamma, vrf_c, vrf_s, vrf_alpha,
    beta_decl, thr_lo, thr_hi,
    *, kes_depth: int,
):
    """All inputs limb-first ([*, B], B a multiple of the tile) ->
    (verdicts [5, B] int32, eta [32, B], leader_value [32, B]).

    Verdict rows: ok_ocert_sig, ok_kes_sig, ok_vrf, ok_leader,
    leader_ambiguous — protocol/batch._pk_materialize re-wraps them into
    the Verdicts the sequential epilogue consumes.
    """
    n_live = all_tiles(vrf_c.shape[-1])
    ed_ok, ed_pt = ed_points(ed_pk, ed_s, ed_hblocks, ed_hnblocks, n_live)
    kes_ok, kes_pt = kes_points(
        kes_vk, kes_period, kes_s, kes_vk_leaf, kes_siblings,
        kes_hblocks, kes_hnblocks, n_live, depth=kes_depth,
    )
    vrf_ok, vrf_pts = vrf_points(
        vrf_pk, vrf_gamma, vrf_c, vrf_s, vrf_alpha, n_live
    )
    return finish(
        ed_ok, ed_pt, ed_r, kes_ok, kes_pt, kes_r, vrf_ok, vrf_pts,
        vrf_c, beta_decl, thr_lo, thr_hi, n_live,
    )


# ---------------------------------------------------------------------------
# Batch-first entry: relayout on DEVICE
# ---------------------------------------------------------------------------


def _bf(a):
    """[B, n] host-staged (any int dtype) -> [n, B] int32, in XLA: the
    transpose+widen costs ~20 us/header on host (pk_arrays) and ~nothing
    fused into the device infeed."""
    return jnp.transpose(jnp.asarray(a).astype(jnp.int32))


def _bf_blocks(w):
    """SHA-512 word blocks [B, NB, 16, 2] uint32 -> [NB, 128, B] int32
    byte blocks (the limb-first hash input layout), in XLA."""
    w = jnp.asarray(w)
    b, nb = w.shape[0], w.shape[1]
    shifts = jnp.asarray([24, 16, 8, 0], jnp.uint32)
    hi = (w[..., 0:1] >> shifts) & jnp.uint32(0xFF)
    lo = (w[..., 1:2] >> shifts) & jnp.uint32(0xFF)
    by = jnp.concatenate([hi, lo], axis=-1)  # [B, NB, 16, 8]
    return jnp.transpose(
        by.reshape(b, nb, 128), (1, 2, 0)
    ).astype(jnp.int32)


def staged_to_limb_first(
    ed_pk, ed_r, ed_s, ed_hblocks, ed_hnblocks,
    kes_vk, kes_period, kes_r, kes_s, kes_vk_leaf, kes_siblings,
    kes_hblocks, kes_hnblocks,
    vrf_pk, vrf_gamma, vrf_c, vrf_s, vrf_alpha,
    beta, thr_lo, thr_hi,
):
    """The in-XLA relayout: host-staged batch-first uint8/uint32 columns
    -> the 21 limb-first int32 arrays verify_praos_tiles consumes."""
    b = beta.shape[0]
    return (
        _bf(ed_pk), _bf(ed_r), _bf(ed_s),
        _bf_blocks(ed_hblocks),
        jnp.asarray(ed_hnblocks).astype(jnp.int32).reshape(1, b),
        _bf(kes_vk),
        jnp.asarray(kes_period).astype(jnp.int32).reshape(1, b),
        _bf(kes_r), _bf(kes_s), _bf(kes_vk_leaf),
        jnp.transpose(
            jnp.asarray(kes_siblings).astype(jnp.int32), (1, 2, 0)
        ),
        _bf_blocks(kes_hblocks),
        jnp.asarray(kes_hnblocks).astype(jnp.int32).reshape(1, b),
        _bf(vrf_pk), _bf(vrf_gamma), _bf(vrf_c), _bf(vrf_s), _bf(vrf_alpha),
        _bf(beta), _bf(thr_lo), _bf(thr_hi),
    )


def staged_to_limb_first_bc(
    ed_pk, ed_r, ed_s, ed_hblocks, ed_hnblocks,
    kes_vk, kes_period, kes_r, kes_s, kes_vk_leaf, kes_siblings,
    kes_hblocks, kes_hnblocks,
    vrf_pk, vrf_gamma, vrf_u, vrf_v, vrf_s, vrf_alpha,
    beta, thr_lo, thr_hi,
):
    """Batch-compatible relayout twin: 22 staged columns (u, v announced
    bytes instead of the 16-byte challenge) -> 22 limb-first arrays."""
    b = beta.shape[0]
    return (
        _bf(ed_pk), _bf(ed_r), _bf(ed_s),
        _bf_blocks(ed_hblocks),
        jnp.asarray(ed_hnblocks).astype(jnp.int32).reshape(1, b),
        _bf(kes_vk),
        jnp.asarray(kes_period).astype(jnp.int32).reshape(1, b),
        _bf(kes_r), _bf(kes_s), _bf(kes_vk_leaf),
        jnp.transpose(
            jnp.asarray(kes_siblings).astype(jnp.int32), (1, 2, 0)
        ),
        _bf_blocks(kes_hblocks),
        jnp.asarray(kes_hnblocks).astype(jnp.int32).reshape(1, b),
        _bf(vrf_pk), _bf(vrf_gamma), _bf(vrf_u), _bf(vrf_v), _bf(vrf_s),
        _bf(vrf_alpha),
        _bf(beta), _bf(thr_lo), _bf(thr_hi),
    )


def staged_to_limb_first_tp(*cols):
    """The TPraos relayout: `unpack_packed`'s 27 staged columns of a
    two-certificate window -> 27 limb-first arrays. The first 18 are
    draft-03's to the letter (ed, kes, the NONCE proof's vrf columns);
    then the LEADER proof's (gamma, c, s, alpha), both declared outputs,
    the 64-byte threshold rows and the overlay row [1, B]."""
    b = cols[22].shape[0]
    head = staged_to_limb_first(*cols[:18], cols[22], cols[24], cols[25])
    return (
        *head[:18],
        *(_bf(c) for c in cols[18:22]),
        head[18], _bf(cols[23]), head[19], head[20],
        jnp.asarray(cols[26]).astype(jnp.int32).reshape(1, b),
    )


def verify_praos_staged(
    ed_pk, ed_r, ed_s, ed_hblocks, ed_hnblocks,
    kes_vk, kes_period, kes_r, kes_s, kes_vk_leaf, kes_siblings,
    kes_hblocks, kes_hnblocks,
    vrf_pk, vrf_gamma, vrf_c, vrf_s, vrf_alpha,
    beta, thr_lo, thr_hi,
    *, kes_depth: int,
):
    """verify_praos_tiles over the HOST-STAGED batch-first layout
    (protocol/batch.stage's uint8/uint32 [B, ...] columns): every
    transpose/widen happens inside the jit so the host dispatch is a
    plain argument pass."""
    args = staged_to_limb_first(
        ed_pk, ed_r, ed_s, ed_hblocks, ed_hnblocks,
        kes_vk, kes_period, kes_r, kes_s, kes_vk_leaf, kes_siblings,
        kes_hblocks, kes_hnblocks,
        vrf_pk, vrf_gamma, vrf_c, vrf_s, vrf_alpha,
        beta, thr_lo, thr_hi,
    )
    return verify_praos_tiles(*args, kes_depth=kes_depth)


# ---------------------------------------------------------------------------
# Split-jit driver: one jit (= one persistent-cache entry = one Mosaic
# compile unit) PER STAGE, chained at the Python level with on-device
# intermediates. A run killed mid-compile loses ONE stage, the
# persistent cache accumulates per-stage entries across runs, and
# warm-up can checkpoint between stages. Hot-path cost vs the single
# fused jit: four extra dispatches
# of ~µs each against ~75 ms/stage kernels — noise.
# ---------------------------------------------------------------------------

_SPLIT_JIT: dict = {}
_AOT_WARM: set = set()
# warmup forensics: (stage@bucket) whose first execute is recorded —
# the compile (or persistent-cache load) happens synchronously inside
# that call, so its wall IS the per-stage compile attribution the
# r02-r05 postmortems were missing
_FIRST_EXEC: set = set()


def _note_first_exec(stage: str, wall_s: float, via: str) -> None:
    if stage in _FIRST_EXEC:
        return
    _FIRST_EXEC.add(stage)
    from ...obs.warmup import WARMUP

    WARMUP.note_stage(stage, wall_s, via=via)


def _begin_first_exec(stage: str) -> None:
    """Breadcrumb BEFORE a stage's first execute: a child killed at the
    wall mid-compile leaves 'X first execute starting' as the LAST note
    in the warmup report — exact attribution of which stage ate it."""
    if stage in _FIRST_EXEC:
        return
    from ...obs.warmup import WARMUP

    WARMUP.note(f"{stage} first execute starting")


def _capture_resources(stage, fn, args, b, kes_depth, via) -> None:
    """Per-stage device resource accounting (obs/resources.py): the AOT
    executable's analyses are free; the jit path pays one re-lower
    (a trace, no XLA compile) — and only while capture is enabled
    (OCT_STAGE_RESOURCES=1). Callers gate this on the stage's FIRST
    execute and call it AFTER the warmup note, so a kill mid-capture
    can never eat the compile-wall forensics (the note is already
    flushed)."""
    from ...obs import resources as obs_resources

    obs_resources.capture_stage(
        stage, fn, args, lanes=b, depth=kes_depth, via=via
    )


def _jit1(key, fn):
    if key not in _SPLIT_JIT:
        _SPLIT_JIT[key] = jax.jit(fn)
    return _SPLIT_JIT[key]


def _stage_call(name, fn, b, kes_depth, *args, span=None):
    """`_run_stage` inside the span `dispatch.<stage>` (the window's id
    and the parent `dispatch` come from the enclosing span). `span`
    names it where one program runs under two (a TPraos window's two
    `vrf` runs) or a program's name is not its stage's (`finish_tp`)."""
    from ...protocol import batch as pbatch

    stage = span or ("unpack" if name.startswith("unpack_") else name)
    with pbatch._enclose("dispatch." + stage):
        return _run_stage(name, fn, b, kes_depth, *args)


def _run_stage(name, fn, b, kes_depth, *args):
    """Dispatch one stage: precompiled AOT executable when available
    (OCT_PK_AOT=1 + a matching scripts/aot_cache entry — see ops/pk/aot),
    else the per-stage jit. An AOT call that fails at runtime disables
    that executable and falls back, so AOT can never be worse than the
    round-4 jit path."""
    from ...testing import chaos
    from . import aot

    # chaos seam (device-error@stage:<name> / compile-stall@stage:<name>):
    # a per-stage failure at the exact host point a real per-stage
    # device error surfaces; disarmed it is one module bool test
    chaos.fire("stage-call", stage=name)

    if aot.enabled():
        sig = aot.sig_of(args)
        key = (name, b, kes_depth, TILE, sig)
        ex = aot.load(name, b, kes_depth, TILE, sig)
        if ex is not None:
            try:
                if key not in _AOT_WARM:
                    _begin_first_exec(f"{name}@b{b}")
                t0 = time.monotonic()
                out = ex(*args)
                if key not in _AOT_WARM:
                    # device-side failures surface asynchronously — the
                    # FIRST call per executable blocks so an incompatible
                    # binary falls back here instead of crashing at the
                    # caller's materialization point; subsequent calls
                    # stay async (the dispatch pipeline depends on it)
                    jax.block_until_ready(out)
                    _AOT_WARM.add(key)
                    wall = time.monotonic() - t0
                    first = f"{name}@b{b}" not in _FIRST_EXEC
                    _note_first_exec(f"{name}@b{b}", wall, "aot")
                    if first:
                        _capture_resources(
                            f"{name}@b{b}", ex, args, b, kes_depth, "aot"
                        )
                return out
            except Exception as e:  # noqa: BLE001 — fail-soft by contract
                import sys

                print(f"# pk-aot: run {key} failed, falling back: {e!r}",
                      file=sys.stderr)
                aot.note_failure(e)  # format rejections latch process-wide
                # the executable LOADED but died on device: without this
                # the report shows only "loaded" plus an unexplained jit
                # first-execute — the one aot outcome load() cannot see
                aot._note_aot(name, "run_failed", detail=repr(e))
                aot._LOADED[key] = None
    stage = f"{name}@b{b}"
    first = stage not in _FIRST_EXEC
    _begin_first_exec(stage)
    t0 = time.monotonic()
    ex = None
    if first and aot.writeback_enabled():
        # the write-back path: compile EXPLICITLY (same wall the jit
        # would have paid) so the executable can be re-serialized into
        # the build-pinned store — the next attempt/round on this build
        # loads warm instead of recompiling, which is what heals the
        # store after a format rejection (ops/pk/aot.compile_and_store)
        ex = aot.compile_and_store(name, b, kes_depth, TILE, fn, args)
    out = ex(*args) if ex is not None else fn(*args)
    _note_first_exec(stage, time.monotonic() - t0, "jit")
    if first:
        _capture_resources(stage, ex if ex is not None else fn, args,
                           b, kes_depth, "jit")
        if ex is not None:
            # later dispatches take the (memoized) store branch async
            _AOT_WARM.add((name, b, kes_depth, TILE, aot.sig_of(args)))
    return out


def kes_points_at(kes_depth: int):
    """`kes_points` at one depth, under its own name: JAX names the jit
    of a bare functools.partial `jit__unknown`, and a device trace is
    read by module name."""
    fn = functools.partial(kes_points, depth=kes_depth)
    fn.__name__ = fn.__qualname__ = "kes_points"
    return fn


def split_stage_fns(kes_depth: int):
    """The per-stage jitted callables, keyed for cache warm-up:
    [(name, fn), ...] in dependency order. Used by verify_praos_split
    and by the bench/session scripts to warm one stage at a time.
    `relayout_bc`/`vrf_bc` are the batch-compatible-proof twins; ed, kes
    and finish are SHARED between the two formats (same executables)."""
    return [
        ("relayout", _jit1("relayout", staged_to_limb_first)),
        ("relayout_bc", _jit1("relayout_bc", staged_to_limb_first_bc)),
        ("ed", _jit1("ed", ed_points)),
        ("kes", _jit1(("kes", kes_depth), kes_points_at(kes_depth))),
        ("vrf", _jit1("vrf", vrf_points)),
        ("vrf_bc", _jit1("vrf_bc", vrf_points_bc)),
        ("finish", _jit1("finish", finish)),
        ("finish_tp", _jit1("finish_tp", finish_tp)),
        ("finish_pbft", _jit1("finish_pbft", finish_pbft)),
    ]


_KES_HBLOCKS = 11  # index of kes.hblocks in flatten_batch order


def kes_hash_blocks(body_len: int) -> int:
    """SHA-512 block count the packed `unpack` stage hands the `kes`
    stage for a window of `body_len`-byte bodies: what a body up to
    half a block (64 bytes) longer would need. The kes program's shape
    depends on this count, and a chain's bodies are not one length — its
    first header has no prev-hash (33 bytes shorter) and CBOR integer
    widths step a few bytes at a time — so without the headroom a
    replay from genesis compiles the most expensive stage twice. Each
    lane hashes its own count (`hnblocks` masks the spare block); the
    trade is at most one masked compression per lane, for the half of
    all body lengths that sit within 64 bytes of a block boundary."""
    from .. import sha512

    return sha512.nblocks_for_len(64 + body_len + 64)


def _mk_packed_unpack(layout):
    """Factory for the packed `unpack` stage: body-sourced packed
    columns -> the SAME 21 limb-first arrays the crypto stages consume
    (protocol/batch.unpack_packed chained into staged_to_limb_first, all
    in one jit) — the 'relayout extended onto the packed wire format'.
    The four crypto stages and their AOT executables are untouched.
    The KES hash column is padded to `kes_hash_blocks(body_len)`."""

    def unpack_limb(*packed):
        from ...protocol import batch as pbatch

        staged = pbatch.unpack_packed(layout, *packed)
        hb = staged[_KES_HBLOCKS]  # [B, NB, 16, 2] SHA-512 word blocks
        spare = kes_hash_blocks(layout.body_len) - hb.shape[1]
        if spare > 0:
            hb = jnp.pad(hb, ((0, 0), (0, spare), (0, 0), (0, 0)))
            staged = (*staged[:_KES_HBLOCKS], hb,
                      *staged[_KES_HBLOCKS + 1:])
        if layout.proofs == 2:
            return staged_to_limb_first_tp(*staged)
        if layout.vrf_proof_len == 128:
            return staged_to_limb_first_bc(*staged)
        return staged_to_limb_first(*staged)

    return unpack_limb


def _mk_pbft_unpack(layout):
    """Factory for a Byron window's `unpack`: the packed wire (signed
    bytes, R ‖ s, the delegate key) -> the `ed` stage's limb-first
    operands (pk, r, s [32, B]; R ‖ A ‖ M in SHA-512 blocks [NB, 128, B];
    blocks a lane [1, B]), NB = 3 at Byron's width."""

    def unpack_limb(signed, sig, vk):  # `^jit_unpack_limb` in a trace
        from ...protocol import pbft

        pk, r, s, hb, hnb = pbft.unpack_pbft(layout, signed, sig, vk)
        b = sig.shape[0]
        return (_bf(pk), _bf(r), _bf(s), _bf_blocks(hb),
                jnp.asarray(hnb).astype(jnp.int32).reshape(1, b))

    return unpack_limb


def reduce_fn(flags, eta):
    """The packed `reduce` stage: verdict-bit packing plus the uint8 eta
    column (protocol/batch.verdict_pack) over the finish stage's
    limb-first outputs. No loop: the evolving/candidate nonce fold is a
    hash chain and runs on the host, in the retire path."""
    from ...protocol import batch as pbatch

    return pbatch.verdict_pack(flags, jnp.transpose(eta))


def packed_unpack_name(layout) -> str:
    """AOT stage name for the packed unpack: the layout is BAKED into
    the traced program but invisible to aot.sig_of's shape hash (two
    layouts with equal body length have identical input shapes), so a
    deterministic layout digest goes into the cache-file name."""
    import hashlib

    tag = hashlib.blake2s(repr(tuple(layout)).encode(),
                          digest_size=3).hexdigest()
    return f"unpack_{tag}"


def stage_operands(a, n_live, proofs: int = 1):
    """`unpack`'s or `relayout`'s limb-first arrays (22 for batch-
    compatible proofs, 21 for draft-03; a TPraos window's, `proofs` = 2
    from its layout, hold two draft-03 proofs) cut into the operands of
    the point stages, in dispatch order: [(stage, operands), ...]
    (TPraos: the `vrf` stage twice, the nonce proof then the leader
    proof, the one program). One cut for every dispatch below and for
    the deviceless builder (scripts/aot_precompile.py): a stored program
    is found again by `aot.sig_of` of these."""
    ed = ("ed", [a[0], a[2], a[3], a[4], n_live])
    kes = ("kes", [a[5], a[6], a[8], a[9], a[10], a[11], a[12], n_live])
    if proofs == 2:
        return [ed, kes, ("vrf", [*a[13:18], n_live]),
                ("vrf", [a[13], *a[18:22], n_live])]
    nv = len(a) - 16  # vrf columns: 6 (announced U, V) or 5 (challenge)
    return [ed, kes, ("vrf_bc" if nv == 6 else "vrf",
                      [*a[13:13 + nv], n_live])]


def finish_operands(a, ed, kes, *vrfs_then_n_live):
    """The `finish` stage's operands: the limb-first arrays `a`, the
    point stages' outputs in `stage_operands`' order, then `n_live`.
    `vrf_bc` hands on the challenge it derived; draft-03's is a staged
    column. Two `vrf` outputs (a TPraos window's: the nonce proof's,
    the leader proof's) give `finish_tp`'s operands."""
    *vrfs, n_live = vrfs_then_n_live
    if len(vrfs) == 2:
        vrf_eta, vrf_l = vrfs
        return [
            ed[0], ed[1], a[1], kes[0], kes[1], a[7],
            vrf_eta[0], vrf_eta[1], a[15], vrf_l[0], vrf_l[1], a[19],
            *a[22:27], n_live,
        ]
    nv = len(a) - 16
    vrf_ok, *c16, vrf_pts = vrfs[0]
    return [
        ed[0], ed[1], a[1], kes[0], kes[1], a[7],
        vrf_ok, vrf_pts, c16[0] if c16 else a[15],
        a[13 + nv], a[14 + nv], a[15 + nv], n_live,
    ]


def _crypto_stages(a, b, kes_depth, n_live, proofs: int = 1):
    """ed, kes, vrf / vrf_bc and finish over limb-first arrays, one
    `_stage_call` each -> finish's (flags, eta, leader_value). The same
    per-stage jits / AOT executables whoever made `a`: the packed
    `unpack` or the generic `relayout`. `proofs` = 2 (a TPraos layout's)
    runs `vrf` twice and `finish_tp` for `finish`."""
    stages = dict(split_stage_fns(kes_depth))
    tp = proofs == 2
    spans = ("ed", "kes", "vrf_eta", "vrf_leader") if tp else (None,) * 3
    outs = [
        _stage_call(name, stages[name], b, kes_depth, *ops, span=span)
        for (name, ops), span in zip(stage_operands(a, n_live, proofs), spans)
    ]
    name = "finish_tp" if tp else "finish"
    return _stage_call(
        name, stages[name], b, kes_depth,
        *finish_operands(a, *outs, n_live), span="finish",
    )


def verify_praos_packed_split(layout, *packed, tiles_live: int):
    """The packed production dispatch: `unpack` (device limb
    decomposition of the packed wire format) -> the ed/kes/vrf/finish
    stage jits/AOT executables -> `reduce` (verdict bitmasks + the
    uint8 eta column). Returns (reduce outputs, flags, eta,
    leader_value) with the per-lane arrays left on device; `packed` is
    the window's `PraosPacked` columns. A two-certificate (TPraos)
    layout's `TPraosPacked` window takes the same road with its own
    `unpack`, the `vrf` program twice and `finish_tp` for `finish`, and
    brings a fifth handle back (which proof held).
    `tiles_live` (`live_tiles` of the window's live lanes) goes to the
    device once and bounds the grid of every stage kernel: the lanes
    behind it come back holding anything, and the caller slices them
    off."""
    kes_depth = layout.kes_depth
    b = np.asarray(packed[0]).shape[0]
    n_live = jax.device_put(np.full((1,), tiles_live, np.int32))
    if layout.proofs == 0:  # a Byron window (protocol/pbft)
        return _verify_pbft(layout, packed, b, n_live)
    unpack = _jit1(("unpack", layout), _mk_packed_unpack(layout))
    a = _stage_call(
        packed_unpack_name(layout), unpack, b, kes_depth, *packed
    )
    flags, eta, *rest = _crypto_stages(a, b, kes_depth, n_live,
                                       layout.proofs)
    red = _stage_call(
        "reduce", _jit1("reduce", reduce_fn), b, kes_depth, flags, eta
    )
    return (red, flags, eta, *rest)


def _verify_pbft(layout, packed, b, n_live):
    """A Byron window: `unpack` -> `ed` (its own width: three SHA-512
    blocks) -> `finish_pbft` -> `reduce`, each stage's program stored
    and dispatched as the other windows' are. No KES, no VRF."""
    stages = dict(split_stage_fns(0))
    unpack = _jit1(("unpack", layout), _mk_pbft_unpack(layout))
    pk, r, s, hb, hnb = _stage_call(
        packed_unpack_name(layout), unpack, b, 0, *packed)
    ed = _stage_call("ed", stages["ed"], b, 0, pk, s, hb, hnb, n_live)
    flags, eta = _stage_call("finish_pbft", stages["finish_pbft"], b, 0,
                             ed[0], ed[1], r, n_live, span="finish")
    red = _stage_call(
        "reduce", _jit1("reduce", reduce_fn), b, 0, flags, eta)
    return (red, flags, eta, eta)


def _verify_praos_generic(relayout: str, cols, kes_depth: int):
    """The generic dispatch: `relayout` / `relayout_bc` of the staged
    columns, then the crypto stages over every tile (a generic window
    hands no live count down)."""
    b = np.asarray(cols[-1]).shape[0]
    fn = dict(split_stage_fns(kes_depth))[relayout]
    a = _stage_call(relayout, fn, b, kes_depth, *cols)
    return _crypto_stages(a, b, kes_depth, jax.device_put(all_tiles(b)))


def verify_praos_split(*cols, kes_depth: int):
    """Same contract as verify_praos_staged (its 21 staged columns),
    per-stage jits (or AOT executables — _stage_call)."""
    return _verify_praos_generic("relayout", cols, kes_depth)


def verify_praos_split_bc(*cols, kes_depth: int):
    """verify_praos_split for the 22 BATCH-COMPATIBLE staged columns
    (`staged_to_limb_first_bc`'s): the vrf stage derives the challenge
    from the announced U, V; ed/kes/finish dispatch the same per-stage
    jits/AOT executables as draft-03."""
    return _verify_praos_generic("relayout_bc", cols, kes_depth)
