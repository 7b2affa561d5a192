"""The leader-value kernel: who wins a slot, and nothing else.

Forging elects over every (slot, pool) pair of a chain: 512 pools x
86,400 slots is 4.4e7 pairs. A pair's leadership needs only the VRF
OUTPUT, beta = SHA-512(suite ‖ 3 ‖ enc(8·Γ)) with Γ = x·H(pk, alpha):
one hash-to-curve and ONE variable-base multiplication, where a proof
needs three and the host prover spends 0.3 ms (3.7 hours over the
grid). So the election runs here, on the verify side's own ladder
(`curve.scalar_mul_w4`, the `vrf` stage's hash-to-curve and
compression), brackets the leader value against the pool's threshold
pair exactly as `verify.finish_core` does, and returns two bits a pair.
The host proves the ~1-in-1000 pairs that won (protocol/forge.py).

`elect_core` is pure jnp over a lane tile like the cores of verify.py;
`elect_points` is its `pallas_call`; `leader_sweep` lays a pools x slots
grid over the lanes on the device, so a dispatch ships one alpha row a
SLOT (the pool columns stay on the device) and brings back bitmaps.

A module of its own: the store of compiled stage programs keys every
entry on a digest of limbs/hashes/curve/verify/kernels.py
(`aot._src_digest`), and the election must not empty the replay's
store. Its own entries carry `source_tag()` in their name instead.
"""

from __future__ import annotations

import functools
import hashlib

import jax
from jax import numpy as jnp

from . import curve as pc
from . import hashes as ph
from . import kernels as pk_kernels
from . import limbs as fe
from . import verify as pv


def elect_core(x, pk, alpha, thr_lo, thr_hi):
    """x, pk, alpha, thr_lo, thr_hi: [32, T] int32 bytes (x the expanded
    VRF secret scalar, little-endian) -> (certain win [T], ambiguous [T]):
    the bracket of `verify.finish_core` over the pair's own beta."""
    t = x.shape[-1]
    h_pt = pv.hash_to_curve(pk, alpha)
    gamma = pc.scalar_mul_w4(
        fe.windows4_from_bytes(x, 256, msb_first=True), h_pt
    )
    g8_enc = pc.compress(pc.mul_cofactor(gamma))
    p3 = ph.const_rows([pv.SUITE, 0x03], t)
    beta = ph.sha512_fixed(jnp.concatenate([p3, g8_enc], axis=0))
    tag_l = ph.const_rows([ord("L")], t)
    lv = ph.blake2b_fixed(jnp.concatenate([tag_l, beta], axis=0), 65, 32)
    win = pv._lt_be(lv, thr_lo)
    return win, ~win & pv._lt_be(lv, thr_hi)


def _elect_kernel(x_ref, pk_ref, al_ref, lo_ref, hi_ref, out_ref):
    tile = x_ref.shape[-1]
    with fe.kernel_consts(tile):
        win, amb = elect_core(x_ref[:], pk_ref[:], al_ref[:], lo_ref[:],
                              hi_ref[:])
        out_ref[:] = (win.astype(jnp.int32)
                      + 2 * amb.astype(jnp.int32))[None, :]


def elect_points(x, pk, alpha, thr_lo, thr_hi):
    """Five [32, B] int32 byte arrays -> [1, B] int32: bit 0 certain win,
    bit 1 ambiguous."""
    b = x.shape[-1]
    (out,) = pk_kernels._call(
        _elect_kernel, "elect_points", b,
        [(32,)] * 5, [(1,)], (x, pk, alpha, thr_lo, thr_hi),
        with_base8=False, n_live=pk_kernels.all_tiles(b),  # a full sweep
    )
    return out


def sweep_lanes(n_slots: int, n_pools: int) -> int:
    """Lanes of one sweep: the grid, rounded up to whole tiles."""
    tile = pk_kernels.TILE
    return -(-(n_slots * n_pools) // tile) * tile


def leader_sweep(x_tab, pk_tab, lo_tab, hi_tab, alpha):
    """The election of `alpha.shape[0]` slots by `x_tab.shape[0]` pools.

    x_tab, pk_tab, lo_tab, hi_tab: [P, 32] uint8, one row a pool; alpha:
    [S, 32] uint8, one row a slot. Lanes are slot-major (s0p0, s0p1, ...,
    s1p0, ...). -> (win, ambiguous), each [S, ceil(P / 8)] uint8: the
    pools of a slot as bits, first pool in the highest bit
    (`np.unpackbits` order)."""
    p, s = x_tab.shape[0], alpha.shape[0]
    lane = jnp.arange(sweep_lanes(s, p), dtype=jnp.int32)
    pool = lane % p
    slot = jnp.minimum(lane // p, s - 1)  # the last tile's spare lanes

    def col(tab, idx):
        return jnp.take(jnp.transpose(tab).astype(jnp.int32), idx, axis=1)

    bits = elect_points(col(x_tab, pool), col(pk_tab, pool),
                        col(alpha, slot), col(lo_tab, pool),
                        col(hi_tab, pool))[0, : s * p].reshape(s, p)
    return (jnp.packbits((bits & 1).astype(jnp.uint8), axis=1),
            jnp.packbits((bits >> 1).astype(jnp.uint8), axis=1))


def source_tag() -> str:
    """Digest of this file, for the name of its entries in the store."""
    with open(__file__, "rb") as f:
        return hashlib.blake2s(f.read(), digest_size=3).hexdigest()


@functools.cache
def jitted_sweep():
    return jax.jit(leader_sweep)
