"""Limb-first Praos verifier cores (pure jnp; run inside Pallas kernels).

The four stages mirror the fused XLA path (protocol/batch.verify_praos):

  ed_core     — Ed25519 verify-point of the OCert cold-key signature
                (Praos.hs:580): P = s·B − h·A, compression deferred.
  kes_core    — CompactSum KES leaf verify-point + Merkle root walk
                (Praos.hs:582).
  vrf_core    — ECVRF-ED25519-SHA512-Elligator2 draft-03 points
                (Praos.hs:543): H, Γ, U = s·B − c·Y, V = s·H − c·Γ, 8Γ.
  finish_core — ONE shared Montgomery inversion compresses all 7 points,
                then the ECVRF challenge/beta hashes, the R-byte
                compare-on-bytes checks, Blake2b leader/nonce range
                extensions (Praos/VRF.hs:103,116) and the bracketed
                leader-threshold compare.
  finish_tp_core — the TPraos finish (Shelley..Alonzo headers carry two
                VRF certificates; vrf_core runs once a certificate): 12
                points in the one inversion, both proofs' challenge and
                output checks, the RAW 64-byte leader output against
                512-bit brackets with the overlay bit in the threshold's
                place, eta = Blake2b-256(beta_eta).

Layout: batch tile T last everywhere (bytes [n, T] int32, points
[20, T] limb coordinates). All control flow is batch-uniform; failures
are mask lanes. Differentially tested against the host verifiers and
the XLA twins in tests/test_pk_verify.py.

Certification (octrange, analysis/absint.py): each core and both
composed graphs are interval-proven no-overflow with inputs at the
byte/limb bound classes of analysis/shapes.json, and the proofs are
LANE-UNIVERSAL — machine-verified to not depend on the batch tile T
(every reduction here is over limb/byte axes, never lanes), so the
registry-tile certificate covers the production 8192-lane window. The
taint pass confirms batch-uniformity semantically: wire marks reach no
branch predicate or access pattern. Ratcheted in analysis/certified.json.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np
from jax import numpy as jnp

from ..host import ed25519 as he
from . import curve as pc
from . import hashes as ph
from . import limbs as fe

SUITE = 0x04


# ---------------------------------------------------------------------------
# Ed25519
# ---------------------------------------------------------------------------


def ed_core(pk, s, hblocks, hnblocks):
    """(ok_pre[T], Point): P = s·B − h·A with h = SHA-512(R‖A‖M) mod L.

    pk, s: [32, T] bytes; hblocks: [NB, 128, T] padded bytes; hnblocks [T].
    """
    ok_a, a_pt = pc.decompress(pk)
    s_ok = fe.is_canonical_scalar(s)
    digest = ph.sha512_var(hblocks, hnblocks)
    h = fe.reduce512(digest)
    sb = pc.base_mul_w8(fe.windows8_from_bytes(s, 256))
    h_digits = fe.windows4_from_limbs(h, 256, msb_first=True)
    nha = pc.scalar_mul_w4(h_digits, pc.neg(a_pt))
    return ok_a & s_ok, pc.add(sb, nha)


# ---------------------------------------------------------------------------
# KES (CompactSum)
# ---------------------------------------------------------------------------


def kes_merkle_ok(vk, period, vk_leaf, siblings, depth: int):
    """Bottom-up CompactSum root reconstruction; bit i of the period
    selects H(vk ‖ sib) vs H(sib ‖ vk)."""
    cur = vk_leaf.astype(jnp.int32)
    for i in range(depth):
        sib = siblings[i]
        bit = (period >> i) & 1
        left = jnp.concatenate([cur, sib], axis=0)
        right = jnp.concatenate([sib, cur], axis=0)
        data = jnp.where((bit == 1)[None, :], right, left)
        cur = ph.blake2b_fixed(data, 64, 32)
    return jnp.all(cur == vk, axis=0)


def kes_core(vk, period, s, vk_leaf, siblings, hblocks, hnblocks, depth: int):
    """(ok_pre[T], Point) — leaf Ed25519 verify-point + root + period
    window check. siblings: [depth, 32, T]."""
    ok_ed, p = ed_core(vk_leaf, s, hblocks, hnblocks)
    root_ok = kes_merkle_ok(vk, period, vk_leaf, siblings, depth)
    period_ok = (period >= 0) & (period < (1 << depth))
    return ok_ed & root_ok & period_ok, p


# ---------------------------------------------------------------------------
# ECVRF (draft-03)
# ---------------------------------------------------------------------------


def _sqrt_of(x: int) -> int:
    """Host-side sqrt mod p (p = 5 mod 8 Shanks); x must be a QR."""
    p = he.P
    s = pow(x % p, (p + 3) // 8, p)
    if (s * s - x) % p != 0:
        s = s * pow(2, (p - 1) // 4, p) % p  # multiply by sqrt(-1)
    assert (s * s - x) % p == 0
    return s


# chi(2) = chi(i) = -1 for p = 2^255-19, so both 2i and -2i are QRs;
# these are the branch-2 fixup constants of the single-chain Elligator2
_SQRT_2I = _sqrt_of(2 * fe.SQRT_M1_INT)
_SQRT_M2I = _sqrt_of(-2 * fe.SQRT_M1_INT)


def elligator2(r):
    """[20, T] field element -> Point (even-x convention, matching
    ops/host/ecvrf.elligator2).

    Projective single-chain formulation: the naive map costs FIVE
    ~254-squaring exponentiation chains (inv(denom), legendre, sqrt,
    inv(v), inv(u+1)); this one costs ONE. Write u = U/W over the
    common denominator W = 1 + 2r² and N(U, W) = U·(U² + A·U·W + W²)
    (the Montgomery RHS numerator, w = N/W³). Then

      x² = c²·u²/w = c²·U²·W / N      (c = sqrt(-486664))

    and ONE Shanks exponentiation for branch 1 decides everything. Let
    ρ = num·n³·(num·n⁷)^((p-5)/8) (the sqrt_ratio candidate for
    num = c²A²W, n = N1): n·ρ² ∈ {±num, ±i·num}, and which of the four
    identifies both the branch (χ(W·N1) = 1 ⟺ w1 square — the host's
    is_square test) and the root:

      n·ρ² = +num   → branch 1, x = ρ
      n·ρ² = -num   → branch 1, x = i·ρ
      n·ρ² = ±i·num → branch 2; u2 = 2r²·u1 and Q(u2) = Q(u1) (with
                      Q(u) = u²+Au+1, since u2 = -u1-A), so
                      w2 = (u2/u1)·w1 = 2r²·w1 and
                      x2² = c²u2²/w2 = 2r²·x1²:
                        n·ρ² = +i·num → x1² = -i·ρ² → x = r·ρ·sqrt(-2i)
                        n·ρ² = -i·num → x1² = +i·ρ² → x = r·ρ·sqrt(2i)

    Everything stays projective: the Edwards y rides as (U−W : U+W) and
    the returned point has Z ≠ 1 (every consumer — ladders, cofactor,
    compress — is projective)."""
    t = r.shape[-1]
    one = fe.ones(t)
    zero = fe.zeros(t)
    A = he.MONT_A % he.P
    A2 = A * A % he.P
    c2 = he.SQRT_M486664 * he.SQRT_M486664 % he.P  # = -486664 mod p
    w_den = fe.add(fe.mul_small(fe.sqr(r), 2), one)
    W = fe.select(fe.is_zero(w_den), one, w_den)  # host denom=0 guard
    W2 = fe.sqr(W)
    # branch 1: U1 = -A (constant numerator)
    #   N1 = (-A)·(A² - A²·W + W²); num1 = (c²·A²)·W
    a2w = fe.mul(fe.constant(A2), W)
    n1 = fe.mul(
        fe.constant((-A) % he.P),
        fe.add(fe.sub(fe.constant(A2), a2w), W2),
    )
    num1 = fe.mul(fe.constant(c2 * A2 % he.P), W)
    # ONE exponentiation chain: the sqrt_ratio candidate and its full
    # classification (limbs.sqrt_ratio_ext — shared with fe.sqrt_ratio)
    rho, good, good_alt, is_pi = fe.sqrt_ratio_ext(num1, n1)
    ok1 = good | good_alt | fe.is_zero(n1)  # w1 = 0 stays on branch 1
    x1 = fe.select(good, rho, fe.mul(rho, fe.constant(fe.SQRT_M1_INT)))
    x2 = fe.mul(
        fe.mul(r, rho),
        fe.select(is_pi, fe.constant(_SQRT_M2I), fe.constant(_SQRT_2I)),
    )
    x = fe.select(ok1, x1, x2)
    x = fe.select(fe.parity(x) == 1, fe.neg(x), x)
    u1 = jnp.broadcast_to(fe.constant((-A) % he.P), (fe.NLIMBS, t))
    u2 = fe.mul(fe.constant(A), fe.sub(one, W))  # U2 = -U1 - A·W
    un = fe.select(ok1, u1, u2)
    # y = (u-1)/(u+1) -> (Y : Z) = (U-W : U+W); host pins y=0 at u=-1
    y_num = fe.sub(un, W)
    z = fe.add(un, W)
    z_zero = fe.is_zero(z)
    y_num = fe.select(z_zero, zero, y_num)
    z = fe.select(z_zero, one, z)
    return pc.Point(fe.mul(x, z), y_num, z, fe.mul(x, y_num))


def hash_to_curve(pk_bytes, alpha_bytes):
    """H = 8 * Elligator2(SHA-512(suite ‖ 1 ‖ pk ‖ alpha) mod 2^255)."""
    t = pk_bytes.shape[-1]
    prefix = ph.const_rows([SUITE, 0x01], t)
    data = jnp.concatenate([prefix, pk_bytes, alpha_bytes], axis=0)  # [66, T]
    digest = ph.sha512_fixed(data)
    r32 = jnp.concatenate(
        [digest[:31], (digest[31] & 0x7F)[None]], axis=0
    )
    r = fe.canonical(fe.from_bytes32(r32))
    return pc.mul_cofactor(elligator2(r))


def vrf_core_prep(pk, gamma, c, s, alpha):
    """Stage A of the VRF check: decode/validate + hash-to-curve (field
    ops and SHA-512 only, no ladders). Split from the ladders so the
    Pallas kernel compiles as two small Mosaic modules instead of one
    31.8 MB / 185k-op monolith (round-3 compile-time attribution)."""
    ok_y, y_pt = pc.decompress(pk)
    ok_g, g_pt = pc.decompress(gamma)
    s_ok = fe.is_canonical_scalar(s)
    h_pt = hash_to_curve(pk, alpha)
    return ok_y & ok_g & s_ok, h_pt, y_pt, g_pt


def vrf_core_ladders(c, s, h_pt, y_pt, g_pt):
    """Stage B: the three scalar ladders (U = sB - cY, V = sH - cΓ, 8Γ)."""
    s_digits = fe.windows4_from_bytes(s, 256, msb_first=True)
    c_digits = fe.windows4_from_bytes(c, 128, msb_first=True)

    sb = pc.base_mul_w8(fe.windows8_from_bytes(s, 256))
    u_pt = pc.add(sb, pc.scalar_mul_w4(c_digits, pc.neg(y_pt)))
    v_pt = pc.double_scalar_mul_w4(s_digits, h_pt, c_digits, pc.neg(g_pt))
    g8 = pc.mul_cofactor(g_pt)
    return h_pt, g_pt, u_pt, v_pt, g8


def vrf_core(pk, gamma, c, s, alpha):
    """(ok_pre[T], (H, Γ, U, V, 8Γ)) — points left uncompressed for the
    shared inversion in finish_core. c: [16, T]; others [32, T]."""
    ok_pre, h_pt, y_pt, g_pt = vrf_core_prep(pk, gamma, c, s, alpha)
    return ok_pre, vrf_core_ladders(c, s, h_pt, y_pt, g_pt)


def vrf_core_bc_prep(pk, gamma, u, v, s, alpha):
    """Stage A for BATCH-COMPATIBLE (128-byte) proofs: decode/validate +
    hash-to-curve + the challenge c = SHA-512(suite ‖ 2 ‖ enc(H) ‖ Γ ‖
    U ‖ V)[:16] derived from the ANNOUNCED bytes (one extra inversion to
    compress H vs vrf_core_prep). Returns (ok_pre, c16 [16, T], H, Y, Γ).

    The ladders (vrf_core_ladders) and finish_core run UNCHANGED on the
    derived c: finish's c' == c compare then holds iff the recomputed
    U' = s·B − c·Y and V' = s·H − c·Γ compress to the announced U, V
    bytes — the compare-on-bytes form of the two batch-compat group
    equations (ops/ecvrf_batch.derive_c_bc rationale)."""
    ok_y, y_pt = pc.decompress(pk)
    ok_g, g_pt = pc.decompress(gamma)
    s_ok = fe.is_canonical_scalar(s)
    h_pt = hash_to_curve(pk, alpha)
    h_enc = pc.compress(h_pt)
    t = pk.shape[-1]
    p2 = ph.const_rows([SUITE, 0x02], t)
    cdata = jnp.concatenate(
        [p2, h_enc, gamma.astype(jnp.int32), u.astype(jnp.int32),
         v.astype(jnp.int32)],
        axis=0,
    )  # [130, T]
    c16 = ph.sha512_fixed(cdata)[:16]
    return ok_y & ok_g & s_ok, c16, h_pt, y_pt, g_pt


def vrf_core_bc(pk, gamma, u, v, s, alpha):
    """(ok_pre[T], c16, (H, Γ, U', V', 8Γ)) — the batch-compat per-lane
    twin of vrf_core (same ladder stage, derived challenge)."""
    ok_pre, c16, h_pt, y_pt, g_pt = vrf_core_bc_prep(pk, gamma, u, v, s, alpha)
    return ok_pre, c16, vrf_core_ladders(c16, s, h_pt, y_pt, g_pt)


# ---------------------------------------------------------------------------
# Finish: shared compression + challenge/beta + leader checks
# ---------------------------------------------------------------------------


class CoreVerdicts(NamedTuple):
    ok_ocert_sig: jnp.ndarray  # [T] bool
    ok_kes_sig: jnp.ndarray
    ok_vrf: jnp.ndarray
    ok_leader: jnp.ndarray
    leader_ambiguous: jnp.ndarray
    eta: jnp.ndarray  # [32, T] int32 bytes
    leader_value: jnp.ndarray  # [32, T] int32 bytes (big-endian value)


def _lt_be(a, b):
    """Big-endian lexicographic a < b over [32, T] byte arrays -> bool[T]."""
    lt = jnp.zeros_like(a[0], dtype=bool)
    gt = jnp.zeros_like(lt)
    for i in range(a.shape[0]):
        lt = lt | (~gt & (a[i] < b[i]))
        gt = gt | (~lt & (a[i] > b[i]))
    return lt


def finish_core(
    ok_ed_pre, ed_point, ed_r,
    ok_kes_pre, kes_point, kes_r,
    ok_vrf_pre, vrf_points, c,
    beta_decl, thr_lo, thr_hi,
):
    """All byte arrays [n, T] int32; points limb-first."""
    t = c.shape[-1]
    encs = pc.compress_many([ed_point, kes_point, *vrf_points])
    ok_ed = ok_ed_pre & jnp.all(encs[0] == ed_r.astype(jnp.int32), axis=0)
    ok_kes = ok_kes_pre & jnp.all(encs[1] == kes_r.astype(jnp.int32), axis=0)

    h_enc, gamma_enc, u_enc, v_enc, g8_enc = encs[2:]
    p2 = ph.const_rows([SUITE, 0x02], t)
    cdata = jnp.concatenate([p2, h_enc, gamma_enc, u_enc, v_enc], axis=0)
    c_prime = ph.sha512_fixed(cdata)[:16]
    p3 = ph.const_rows([SUITE, 0x03], t)
    beta = ph.sha512_fixed(jnp.concatenate([p3, g8_enc], axis=0))

    c = c.astype(jnp.int32)
    beta_decl = beta_decl.astype(jnp.int32)
    ok_proof = ok_vrf_pre & jnp.all(c_prime == c, axis=0)
    ok_vrf = ok_proof & jnp.all(beta == beta_decl, axis=0)

    tag_l = ph.const_rows([ord("L")], t)
    lv = ph.blake2b_fixed(jnp.concatenate([tag_l, beta_decl], axis=0), 65, 32)
    tag_n = ph.const_rows([ord("N")], t)
    eta1 = ph.blake2b_fixed(jnp.concatenate([tag_n, beta_decl], axis=0), 65, 32)
    eta = ph.blake2b_fixed(eta1, 32, 32)

    thr_lo = thr_lo.astype(jnp.int32)
    thr_hi = thr_hi.astype(jnp.int32)
    certain_win = _lt_be(lv, thr_lo)
    certain_loss = ~_lt_be(lv, thr_hi)
    ambiguous = ~certain_win & ~certain_loss
    return CoreVerdicts(ok_ed, ok_kes, ok_vrf, certain_win, ambiguous, eta, lv)


def finish_pbft_core(ok_ed_pre, ed_point, ed_r):
    """A Byron lane's finish: the Ed25519 check's point compressed and
    compared with the signature's R -> ok [T] bool."""
    enc = pc.compress_many([ed_point])[0]
    return ok_ed_pre & jnp.all(enc == ed_r.astype(jnp.int32), axis=0)


class TPraosCoreVerdicts(NamedTuple):
    ok_ocert_sig: jnp.ndarray  # [T] bool
    ok_kes_sig: jnp.ndarray
    ok_vrf: jnp.ndarray  # both certificates
    ok_leader: jnp.ndarray
    leader_ambiguous: jnp.ndarray
    eta: jnp.ndarray  # [32, T] Blake2b-256(beta_eta)
    leader_value: jnp.ndarray  # [64, T] the raw beta_L (big-endian value)
    ok_vrf_nonce: jnp.ndarray  # [T] the nonce certificate alone
    ok_vrf_leader: jnp.ndarray  # [T] the leader certificate alone


def _proof_ok(ok_pre, encs, c, beta_decl):
    """One ECVRF proof's tail over its five compressed points (H, Γ, U,
    V, 8Γ): the challenge recomputed and the output compared with the
    declared one."""
    t = c.shape[-1]
    h_enc, gamma_enc, u_enc, v_enc, g8_enc = encs
    p2 = ph.const_rows([SUITE, 0x02], t)
    cdata = jnp.concatenate([p2, h_enc, gamma_enc, u_enc, v_enc], axis=0)
    c_prime = ph.sha512_fixed(cdata)[:16]
    p3 = ph.const_rows([SUITE, 0x03], t)
    beta = ph.sha512_fixed(jnp.concatenate([p3, g8_enc], axis=0))
    return (ok_pre & jnp.all(c_prime == c.astype(jnp.int32), axis=0)
            & jnp.all(beta == beta_decl, axis=0))


def finish_tp_core(
    ok_ed_pre, ed_point, ed_r,
    ok_kes_pre, kes_point, kes_r,
    ok_eta_pre, eta_points, c_eta,
    ok_l_pre, l_points, c_l,
    beta_eta, beta_l, thr_lo, thr_hi, overlay,
):
    """The TPraos finish. Byte arrays [n, T] int32 (`beta_*`, `thr_*`
    64 rows: the leader rule is nat(beta_L) / 2^512 < 1 - (1-f)^sigma on
    the RAW output, cardano-protocol-tpraos `checkLeaderValue`);
    `overlay` [T], nonzero on a lane whose slot the overlay schedule
    gave its issuer: there the threshold is not consulted (`ok_leader`
    forced, `leader_ambiguous` cleared; pbftVrfChecks). eta is
    Blake2b-256(beta_eta), the UPDN rule's contribution."""
    encs = pc.compress_many(
        [ed_point, kes_point, *eta_points, *l_points])
    ok_ed = ok_ed_pre & jnp.all(encs[0] == ed_r.astype(jnp.int32), axis=0)
    ok_kes = ok_kes_pre & jnp.all(encs[1] == kes_r.astype(jnp.int32), axis=0)
    beta_eta = beta_eta.astype(jnp.int32)
    beta_l = beta_l.astype(jnp.int32)
    ok_e = _proof_ok(ok_eta_pre, encs[2:7], c_eta, beta_eta)
    ok_l = _proof_ok(ok_l_pre, encs[7:12], c_l, beta_l)
    eta = ph.blake2b_fixed(beta_eta, 64, 32)
    over = overlay != 0
    certain_win = _lt_be(beta_l, thr_lo.astype(jnp.int32))
    ambiguous = (~certain_win & _lt_be(beta_l, thr_hi.astype(jnp.int32))
                 & ~over)
    return TPraosCoreVerdicts(
        ok_ed, ok_kes, ok_e & ok_l, certain_win | over, ambiguous, eta,
        beta_l, ok_e, ok_l,
    )


def verify_praos_core(
    ed_pk, ed_r, ed_s, ed_hblocks, ed_hnblocks,
    kes_vk, kes_period, kes_r, kes_s, kes_vk_leaf, kes_siblings,
    kes_hblocks, kes_hnblocks,
    vrf_pk, vrf_gamma, vrf_c, vrf_s, vrf_alpha,
    beta_decl, thr_lo, thr_hi,
    *, kes_depth: int,
) -> CoreVerdicts:
    """The whole fused hot path over one tile (argument order mirrors
    protocol/batch.verify_praos, transposed to limb-first layout)."""
    ok_ed_pre, ed_point = ed_core(ed_pk, ed_s, ed_hblocks, ed_hnblocks)
    ok_kes_pre, kes_point = kes_core(
        kes_vk, kes_period, kes_s, kes_vk_leaf, kes_siblings,
        kes_hblocks, kes_hnblocks, kes_depth,
    )
    ok_vrf_pre, vrf_points = vrf_core(vrf_pk, vrf_gamma, vrf_c, vrf_s, vrf_alpha)
    return finish_core(
        ok_ed_pre, ed_point, ed_r,
        ok_kes_pre, kes_point, kes_r,
        ok_vrf_pre, vrf_points, vrf_c,
        beta_decl, thr_lo, thr_hi,
    )


def verify_praos_core_bc(
    ed_pk, ed_r, ed_s, ed_hblocks, ed_hnblocks,
    kes_vk, kes_period, kes_r, kes_s, kes_vk_leaf, kes_siblings,
    kes_hblocks, kes_hnblocks,
    vrf_pk, vrf_gamma, vrf_u, vrf_v, vrf_s, vrf_alpha,
    beta_decl, thr_lo, thr_hi,
    *, kes_depth: int,
) -> CoreVerdicts:
    """The composed hot path over BATCH-COMPATIBLE proofs: identical to
    verify_praos_core except the vrf challenge is derived on device from
    the announced U, V (vrf_core_bc); ed/kes/finish are byte-identical."""
    ok_ed_pre, ed_point = ed_core(ed_pk, ed_s, ed_hblocks, ed_hnblocks)
    ok_kes_pre, kes_point = kes_core(
        kes_vk, kes_period, kes_s, kes_vk_leaf, kes_siblings,
        kes_hblocks, kes_hnblocks, kes_depth,
    )
    ok_vrf_pre, c16, vrf_points = vrf_core_bc(
        vrf_pk, vrf_gamma, vrf_u, vrf_v, vrf_s, vrf_alpha
    )
    return finish_core(
        ok_ed_pre, ed_point, ed_r,
        ok_kes_pre, kes_point, kes_r,
        ok_vrf_pre, vrf_points, c16,
        beta_decl, thr_lo, thr_hi,
    )
