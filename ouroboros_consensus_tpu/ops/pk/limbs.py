"""Limb-first GF(2^255-19) field + mod-L scalar arithmetic ([20, T] int32).

The transposed twin of ops/field.py and ops/bigint.py / ops/scalar.py:
identical representation invariants (13-bit limbs in int32, nearly
normalized bound B_MAX), identical reduction identities (2^260 == 608
mod p), but with the limb axis FIRST so that inside Pallas kernels the
limbs occupy sublanes and the batch tile occupies lanes.

The multiply is pad-accumulate at vreg-aligned rows from eight
row-offset copies: 2D broadcasts, no roll, no scatter, so both Mosaic
and XLA vectorize it fully. A vreg holds 8 int32 rows (sublanes), so a
term padded to row i with i % 8 != 0 costs a sublane shift and select
over every vreg it spans; padding each of the 20 terms to its own row
of a [41, T] accumulator paid that 17 times a multiply. Instead the
multiplicand is placed once at each row offset r = 0..7 (seven shifts)
and term i = 8q + r is that copy times b[i], added at row 8q: whole
vregs at an aligned row. Every ladder step of every stage kernel is
these multiplies.

Reference equivalent: libsodium fe25519 / sc25519 (see ops/field.py,
ops/scalar.py docstrings for the reference call sites).

Bound certification (octrange, analysis/absint.py): the carry headroom
claims in the docstrings below are machine-checked per ROW of the limb
axis — inputs seeded at the B_MAX = 9500 nearly-normalized bound (or
8191 for normalized scalars), every int32 intermediate proven inside
2^31 at the production lane counts (`python -m
ouroboros_consensus_tpu.analysis range`), pinned in
analysis/certified.json. Per-row tracking is what makes `mul` provable
at all: rows 39-40 of the accumulator hold only carry residues, so the
FOLD^2 fold on row 40 is bounded by ~21·FOLD^2, far under the
whole-tensor worst case 9500·FOLD^2 > 2^31. `sum_mod_l`'s per-term
normalization is proven at the 3×87381 = 262,143-lane-term boundary
(just under the 2^31/8191 = 262,177 threshold an un-normalized
accumulator trips) and regression-flagged when reverted
(tests/test_absint.py).
"""

from __future__ import annotations

import numpy as np
from jax import lax
from jax import numpy as jnp

from .. import field as _f

BITS = _f.BITS  # 13
NLIMBS = _f.NLIMBS  # 20
MASK = _f.MASK
FOLD = _f.FOLD  # 19 * 2^5
P_INT = _f.P_INT
D_INT = _f.D_INT
SQRT_M1_INT = _f.SQRT_M1_INT

_SUBC_COL = _f.SUBC.reshape(NLIMBS, 1)  # [20, 1] broadcasts over lanes
_P_COL = _f.P_LIMBS.reshape(NLIMBS, 1)


# ---------------------------------------------------------------------------
# Constants inside kernels
#
# Pallas kernels may not close over array constants (jax requires them
# as inputs), and this Mosaic version cannot even broadcast [n, 1]
# columns over lanes. But every constant here is a compile-time Python
# int vector — so inside a kernel each one is materialized as a stack
# of scalar-immediate fills ([n, T], memoized per trace), which lowers
# to native scalar->vector broadcasts. Outside kernels the accessors
# return plain [n, 1] jnp constants and XLA broadcasting applies.
# ---------------------------------------------------------------------------

# The context dict is read at TRACE time only and every per-trace entry
# is rebuilt on __enter__, so the jit capture octlint flags cannot
# desync; the whole module is the reviewed exception.
# octlint: disable-file=OCT103
_KCTX: dict = {"t": None, "cache": None}


def kernel_consts(t: int):
    """Enter kernel-constants mode for a trace over tile width t."""

    class _Ctx:
        def __enter__(self):
            _KCTX["t"] = int(t)
            _KCTX["cache"] = {}

        def __exit__(self, *exc):
            _KCTX["t"] = None
            _KCTX["cache"] = None

    return _Ctx()


def _named_consts():
    from ..host import ed25519 as _he

    return {
        "subc": _f.SUBC,
        "p": _f.P_LIMBS,
        "one": _f.ONE,
        "d": _f.int_to_limbs_np(D_INT),
        "sqrt_m1": _f.int_to_limbs_np(SQRT_M1_INT),
        "mont_a": _f.int_to_limbs_np(_he.MONT_A % P_INT),
        "sqrt_m486664": _f.int_to_limbs_np(_he.SQRT_M486664 % P_INT),
    }


def _fill_rows(ints, t):
    return jnp.stack(
        [jnp.full((t,), int(v), jnp.int32) for v in ints], axis=0
    )


def _kc(name):
    arr = _NP_CONSTS[name]
    if _KCTX["t"] is None:
        return jnp.asarray(np.asarray(arr, np.int32).reshape(-1, 1))
    cache = _KCTX["cache"]
    if name not in cache:
        cache[name] = _fill_rows(np.asarray(arr).reshape(-1), _KCTX["t"])
    return cache[name]


def constant(x: int):
    """Field constant: [20, 1] outside kernels (XLA broadcasts), full
    [20, T] scalar-immediate fills inside kernels."""
    x = x % P_INT
    if _KCTX["t"] is None:
        return jnp.asarray(_f.int_to_limbs_np(x).reshape(NLIMBS, 1))
    cache = _KCTX["cache"]
    key = ("int", x)
    if key not in cache:
        cache[key] = _fill_rows(_f.int_to_limbs_np(x), _KCTX["t"])
    return cache[key]


def zeros(t: int):
    return jnp.zeros((NLIMBS, t), jnp.int32)


def ones(t: int):
    if _KCTX["t"] is None:
        return jnp.broadcast_to(_kc("one"), (NLIMBS, t))
    return _kc("one")


# ---------------------------------------------------------------------------
# Carries and ring ops
# ---------------------------------------------------------------------------


def _carry_pass(z):
    c = z >> BITS
    wrapped = jnp.concatenate([c[-1:] * FOLD, c[:-1]], axis=0)
    return (z & MASK) + wrapped


def weak_reduce(z, passes: int = 2):
    for _ in range(passes):
        z = _carry_pass(z)
    return z


def add(a, b):
    return _carry_pass(a + b)


def sub(a, b):
    return _carry_pass(a - b + _kc("subc"))


def neg(a):
    return sub(jnp.zeros_like(a), a)


def mul_small(a, k: int):
    return weak_reduce(a * k, passes=3)


SUBLANES = 8  # int32 rows a vreg holds


def _row_offset_copies(a):
    """a placed at row r of a zero array a whole number of vregs high,
    r = 0..SUBLANES-1: [24, w] for r <= 4, [32, w] above. Row r = 0 is a
    itself; each other copy is one sublane shift of a."""
    w = a.shape[-1]
    copies = []
    for r in range(SUBLANES):
        h = -(-(NLIMBS + r) // SUBLANES) * SUBLANES
        # Mosaic rejects zero-size concat operands: only emit non-empty pads
        parts = [jnp.zeros((r, w), jnp.int32)] if r else []
        parts.append(a)
        if h - NLIMBS - r:
            parts.append(jnp.zeros((h - NLIMBS - r, w), jnp.int32))
        copies.append(jnp.concatenate(parts, axis=0))
    return copies


def mul(a, b):
    """Field multiplication, [20, T] x [20, T] -> [20, T].

    Same bound analysis as ops/field.mul: coefficients < 20 * B_MAX^2 <
    2^31; carries can reach limb 40, so the accumulator is 41 rows and
    row 40 folds with weight FOLD^2 (= 2^520 mod p).

    Term i = 8q + r (a * b[i], due at row i) is the row-offset copy a_r
    times b[i], added at row 8q: every add of the accumulator is whole
    vregs at a vreg-aligned row, and the only sublane shifts are the
    seven that build a_1..a_7. Each accumulator row sums the same
    products a_j * b_i (i + j = row) as a term-by-term shift would, in
    another order: int32 addition gives the same bits.
    """
    t = max(a.shape[-1], b.shape[-1])  # constants may be [20, 1]
    copies = _row_offset_copies(a)
    # sum the terms of one row block q and one height first, then add
    # each sum into the accumulator's vreg blocks it covers
    sums = {}
    for i in range(NLIMBS):
        q, r = divmod(i, SUBLANES)
        term = copies[r] * b[i : i + 1]
        key = (q, term.shape[0])
        sums[key] = term if key not in sums else sums[key] + term
    blocks = [None] * (2 * NLIMBS // SUBLANES)  # rows 0..39
    for (q, h), s in sums.items():
        for k in range(h // SUBLANES):
            blk = s[k * SUBLANES : (k + 1) * SUBLANES]
            j = q + k
            blocks[j] = blk if blocks[j] is None else blocks[j] + blk
    # row 39 holds no product (i + j <= 38) and row 40 only what the
    # carries bring: [41, T]
    acc = jnp.concatenate(blocks + [jnp.zeros((1, t), jnp.int32)], axis=0)
    # two carry passes over 41 rows (carry cannot leave row 40)
    for _ in range(2):
        c = acc >> BITS
        acc = (acc & MASK) + jnp.concatenate(
            [jnp.zeros((1, t), jnp.int32), c[:-1]], axis=0
        )
    lo, hi, top = acc[:NLIMBS], acc[NLIMBS : 2 * NLIMBS], acc[2 * NLIMBS :]
    lo = lo + hi * FOLD
    row0 = lo[:1] + top * (FOLD * FOLD)
    lo = jnp.concatenate([row0, lo[1:]], axis=0)
    return weak_reduce(lo, passes=2)


def sqr(a):
    return mul(a, a)


def pow2k(a, k: int):
    """a^(2^k), k static. Small k unrolls; large k loops in-kernel."""
    if k <= 4:
        for _ in range(k):
            a = sqr(a)
        return a
    return lax.fori_loop(0, k, lambda _, v: sqr(v), a)


def _chain_2_250m1(x):
    t0 = sqr(x)
    t1 = mul(x, pow2k(t0, 2))  # x^9
    x11 = mul(t0, t1)
    t31 = mul(t1, sqr(x11))
    a = mul(pow2k(t31, 5), t31)
    b = mul(pow2k(a, 10), a)
    c = mul(pow2k(b, 20), b)
    d = mul(pow2k(c, 10), a)
    e = mul(pow2k(d, 50), d)
    f = mul(pow2k(e, 100), e)
    g = mul(pow2k(f, 50), d)
    return g, x11


def inv(x):
    g, x11 = _chain_2_250m1(x)
    return mul(pow2k(g, 5), x11)


def pow22523(x):
    g, _ = _chain_2_250m1(x)
    return mul(pow2k(g, 2), x)


def legendre(x):
    g, _ = _chain_2_250m1(x)
    x4 = pow2k(x, 2)
    x6 = mul(x4, sqr(x))
    return mul(pow2k(g, 4), x6)


# ---------------------------------------------------------------------------
# Canonicalization, comparison, selection
# ---------------------------------------------------------------------------


def canonical(x):
    """Unique representative in [0, p): sequential carries + cond-subs,
    exactly mirroring ops/field.canonical."""
    for _ in range(2):
        c = jnp.zeros_like(x[0])
        out = []
        for i in range(NLIMBS):
            v = x[i] + c
            out.append(v & MASK)
            c = v >> BITS
        hi = out[-1] >> 8
        out[-1] = out[-1] & 0xFF
        out[0] = out[0] + c * FOLD + hi * 19
        x = jnp.stack(out, axis=0)
    p = _kc("p")
    for _ in range(2):
        borrow = jnp.zeros_like(x[0])
        diff = []
        for i in range(NLIMBS):
            v = x[i] - p[i] - borrow
            diff.append(v & MASK)
            borrow = jnp.where(v < 0, 1, 0)
        d = jnp.stack(diff, axis=0)
        x = jnp.where((borrow == 0)[None, :], d, x)
    return x


def eq(a, b):
    """Field equality -> bool[T]."""
    return jnp.all(canonical(a) == canonical(b), axis=0)


def is_zero(a):
    return jnp.all(canonical(a) == 0, axis=0)


def select(cond, a, b):
    """cond ? a : b with cond shaped [T]."""
    return jnp.where(cond[None, :], a, b)


def parity(x):
    return canonical(x)[0] & 1


# ---------------------------------------------------------------------------
# Bytes <-> limbs (little-endian 32-byte strings, [32, T] int32)
# ---------------------------------------------------------------------------


def bytes_to_limbs(b, n: int):
    """[nbytes, T] LE bytes -> [n, T] normalized 13-bit limbs."""
    nbytes = b.shape[0]
    b = b.astype(jnp.int32)
    rows = []
    for i in range(n):
        lo_bit = i * BITS
        acc = None
        for byte in range(lo_bit // 8, min((lo_bit + BITS + 7) // 8, nbytes)):
            sh = byte * 8 - lo_bit
            v = b[byte]
            contrib = (v << sh) if sh >= 0 else (v >> (-sh))
            acc = contrib if acc is None else acc + contrib
        if acc is None:
            acc = jnp.zeros_like(b[0])
        rows.append(acc & MASK)
    return jnp.stack(rows, axis=0)


def from_bytes32(b):
    """[32, T] bytes -> nearly-normalized [20, T] limbs (no mod-p check)."""
    return bytes_to_limbs(b, NLIMBS)


def to_bytes(x):
    """Canonical field element -> [32, T] int32 bytes (values 0..255)."""
    x = canonical(x)
    rows = []
    for byte in range(32):
        lo_bit = byte * 8
        limb = lo_bit // BITS
        off = lo_bit - limb * BITS
        acc = x[limb] >> off
        if limb + 1 < NLIMBS and off + 8 > BITS:
            acc = acc | (x[limb + 1] << (BITS - off))
        rows.append(acc & 0xFF)
    return jnp.stack(rows, axis=0)


def geq_limbs(a, b):
    """a >= b for normalized equal-length limb arrays [n, T] -> bool[T]."""
    borrow = jnp.zeros_like(a[0])
    for i in range(a.shape[0]):
        v = a[i] - b[i] - borrow
        borrow = jnp.where(v < 0, 1, 0)
    return borrow == 0


# ---------------------------------------------------------------------------
# Square roots
# ---------------------------------------------------------------------------


def sqrt_ratio_ext(n, d):
    """The Shanks candidate for sqrt(n/d) and its full classification:
    (rho, good, good_alt, is_pi) where d·rho² equals +n (good), -n
    (good_alt: the root is i·rho), +i·n (is_pi) or -i·n. n/d is a QR
    iff good|good_alt; the ±i·n cases identify which non-residue class
    n/d fell in — the single-exponentiation Elligator2 (pk/verify)
    derives its branch-2 root from them. One ~254-squaring chain total."""
    d2 = sqr(d)
    d3 = mul(d, d2)
    d7 = mul(d3, sqr(d2))
    rho = mul(mul(n, d3), pow22523(mul(n, d7)))
    check = mul(d, sqr(rho))
    good = eq(check, n)
    good_alt = eq(check, neg(n))
    is_pi = eq(check, mul(constant(SQRT_M1_INT), n))
    return rho, good, good_alt, is_pi


def sqrt_ratio(n, d):
    """(ok[T], r) with r = sqrt(n/d), even-parity root (ops/field twin)."""
    rho, good, good_alt, _ = sqrt_ratio_ext(n, d)
    r = select(good, rho, mul(rho, constant(SQRT_M1_INT)))
    ok = good | good_alt
    r = select(parity(r) == 1, neg(r), r)
    return ok, r


def sqrt(x):
    return sqrt_ratio(x, ones(x.shape[-1]))


# ---------------------------------------------------------------------------
# Scalar arithmetic mod L (Barrett, limb-first twin of ops/scalar.py)
# ---------------------------------------------------------------------------

L_INT = 2**252 + 27742317777372353535851937790883648493

from .. import bigint as _bi  # noqa: E402  (host-side limb constants)

L20 = _bi.int_to_limbs_np(L_INT, 20).reshape(20, 1)
L21 = _bi.int_to_limbs_np(L_INT, 21).reshape(21, 1)
_A_LIMBS = 19
_B_LIMBS = 21
MU21 = _bi.int_to_limbs_np(
    (1 << (BITS * (_A_LIMBS + _B_LIMBS))) // L_INT, 21
).reshape(21, 1)


def _seq_carry(z):
    """Full sequential carry over rows -> (normalized, carry_out[T])."""
    c = jnp.zeros_like(z[0])
    out = []
    for i in range(z.shape[0]):
        v = z[i] + c
        out.append(v & MASK)
        c = v >> BITS
    return jnp.stack(out, axis=0), c


def _mul_limbs(a, b):
    """[n, T] x [m, T] -> [n+m, T] nearly normalized (min(n,m) <= 32)."""
    n, m = a.shape[0], b.shape[0]
    t = a.shape[-1]
    out_rows = n + m
    acc = jnp.zeros((out_rows, t), jnp.int32)
    for i in range(m):
        term = a * b[i : i + 1]
        # Mosaic rejects zero-size concat operands: only emit non-empty pads
        parts = []
        if i:
            parts.append(jnp.zeros((i, t), jnp.int32))
        parts.append(term)
        if out_rows - n - i:
            parts.append(jnp.zeros((out_rows - n - i, t), jnp.int32))
        shifted = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
        acc = acc + shifted
    for _ in range(2):
        c = acc >> BITS
        acc = (acc & MASK) + jnp.concatenate(
            [jnp.zeros((1, t), jnp.int32), c[:-1]], axis=0
        )
    return acc


def _sub_mod_2k(a, b, n: int):
    borrow = jnp.zeros_like(a[0])
    out = []
    for i in range(n):
        av = a[i] if i < a.shape[0] else jnp.zeros_like(a[0])
        bv = b[i] if i < b.shape[0] else jnp.zeros_like(b[0])
        v = av - bv - borrow
        out.append(v & MASK)
        borrow = jnp.where(v < 0, 1, 0)
    return jnp.stack(out, axis=0)


def _cond_sub(a, bcol):
    n = a.shape[0]
    b = jnp.broadcast_to(jnp.asarray(bcol), a.shape)
    d = _sub_mod_2k(a, b, n)
    return jnp.where(geq_limbs(a, b)[None, :], d, a)


def barrett_reduce40(v):
    """[40, T] normalized limbs (< 2^512) -> [20, T] limbs < L."""
    t = v.shape[-1]
    v1 = v[_A_LIMBS:]  # [21, T]
    mu = jnp.broadcast_to(_kc("mu21"), (21, t))
    prod = _mul_limbs(v1, mu)
    q = prod[_B_LIMBS:][:21]  # [21, T]
    lc = jnp.broadcast_to(_kc("l21"), (21, t))
    ql = _mul_limbs(q, lc)
    ql, _ = _seq_carry(ql)
    r = _sub_mod_2k(v, ql, 21)
    for _ in range(3):
        r = _cond_sub(r, _kc("l21"))
    return r[:20]


def reduce512(digest_bytes):
    """[64, T] LE bytes (SHA-512 output) -> [20, T] limbs < L."""
    return barrett_reduce40(bytes_to_limbs(digest_bytes, 40))


def mul_mod_l(a, b):
    """[20, T] x [20, T] normalized limb scalars (< 2^253) ->
    [20, T] limbs of a·b mod L (the per-lane coefficient products of the
    aggregated verifier, ops/pk/aggregate.py)."""
    prod = _mul_limbs(a, b)  # [40, T] nearly normalized; a·b < 2^506
    prod, _ = _seq_carry(prod)  # carry cannot leave row 39 (< 2^520)
    return barrett_reduce40(prod)


def reduce_raw_sums(v):
    """[20, T] UN-normalized limb rows (each < 2^30, e.g. the raw int32
    scatter-sums of the aggregate verifier's repeated-key coefficient
    tables: ≤ 2^17 lanes x 13-bit rows < 2^30) -> [20, T] limbs < L.
    One carry pass restores 13-bit rows (value < 2^278 fits 22 rows of
    the zero-padded 40), then the shared Barrett step reduces mod L."""
    t = v.shape[-1]
    wide = jnp.concatenate([v, jnp.zeros((40 - NLIMBS, t), jnp.int32)],
                           axis=0)
    wide, _ = _seq_carry(wide)
    return barrett_reduce40(wide)


def sum_mod_l(terms):
    """Sum a list of [20, T] limb scalars (< L each) over BOTH the list
    and the lane axis -> [20, 1] limbs < L. Each term's lane sum stays
    under int32 on its own (13-bit limbs x T ≤ 2^17 lanes < 2^30,
    asserted), but an UN-normalized cross-term accumulator does not
    (3 terms x 87k lanes overflows 2^31) — so every term is
    carry-normalized back to 13-bit rows before the cross-term add,
    bounding accumulator rows by 2^13·len(terms)."""
    acc = None
    for t in terms:
        assert t.shape[-1] <= 1 << 17, "limb-wise lane sum would overflow int32"
        s = jnp.sum(t, axis=-1, keepdims=True)
        wide = jnp.concatenate(
            [s, jnp.zeros((40 - NLIMBS, 1), jnp.int32)], axis=0
        )
        wide, _ = _seq_carry(wide)  # rows < 2^13; total < 2^260 so no
        acc = wide if acc is None else acc + wide  # carry leaves row 39
    acc, _ = _seq_carry(acc)
    return barrett_reduce40(acc)


def is_canonical_scalar(s_bytes):
    """s < L for [32, T] LE byte scalars -> bool[T]."""
    s = bytes_to_limbs(s_bytes, 20)
    lim = jnp.broadcast_to(_kc("l20"), s.shape)
    return ~geq_limbs(s, lim)


# ---------------------------------------------------------------------------
# Digit windows
# ---------------------------------------------------------------------------


def bits_from_bytes(b, nbits: int):
    """[n, T] LE bytes -> [nbits, T] bits."""
    rows = [(b[i // 8] >> (i % 8)) & 1 for i in range(nbits)]
    return jnp.stack(rows, axis=0)


def windows4_from_bytes(b, nbits: int, msb_first: bool = False):
    """[n, T] LE bytes -> [ceil(nbits/4), T] base-16 digits. msb_first
    reverses the window order at build time (Mosaic has no rev/flip)."""
    assert nbits % 4 == 0
    rows = []
    for w in range(nbits // 4):
        lo_bit = 4 * w
        byte = lo_bit // 8
        off = lo_bit % 8
        rows.append((b[byte] >> off) & 0xF)  # off is 0 or 4: no spill
    if msb_first:
        rows.reverse()
    return jnp.stack(rows, axis=0)


def windows8_from_bytes(b, nbits: int):
    """[n, T] LE bytes -> [nbits/8, T] base-256 digits."""
    assert nbits % 8 == 0
    return b[: nbits // 8].astype(jnp.int32)


def windows4_from_limbs(x, nbits: int = 256, msb_first: bool = False):
    """[20, T] normalized limbs -> [nbits/4, T] base-16 digits."""
    assert nbits % 4 == 0
    rows = []
    for w in range(nbits // 4):
        lo_bit = 4 * w
        limb = lo_bit // BITS
        off = lo_bit - limb * BITS
        acc = x[limb] >> off
        if limb + 1 < x.shape[0] and off + 4 > BITS:
            acc = acc | (x[limb + 1] << (BITS - off))
        rows.append(acc & 0xF)
    if msb_first:
        rows.reverse()
    return jnp.stack(rows, axis=0)


def windows8_from_limbs(x, nbits: int = 256):
    """[20, T] normalized limbs -> [nbits/8, T] base-256 digits."""
    assert nbits % 8 == 0
    rows = []
    for w in range(nbits // 8):
        lo_bit = 8 * w
        limb = lo_bit // BITS
        off = lo_bit - limb * BITS
        acc = x[limb] >> off
        if limb + 1 < x.shape[0] and off + 8 > BITS:
            acc = acc | (x[limb + 1] << (BITS - off))
        rows.append(acc & 0xFF)
    return jnp.stack(rows, axis=0)


# ---------------------------------------------------------------------------
# Named-constants table (after all constants above exist)
# ---------------------------------------------------------------------------

_NP_CONSTS = _named_consts()
_NP_CONSTS["l20"] = _bi.int_to_limbs_np(L_INT, 20)
_NP_CONSTS["l21"] = _bi.int_to_limbs_np(L_INT, 21)
_NP_CONSTS["mu21"] = MU21.reshape(-1)


def p_col():
    """The prime p as a per-limb column/tile array (context-aware)."""
    return _kc("p")
