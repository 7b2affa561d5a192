"""Abstract domains for the octrange jaxpr interpreter (analysis/absint.py).

Two pluggable domains:

  Interval  — value bounds as exact Python ints (arbitrary precision,
              so 20 * B_MAX^2-style products never lose bits), at
              PER-ROW granularity along the limb axis: an abstract
              value is one (lo, hi) covering the whole tensor, a `Rows`
              tuple with one (lo, hi) per index along axis 0 (the
              limb-FIRST ops/pk convention), or a `LastRows` tuple per
              index along the MINOR axis (the XLA-twin ops/field.py
              [..., 20] convention).  The limb kernels' safety story is
              inherently per-row — `mul`'s rows 39-40 hold only carry
              residues, SUBC's top limb is 12287 while the others reach
              2^15.5, and the FOLD/FOLD^2 wraps multiply exactly those
              rows — so a whole-tensor bound provably cannot certify
              them (it flags the `top * FOLD^2` fold of `limbs.mul` /
              `field.mul` that is in fact bounded by ~21 * FOLD^2).
              The interpreter checks every SIGNED integer eqn against
              its dtype range; UNSIGNED arithmetic wraps to the full
              dtype range silently (two's-complement wrap is defined
              XLA semantics and the SHA-512/Blake2b lanes rely on it),
              and bitwise ops never overflow by construction.
  Taint     — a frozenset of `level:label` marks with two levels:
              `wire`  — untrusted but PUBLIC wire data (signatures,
                        keys, proofs: everything a verifier sees is
                        public, so wire taint may steer memory access),
              `secret`— sign-path secrets (scalars, nonces) that must
                        never reach control flow or an access pattern.

Widening (for scan/while fixpoints) jumps each growing bound to the
next rung of a power-ladder so the fixpoint terminates in a handful of
iterations; _WIDEN_TOP is the ladder's top and doubles as the domain's
"unbounded" sentinel (any bound at or past it means the interpreter
could not prove a finite bound).
"""

from __future__ import annotations

from typing import FrozenSet, Tuple

Interval = Tuple[int, int]  # (lo, hi), inclusive, exact Python ints
Taint = FrozenSet[str]  # {"secret:a", "wire:ed_s", ...}

# the widening ladder top: far above any real 64-bit-dtype range, so a
# bound that climbs here is genuinely unprovable, not merely large
_WIDEN_TOP = 1 << 200

# rungs chosen around the representation constants this repo actually
# uses (13-bit limbs, the B_MAX=9500 nearly-normalized bound, bytes,
# 2^16 packing, u32/u64 hash words) so the first widening usually lands
# exactly on the invariant bound.  9500 is load-bearing: a field-element
# loop carry that widened past it to 2^14 would make the very next
# `mul` bound 20 * (2^14)^2 > 2^31 and the fixpoint could never prove
# the B_MAX invariant the kernels actually maintain.
_LADDER = [
    0, 1, 2, 255, 256, 8191, 8192, 9500, (1 << 14), (1 << 16), (1 << 17),
    (1 << 20), (1 << 26), (1 << 31) - 1, (1 << 32) - 1, (1 << 40),
    (1 << 63) - 1, (1 << 64) - 1, (1 << 80), (1 << 128), _WIDEN_TOP,
]

NO_TAINT: Taint = frozenset()


class Rows(tuple):
    """Per-row (axis-0) intervals: a tuple of (lo, hi) pairs, one per
    index along the tensor's leading axis. Always build through
    `rows()` so an all-equal tuple canonicalizes to a plain uniform
    interval — canonical forms make fixpoint equality checks and memo
    keys stable. This is the limb-first (ops/pk) convention: limbs
    occupy axis 0, lanes the tail."""

    __slots__ = ()


class LastRows(tuple):
    """Per-row intervals along the LAST axis — the XLA-twin convention
    (ops/field.py, ops/bigint.py: shape [..., 20] with limbs minor).
    Same canonical forms as Rows; build through `last_rows()`. A value
    is never both: mixing conventions in one op collapses the less
    structured side (sound, just less precise)."""

    __slots__ = ()


def _canon(cls, ivs):
    ivs = tuple(ivs)
    if not ivs:
        return (0, 0)  # zero-extent axis: any bound holds vacuously
    first = ivs[0]
    for v in ivs[1:]:
        if v != first:
            return cls(ivs)
    return first


def rows(ivs) -> "Rows | Interval":
    return _canon(Rows, ivs)


def last_rows(ivs) -> "LastRows | Interval":
    return _canon(LastRows, ivs)


def rows_of(a, n: int) -> list:
    """Expand an abstract value to n per-axis-0-row intervals (LastRows
    structure lives on a different axis: collapse it)."""
    if isinstance(a, Rows):
        assert len(a) == n, (len(a), n)
        return list(a)
    return [collapse(a)] * n


def last_rows_of(a, n: int) -> list:
    if isinstance(a, LastRows):
        assert len(a) == n, (len(a), n)
        return list(a)
    return [collapse(a)] * n


def collapse(a) -> Interval:
    """Whole-tensor bound: the join of all rows."""
    if isinstance(a, (Rows, LastRows)):
        return (min(v[0] for v in a), max(v[1] for v in a))
    return a


def _zip_any(a, b, f):
    """Apply f pairwise, preserving whichever row structure the two
    sides share (same class, same length); collapse otherwise."""
    for cls, build in ((Rows, rows), (LastRows, last_rows)):
        ar, br = isinstance(a, cls), isinstance(b, cls)
        if not (ar or br):
            continue
        other = b if ar else a
        if isinstance(other, (Rows, LastRows)) and not isinstance(
            other, cls
        ):
            break  # mixed conventions: collapse both
        n = len(a) if ar else len(b)
        if ar and br and len(a) != len(b):
            break  # defensive; same-var joins match
        ex = last_rows_of if cls is LastRows else rows_of
        return build(f(x, y) for x, y in zip(ex(a, n), ex(b, n)))
    return f(collapse(a), collapse(b))


def iv_join_any(a, b):
    """Join that preserves row structure when either side has it."""
    if not isinstance(a, (Rows, LastRows)) and not isinstance(
        b, (Rows, LastRows)
    ):
        return iv_join(a, b)
    return _zip_any(a, b, iv_join)


def iv_widen_any(old, new):
    if not isinstance(old, (Rows, LastRows)) and not isinstance(
        new, (Rows, LastRows)
    ):
        return iv_widen(old, new)
    return _zip_any(old, new, iv_widen)


def iv(lo: int, hi: int) -> Interval:
    assert lo <= hi, (lo, hi)
    return (int(lo), int(hi))


def iv_const(v) -> Interval:
    v = int(v)
    return (v, v)


def iv_join(a: Interval, b: Interval) -> Interval:
    return (min(a[0], b[0]), max(a[1], b[1]))


def iv_add(a: Interval, b: Interval) -> Interval:
    return (a[0] + b[0], a[1] + b[1])


def iv_sub(a: Interval, b: Interval) -> Interval:
    return (a[0] - b[1], a[1] - b[0])


def iv_mul(a: Interval, b: Interval) -> Interval:
    cands = (a[0] * b[0], a[0] * b[1], a[1] * b[0], a[1] * b[1])
    return (min(cands), max(cands))


def iv_scale(a: Interval, n: int) -> Interval:
    """n non-negative copies summed (reduce_sum / dot contraction)."""
    assert n >= 0
    return (a[0] * n, a[1] * n)


def _tdiv(a: int, b: int) -> int:
    """C-style truncated division (XLA integer `div` semantics)."""
    q = abs(a) // abs(b)
    return -q if (a < 0) != (b < 0) else q


def iv_div(a: Interval, b: Interval) -> Interval:
    """Integer division; divisor interval must exclude 0 for precision,
    otherwise falls back to the symmetric magnitude bound."""
    if b[0] <= 0 <= b[1]:
        m = max(abs(a[0]), abs(a[1]))  # |a / b| <= |a| for |b| >= 1
        return (-m, m)
    cands = [_tdiv(x, y) for x in a for y in b]
    return (min(cands), max(cands))


def iv_rem(a: Interval, b: Interval) -> Interval:
    """XLA `rem` takes the dividend's sign; |rem| < |divisor|."""
    m = max(abs(b[0]), abs(b[1]))
    if m == 0:
        return (0, 0)
    lo = -(m - 1) if a[0] < 0 else 0
    hi = (m - 1) if a[1] > 0 else 0
    return (min(lo, 0), max(hi, 0))


def iv_shr(a: Interval, s: Interval) -> Interval:
    """Arithmetic shift right == floor division by a power of two.
    Python's >> on negative ints is arithmetic, matching XLA."""
    slo, shi = max(0, s[0]), min(128, max(0, s[1]))
    cands = [x >> y for x in a for y in (slo, shi)]
    return (min(cands), max(cands))


def iv_shl(a: Interval, s: Interval) -> Interval:
    slo, shi = max(0, s[0]), min(128, max(0, s[1]))
    cands = [x << y for x in a for y in (slo, shi)]
    return (min(cands), max(cands))


def _bits_cover(hi: int) -> int:
    """Smallest all-ones value covering hi (>= 0)."""
    return (1 << max(hi, 0).bit_length()) - 1


def iv_and(a: Interval, b: Interval, dtype_range: Interval) -> Interval:
    """Bitwise AND. With one non-negative operand the result is bounded
    by it (the `v & MASK` idiom works on negative v too); with both
    possibly negative fall back to the dtype range (never an overflow —
    bitwise results always fit the dtype)."""
    if a[0] >= 0 and b[0] >= 0:
        return (0, min(_bits_cover(a[1]), _bits_cover(b[1])))
    if a[0] >= 0:
        return (0, a[1])
    if b[0] >= 0:
        return (0, b[1])
    return dtype_range


def iv_or(a: Interval, b: Interval, dtype_range: Interval) -> Interval:
    if a[0] >= 0 and b[0] >= 0:
        return (max(a[0], b[0]), max(_bits_cover(a[1]), _bits_cover(b[1])))
    return dtype_range


def iv_xor(a: Interval, b: Interval, dtype_range: Interval) -> Interval:
    if a[0] >= 0 and b[0] >= 0:
        return (0, max(_bits_cover(a[1]), _bits_cover(b[1])))
    return dtype_range


def iv_widen(old: Interval, new: Interval) -> Interval:
    """Widen `old` toward `new` along the threshold ladder: any bound
    that moved jumps straight to the next rung, so a scan fixpoint
    stabilizes in O(len(ladder)) iterations worst case."""
    lo, hi = old
    if new[0] < lo:
        lo = -_WIDEN_TOP
        for r in _LADDER:
            if -r <= new[0]:
                lo = -r
                break
    if new[1] > hi:
        hi = _WIDEN_TOP
        for r in _LADDER:
            if r >= new[1]:
                hi = r
                break
    return (lo, hi)


def iv_contains(outer: Interval, inner: Interval) -> bool:
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def iv_is_top(a: Interval) -> bool:
    return a[0] <= -_WIDEN_TOP or a[1] >= _WIDEN_TOP


# ---------------------------------------------------------------------------
# Taint
# ---------------------------------------------------------------------------


def taint(level: str, label: str) -> Taint:
    assert level in ("wire", "secret"), level
    return frozenset((f"{level}:{label}",))


def taint_join(*ts: Taint) -> Taint:
    out: Taint = NO_TAINT
    for t in ts:
        if t:
            out = out | t if out else t
    return out


def taint_secret(t: Taint) -> Taint:
    return frozenset(m for m in t if m.startswith("secret:"))


def taint_wire(t: Taint) -> Taint:
    return frozenset(m for m in t if m.startswith("wire:"))
