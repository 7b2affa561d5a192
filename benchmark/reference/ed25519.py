"""[benchmark copy of ouroboros_consensus_tpu/ops/host/ed25519.py: the plain reference may
import nothing of the program, and no later PR may move the yardstick]

Pure-Python Ed25519 (RFC 8032) host reference implementation.

This is the CPU reference against which the batched JAX kernels
(ops/ed25519_batch.py) are differentially tested, and the sign-side
primitive used by the chain synthesizer (tools/db_synthesizer.py).

Reference equivalents: the external `cardano-crypto-class` package's
libsodium-backed `Ed25519DSIGN` (called from the Praos hot path at
ouroboros-consensus-protocol/.../Protocol/Praos.hs:580 for OCert cold-key
checks). Verification is cofactorless (checks s*B == R + h*A exactly),
matching libsodium's crypto_sign_verify_detached semantics.

Exposes low-level group operations (field, point add/mul, decompress)
because the ECVRF implementation (ops/host/ecvrf.py) builds on them.
"""

from __future__ import annotations

import hashlib

# ---------------------------------------------------------------------------
# Field GF(2^255 - 19)
# ---------------------------------------------------------------------------

P = 2**255 - 19
# Group order: L = 2^252 + 27742317777372353535851937790883648493
L = 2**252 + 27742317777372353535851937790883648493
# Edwards curve constant d = -121665/121666 mod p
D = (-121665 * pow(121666, P - 2, P)) % P
SQRT_M1 = pow(2, (P - 1) // 4, P)  # sqrt(-1) mod p
# Montgomery curve25519 constant (for Elligator2 in ECVRF)
MONT_A = 486662
# sqrt(-486664) mod p, used in the Montgomery -> Edwards birational map.
# Chosen as the even root to fix a deterministic mapping.
_s = pow(-486664 % P, (P + 3) // 8, P)
if (_s * _s) % P != (-486664) % P:
    _s = (_s * SQRT_M1) % P
assert (_s * _s) % P == (-486664) % P
SQRT_M486664 = _s if _s % 2 == 0 else P - _s


def fe_inv(x: int) -> int:
    return pow(x, P - 2, P)


def fe_sqrt(x: int) -> int | None:
    """Square root mod p (returns the root with even low bit), or None."""
    r = pow(x, (P + 3) // 8, P)
    if (r * r) % P != x % P:
        r = (r * SQRT_M1) % P
    if (r * r) % P != x % P:
        return None
    return r if r % 2 == 0 else P - r


def is_square(x: int) -> bool:
    return x % P == 0 or pow(x, (P - 1) // 2, P) == 1


# ---------------------------------------------------------------------------
# Edwards point arithmetic (extended homogeneous coordinates X,Y,Z,T)
# ---------------------------------------------------------------------------

# Base point: y = 4/5, x recovered with even-ness per RFC 8032.
_by = (4 * fe_inv(5)) % P
_bx2 = ((_by * _by - 1) * fe_inv(D * _by * _by + 1)) % P
_bx = fe_sqrt(_bx2)
assert _bx is not None
if _bx % 2 != 0:
    _bx = P - _bx
B = (_bx, _by, 1, (_bx * _by) % P)
IDENT = (0, 1, 1, 0)


def point_add(p, q):
    """Unified addition (complete for twisted Edwards a=-1)."""
    X1, Y1, Z1, T1 = p
    X2, Y2, Z2, T2 = q
    A_ = (Y1 - X1) * (Y2 - X2) % P
    B_ = (Y1 + X1) * (Y2 + X2) % P
    C_ = 2 * T1 * T2 * D % P
    D_ = 2 * Z1 * Z2 % P
    E = B_ - A_
    F = D_ - C_
    G = D_ + C_
    H = B_ + A_
    return (E * F % P, G * H % P, F * G % P, E * H % P)


def point_double(p):
    """Dedicated doubling (dbl-2008-hwcd)."""
    X1, Y1, Z1, _ = p
    A_ = X1 * X1 % P
    B_ = Y1 * Y1 % P
    C_ = 2 * Z1 * Z1 % P
    H = (A_ + B_) % P
    E = (H - (X1 + Y1) * (X1 + Y1)) % P
    G = (A_ - B_) % P
    F = (C_ + G) % P
    return (E * F % P, G * H % P, F * G % P, E * H % P)


def point_neg(p):
    X, Y, Z, T = p
    return (P - X if X else 0, Y, Z, P - T if T else 0)


_BASE_COMB: list | None = None


def _base_comb():
    """Lazy fixed-base table: COMB[w][d] = d * 256^w * B as extended
    coords — turns every s*B into 32 point adds (the host synthesizer's
    per-block Ed25519/KES signing cost would otherwise be a full ladder)."""
    global _BASE_COMB
    if _BASE_COMB is None:
        tbl = []
        wbase = B
        for _w in range(32):
            row = [IDENT]
            acc = wbase
            for _d in range(1, 256):
                row.append(acc)
                acc = point_add(acc, wbase)
            tbl.append(row)
            for _ in range(8):
                wbase = point_double(wbase)
        _BASE_COMB = tbl
    return _BASE_COMB


def base_point_mul(s: int):
    """s*B via the fixed-base comb (s < 2^256)."""
    tbl = _base_comb()
    q = IDENT
    for w in range(32):
        d = (s >> (8 * w)) & 0xFF
        if d:
            q = point_add(q, tbl[w][d])
    return q


def point_mul(s: int, p):
    q = IDENT
    while s > 0:
        if s & 1:
            q = point_add(q, p)
        p = point_double(p)
        s >>= 1
    return q


def point_equal(p, q) -> bool:
    X1, Y1, Z1, _ = p
    X2, Y2, Z2, _ = q
    return (X1 * Z2 - X2 * Z1) % P == 0 and (Y1 * Z2 - Y2 * Z1) % P == 0


def point_compress(p) -> bytes:
    X, Y, Z, _ = p
    zi = fe_inv(Z)
    x = X * zi % P
    y = Y * zi % P
    return int.to_bytes(y | ((x & 1) << 255), 32, "little")


def point_decompress(s: bytes):
    """Decode 32-byte point encoding; None on failure (non-canonical y,
    non-residue x^2, or x=0 with sign bit set)."""
    if len(s) != 32:
        return None
    n = int.from_bytes(s, "little")
    sign = n >> 255
    y = n & ((1 << 255) - 1)
    if y >= P:
        return None
    x2 = (y * y - 1) * fe_inv(D * y * y + 1) % P
    x = fe_sqrt(x2)
    if x is None:
        return None
    if x == 0 and sign:
        return None
    if (x & 1) != sign:
        x = P - x
    return (x, y, 1, x * y % P)


def point_is_on_curve(p) -> bool:
    X, Y, Z, T = p
    zi = fe_inv(Z)
    x, y = X * zi % P, Y * zi % P
    return (-x * x + y * y - 1 - D * x * x % P * y % P * y) % P == 0


# ---------------------------------------------------------------------------
# Ed25519 sign / verify (RFC 8032)
# ---------------------------------------------------------------------------


def _sha512(data: bytes) -> bytes:
    return hashlib.sha512(data).digest()


def _clamp(b: bytes) -> int:
    a = bytearray(b[:32])
    a[0] &= 248
    a[31] &= 127
    a[31] |= 64
    return int.from_bytes(bytes(a), "little")


def secret_expand(seed: bytes):
    h = _sha512(seed[:32])
    return _clamp(h[:32]), h[32:]


from functools import lru_cache


@lru_cache(maxsize=4096)
def expand_for_staging(seed: bytes):
    """(clamped scalar LE bytes, prefix, pk bytes) — cached: batched
    forging repeats the same few pool seeds across thousands of lanes."""
    a, prefix = secret_expand(seed)
    return int.to_bytes(a, 32, "little"), prefix, secret_to_public(seed)


def secret_to_public(seed: bytes) -> bytes:
    a, _ = secret_expand(seed)
    return point_compress(base_point_mul(a))


def sign(seed: bytes, msg: bytes) -> bytes:
    a, prefix = secret_expand(seed)
    A_enc = point_compress(base_point_mul(a))
    r = int.from_bytes(_sha512(prefix + msg), "little") % L
    R_enc = point_compress(base_point_mul(r))
    h = int.from_bytes(_sha512(R_enc + A_enc + msg), "little") % L
    s = (r + h * a) % L
    return R_enc + int.to_bytes(s, 32, "little")


def verify(public: bytes, msg: bytes, sig: bytes) -> bool:
    if len(sig) != 64 or len(public) != 32:
        return False
    A = point_decompress(public)
    R = point_decompress(sig[:32])
    if A is None or R is None:
        return False
    s = int.from_bytes(sig[32:], "little")
    if s >= L:
        return False
    h = int.from_bytes(_sha512(sig[:32] + public + msg), "little") % L
    # Cofactorless check: s*B == R + h*A
    return point_equal(point_mul(s, B), point_add(R, point_mul(h, A)))
