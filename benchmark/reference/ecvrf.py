"""[benchmark copy of ouroboros_consensus_tpu/ops/host/ecvrf.py: the plain reference may
import nothing of the program, and no later PR may move the yardstick]

ECVRF-ED25519-SHA512-Elligator2 (IETF draft-03) host reference.

Pure-Python reference implementation of the VRF used by Praos leader
election. Reference equivalents: the C libsodium fork vendored by
`cardano-crypto-praos` ("ietfdraft03" suite), reached from the hot path at
ouroboros-consensus-protocol/.../Protocol/Praos.hs:543 (verifyCertified)
and Praos.hs:397 (evalCertified, forging side).

Proof formats:
  * draft-03 (80 bytes): Gamma (32) || c (16) || s (32).
  * batch-compatible (128 bytes): Gamma (32) || U (32) || V (32) || s (32)
    — the Badertscher–Gaži–Querejeta-Azurmendi–Russell (ESORICS 2022)
    scheme behind cardano-base's `PraosBatchCompat` VRF: the proof
    ANNOUNCES the commitment points U = k·B and V = k·H instead of the
    challenge, the verifier derives c = H(suite ‖ 2 ‖ H ‖ Γ ‖ U ‖ V)
    from the announced bytes and checks the two group equations
    U = s·B − c·Y and V = s·H − c·Γ. For an honest prover the two
    formats carry the same (Γ, s) and yield the same beta; the
    announced-points form is what makes window-level random-linear-
    combination aggregation possible (ops/pk/aggregate.py).
Output (beta) is 64 bytes for both; the format is discriminated by
proof length everywhere in the framework.

NOTE on conformance: no libsodium test vectors are available in this
offline environment; this implementation follows draft-03 semantics
(suite 0x04) and is the single source of truth for the framework — the
batched JAX verifier (ops/ecvrf_batch.py), the synthesizer's prover, and
these host functions are differentially tested against each other.
"""

from __future__ import annotations

import hashlib

from .ed25519 import (
    B,
    IDENT,
    L,
    MONT_A,
    P,
    SQRT_M1,
    SQRT_M486664,
    _clamp,
    fe_inv,
    fe_sqrt,
    is_square,
    point_add,
    point_compress,
    point_decompress,
    point_equal,
    point_mul,
    point_neg,
)

SUITE = b"\x04"
PROOF_BYTES = 80
PROOF_BYTES_BATCH = 128
OUTPUT_BYTES = 64


def _sha512(data: bytes) -> bytes:
    return hashlib.sha512(data).digest()


# ---------------------------------------------------------------------------
# Elligator2 hash-to-curve (draft-03 section 5.4.1.2 semantics)
# ---------------------------------------------------------------------------


def elligator2(r: int):
    """Map a field element r to a point on the Edwards curve.

    Deterministic Elligator2 on curve25519 followed by the birational map
    to edwards25519. Returns an extended-coordinate point (not yet
    cofactor-cleared). Sign convention: the Edwards x-coordinate is negated
    when the Montgomery v coordinate is "negative" (odd), giving a fixed
    deterministic choice mirrored exactly by the batched JAX kernel.
    """
    # u = -A / (1 + 2 r^2); if 1 + 2 r^2 == 0 use u = -A (r excluded anyway)
    t = (2 * r * r) % P
    denom = (t + 1) % P
    if denom == 0:
        denom = 1
    u = (-MONT_A * fe_inv(denom)) % P
    # w = u (u^2 + A u + 1): the Montgomery curve RHS at u
    w = u * ((u * u + MONT_A * u + 1) % P) % P
    if not is_square(w):
        # switch to the other candidate u' = -u - A; RHS becomes square
        u = (-u - MONT_A) % P
        w = u * ((u * u + MONT_A * u + 1) % P) % P
    v = fe_sqrt(w)
    assert v is not None
    # Birational map curve25519 -> edwards25519:
    #   x = sqrt(-486664) * u / v ;  y = (u - 1) / (u + 1)
    if v == 0:
        x = 0
    else:
        x = SQRT_M486664 * u % P * fe_inv(v) % P
    up1 = (u + 1) % P
    y = ((u - 1) * fe_inv(up1)) % P if up1 != 0 else 0
    # Fix sign deterministically: force x even
    if x % 2 == 1:
        x = P - x
    return (x, y, 1, x * y % P)


def hash_to_curve(pk: bytes, alpha: bytes):
    """H = cofactor * Elligator2(SHA512(suite || 0x01 || pk || alpha))."""
    h = _sha512(SUITE + b"\x01" + pk + alpha)
    r_bytes = bytearray(h[:32])
    r_bytes[31] &= 0x7F  # clear sign bit => r < 2^255
    r = int.from_bytes(bytes(r_bytes), "little") % P
    e = elligator2(r)
    # clear cofactor (multiply by 8)
    h8 = point_mul(8, e)
    return h8


def _hash_points(h, gamma, u, v) -> bytes:
    """c = first 16 bytes of SHA512(suite || 0x02 || H || Gamma || U || V)."""
    data = (
        SUITE
        + b"\x02"
        + point_compress(h)
        + point_compress(gamma)
        + point_compress(u)
        + point_compress(v)
    )
    return _sha512(data)[:16]


# ---------------------------------------------------------------------------
# Prove / verify / proof-to-hash
# ---------------------------------------------------------------------------


def _prove_parts(seed: bytes, alpha: bytes):
    """Shared prove core -> (gamma, c_bytes, s, u_enc, v_enc): both proof
    formats are serializations of the same transcript."""
    h = _sha512(seed[:32])
    x = _clamp(h[:32])
    prefix = h[32:]
    pk = point_compress(point_mul(x, B))
    H = hash_to_curve(pk, alpha)
    H_enc = point_compress(H)
    gamma = point_mul(x, H)
    # nonce k = SHA512(prefix || H) mod L   (draft-03 section 5.4.2.2)
    k = int.from_bytes(_sha512(prefix + H_enc), "little") % L
    u = point_mul(k, B)
    v = point_mul(k, H)
    c_bytes = _hash_points(H, gamma, u, v)
    c = int.from_bytes(c_bytes, "little")
    s = (k + c * x) % L
    return gamma, c_bytes, s, point_compress(u), point_compress(v)


def prove(seed: bytes, alpha: bytes) -> bytes:
    """Produce an 80-byte draft-03 proof pi for alpha under sk seed."""
    gamma, c_bytes, s, _u, _v = _prove_parts(seed, alpha)
    return point_compress(gamma) + c_bytes + int.to_bytes(s, 32, "little")


def prove_batch_compat(seed: bytes, alpha: bytes) -> bytes:
    """128-byte batch-compatible proof: Gamma ‖ U ‖ V ‖ s (the challenge
    is re-derived by the verifier from the announced U, V)."""
    gamma, _c, s, u_enc, v_enc = _prove_parts(seed, alpha)
    return point_compress(gamma) + u_enc + v_enc + int.to_bytes(s, 32, "little")


def decode_proof(pi: bytes):
    """Split pi into (Gamma point, c int, s int); None on malformed."""
    if len(pi) != PROOF_BYTES:
        return None
    gamma = point_decompress(pi[:32])
    if gamma is None:
        return None
    c = int.from_bytes(pi[32:48], "little")
    s = int.from_bytes(pi[48:80], "little")
    if s >= L:  # non-canonical scalar
        return None
    return gamma, c, s


def verify(pk: bytes, pi: bytes, alpha: bytes) -> bytes | None:
    """Verify proof (either format, by length); return beta or None."""
    if len(pi) == PROOF_BYTES_BATCH:
        return verify_batch_compat(pk, pi, alpha)
    y = point_decompress(pk)
    if y is None:
        return None
    dec = decode_proof(pi)
    if dec is None:
        return None
    gamma, c, s = dec
    H = hash_to_curve(pk, alpha)
    # U = s*B - c*Y ;  V = s*H - c*Gamma
    U = point_add(point_mul(s, B), point_neg(point_mul(c, y)))
    V = point_add(point_mul(s, H), point_neg(point_mul(c, gamma)))
    c_prime = _hash_points(H, gamma, U, V)
    if int.from_bytes(c_prime, "little") != c:
        return None
    return proof_to_hash(pi)


def verify_batch_compat(pk: bytes, pi: bytes, alpha: bytes) -> bytes | None:
    """Verify a 128-byte batch-compatible proof; return beta or None.

    The challenge is DERIVED from the announced U, V bytes, then the two
    group equations U = s·B − c·Y and V = s·H − c·Γ are checked — the
    per-lane form of the aggregated window check (ops/pk/aggregate.py),
    and the exact reference the fallback path must reproduce."""
    if len(pi) != PROOF_BYTES_BATCH:
        return None
    y = point_decompress(pk)
    if y is None:
        return None
    gamma = point_decompress(pi[:32])
    u = point_decompress(pi[32:64])
    v = point_decompress(pi[64:96])
    if gamma is None or u is None or v is None:
        return None
    s = int.from_bytes(pi[96:128], "little")
    if s >= L:
        return None
    H = hash_to_curve(pk, alpha)
    c_bytes = _sha512(
        SUITE + b"\x02" + point_compress(H) + pi[:32] + pi[32:64] + pi[64:96]
    )[:16]
    c = int.from_bytes(c_bytes, "little")
    if not point_equal(
        point_mul(s, B), point_add(u, point_mul(c, y))
    ):
        return None
    if not point_equal(
        point_mul(s, H), point_add(v, point_mul(c, gamma))
    ):
        return None
    return proof_to_hash(pi)


def proof_to_hash(pi: bytes) -> bytes:
    """beta = SHA512(suite || 0x03 || encode(cofactor * Gamma))."""
    gamma = point_decompress(pi[:32])
    if gamma is None:
        raise ValueError("malformed proof")
    g8 = point_mul(8, gamma)
    return _sha512(SUITE + b"\x03" + point_compress(g8))
