"""CompactSum KES (key-evolving signatures) host reference implementation.

Reference equivalents: `cardano-crypto-class` `Cardano.Crypto.KES.CompactSum`
(Haskell over libsodium Ed25519 + Blake2b-256), reached from the Praos hot
path at ouroboros-consensus-protocol/.../Protocol/Praos.hs:582
(verifySignedKES on the header body) and from storage integrity checks at
ouroboros-consensus-cardano/src/shelley/.../Ledger/Integrity.hs:14-20.

Structure (depth d, 2^d periods, the default d=7 follows SURVEY.md §2.5):
  * verification key of a node = Blake2b-256(vk_left || vk_right)
  * a CompactSum signature carries the leaf Ed25519 signature, the leaf
    verification key, and ONE sibling vk per level; the verifier
    reconstructs the root hash bottom-up and compares with the declared vk.
  * signature size = 64 + 32 + 32*d bytes (d=7 -> 320).

Key derivation: seeds split top-down, left = Blake2b-256(0x01 || seed),
right = Blake2b-256(0x02 || seed); the leaf seed is an Ed25519 seed.
Subtree vks are memoised so a full tree is derived once per cold key.
"""

from __future__ import annotations

import hashlib
from functools import lru_cache

from . import ed25519

# Cardano's StandardCrypto resolves KES to Sum6KES (6 levels, 64 periods;
# consistent with maxKESEvolutions=62). Depth stays a parameter everywhere;
# callers wanting the 128-period variant pass depth=7.
DEFAULT_DEPTH = 6

SIG_BYTES_LEAF = 96  # 64-byte Ed25519 sig + 32-byte leaf vk


def sig_bytes(depth: int) -> int:
    return SIG_BYTES_LEAF + 32 * depth


def _h256(data: bytes) -> bytes:
    return hashlib.blake2b(data, digest_size=32).digest()


def _seed_left(seed: bytes) -> bytes:
    return _h256(b"\x01" + seed)


def _seed_right(seed: bytes) -> bytes:
    return _h256(b"\x02" + seed)


# 255 subtrees a depth-7 key: room for the 512 pools of a mainnet-shaped
# forge, whose slots interleave every pool's tree (2^14 held 64 of them)
@lru_cache(maxsize=1 << 18)
def derive_vk(seed: bytes, depth: int) -> bytes:
    """Verification key of the subtree rooted at `seed` with `depth` levels."""
    if depth == 0:
        # leaf key derivation routes through the fast dispatch (native
        # C when built; byte-identical) — tree derivation dominates the
        # sign-side cost otherwise
        from . import fast

        return fast.ed25519_public(seed)
    return _h256(
        derive_vk(_seed_left(seed), depth - 1)
        + derive_vk(_seed_right(seed), depth - 1)
    )


def sign(seed: bytes, depth: int, period: int, msg: bytes) -> bytes:
    """CompactSum signature for `period` (0 <= period < 2^depth)."""
    if not 0 <= period < (1 << depth):
        raise ValueError(f"period {period} out of range for depth {depth}")
    if depth == 0:
        from . import fast

        return fast.ed25519_sign(seed, msg) + fast.ed25519_public(seed)
    half = 1 << (depth - 1)
    s0, s1 = _seed_left(seed), _seed_right(seed)
    if period < half:
        inner = sign(s0, depth - 1, period, msg)
        vk_other = derive_vk(s1, depth - 1)
    else:
        inner = sign(s1, depth - 1, period - half, msg)
        vk_other = derive_vk(s0, depth - 1)
    return inner + vk_other


def _reconstruct_vk(sig: bytes, depth: int, period: int, msg: bytes) -> bytes | None:
    """Verify the leaf signature and reconstruct the root vk, or None."""
    if depth == 0:
        if len(sig) != SIG_BYTES_LEAF:
            return None
        ed_sig, vk_leaf = sig[:64], sig[64:96]
        if not ed25519.verify(vk_leaf, msg, ed_sig):
            return None
        return vk_leaf
    half = 1 << (depth - 1)
    inner, vk_other = sig[:-32], sig[-32:]
    if period < half:
        vk0 = _reconstruct_vk(inner, depth - 1, period, msg)
        if vk0 is None:
            return None
        return _h256(vk0 + vk_other)
    vk1 = _reconstruct_vk(inner, depth - 1, period - half, msg)
    if vk1 is None:
        return None
    return _h256(vk_other + vk1)


def verify(vk: bytes, depth: int, period: int, msg: bytes, sig: bytes) -> bool:
    if len(sig) != sig_bytes(depth) or not 0 <= period < (1 << depth):
        return False
    return _reconstruct_vk(sig, depth, period, msg) == vk


def leaf_path(seed: bytes, depth: int, period: int):
    """(leaf_seed, siblings bottom-up) for `period` — the static part of
    a CompactSum signature: sign the leaf seed over the message (host or
    ops/ed25519_batch.sign) and append vk_leaf + this sibling path to
    assemble the full signature."""
    if not 0 <= period < (1 << depth):
        raise ValueError(f"period {period} out of range for depth {depth}")
    sibs: list[bytes] = []

    def walk(sd: bytes, d: int, per: int) -> bytes:
        if d == 0:
            return sd
        half = 1 << (d - 1)
        s0, s1 = _seed_left(sd), _seed_right(sd)
        if per < half:
            leaf = walk(s0, d - 1, per)
            sibs.append(derive_vk(s1, d - 1))
        else:
            leaf = walk(s1, d - 1, per - half)
            sibs.append(derive_vk(s0, d - 1))
        return leaf

    leaf = walk(seed, depth, period)
    return leaf, sibs


def decompose_sig(sig: bytes, depth: int):
    """Split a CompactSum signature into (ed_sig 64, vk_leaf 32, [sibling vks
    bottom-up: level 1 .. depth]). Used by SoA staging for the batch kernel."""
    if len(sig) != sig_bytes(depth):
        raise ValueError("bad signature size")
    ed_sig, vk_leaf = sig[:64], sig[64:96]
    siblings = [sig[96 + 32 * i : 128 + 32 * i] for i in range(depth)]
    return ed_sig, vk_leaf, siblings
