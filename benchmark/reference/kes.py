"""CompactSum KES verification: the benchmark's plain reference.

Copied from ouroboros_consensus_tpu/ops/host/kes.py (verification side only:
the sign side and key derivation went, with their import of the program's
native signer) so that no later PR to the program can move the yardstick.

CompactSum KES (key-evolving signatures) host reference implementation.

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
