"""[benchmark copy of ouroboros_consensus_tpu/ops/host/hashes.py: the plain reference may
import nothing of the program, and no later PR may move the yardstick]

Host hashing primitives (stdlib-backed) + Praos nonce/leader-value helpers.

Reference equivalents: `cardano-crypto-class` hash classes (Blake2b_256,
Blake2b_224) and the VRF range-extension helpers at
ouroboros-consensus-protocol/.../Protocol/Praos/VRF.hs:
  * InputVRF  = Blake2b-256(slot_be8 || epoch_nonce)     (VRF.hs:47,55-69)
  * leader value = "L"-tagged hash of the VRF output      (VRF.hs:103)
  * nonce value  = "N"-tagged double hash                 (VRF.hs:116)
"""

from __future__ import annotations

import hashlib


def blake2b_256(data: bytes) -> bytes:
    return hashlib.blake2b(data, digest_size=32).digest()


def blake2b_224(data: bytes) -> bytes:
    return hashlib.blake2b(data, digest_size=28).digest()


def sha512(data: bytes) -> bytes:
    return hashlib.sha512(data).digest()


# -- Praos range extension ---------------------------------------------------


def input_vrf(slot: int, epoch_nonce: bytes) -> bytes:
    """Seed for the per-slot VRF evaluation."""
    return blake2b_256(slot.to_bytes(8, "big") + epoch_nonce)


def vrf_leader_value(beta: bytes) -> int:
    """256-bit leader-election value derived from the VRF output beta."""
    return int.from_bytes(blake2b_256(b"L" + beta), "big")


def vrf_nonce_value(beta: bytes) -> bytes:
    """Per-block nonce contribution ("N"-tagged double hash)."""
    return blake2b_256(blake2b_256(b"N" + beta))


def nonce_combine(a: bytes, b: bytes) -> bytes:
    """Nonce evolution eta' = eta (*) v  (hash of concatenation).

    NOT associative (hash(hash(a||b)||c) != hash(a||hash(b||c))): nonce
    evolution is inherently a sequential fold. The TPU pipeline computes
    the per-header nonce values (vrf_nonce_value) in batch on device and
    threads this fold on host — do not replace it with a parallel scan.
    """
    return blake2b_256(a + b)
