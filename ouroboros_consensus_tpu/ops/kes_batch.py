"""Batched CompactSum KES verification on device.

Per lane: one Ed25519 leaf verification (the KES-signed message) plus
`depth` Blake2b-256 Merkle-node recomputations walking bottom-up; at level
i the period's bit i selects H(vk ‖ sib) vs H(sib ‖ vk) — realized as a
masked select, batch-uniform. The reconstructed root must equal the
declared KES verification key.

Reference equivalent: `cardano-crypto-class` `Cardano.Crypto.KES.CompactSum`
verifySignedKES, the header-signature check in the Praos hot path
(ouroboros-consensus-protocol/.../Protocol/Praos.hs:582) and the storage
integrity check (ouroboros-consensus-cardano shelley Ledger/Integrity.hs:14).
Differentially tested against ops/host/kes.py.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import numpy as np
from jax import numpy as jnp

from . import blake2b, curve, scalar, sha512
from .host import kes as hk


class KesBatch(NamedTuple):
    vk: np.ndarray  # [B, 32] uint8 — declared root vk
    period: np.ndarray  # [B] int32
    r: np.ndarray  # [B, 32] uint8 — leaf Ed25519 sig R
    s: np.ndarray  # [B, 32] uint8 — leaf Ed25519 sig s
    vk_leaf: np.ndarray  # [B, 32] uint8
    siblings: np.ndarray  # [B, depth, 32] uint8, bottom-up
    hblocks: np.ndarray  # [B, NB, 16, 2] — padded SHA-512(R ‖ vk_leaf ‖ msg)
    hnblocks: np.ndarray  # [B] int32


def stage_np(
    vks: Sequence[bytes],
    periods: Sequence[int],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    depth: int = hk.DEFAULT_DEPTH,
    nb: int | None = None,
) -> KesBatch:
    b = len(vks)
    assert len(periods) == len(msgs) == len(sigs) == b
    sig_len = hk.sig_bytes(depth)
    assert all(len(v) == 32 for v in vks)
    assert all(len(sig) == sig_len for sig in sigs)
    # CompactSum signature layout is fixed-width: slice the whole batch
    # column-wise out of ONE buffer (sig = ed_sig(64) ‖ leaf(32) ‖
    # siblings(depth*32) — hk.decompose_sig per lane, vectorized)
    vk = np.frombuffer(b"".join(vks), np.uint8).reshape(b, 32).copy()
    period = np.asarray(periods, np.int32)
    sg = np.frombuffer(b"".join(sigs), np.uint8).reshape(b, sig_len)
    r = np.ascontiguousarray(sg[:, :32])
    s = np.ascontiguousarray(sg[:, 32:64])
    vk_leaf = np.ascontiguousarray(sg[:, 64:96])
    siblings = np.ascontiguousarray(sg[:, 96:].reshape(b, depth, 32))
    hmsgs = [
        sig[:32] + sig[64:96] + m for sig, m in zip(sigs, msgs)
    ]
    hblocks, hnblocks = sha512.pad_messages_np(hmsgs, nb)
    return KesBatch(vk, period, r, s, vk_leaf, siblings, hblocks, hnblocks)


def _pad_rows(xp, data, msg_len):
    """SHA-512 padding of [B, M] message rows that are each `msg_len`
    [B] bytes long and zero past it, at each row's own length: the 0x80
    byte, zeros, the 128-bit big-endian bit length at the end of the
    row's own last block -> ([B, NB * 128] bytes, [B] int32 block
    counts), NB the longest row's count. `xp` is numpy (host staging)
    or jax.numpy (inside the jit): one arithmetic for both."""
    nb = sha512.nblocks_for_len(data.shape[-1])
    width = nb * sha512.BLOCK
    data = xp.concatenate(
        [data, xp.zeros((data.shape[0], width - data.shape[-1]), xp.uint8)],
        axis=-1,
    ).astype(xp.int32)
    ln = msg_len.astype(xp.int32)[:, None]
    pos = xp.arange(width, dtype=xp.int32)[None, :]
    k = (ln + 16 + sha512.BLOCK) // sha512.BLOCK  # nblocks_for_len
    # the bit length is under 2^32: the tail's last four bytes hold it
    j = xp.clip(pos - (k * sha512.BLOCK - 4), 0, 3)
    tail = ((ln * 8) >> (8 * (3 - j))) & 0xFF
    out = xp.where(
        pos < ln, data,
        xp.where(pos == ln, 0x80,
                 xp.where(pos >= k * sha512.BLOCK - 4,
                          xp.where(pos < k * sha512.BLOCK, tail, 0), 0)),
    )
    return out.astype(xp.uint8), k[:, 0].astype(xp.int32)


def pad_rows_np(mat: np.ndarray, msg_len: np.ndarray):
    """Host staging of [B, M] uint8 message rows of their own lengths
    (zero past them) -> (blocks [B, NB, 16, 2] uint32, nblocks [B]
    int32), byte-identical to `sha512.pad_messages_np` on the rows."""
    out, k = _pad_rows(np, mat, np.asarray(msg_len))
    n = mat.shape[0]
    return sha512.bytes_to_blocks_np(out.reshape(n, -1, sha512.BLOCK)), k


def build_hblocks(r, vk_leaf, body, body_len):
    """Device staging of the KES leaf-signature hash input
    R ‖ vk_leaf ‖ body — the packed H2D contract: the host ships the raw
    signed header-body column once (no padded block columns, no
    duplicated R ‖ leaf prefix) and the SHA padding runs inside the jit.
    `body` is zero-padded to the window's widest and each lane is padded
    at its own length `body_len` [B], so it hashes the blocks its own
    body needs and no more (the ed/kes kernels take per-lane block
    counts). Byte-identical to `stage_np`'s blocks."""
    data = jnp.concatenate(
        [r.astype(jnp.uint8), vk_leaf.astype(jnp.uint8),
         body.astype(jnp.uint8)],
        axis=-1,
    )
    out, k = _pad_rows(jnp, data, 64 + jnp.asarray(body_len))
    b = data.shape[0]
    return (
        sha512.bytes_to_blocks(
            out.reshape(b, -1, sha512.BLOCK).astype(jnp.int32)),
        k,
    )


def verify(vk, period, r, s, vk_leaf, siblings, hblocks, hnblocks, *, depth: int | None = None):
    """Device kernel -> ok bool[B]. depth defaults to siblings.shape[-2]."""
    ok_pre, p = verify_point(vk, period, s, vk_leaf, siblings, hblocks, hnblocks, depth=depth)
    enc = curve.compress(p)
    return ok_pre & jnp.all(enc == jnp.asarray(r).astype(jnp.int32), axis=-1)


def verify_point(vk, period, s, vk_leaf, siblings, hblocks, hnblocks, *, depth: int | None = None):
    """(ok_pre bool[B], P Point): Merkle-root + period checks folded into
    ok_pre; P = s·B − h·A of the leaf signature must equal the R bytes
    (compression deferred so the fused kernel shares one inversion)."""
    from . import ed25519_batch

    vk = jnp.asarray(vk).astype(jnp.int32)
    period = jnp.asarray(period)
    vk_leaf = jnp.asarray(vk_leaf).astype(jnp.int32)
    siblings = jnp.asarray(siblings).astype(jnp.int32)
    if depth is None:
        depth = siblings.shape[-2]

    ok_ed, p = ed25519_batch.verify_point(vk_leaf, s, hblocks, hnblocks)
    root_ok = merkle_root_ok(vk, period, vk_leaf, siblings, depth)
    period_ok = (period >= 0) & (period < (1 << depth))
    return ok_ed & root_ok & period_ok, p


def merkle_root_ok(vk, period, vk_leaf, siblings, depth: int):
    """Reconstruct the CompactSum root bottom-up; bit i of the period
    selects H(vk ‖ sib) vs H(sib ‖ vk) — masked select, batch-uniform."""
    cur = vk_leaf
    for i in range(depth):
        sib = siblings[..., i, :]
        bit = (period >> i) & 1
        left = jnp.concatenate([cur, sib], axis=-1)
        right = jnp.concatenate([sib, cur], axis=-1)
        data = jnp.where((bit == 1)[..., None], right, left)
        cur = blake2b.blake2b_fixed(data, 64, 32)
    return jnp.all(cur == vk, axis=-1)


_JIT: dict = {}


def verify_batch(vks, periods, msgs, sigs, depth: int = hk.DEFAULT_DEPTH) -> np.ndarray:
    global _JIT
    if depth not in _JIT:
        import jax

        _JIT[depth] = jax.jit(verify)
    batch = stage_np(vks, periods, msgs, sigs, depth)
    return np.asarray(_JIT[depth](*(jnp.asarray(x) for x in batch)))
