"""Batched Blake2b device kernel (RFC 7693; unkeyed; digest size 1..64).

Host staging pads messages into zero-filled 128-byte blocks
(`pad_messages_np`); the device kernel runs each lane through the batch-max
block count with masked updates, threading the byte counter and final-block
flag per lane.

Reference equivalents: `cardano-crypto-class` Blake2b_256/Blake2b_224 hash
classes (C libsodium), used for KES Merkle nodes (CompactSum), header
hashes (Praos/Header.hs:158), the VRF input `Blake2b-256(slot ‖ nonce)`
(Praos/VRF.hs:47), leader/nonce range extension (VRF.hs:103,116), and pool
key hashes.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np
from jax import lax
from jax import numpy as jnp

from . import u64
from .sha512 import _H0_INTS  # Blake2b IV == SHA-512 IV

BLOCK = 128

IV = u64.split_np(_H0_INTS)  # [8, 2]

_SIGMA = [
    [0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15],
    [14, 10, 4, 8, 9, 15, 13, 6, 1, 12, 0, 2, 11, 7, 5, 3],
    [11, 8, 12, 0, 5, 2, 15, 13, 10, 14, 3, 6, 7, 1, 9, 4],
    [7, 9, 3, 1, 13, 12, 11, 14, 2, 6, 5, 10, 4, 0, 15, 8],
    [9, 0, 5, 7, 2, 4, 10, 15, 14, 1, 11, 12, 6, 8, 3, 13],
    [2, 12, 6, 10, 0, 11, 8, 3, 4, 13, 7, 5, 15, 14, 1, 9],
    [12, 5, 1, 15, 14, 13, 4, 10, 0, 7, 6, 3, 9, 2, 8, 11],
    [13, 11, 7, 14, 12, 1, 3, 9, 5, 0, 15, 4, 8, 6, 2, 10],
    [6, 15, 14, 9, 11, 3, 0, 8, 12, 2, 13, 7, 1, 4, 10, 5],
    [10, 2, 8, 4, 7, 6, 1, 5, 15, 11, 9, 14, 3, 12, 13, 0],
]


def nblocks_for_len(n: int) -> int:
    return max(1, (n + BLOCK - 1) // BLOCK)


def pad_messages_np(msgs: Sequence[bytes], nb: int | None = None):
    """Messages -> (blocks [B, NB, 16, 2] uint32 LE words, nblocks [B],
    total_len [B]). Zero-padding only (Blake2b has no padding bits)."""
    need = max((nblocks_for_len(len(m)) for m in msgs), default=1)
    if nb is None:
        nb = need
    assert nb >= need
    buf = np.zeros((len(msgs), nb * BLOCK), dtype=np.uint8)
    nblocks = np.zeros((len(msgs),), dtype=np.int32)
    total = np.zeros((len(msgs),), dtype=np.int32)
    for i, m in enumerate(msgs):
        buf[i, : len(m)] = np.frombuffer(m, dtype=np.uint8)
        nblocks[i] = nblocks_for_len(len(m))
        total[i] = len(m)
    return (
        bytes_to_blocks_np(buf.reshape(len(msgs), nb, BLOCK)),
        nblocks,
        total,
    )


def bytes_to_blocks_np(b: np.ndarray) -> np.ndarray:
    """[..., 128] uint8 -> [..., 16, 2] uint32 little-endian words."""
    w = b.reshape(*b.shape[:-1], 16, 8).astype(np.uint32)
    shifts = np.array([0, 8, 16, 24], dtype=np.uint32)
    lo = (w[..., :4] << shifts).sum(axis=-1, dtype=np.uint32)
    hi = (w[..., 4:] << shifts).sum(axis=-1, dtype=np.uint32)
    return np.stack([hi, lo], axis=-1)


def bytes_to_blocks(b):
    """Device variant: [..., 128] int32 bytes -> [..., 16, 2] uint32 LE words."""
    w = b.astype(jnp.uint32).reshape(*b.shape[:-1], 16, 8)
    shifts = jnp.asarray([0, 8, 16, 24], jnp.uint32)
    lo = (w[..., :4] << shifts).sum(axis=-1).astype(jnp.uint32)
    hi = (w[..., 4:] << shifts).sum(axis=-1).astype(jnp.uint32)
    return jnp.stack([hi, lo], axis=-1)


def _g(v, a, b, c, d, x, y):
    v[a] = u64.add_many(v[a], v[b], x)
    v[d] = u64.rotr(u64.xor(v[d], v[a]), 32)
    v[c] = u64.add(v[c], v[d])
    v[b] = u64.rotr(u64.xor(v[b], v[c]), 24)
    v[a] = u64.add_many(v[a], v[b], y)
    v[d] = u64.rotr(u64.xor(v[d], v[a]), 16)
    v[c] = u64.add(v[c], v[d])
    v[b] = u64.rotr(u64.xor(v[b], v[c]), 63)


def compress(state, block, t_bytes, is_final):
    """One Blake2b compression.

    state [..., 8, 2]; block [..., 16, 2] LE words; t_bytes [...] int32
    (bytes hashed including this block, < 2^31); is_final [...] bool.

    The 12 rounds run as a `lax.fori_loop` whose body gathers the
    round's SIGMA message permutation from a table — same rationale as
    sha512.compress: the Python-unrolled form (~1.5k HLO ops) drives
    XLA:CPU into multi-minute LLVM optimization; the rolled body
    compiles in seconds with identical runtime (rounds are sequential).
    """
    iv = jnp.asarray(IV)
    sig = jnp.asarray(np.array(_SIGMA, dtype=np.int32))  # [10, 16]
    mh, ml = block[..., 0], block[..., 1]  # [..., 16]
    batch = state.shape[:-2]
    vh0 = jnp.concatenate(
        [state[..., 0], jnp.broadcast_to(iv[:, 0], (*batch, 8))], axis=-1
    )
    vl0 = jnp.concatenate(
        [state[..., 1], jnp.broadcast_to(iv[:, 1], (*batch, 8))], axis=-1
    )
    # v12 ^= t (counter fits 31 bits: t_hi = 0); v14 inverted on final block
    vl0 = vl0.at[..., 12].set(vl0[..., 12] ^ t_bytes.astype(jnp.uint32))
    fmask = jnp.where(is_final, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
    vh0 = vh0.at[..., 14].set(vh0[..., 14] ^ fmask)
    vl0 = vl0.at[..., 14].set(vl0[..., 14] ^ fmask)

    def body(r, carry):
        vh, vl = carry
        s = sig[r % 10]
        smh = jnp.take(mh, s, axis=-1)
        sml = jnp.take(ml, s, axis=-1)
        v = [(vh[..., i], vl[..., i]) for i in range(16)]

        def g(a, b, c, d, i):
            x = (smh[..., 2 * i], sml[..., 2 * i])
            y = (smh[..., 2 * i + 1], sml[..., 2 * i + 1])
            _g(v, a, b, c, d, x, y)

        g(0, 4, 8, 12, 0)
        g(1, 5, 9, 13, 1)
        g(2, 6, 10, 14, 2)
        g(3, 7, 11, 15, 3)
        g(0, 5, 10, 15, 4)
        g(1, 6, 11, 12, 5)
        g(2, 7, 8, 13, 6)
        g(3, 4, 9, 14, 7)
        vh2 = jnp.stack([v[i][0] for i in range(16)], axis=-1)
        vl2 = jnp.stack([v[i][1] for i in range(16)], axis=-1)
        return vh2, vl2

    vh, vl = lax.fori_loop(0, 12, body, (vh0, vl0))
    oh = state[..., 0] ^ vh[..., :8] ^ vh[..., 8:]
    ol = state[..., 1] ^ vl[..., :8] ^ vl[..., 8:]
    return jnp.stack([oh, ol], axis=-1)


def init_state(batch_shape, digest_size: int):
    h = np.array(IV, dtype=np.uint32).copy()
    h[0, 1] ^= np.uint32(0x01010000 ^ digest_size)
    return jnp.broadcast_to(jnp.asarray(h), (*batch_shape, 8, 2))


def blake2b_blocks(blocks, nblocks, total_len, digest_size: int = 32):
    """Batched Blake2b over zero-padded blocks -> [..., digest_size] bytes.

    blocks [..., NB, 16, 2]; nblocks, total_len [...] int32.
    """
    nb = blocks.shape[-3]
    batch = blocks.shape[:-3]
    nblocks = jnp.asarray(nblocks)
    total_len = jnp.asarray(total_len)
    state = init_state(batch, digest_size)

    def step(st, i, blk):
        is_final = i == nblocks - 1
        t = jnp.where(is_final, total_len, (i + 1) * BLOCK)
        nxt = compress(st, blk, t, is_final)
        return jnp.where((i < nblocks)[..., None, None], nxt, st)

    if nb == 1:
        state = step(state, jnp.int32(0), blocks[..., 0, :, :])
    else:
        def body(i, st):
            blk = lax.dynamic_index_in_dim(blocks, i, axis=len(batch), keepdims=False)
            return step(st, i, blk)

        state = lax.fori_loop(0, nb, body, state)
    nwords = (digest_size + 7) // 8
    outs = [u64.to_bytes_le((state[..., i, 0], state[..., i, 1])) for i in range(nwords)]
    return jnp.concatenate(outs, axis=-1)[..., :digest_size]


_ENV_DEVICE_HASH = "OCT_SIDECAR_DEVICE_HASH"
_hash_spans_jit = None


def _device_hash_enabled() -> bool:
    """``OCT_SIDECAR_DEVICE_HASH`` (default 0): route the sidecar hot
    path's body-hash batch through the device Blake2b kernel instead
    of hashlib. Off by default — the host loop is exact and the device
    batch only pays off once the span batch is large and a device is
    attached; read per call so tests A/B both paths."""
    import os

    return os.environ.get(_ENV_DEVICE_HASH, "0") == "1"


def hash_spans(data, starts, ends, digest_size: int = 32) -> np.ndarray:
    """Blake2b over ``data[starts[i]:ends[i])`` for every i →
    [n, digest_size] uint8 digests — the columnar-sidecar hot path's
    per-header body-hash compare (storage/sidecar.integrity_batch_hook)
    with ZERO header parsing: the spans come straight from the
    sidecar's ``header_end`` column and the index entries. One native
    batch call when the host-crypto library is available (the hot
    path), hashlib loop otherwise; `_device_hash_enabled` routes the
    whole batch through `blake2b_blocks` with bucket-padded shapes."""
    import hashlib

    n = len(starts)
    out = np.empty((n, digest_size), np.uint8)
    if n == 0:
        return out
    mv = memoryview(data)
    if _device_hash_enabled():
        msgs = [bytes(mv[int(s):int(e)]) for s, e in zip(starts, ends)]
        return _hash_spans_device(msgs, digest_size)
    from .. import native_loader

    native = native_loader.native_blake2b_spans(data, starts, ends, digest_size)
    if native is not None:
        return native
    for i in range(n):
        out[i] = np.frombuffer(
            hashlib.blake2b(
                mv[int(starts[i]):int(ends[i])], digest_size=digest_size
            ).digest(),
            np.uint8,
        )
    return out


def _hash_spans_device(msgs, digest_size: int) -> np.ndarray:
    """Bucket-padded device batch: nblocks rounds up to a power of two
    and the batch to a multiple of 256 (zero-length pad lanes, outputs
    dropped), so repeated chunks reuse ONE compiled executable per
    bucket instead of re-tracing per chunk shape."""
    global _hash_spans_jit
    import jax

    if _hash_spans_jit is None:
        _hash_spans_jit = jax.jit(
            blake2b_blocks, static_argnames=("digest_size",)
        )
    need = max(nblocks_for_len(len(m)) for m in msgs)
    nb = 1 << max(0, need - 1).bit_length()
    blocks, nblocks, total = pad_messages_np(msgs, nb=nb)
    n = len(msgs)
    b = max(256, ((n + 255) // 256) * 256)
    if b != n:
        pad = b - n
        blocks = np.concatenate(
            [blocks, np.zeros((pad, *blocks.shape[1:]), blocks.dtype)]
        )
        nblocks = np.concatenate([nblocks, np.ones(pad, np.int32)])
        total = np.concatenate([total, np.zeros(pad, np.int32)])
    dig = np.asarray(
        _hash_spans_jit(blocks, nblocks, total, digest_size=digest_size)
    )
    return dig[:n].astype(np.uint8)


def nonce_fold_scan(etas, within, is_real, ev0, ev0_set, cand0, cand0_set):
    """REFERENCE ONLY (protocol/batch.verdict_reduce; no dispatch path
    reaches it). Device-side Praos nonce fold: `jax.lax.scan` of the
    evolving / candidate nonce bookkeeping over a window's per-lane eta
    values, mirroring protocol/nonces.combine + protocol/praos.reupdate
    exactly.

    The combine is a NON-associative hash fold (eta' = Blake2b-256(eta ‖
    v), neutral = identity), so the scan is one unbatched compression a
    lane: 0.40 ms a step on a v5e, 3.3 s a window of 8192 lanes, 97.9%
    of a replay (PERF.md, PRs 27-29). It ran on the device from round 6
    to save the D2H of the [B, 32] eta column (262 KB a window, under a
    millisecond on an attached chip); the host folds that column in
    12 ms (protocol/batch._fold_nonces), so PR 29 took it off every
    dispatch path. Kept for the analysis goldens that trace it and the
    test that holds it equal to the host fold; ROADMAP queues its
    deletion.

      etas     [B, 32] int32 bytes — vrfNonceValue per lane
      within   [B] bool — slot within the stability window (candidate
               freezing, Praos.hs:497)
      is_real  [B] bool — lane < the window's true size (bucket-pad
               lanes must not fold)
      ev0, cand0 [32] int32; ev0_set, cand0_set [] bool — the carry-in
               (set=False encodes the neutral nonce)

    Returns the carry-out (ev, ev_set, cand, cand_set) after folding
    every real lane in order.
    """

    def step(carry, x):
        ev, evs, cand, cands = carry
        eta_i, w_i, r_i = x
        h = blake2b_fixed(jnp.concatenate([ev, eta_i], axis=-1), 64, 32)
        new_ev = jnp.where(evs, h, eta_i)  # combine(neutral, v) = v
        ev2 = jnp.where(r_i, new_ev, ev)
        evs2 = evs | r_i
        upd = r_i & w_i
        cand2 = jnp.where(upd, ev2, cand)
        cands2 = cands | upd
        return (ev2, evs2, cand2, cands2), ()

    carry, _ = lax.scan(
        step, (ev0, ev0_set, cand0, cand0_set), (etas, within, is_real)
    )
    return carry


def blake2b_fixed(data_bytes, data_len: int, digest_size: int = 32):
    """Single-block fast path: [..., n] int32 bytes with a STATIC common
    length data_len <= 128 (the KES Merkle-node / nonce-evolution shape).
    """
    assert 0 < data_len <= BLOCK
    batch = data_bytes.shape[:-1]
    pad = BLOCK - data_bytes.shape[-1]
    if pad:
        data_bytes = jnp.concatenate(
            [data_bytes, jnp.zeros((*batch, pad), jnp.int32)], axis=-1
        )
    blk = bytes_to_blocks(data_bytes)
    state = init_state(batch, digest_size)
    t = jnp.broadcast_to(jnp.int32(data_len), batch)
    fin = jnp.broadcast_to(jnp.bool_(True), batch)
    state = compress(state, blk, t, fin)
    nwords = (digest_size + 7) // 8
    outs = [u64.to_bytes_le((state[..., i, 0], state[..., i, 1])) for i in range(nwords)]
    return jnp.concatenate(outs, axis=-1)[..., :digest_size]
