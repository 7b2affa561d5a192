"""Native chunk scanner: differential vs the pure-Python CBOR parser."""

from fractions import Fraction

import numpy as np
import pytest

from ouroboros_consensus_tpu import native_loader
from ouroboros_consensus_tpu.block.forge import forge_block
from ouroboros_consensus_tpu.block.praos_block import Block
from ouroboros_consensus_tpu.protocol import praos
from ouroboros_consensus_tpu.testing import fixtures

PARAMS = praos.PraosParams(
    slots_per_kes_period=100,
    max_kes_evolutions=62,
    security_param=4,
    active_slot_coeff=Fraction(1),
    epoch_length=1000,
    kes_depth=3,
)


@pytest.fixture(scope="module")
def chunk():
    pool = fixtures.make_pool(0, kes_depth=3)
    nonce = b"\x07" * 32
    blocks, prev = [], None
    for s in range(6):
        b = forge_block(
            PARAMS, pool, slot=s, block_no=s, prev_hash=prev,
            epoch_nonce=nonce, txs=(b"tx-%d" % s,),
        )
        blocks.append(b)
        prev = b.hash_
    return b"".join(b.bytes_ for b in blocks), blocks


def require_native():
    if native_loader.load() is None:
        pytest.skip("native library unavailable (no g++?)")


def test_scan_items(chunk):
    require_native()
    buf, blocks = chunk
    offsets, sizes, end = native_loader.scan_items(buf)
    assert len(offsets) == len(blocks)
    assert end == len(buf)
    pos = 0
    for off, sz, b in zip(offsets, sizes, blocks):
        assert off == pos and sz == len(b.bytes_)
        pos += sz


def test_scan_detects_corruption(chunk):
    buf, blocks = chunk
    require_native()
    cut = buf[: len(buf) - 10]  # torn tail
    offsets, sizes, end = native_loader.scan_items(cut)
    assert len(offsets) == len(blocks) - 1
    assert end == sum(len(b.bytes_) for b in blocks[:-1])


def test_extract_headers_matches_python(chunk):
    require_native()
    buf, blocks = chunk
    offsets, sizes, _ = native_loader.scan_items(buf)
    cols = native_loader.extract_headers(buf, offsets)
    assert cols.n == len(blocks)
    for i, blk in enumerate(blocks):
        body = blk.header.body
        assert cols.block_no[i] == body.block_no
        assert cols.slot[i] == body.slot
        if body.prev_hash is None:
            assert cols.has_prev[i] == 0
        else:
            assert cols.has_prev[i] == 1
            assert bytes(cols.prev_hash[i]) == body.prev_hash
        assert bytes(cols.issuer_vk[i]) == body.issuer_vk
        assert bytes(cols.vrf_vk[i]) == body.vrf_vk
        assert bytes(cols.vrf_output[i]) == body.vrf_output
        # the proof column is 128-wide zero-padded; per-row length
        # discriminates the format (80 draft-03 / 128 batch-compatible)
        assert cols.vrf_proof_len[i] == len(body.vrf_proof)
        assert (bytes(cols.vrf_proof[i][: cols.vrf_proof_len[i]])
                == body.vrf_proof)
        assert bytes(cols.body_hash[i]) == body.body_hash
        assert bytes(cols.ocert_vk[i]) == body.ocert.vk_hot
        assert cols.ocert_counter[i] == body.ocert.counter
        assert cols.ocert_kes_period[i] == body.ocert.kes_period
        assert cols.ocert_sigma[i] == body.ocert.sigma
        assert (cols.pv_major[i], cols.pv_minor[i]) == body.protocol_version
        assert cols.kes_sig[i] == blk.header.kes_sig
        # the signed span must be byte-identical to the memoised encoding
        assert cols.signed_bytes[i] == body.signed_bytes


def test_extract_rejects_garbage():
    require_native()
    with pytest.raises(ValueError):
        native_loader.extract_headers(b"\x82\x00\x00", np.array([0], np.int64))


def test_native_reparse_matches_python(chunk, tmp_path):
    """ImmutableDB index rebuild: native scanner and pure-Python walk
    must produce identical entries (incl. header hashes)."""
    require_native()
    import os

    from ouroboros_consensus_tpu.storage.immutable import ImmutableDB

    buf, blocks = chunk
    rebuilt = []
    for sub, native in (("n", True), ("p", False)):
        d = str(tmp_path / sub)
        os.makedirs(d)
        with open(os.path.join(d, "00000.chunk"), "wb") as f:
            f.write(buf)
        if not native:
            import ouroboros_consensus_tpu.storage.immutable as imm_mod

            orig = imm_mod.ImmutableDB._reparse_chunk_native
            imm_mod.ImmutableDB._reparse_chunk_native = lambda self, n, data: None
            try:
                db = ImmutableDB(d)
            finally:
                imm_mod.ImmutableDB._reparse_chunk_native = orig
        else:
            db = ImmutableDB(d)
        entries = db._entries[0]
        assert [e.hash_ for e in entries] == [b.hash_ for b in blocks]
        assert [e.slot for e in entries] == [b.slot for b in blocks]
        rebuilt.append(entries)
    # the native rebuild's columns against the Python walk's entries
    assert rebuilt[0] == list(rebuilt[1]) and len(rebuilt[0]) == len(blocks)
