"""ChainDB assembly: the openDB path of node startup.

Reference: `ChainDB.openDB` via `openChainDB` (diffusion Node.hs:568-580)
— open ImmutableDB (with validation policy), VolatileDB (reparse),
initialize LedgerDB from newest snapshot + replay, then initial chain
selection. The `validate_all` flag is the clean-shutdown-marker policy
(Node/Recovery.hs:24-59): absent marker ⇒ last run crashed ⇒ full
revalidation of all chunks.
"""

from __future__ import annotations

import os
from typing import Callable

from ..block.praos_block import Block
from ..ledger.extended import ExtLedger, ExtLedgerState
from .chaindb import ChainDB
from .immutable import ImmutableDB
from .ledgerdb import LedgerDB
from .volatile import VolatileDB

# Validation policies (Run.hs:133-143): `--only-validation` forces
# ValidateAllChunks; normal startup validates the most recent chunk and
# trusts the clean-shutdown marker for the rest. The policy threads
# through this codebase as the `validate_all` flag — these names exist
# so the protocol layer (storage/guard.py, db_analyser, db_truncater)
# can speak the reference vocabulary. db_analyser adds a third value,
# "stream": the SAME all-chunks checks folded into the replay's own
# chunk reads (one disk pass, identical truncation points).
ValidateAllChunks = True
ValidateMostRecentChunk = False


def escalate_policy(policy, opened_dirty: bool):
    """Node/Recovery.hs:24-59 — forced revalidation after a crash: a
    store that cannot prove a clean shutdown revalidates EVERYTHING.
    `ValidateMostRecentChunk` escalates to `ValidateAllChunks`;
    "stream" already runs the all-chunks checks (at read time) and
    stays stream; an explicit all-chunks policy is unchanged."""
    if opened_dirty and not policy:
        return ValidateAllChunks
    return policy


def open_repair_store(path: str, chunk_size: int = 21600, fs=None,
                      quarantine_dir: str | None = None,
                      repair: bool = True) -> ImmutableDB:
    """The deep-open recipe in ONE place: full `ValidateAllChunks` walk
    (CRC + body-hash integrity, chunk-batched fast path) with on-disk
    repair — the bundle every dirty-store escalation opens
    (db_synthesizer resume, db_truncater slot-rewind and --to-last-valid).
    ``repair=False`` is the read-only twin (--dry-run): identical scan,
    actions computed in memory only."""
    return ImmutableDB(
        os.path.join(path, "immutable"),
        chunk_size=chunk_size,
        check_integrity=default_check_integrity,
        validate_all=True,
        check_integrity_batch=default_check_integrity_batch,
        repair=repair,
        quarantine_dir=quarantine_dir,
        fs=fs,
    )


def _era_tagged(raw) -> bool:
    """An era-tagged block ([era, inner], hardfork/combinator): a 2-array
    whose first item is an integer (a Praos block's is its header)."""
    return len(raw) > 1 and raw[0] == 0x82 and raw[1] <= 0x17


def default_check_integrity(raw: bytes) -> bool:
    """nodeCheckIntegrity (Node/InitStorage.hs:25 → shelley
    Ledger/Integrity.hs): parseable + body hash matches. (The KES check
    runs batched when the analyser revalidates headers.) An era-tagged
    block is checked by its era's block type (the Byron block's tx root
    or the Shelley-family body hash)."""
    try:
        if _era_tagged(raw):
            from ..hardfork.byron_mock import ByronMockBlock
            from ..utils import cbor

            _era, inner = cbor.decode(raw)
            if inner[:1] == b"\x83":  # [kind, header, txs]
                return ByronMockBlock.from_bytes(inner).check_integrity()
            return Block.from_bytes(inner).check_integrity()
        return Block.from_bytes(raw).check_integrity()
    except Exception:  # octflow: disable=FLOW303 — fail-closed IS the
        # verdict here: nodeCheckIntegrity treats any parse/hash failure
        # as not-intact; the open-with-repair scan owns what follows
        return False


def default_check_integrity_batch(data, entries):
    """Chunk-wide twin of default_check_integrity: native columnar
    header parse + blake2b over each block's WIRE txs span (the codec
    writes canonical CBOR, so the span IS cbor.encode(txs); a mismatch
    is arbitrated by the per-block Python check so a non-canonical but
    internally consistent block is not wrongly truncated). Returns the
    index of the first bad block, len(entries) if all pass, or None
    when the native scanner is unavailable (caller falls back to the
    per-block loop). The per-block Python hook costs ~80 us/block of
    decode; this path is ~2 us/block."""
    import hashlib

    from .. import native_loader

    if native_loader.load() is None:
        return None
    offsets = entries.offset
    limit = len(entries)
    if limit and _era_tagged(data[int(offsets[0]):int(offsets[0]) + 2]):
        return _era_integrity_batch(data, entries)
    try:
        cols = native_loader.extract_headers(data, offsets)
    except native_loader.MalformedBlock as exc:
        # blocks before the malformed one parsed clean, but they must
        # STILL pass the body-hash check — a written-corrupt block
        # earlier in the chunk truncates earlier (per-blob loop order)
        limit = exc.index
        if limit == 0:
            return 0
        cols = native_loader.extract_headers(data, offsets[:limit])
    spans = zip(cols.header_end.tolist(), entries.ends[:limit].tolist())
    for i, (start, end) in enumerate(spans):
        if (
            hashlib.blake2b(data[start:end], digest_size=32).digest()
            != cols.body_hash[i].tobytes()
        ):
            if not default_check_integrity(data[int(offsets[i]) : end]):
                return i
    return limit


def open_chaindb(
    path: str,
    ext: ExtLedger,
    genesis: ExtLedgerState,
    k: int,
    validate_all: bool = False,
    chunk_size: int = 21600,
    trace: Callable[[str], None] = lambda s: None,
    fs=None,  # HasFS seam — a MockFS here runs the whole ChainDB in memory
    check_in_future=None,  # block.infuture.CheckInFuture | None
    decode_block=None,  # block codec seam; default = Praos Block
    check_integrity=None,  # per-block-type integrity hook
    tracer=None,  # typed ChainDB event tracer (utils.trace algebra)
) -> ChainDB:
    check_integrity_batch = None
    if check_integrity is None and validate_all:
        check_integrity = default_check_integrity
        if decode_block is None:
            # the batched twin only parses the default Praos layout
            check_integrity_batch = default_check_integrity_batch
    imm = ImmutableDB(
        os.path.join(path, "immutable"),
        chunk_size=chunk_size,
        check_integrity=check_integrity if validate_all else None,
        validate_all=validate_all,
        fs=fs,
        decode_block=decode_block,
        check_integrity_batch=check_integrity_batch if validate_all else None,
    )
    vol = VolatileDB(
        os.path.join(path, "volatile"), fs=fs, decode_block=decode_block
    )
    snap_dir = os.path.join(path, "ledger")
    ldb = LedgerDB.init_from_snapshots(
        ext, k, snap_dir, genesis, imm, trace, fs=fs, decode_block=decode_block
    )
    return ChainDB(
        ext, imm, vol, ldb, k, snap_dir=snap_dir, trace=trace,
        check_in_future=check_in_future, decode_block=decode_block,
        tracer=tracer,
    )


def _era_integrity_batch(data, entries):
    """`default_check_integrity_batch` over era-tagged blocks: the inner
    blocks parsed by era (Byron: the txs span against the header's tx
    root; Shelley-family: the txs span against the body hash), one
    batched Blake2b over every span. None (the per-block loop) where a
    block does not parse; a mismatch is arbitrated per block."""
    import numpy as np

    from .. import native_loader

    offsets = entries.offset
    try:
        _era, io, il = native_loader.scan_eras(data, offsets)
        buf = np.frombuffer(data, np.uint8)
        byron = buf[io] == 0x83
        n = len(io)
        starts = np.zeros(n, np.int64)
        refs = np.zeros((n, 32), np.uint8)
        if byron.any():
            cols = native_loader.extract_byron(data, io[byron])
            starts[byron] = cols["txs_off"]
            refs[byron] = cols["tx_root"]
        if (~byron).any():
            hc = native_loader.extract_headers(data, io[~byron])
            starts[~byron] = hc.header_end
            refs[~byron] = hc.body_hash
    except native_loader.MalformedBlock:
        return None
    return check_spans(data, entries, starts, io + il, refs)


def check_spans(data, entries, starts, ends, refs) -> int:
    """The body-hash compare of parsed blocks: Blake2b-256 over each
    block's txs span `data[starts[i]:ends[i]]` against the hash its
    header commits to (`refs[i]`), one batched call; a mismatch is
    arbitrated by the per-block check. -> the index of the first bad
    block, len(entries) if all pass."""
    import numpy as np

    from ..ops import blake2b

    n = len(entries)
    digests = blake2b.hash_spans(data, starts[:n], ends[:n])
    for i in np.flatnonzero((digests != refs[:n]).any(axis=1)).tolist():
        off, end = int(entries.offset[i]), int(entries.ends[i])
        if not default_check_integrity(data[off:end]):
            return i
    return n
