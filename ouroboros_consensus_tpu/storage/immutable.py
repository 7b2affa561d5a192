"""ImmutableDB: append-only chunked store of the immutable chain.

Reference: `Ouroboros.Consensus.Storage.ImmutableDB` (15 files, ~4.9k LoC)
— `NNNNN.chunk` files of concatenated block bytes plus two indices: a
primary index of fixed-width offsets per relative slot
(Impl/Index/Primary.hs:96) and a secondary index of per-block entries with
CRCs (Impl/Index/Secondary.hs). This implementation keeps the same
on-disk shape with one combined index file per chunk:

    NNNNN.chunk      block bytes, concatenated
    NNNNN.index      CBOR [[slot, block_no, hash, offset, size, crc32], …]

Startup validation (Impl/Validation.hs:67) reparses the last chunk (or all
chunks under `validate_all`), checks CRCs and hashes, optionally runs the
`check_integrity` hook (body hash + KES — batched on device by the
caller), and TRUNCATES the corrupted tail rather than failing. Every
on-disk repair the validation takes — truncated tails, rebuilt indices,
dropped chunks, swept orphan indices — QUARANTINES the snipped bytes
under ``quarantine/`` (never deletes) and is banked as a first-class
repair action (storage/repair.py: warmup forensics +
``oct_repair_total{action=}``). ``repair=False`` opens read-only: the
same scan computes every action in memory (``applied=False`` rows, the
db-truncater ``--dry-run`` report) and the disk is never touched.

Iterators stream blocks in slot order across chunk boundaries
(Impl/Iterator.hs). Appends go through an in-memory tail buffer flushed
per block — the OS page cache does the batching; `fsync` on chunk close.
"""

from __future__ import annotations

import contextlib
import os
import sys
import zlib
from dataclasses import dataclass
from typing import Callable, Iterator

import numpy as np

from ..block.abstract import Point
from ..testing import chaos
from ..utils import cbor
from ..utils.fs import REAL_FS
from . import repair as repair_mod


class ImmutableDBError(Exception):
    pass


class MissingBlock(ImmutableDBError):
    pass


@dataclass(frozen=True)
class IndexEntry:
    slot: int
    block_no: int
    hash_: bytes
    offset: int
    size: int
    crc32: int

    def to_cbor_obj(self):
        return [self.slot, self.block_no, self.hash_, self.offset, self.size, self.crc32]

    @classmethod
    def from_cbor_obj(cls, o):
        return cls(o[0], o[1], bytes(o[2]), o[3], o[4], o[5])


class ChunkIndex:
    """One chunk's secondary index, held as columns from the native
    parse to its last reader: `slot`, `block_no`, `offset`, `size`,
    `crc32` (int64) and `hash_` ((n, 32) uint8), in `IndexEntry`'s field
    order. A replay reads the columns; an `IndexEntry` is built only
    where a caller asks for ONE row (`idx[i]`, iteration). A slice is a
    view of the rows it names. The writer appends in place (capacity
    doubling); written rows never change and a view has no spare
    capacity, so a view stays true while the index it was cut from
    grows, and an append to a view copies first."""

    __slots__ = ("_cols", "_n")

    def __init__(self, slot=(), block_no=(), hash_=(), offset=(), size=(),
                 crc32=()):
        n = len(slot)
        self._cols = tuple(
            np.asarray(c, np.uint8).reshape(n, 32) if i == 2
            else np.asarray(c, np.int64)
            for i, c in enumerate((slot, block_no, hash_, offset, size, crc32))
        )
        self._n = n

    @classmethod
    def from_entries(cls, entries) -> "ChunkIndex":
        """The columns of a list of entries (the Python CBOR loop, an
        index rebuilt from chunk bytes): one conversion at its end."""
        return cls(
            [e.slot for e in entries], [e.block_no for e in entries],
            np.frombuffer(b"".join(e.hash_ for e in entries), np.uint8),
            [e.offset for e in entries], [e.size for e in entries],
            [e.crc32 for e in entries],
        )

    @property
    def _live(self) -> tuple:
        """The columns without the writer's spare capacity."""
        return tuple(c[: self._n] for c in self._cols)

    slot = property(lambda self: self._cols[0][: self._n])
    block_no = property(lambda self: self._cols[1][: self._n])
    hash_ = property(lambda self: self._cols[2][: self._n])
    offset = property(lambda self: self._cols[3][: self._n])
    size = property(lambda self: self._cols[4][: self._n])
    crc32 = property(lambda self: self._cols[5][: self._n])

    @property
    def ends(self):
        """Each block's end offset in the chunk file."""
        return self.offset + self.size

    @property
    def end(self) -> int:
        """The indexed end of the chunk file (0 for an empty index)."""
        return int(self.offset[-1] + self.size[-1]) if self._n else 0

    def __len__(self) -> int:
        return self._n

    def __getitem__(self, i):
        cols = [c[i] for c in self._live]
        if isinstance(i, slice):
            return ChunkIndex(*cols)
        s, b, h, o, z, c = cols
        return IndexEntry(int(s), int(b), h.tobytes(), int(o), int(z), int(c))

    def __iter__(self) -> Iterator[IndexEntry]:
        s, b, h, o, z, c = self._live
        hb = h.tobytes()
        rows = zip(s.tolist(), b.tolist(), o.tolist(), z.tolist(), c.tolist())
        for i, (s, b, o, z, c) in enumerate(rows):
            yield IndexEntry(s, b, hb[32 * i : 32 * i + 32], o, z, c)

    def __eq__(self, other):
        if isinstance(other, ChunkIndex):
            return len(other) == self._n and all(
                map(np.array_equal, self._live, other._live)
            )
        if isinstance(other, (list, tuple)):
            return len(other) == self._n and all(
                a == b for a, b in zip(self, other)
            )
        return NotImplemented

    __hash__ = None

    def __repr__(self) -> str:
        return f"ChunkIndex({list(self)!r})"

    def append(self, slot: int, block_no: int, hash_: bytes, offset: int,
               size: int, crc32: int) -> None:
        n = self._n
        if n == len(self._cols[0]):
            grown = []
            for c in self._cols:
                g = np.zeros((max(64, 2 * n),) + c.shape[1:], c.dtype)
                g[:n] = c
                grown.append(g)
            self._cols = tuple(grown)
        row = (slot, block_no, np.frombuffer(hash_, np.uint8), offset, size,
               crc32)
        for c, v in zip(self._cols, row):
            c[n] = v
        self._n = n + 1


def _span(label: str):
    """The span `label` of the replay in progress (protocol/batch's
    `_enclose`). The tracer lives in that module: where nothing has
    imported it there is no tracer, and this module never imports JAX
    for the asking."""
    pbatch = sys.modules.get("ouroboros_consensus_tpu.protocol.batch")
    return contextlib.nullcontext() if pbatch is None else pbatch._enclose(label)


def _chunk_name(n: int) -> str:
    return f"{n:05d}.chunk"


def _index_name(n: int) -> str:
    return f"{n:05d}.index"


def _cols_name(n: int) -> str:
    """Chunk n's columnar sidecar (storage/sidecar.py) — lives beside
    the chunk + index it is derived from."""
    return f"{n:05d}.cols"


class ImmutableDB:
    """Append-only block store; blocks arrive in strictly increasing slot
    order (the chain ≥ k deep is immutable — ChainDB background copy).
    """

    def __init__(
        self,
        path: str,
        chunk_size: int = 21600,  # slots per chunk (reference: epoch-ish)
        check_integrity: Callable[[bytes], bool] | None = None,
        validate_all: bool = False,
        fs=None,  # HasFS seam (utils/fs.py); None = the real filesystem
        decode_block=None,  # block codec for index rebuilds; None = Praos
        check_integrity_batch=None,  # chunk-wide twin of check_integrity:
        # (data, entries) -> count of good leading entries | None
        stream_deep: bool = False,  # validate-all checks owed at READ
        # time: streaming consumers run deep_check_loaded per chunk as
        # they read (single-pass validation; db-analyser "stream" mode)
        repair: bool = True,  # may validation MUTATE the disk? False =
        # read-only scan: truncations computed in memory only, every
        # would-be action recorded with applied=False (--dry-run)
        quarantine_dir: str | None = None,  # where snipped bytes go
        # (default <path>/quarantine); never deleted, always moved
        stream_repair: bool = False,  # stream-mode consumers may call
        # repair_to() to write back the truncation their deep read
        # computed (db_analyser.revalidate --repair)
    ):
        self.path = path
        self.chunk_size = chunk_size
        self.stream_deep = stream_deep
        self.stream_repair = stream_repair
        self._decode_block = decode_block
        self._check_integrity_batch = check_integrity_batch
        self.fs = fs if fs is not None else REAL_FS
        if repair:
            # only a store that may WRITE creates its directory; a
            # read-only scan (--dry-run, stream analysis) of a virgin
            # or typo'd path must leave no side effect — a dir created
            # here would make the NEXT open see a marker-less non-first
            # run and misclassify the untouched store as dirty
            self.fs.makedirs(path)
        self._repair = repair
        self._quarantine = repair_mod.Quarantine(
            path, self.fs, quarantine_dir
        )
        self.repairs: list[dict] = []  # repair rows of THIS open
        self._entries: dict[int, ChunkIndex] = {}  # chunk -> its index
        self._chunks: list[int] = []
        self._truncated: dict[int, bool] = {}
        self._validate(check_integrity, validate_all)

    def prepare_write(self) -> None:
        """A read-only probe being adopted as the writer store (the
        synthesizer's fresh-forge path, after its refusal checks
        passed): create the directory the read-only open deliberately
        left uncreated, and allow mutations from here on."""
        self.fs.makedirs(self.path)
        self._repair = True

    # -- startup validation --------------------------------------------------

    def _chunk_numbers(self) -> list[int]:
        ns = []
        if not self.fs.isdir(self.path):  # read-only open, virgin path
            return ns
        for f in self.fs.listdir(self.path):
            if f.endswith(".chunk"):
                ns.append(int(f.split(".")[0]))
        return sorted(ns)

    def _validate(self, check_integrity, validate_all: bool) -> None:
        """Load indices; reparse + CRC-check the last chunk (or all); on
        mismatch truncate the tail from the first bad block onward."""
        chunks = self._chunk_numbers()
        for i, n in enumerate(chunks):
            deep = validate_all or i == len(chunks) - 1
            entries = self._load_chunk(n, deep, check_integrity)
            if entries is None:  # wholly corrupt chunk: drop it and the rest
                for m in chunks[i:]:
                    self._repair_drop_chunk(
                        m,
                        detail=("wholly corrupt chunk" if m == n
                                else "stranded past a dropped chunk"),
                    )
                break
            self._entries[n] = entries
            self._chunks.append(n)
            if self._truncated.get(n):
                # truncated inside this chunk (deep check OR a reparse of
                # a stale/missing index): later chunks would leave a gap
                # in the chain — drop them (truncate-corrupted-tail)
                for m in chunks[i + 1 :]:
                    self._repair_drop_chunk(
                        m, detail="stranded past a truncated chunk"
                    )
                break
        # sweep ORPHANED index files: an index written atomically (hence
        # durable) whose chunk file's creation was never synced survives a
        # crash alone; a later append to that chunk would extend the stale
        # index and duplicate entries (ImmutableModel finding)
        live = set(self._chunks)
        names = self.fs.listdir(self.path) if self.fs.isdir(self.path) else ()
        for f in names:
            if f.endswith(".index") and int(f.split(".")[0]) not in live:
                q = 0
                if self._repair:
                    q = self._quarantine_file(f)  # moved, not copied
                self._note_repair(
                    "sweep-orphan-index", int(f.split(".")[0]), qbytes=q,
                    detail="index file without a chunk",
                )
            elif f.endswith(".cols.tmp") or (
                f.endswith(".cols") and int(f.split(".")[0]) not in live
            ):
                # a sidecar tmp is NEVER live (the rename it awaited
                # died — a crash mid-build); a final-name sidecar is
                # orphaned when its chunk is gone. Both are derived
                # data with no referent — quarantined like any orphan,
                # never trusted, never deleted (storage/sidecar.py
                # trust contract)
                q = 0
                if self._repair:
                    q = self._quarantine_file(f)
                self._note_repair(
                    "sweep-orphan-sidecar", int(f.split(".")[0]), qbytes=q,
                    detail="sidecar without a chunk"
                    if f.endswith(".cols")
                    else "sidecar tmp stranded by a crash mid-build",
                )

    # -- the repair plane ----------------------------------------------------

    def _quarantine_file(self, name: str) -> int:
        """MOVE a live file into quarantine — atomic rename, no bytes
        through memory (a production chunk is hundreds of MB). A move
        that cannot happen refuses (`QuarantineError`) BEFORE anything
        is destroyed: a drop that cannot bank its bytes must not run."""
        return self._quarantine.store_file(
            name, os.path.join(self.path, name)
        )

    def _note_repair(self, action: str, chunk: int, kept: int = 0,
                     dropped: int = 0, qbytes: int = 0,
                     detail: str = "") -> None:
        """Bank one validation repair (storage/repair.note_repair:
        warmup forensics + RepairEvent → oct_repair_total) and keep the
        row on this open's `repairs` report. applied reflects whether
        the disk actually changed (read-only scans compute only)."""
        self.repairs.append(repair_mod.note_repair(
            action, chunk=chunk, kept=kept, dropped=dropped,
            bytes_quarantined=qbytes, applied=self._repair, detail=detail,
        ))

    def _repair_truncate(self, n: int, data: bytes,
                         entries: ChunkIndex, dropped: int = 0,
                         detail: str = "") -> None:
        """Cut chunk n's corrupted on-disk tail to `entries`:
        quarantine the snipped bytes, rewrite chunk + index — or,
        read-only, record the would-be action."""
        end = entries.end
        snip = max(0, len(data) - end)
        q = snip
        if self._repair:
            q = self._quarantine.store(_chunk_name(n) + ".tail", data[end:])
            self._rewrite_chunk(n, data, entries)
        self._note_repair("truncate-chunk", n, kept=len(entries),
                          dropped=dropped, qbytes=q, detail=detail)

    def _repair_drop_chunk(self, n: int, detail: str = "") -> None:
        """Remove chunk n's files (quarantining both) — a wholly
        corrupt chunk, or one stranded past a truncation gap."""
        dropped = len(self._entries.get(n, ()))
        if n not in self._entries:
            # dropped before its entries were ever loaded (_validate
            # breaks at the first bad chunk): best-effort count from
            # the on-disk index so the repair row reports the real
            # data loss instead of 0 (unreadable index -> 0, honest)
            idx = self._load_index(
                os.path.join(self.path, _index_name(n))
            )
            dropped = 0 if idx is None else len(idx)
        q = 0
        if self._repair:
            for name in (_chunk_name(n), _index_name(n), _cols_name(n)):
                if self.fs.exists(os.path.join(self.path, name)):
                    q += self._quarantine_file(name)  # moved, not copied
        self._note_repair("drop-chunk", n, kept=0, dropped=dropped,
                          qbytes=q, detail=detail)

    def repair_to(self, n: int, good: int,
                  detail: str = "stream deep-validation write-back",
                  data: bytes | None = None) -> None:
        """Stream-mode write-back (db_analyser --repair): truncate
        chunk `n` on disk at entry count `good` — the truncation point
        the deep READ computed — and drop every chunk past it, exactly
        the repair the deep open would have taken. Quarantine + events
        like any other repair; in-memory state mirrors the disk so
        subsequent queries see the repaired store. Pass `data` when the
        chunk bytes are already in hand (the stream reader just loaded
        them) — re-reading a production chunk is hundreds of MB of I/O
        on the exact path where the disk is already suspect."""
        entries = self._entries.get(n, ChunkIndex())
        if data is None:
            try:
                data = self.fs.read_bytes(
                    os.path.join(self.path, _chunk_name(n))
                )
            except OSError:
                data = b""
        kept = entries[:good]
        self._truncated[n] = True
        self._repair_truncate(n, data, kept,
                              dropped=len(entries) - len(kept),
                              detail=detail)
        self._entries[n] = kept
        for m in [m for m in self._chunks if m > n]:
            self._repair_drop_chunk(
                m, detail="stranded past stream truncation"
            )
            self._entries.pop(m, None)
            self._chunks.remove(m)

    def _load_chunk(self, n: int, deep: bool, check_integrity):
        ipath = os.path.join(self.path, _index_name(n))
        cpath = os.path.join(self.path, _chunk_name(n))
        with _span("open.index"):
            entries = self._load_index(ipath)
        if entries is None:
            # index missing/corrupt (e.g. crash before flush): rebuild it
            # from the chunk data — blocks are self-delimiting CBOR
            entries = self._reparse_chunk(
                n, check_integrity, why="index missing or corrupt"
            )
            return entries
        # deferred index writes mean the on-disk index can LAG the chunk
        # data after a crash: reparse any bytes past the indexed end
        end = entries.end
        try:
            fsize = self.fs.getsize(cpath)
        except OSError:
            return None
        if fsize > end:
            entries = self._reparse_chunk(
                n, check_integrity,
                why=f"index lags chunk data ({fsize} > {end})",
            )
            return entries
        if deep:
            # reparse against the index, truncating at the first corruption
            try:
                data = self.fs.read_bytes(cpath)
            except OSError:
                return None
            n_indexed = len(entries)
            first_bad = self._deep_check_fast(data, entries, check_integrity)
            if first_bad is not None:
                if first_bad < len(entries):
                    self._truncated[n] = True
                entries = entries[:first_bad]
            else:
                # no native library (or a custom per-block hook without a
                # batched twin): the per-blob reference loop
                good = self._deep_check_slow(data, entries, check_integrity)
                if good < len(entries):
                    self._truncated[n] = True
                entries = entries[:good]
            if self._truncated.get(n):
                self._repair_truncate(
                    n, data, entries, dropped=n_indexed - len(entries),
                    detail="deep validation (CRC + integrity) found a "
                           "corrupt tail",
                )
        return entries

    def deep_check_loaded(
        self, data, entries, check_integrity=None, check_integrity_batch=None
    ) -> int:
        """validate-all check of one LOADED chunk without disk mutation:
        count of good leading entries (CRC + integrity, per-blob order).
        Streaming consumers (db-analyser single-pass validation) call
        this per chunk as they read, folding the deep-validation walk
        into the replay's own read — same checks as open-time
        validate_all, one disk pass instead of two."""
        fast = self._deep_check_fast(
            data, entries, check_integrity, check_integrity_batch
        )
        if fast is not None:
            return fast
        return self._deep_check_slow(data, entries, check_integrity)

    @staticmethod
    def _deep_check_slow(data, entries, check_integrity) -> int:
        """The per-blob reference loop (no native library, or a custom
        per-block hook without a batched twin): count of good leading
        entries."""
        good = 0
        for e in entries:
            blob = data[e.offset : e.offset + e.size]
            if len(blob) != e.size or zlib.crc32(blob) != e.crc32:
                break
            if check_integrity is not None and not check_integrity(blob):
                break
            good += 1
        return good

    def _deep_check_fast(self, data, entries, check_integrity,
                         batch_hook=None):
        """Vectorized deep validation: ONE native CRC walk over every
        indexed span, then the chunk-wide integrity hook (if any). The
        per-blob Python loop costs ~25 us/block of interpreter overhead
        plus ~80 us/block for the decode-based integrity hook — the
        startup-validation bottleneck on large chains (VERDICT r4 item
        3 profiling). Returns the count of good leading entries, or
        None when the fast path does not apply (caller falls back)."""
        if not entries:
            return None
        if batch_hook is None:
            batch_hook = self._check_integrity_batch
        if check_integrity is not None and batch_hook is None:
            return None  # custom hook, no batched twin
        from .. import native_loader

        rc = native_loader.crc32_first_bad(
            data, entries.offset, entries.size, entries.crc32
        )
        if rc is None:
            return None  # no native library
        good = len(entries) if rc < 0 else rc
        if check_integrity is None or good == 0:
            return good
        # the integrity hook must still vet every entry BEFORE the first
        # CRC-bad one: a written-corrupt block (consistent CRC, wrong
        # body hash) earlier in the chunk truncates earlier — order
        # matches the per-blob reference loop
        fb = batch_hook(data, entries[:good])
        if fb is None:
            return None  # hook unavailable -> slow loop
        return min(good, fb)

    def _reparse_chunk(self, n: int, check_integrity, why: str = ""):
        """Walk self-delimiting CBOR blocks in the chunk file, rebuilding
        index entries; truncate at the first unparseable/bad block.

        Uses the native scanner (native/headerscan.cpp) when available,
        no integrity predicate is requested and the block codec is the
        default Praos layout — the pure-Python CBOR walk is the
        startup-validation bottleneck on large DBs."""
        if self._decode_block is None:
            from ..block.praos_block import Block

            decode = Block.from_bytes
        else:
            decode = self._decode_block

        cpath = os.path.join(self.path, _chunk_name(n))
        try:
            data = self.fs.read_bytes(cpath)
        except OSError:
            return None

        if check_integrity is None and self._decode_block is None:
            fast = self._reparse_chunk_native(n, data)
            if fast is not None:
                return self._finish_reparse(n, data, fast, why)

        entries: list[IndexEntry] = []
        off = 0
        while off < len(data):
            try:
                _, end = cbor.decode_prefix(data, off)
                blob = data[off:end]
                blk = decode(blob)
            except Exception:
                self._truncated[n] = True
                break
            if check_integrity is not None and not check_integrity(blob):
                self._truncated[n] = True
                break
            entries.append(
                IndexEntry(
                    blk.slot, blk.block_no, blk.hash_, off, len(blob), zlib.crc32(blob)
                )
            )
            off = end
        return self._finish_reparse(
            n, data, ChunkIndex.from_entries(entries), why
        )

    def _finish_reparse(self, n: int, data: bytes,
                        entries: ChunkIndex, why: str):
        """Bank the rebuild and write it back (repair permitting): the
        index is reconstructed from chunk bytes; a torn chunk tail
        found on the way is truncated + quarantined too."""
        self._note_repair("rebuild-index", n, kept=len(entries),
                          detail=why)
        if self._truncated.get(n):
            self._repair_truncate(
                n, data, entries,
                detail=f"unparseable/bad chunk tail ({why})" if why
                       else "unparseable/bad chunk tail",
            )
        elif self._repair:
            self._write_index(n, entries)
        return entries

    def _reparse_chunk_native(self, n: int, data: bytes) -> ChunkIndex | None:
        """Native-scanner reparse (no integrity predicate): columnar
        header extraction + hashlib blake2b for the header hashes.
        Returns None when the native library is unavailable or the
        chunk's shape defeats the fast path (falls back to Python)."""
        import hashlib

        from .. import native_loader

        scan = native_loader.scan_items(data)
        if scan is None:
            return None
        offsets, sizes, end = scan
        try:
            cols = (
                native_loader.extract_headers(data, offsets)
                if len(offsets)
                else None
            )
        except ValueError:
            return None  # parseable CBOR but not our block layout
        if end < len(data):
            self._truncated[n] = True  # _finish_reparse writes back
        if cols is None:
            return ChunkIndex()
        offs = offsets.tolist()
        # header bytes span: after the block's array(2) head (1 byte),
        # through the end of the kes_sig item
        hashes = b"".join(
            hashlib.blake2b(data[off + 1 : he], digest_size=32).digest()
            for off, he in zip(offs, cols.header_end.tolist())
        )
        crcs = [
            zlib.crc32(data[off : off + sz])
            for off, sz in zip(offs, sizes.tolist())
        ]
        return ChunkIndex(cols.slot, cols.block_no,
                          np.frombuffer(hashes, np.uint8), offsets, sizes,
                          crcs)

    def _rewrite_chunk(self, n: int, data: bytes, entries: ChunkIndex):
        # the chunk bytes change, so any sidecar's seal is now a lie:
        # quarantine it BEFORE the rewrite (never trusted past its
        # seal, never deleted) — the next writer replay backfills
        self._invalidate_sidecar(n)
        self.fs.write_bytes(
            os.path.join(self.path, _chunk_name(n)), data[: entries.end]
        )
        self._write_index(n, entries)

    def _invalidate_sidecar(self, n: int) -> int:
        """Move chunk n's sidecar (if any) into quarantine — every
        path that mutates chunk bytes calls this first, so a stale
        seal can never linger beside the rewritten chunk."""
        if self.fs.exists(os.path.join(self.path, _cols_name(n))):
            return self._quarantine_file(_cols_name(n))
        return 0

    def _remove_chunk(self, n: int):
        for name in (_chunk_name(n), _index_name(n), _cols_name(n)):
            self.fs.remove(os.path.join(self.path, name))

    def _load_index(self, ipath: str) -> ChunkIndex | None:
        """Index file = concatenated CBOR entry arrays (append-only, like
        the reference's secondary index). A torn final entry (crash
        mid-append) just ends the list — the fsize-lag check reparses."""
        try:
            data = self.fs.read_bytes(ipath)
        except OSError:
            return None
        fast = self._load_index_native(data)
        if fast is not None:
            return fast
        entries: list[IndexEntry] = []
        off = 0
        end = 0
        while off < len(data):
            try:
                obj, off = cbor.decode_prefix(data, off)
                e = IndexEntry.from_cbor_obj(obj)
                # sanity: offsets must tile the chunk contiguously with
                # plausible sizes — a corrupt entry with a huge
                # offset/size must surface as "index corrupt -> reparse"
                # (the reference truncates gracefully), not as an int64
                # overflow crash in the vectorized deep check. The
                # columns are int64 and (n, 32) uint8: what does not fit
                # them is corrupt too, as the native parse reads it
                bad = (
                    e.offset != end
                    or e.size <= 0
                    or e.size > (1 << 40)
                    or not all(
                        isinstance(v, int) and 0 <= v < (1 << 63)
                        for v in (e.slot, e.block_no, e.crc32)
                    )
                    or len(e.hash_) != 32
                )
            except Exception:
                break
            if bad:
                break
            end = e.offset + e.size
            entries.append(e)
        return ChunkIndex.from_entries(entries)

    def _load_index_native(self, data: bytes) -> ChunkIndex | None:
        """Columnar native index parse + vectorized sanity checks (the
        open-time bottleneck at the 1M-header scale: ~9 us/entry of
        Python CBOR decode vs ~20 ns here). None -> Python loop."""
        from .. import native_loader

        cols = native_loader.parse_index(data)
        if cols is None:
            return None
        idx = ChunkIndex(*cols)
        if not idx:
            return idx
        # same contiguous-tiling sanity as the Python loop: offsets must
        # tile from 0 with plausible sizes (and a field past int64 reads
        # negative here); keep the good prefix only
        starts = np.concatenate(([0], idx.ends[:-1]))
        good = (idx.offset == starts) & (idx.size > 0) & (idx.size <= (1 << 40))
        good &= (idx.slot >= 0) & (idx.block_no >= 0) & (idx.crc32 >= 0)
        bad = np.flatnonzero(~good)
        return idx[: int(bad[0])] if bad.size else idx

    def _write_index(self, n: int, entries: ChunkIndex):
        data = b"".join(cbor.encode(e.to_cbor_obj()) for e in entries)
        self.fs.write_atomic(os.path.join(self.path, _index_name(n)), data)

    # -- queries -------------------------------------------------------------

    @property
    def is_empty(self) -> bool:
        return not any(self._entries.values())

    def tip(self) -> IndexEntry | None:
        for n in reversed(self._chunks):
            if self._entries[n]:
                return self._entries[n][-1]
        return None

    def tip_point(self) -> Point | None:
        t = self.tip()
        return None if t is None else Point(t.slot, t.hash_)

    def n_blocks(self) -> int:
        return sum(len(v) for v in self._entries.values())

    # -- appending -----------------------------------------------------------

    def append_block(self, slot: int, block_no: int, hash_: bytes, raw: bytes) -> None:
        t = self.tip()
        if t is not None and slot <= t.slot:
            raise ImmutableDBError(f"append out of order: {slot} <= {t.slot}")
        n = slot // self.chunk_size
        if n not in self._entries:
            self._entries[n] = ChunkIndex()
            self._chunks.append(n)
            self._chunks.sort()
        cpath = os.path.join(self.path, _chunk_name(n))
        offset = self.fs.getsize(cpath) if self.fs.exists(cpath) else 0
        # the write-path chaos seam (testing/chaos.write_fault): the
        # torn-write/bit-rot fault matrix detonates HERE, where the
        # bytes meet the disk — one bool check disarmed
        fault = chaos.write_fault(chunk=n)
        if fault == "torn-write":
            # crash mid-append: a PREFIX of the block lands in the
            # chunk, no index entry, and the writer dies — startup
            # reparse finds the unparseable tail and truncates it
            self.fs.append(cpath, raw[: max(1, len(raw) // 2)])
            raise chaos.TornWriteChaos(
                f"chaos: append torn at chunk {n} slot {slot}"
            )
        data = raw
        if fault == "bitflip":
            # silent bit rot: the write "succeeds" with one byte flipped
            # on disk; the index entry records the TRUE crc, so only a
            # deep (all-chunks / stream) walk can catch it later
            buf = bytearray(raw)
            buf[len(buf) // 2] ^= 0x01
            data = bytes(buf)
        self.fs.append(cpath, data)
        if fault == "sigkill":
            import signal

            # a REAL kill between the chunk append and the index
            # append: the reopened store finds the index lagging
            os.kill(os.getpid(), signal.SIGKILL)
        row = (slot, block_no, hash_, offset, len(raw), zlib.crc32(raw))
        self._entries[n].append(*row)
        # O(1) append-only index write (no fsync: startup validation
        # recovers from torn tails); CRC lives in the entry
        enc = cbor.encode(list(row))
        ipath = os.path.join(self.path, _index_name(n))
        self.fs.append(ipath, enc)
        if fault == "index-truncate":
            # the index file is torn mid-entry and the writer dies —
            # the reopened store sees the index lag the chunk and
            # rebuilds it from chunk bytes
            size = self.fs.getsize(ipath)
            self.fs.truncate(ipath, max(0, size - max(1, len(enc) // 2)))
            raise chaos.IndexTornChaos(
                f"chaos: index torn at chunk {n} slot {slot}"
            )

    def flush(self) -> None:
        """fsync chunk + index data of the newest chunk (clean shutdown)."""
        if not self._chunks:
            return
        n = self._chunks[-1]
        for name in (_chunk_name(n), _index_name(n)):
            p = os.path.join(self.path, name)
            if self.fs.exists(p):
                self.fs.fsync(p)

    # -- reading -------------------------------------------------------------

    def get_block_bytes(self, point: Point) -> bytes:
        n = point.slot // self.chunk_size
        idx = self._entries.get(n)
        if idx is not None:
            # slots rise within a chunk: the row by bisection, not a scan
            i = int(np.searchsorted(idx.slot, point.slot))
            if (
                i < len(idx)
                and idx.slot[i] == point.slot
                and idx.hash_[i].tobytes() == point.hash_
            ):
                return self.fs.read_at(
                    os.path.join(self.path, _chunk_name(n)),
                    int(idx.offset[i]), int(idx.size[i]),
                )
        raise MissingBlock(point)

    def iter_entries(self) -> Iterator[IndexEntry]:
        """All index entries in slot order WITHOUT reading bodies (the
        secondary index walk: sizes, CRCs, hashes for stats/plans)."""
        for n in self._chunks:
            yield from self._entries[n]

    def iter_points(self) -> Iterator[Point]:
        """All block points in slot order WITHOUT reading bodies — the
        cheap plan walk ranged ChainDB iterators build on."""
        for e in self.iter_entries():
            yield Point(e.slot, e.hash_)

    def stream_all(self) -> Iterator[tuple[IndexEntry, bytes]]:
        """Stream every block in slot order (db-analyser processAll)."""
        yield from self.stream_from(-1)

    def stream_from(self, after_slot: int) -> Iterator[tuple[IndexEntry, bytes]]:
        """Stream blocks with slot > after_slot, seeking to the first
        relevant chunk instead of scanning from genesis (snapshot-resume
        replay, LedgerDB/Init.hs:116 — must not reread the whole DB)."""
        for n in self._chunks:
            entries = self._entries[n]
            first = int(np.searchsorted(entries.slot, after_slot, "right"))
            if first == len(entries):
                continue  # chunk entirely at or before the snapshot point
            data = self.fs.read_bytes(os.path.join(self.path, _chunk_name(n)))
            for e in entries[first:]:
                yield e, data[e.offset : e.offset + e.size]

    def truncate_after(self, point: Point | None) -> None:
        """db-truncater (Tools/DBTruncater/Run.hs): drop everything after
        `point` (None = wipe)."""
        keep_through = -1 if point is None else point.slot
        for n in list(self._chunks):
            idx = self._entries[n]
            entries = idx[: int(np.searchsorted(idx.slot, keep_through, "right"))]
            if len(entries) != len(idx):
                if entries:
                    data = self.fs.read_bytes(os.path.join(self.path, _chunk_name(n)))
                    self._entries[n] = entries
                    self._rewrite_chunk(n, data, entries)
                else:
                    self._remove_chunk(n)
                    self._entries.pop(n, None)
                    self._chunks.remove(n)
