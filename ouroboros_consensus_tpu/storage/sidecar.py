"""Write-once columnar sidecar plane (``NNNNN.cols``) for the ImmutableDB.

SURVEY.md §7.3 (hard part 5) predicted host decode becomes the wall at
≥10x, and the PR-12/15 rounds proved it: with the device point-ops cut
13.6x, the hot replay ceiling (~177k headers/s) is dominated by the
per-header chunk parse (headerscan offsets → ``HeaderColumns`` → span
materialization) and the per-blob integrity walk. This module kills the
parse: each chunk gets a write-once, CRC-sealed ``NNNNN.cols`` sidecar
holding the chunk's header columns ALREADY in ``protocol/views
.ViewColumns`` shape, so a warm replay builds device-ready windows
straight off disk (mmap on the real filesystem) with zero per-header
work.

Format v1 (all little-endian):

    header   magic ``OCTCOLS1`` + version + flags + n + kes_w + sgn_w
             + chunk_len + chunk_crc32 + payload_crc32 + layout digest
             (blake2b-256 of the column plan below — a layout change
             bumps the digest, so old sidecars read as stale, never as
             garbage columns)
    payload  fixed-width column blobs, one after another, in the plan's
             order: slot/prev_hash/…/ocert_sigma (the ViewColumns
             fields), header_end + body_hash (the integrity columns —
             the hot path's body-hash compare without a parse), and the
             int32 sig/kes/sgn offset+len span arrays (the variable-
             width fallback). When every row shares one KES-signature
             and signed-body width (flag ``UNIFORM`` — the common case
             on real chains between CBOR integer-width steps) the
             ``kes_sig`` and ``signed_bytes`` matrices are appended
             too and the loader never touches the chunk bytes for
             column data.

Trust contract — **never trusted past the seal**: the freshness probe
re-derives the live chunk's length + CRC32 and the payload's CRC32 on
every open and rejects on any mismatch (``stale``), on any structural
truncation (``torn``), and on a layout/version/entry-count change. A
rejected or missing sidecar costs exactly one parse: the caller falls
back to ``native_loader.extract_headers`` and — writer opens only —
rebuilds the sidecar through the PR 13 tmp+rename durability protocol
(``fs.write_atomic``). Read-only opens NEVER write a sidecar.

Chaos seams (testing/chaos.py): ``sidecar-torn@build:N`` makes the
writer bypass the atomic protocol and land a torn prefix at the final
name (the crash-consistency hole under test); ``sigkill@build:N`` kills
the process between the tmp write and the rename; ``sidecar-stale@
open:N`` forces the Nth freshness probe to report stale. All three must
never change a replay verdict — the matrix cells in tests/test_repair.py
prove fallback → rebuild → hit.

Every probe/build outcome is one ``SidecarEvent`` through the batch
tracer (``oct_sidecar_total{outcome=hit|miss|stale|rebuilt|torn}`` when
the flight recorder is installed) plus a module-level counter snapshot
(``counters()``) that profile_replay/bench bank into the round JSON.

``OCT_SIDECAR=0`` is the kill-switch: probes and writes both disabled,
the replay is byte-identical to the parse path. Read per call (like
``OCT_COLUMNAR``) so the differential tests can A/B in one process.
"""

from __future__ import annotations

import hashlib
import os
import struct
import zlib
from dataclasses import dataclass, field

import numpy as np

from .immutable import _cols_name

_ENV = "OCT_SIDECAR"

MAGIC = b"OCTCOLS1"
VERSION = 1
FLAG_UNIFORM = 1
# The builder covered a full integrity walk of the chunk it sealed
# (forge-time construction, a stream-deep replay that walked every
# blob, truncater regeneration after truncate-to-last-valid). A HIT on
# a WALKED seal lets the hot path skip the per-blob CRC sweep: the
# probe's whole-chunk CRC already proved the live bytes are the
# build-time bytes, and the build-time walk proved those bytes pass.
# Unwalked seals (a shallow replay's backfill) keep the full sweep —
# rot that predates the build would otherwise change the verdict.
FLAG_WALKED = 2
# Every row is a TPraos (two-certificate) header: the leader
# certificate's [n, 64] output and [n, 80] proof matrices close the
# payload. A Praos chunk's sidecar is byte for byte what it was.
FLAG_TWO_CERTS = 4
_LEADER_CERT_BYTES = 64 + 80

# magic, version, flags, n, kes_w, sgn_w, chunk_len, chunk_crc,
# payload_crc, layout digest
_HEADER = struct.Struct("<8sIIIIIQII32s")
HEADER_SIZE = _HEADER.size

SIDECAR_OUTCOMES = ("hit", "miss", "stale", "rebuilt", "torn")

# the column plan: name, numpy dtype, row width (elements). Payload =
# these blobs concatenated in order, then (UNIFORM only) the kes_sig
# [n, kes_w] and signed_bytes [n, sgn_w] matrices. The layout digest
# seals this plan into every sidecar header.
_FIXED_COLS = (
    ("slot", "<i8", 1),
    ("prev_hash", "u1", 32),
    ("has_prev", "u1", 1),
    ("vk_cold", "u1", 32),
    ("vrf_vk", "u1", 32),
    ("vrf_output", "u1", 64),
    ("vrf_proof", "u1", 128),
    ("vrf_proof_len", "<i8", 1),
    ("ocert_vk_hot", "u1", 32),
    ("ocert_counter", "<i8", 1),
    ("ocert_kes_period", "<i8", 1),
    ("ocert_sigma", "u1", 64),
    ("header_end", "<i8", 1),
    ("body_hash", "u1", 32),
    ("sig_off", "<i4", 1),
    ("sig_len", "<i4", 1),
    ("kes_off", "<i4", 1),
    ("kes_len", "<i4", 1),
    ("sgn_off", "<i4", 1),
    ("sgn_len", "<i4", 1),
)

_LAYOUT = "v1;" + ",".join(
    f"{name}:{dt}x{w}" for name, dt, w in _FIXED_COLS
) + ";uniform:kes_sig,signed_bytes"
LAYOUT_DIGEST = hashlib.blake2b(
    _LAYOUT.encode(), digest_size=32
).digest()

_ROW_BYTES = sum(np.dtype(dt).itemsize * w for _, dt, w in _FIXED_COLS)


def enabled() -> bool:
    """``OCT_SIDECAR`` (default 1): probe + build the columnar sidecar
    plane. =0 is the kill-switch — the replay runs the parse path
    byte-identically; read per call so tests A/B in one process."""
    return os.environ.get(_ENV, "1") != "0"


def _crc32(data) -> int:
    """CRC32 of `data` — the native PCLMULQDQ fold when the host-crypto
    library is loadable (the probe's seal check is on the replay hot
    path), ``zlib.crc32`` otherwise. Both are the same polynomial and
    bit-identical; seals written by either verify under the other."""
    from .. import native_loader

    crc = native_loader.native_crc32(data)
    if crc is None:
        crc = zlib.crc32(data) & 0xFFFFFFFF
    return crc


def sidecar_path(db_dir: str, chunk: int) -> str:
    """The one path rule for chunk `chunk`'s sidecar (octsync SYNC207
    durability root: every write to this path goes through the
    tmp+rename protocol)."""
    return os.path.join(db_dir, _cols_name(chunk))


# ---------------------------------------------------------------------------
# counters + events
# ---------------------------------------------------------------------------

_COUNTS = {k: 0 for k in SIDECAR_OUTCOMES}


def record(outcome: str, chunk: int = -1) -> None:
    """Bank one probe/build outcome: the module counter snapshot
    (profile_replay/bench round JSON) and a `SidecarEvent` through the
    batch tracer (→ ``oct_sidecar_total{outcome=}`` when the flight
    recorder is installed). Fail-soft: telemetry may never break a
    replay."""
    if outcome in _COUNTS:
        _COUNTS[outcome] += 1
    try:
        from ..protocol import batch as pbatch
        from ..utils.trace import SidecarEvent

        if pbatch.BATCH_TRACER is not None:
            pbatch.BATCH_TRACER(SidecarEvent(outcome=outcome, chunk=chunk))
    except Exception:  # noqa: BLE001 # octflow: disable=FLOW303 — the
        # outcome counter already ticked; only the tracer mirror is
        # best-effort, and sidecar verdicts never depend on telemetry
        pass


def counters() -> dict:
    """Snapshot of the per-process outcome counts."""
    return dict(_COUNTS)


def reset_counters() -> None:
    for k in _COUNTS:
        _COUNTS[k] = 0


# ---------------------------------------------------------------------------
# writer
# ---------------------------------------------------------------------------


def build_bytes(hc, chunk_bytes, walked: bool = False) -> bytes | None:
    """Serialize one chunk's ``native_loader.HeaderColumns`` into a
    sealed sidecar blob, or None when the chunk cannot columnarize
    (zero entries, a non-64-byte OCert sigma, offsets past int32 —
    the parse path owns such chunks; an absent sidecar is always
    correct)."""
    n = int(hc.n)
    if n == 0:
        return None
    sig_len = np.asarray(hc.sig_len)
    if not bool((sig_len == 64).all()):
        return None  # ViewColumns requires a rectangular 64-byte sigma
    if int(hc.sgn_off.max()) + int(hc.sgn_len.max()) >= 2**31:
        return None  # span arrays are int32 by format
    from ..native_loader import _span_matrix

    buf = hc._buf_u8
    sigma = np.ascontiguousarray(_span_matrix(buf, hc.sig_off, hc.sig_len))
    uniform = (
        np.unique(np.asarray(hc.kes_len)).size == 1
        and np.unique(np.asarray(hc.sgn_len)).size == 1
    )
    kes_w = int(hc.kes_len[0]) if uniform else 0
    sgn_w = int(hc.sgn_len[0]) if uniform else 0
    two = np.asarray(hc.vrf_two).astype(bool)
    if two.any() and not two.all():
        return None  # one header shape a chunk, or the parse owns it
    cols = {
        "slot": hc.slot,
        "prev_hash": hc.prev_hash,
        "has_prev": hc.has_prev,
        "vk_cold": hc.issuer_vk,
        "vrf_vk": hc.vrf_vk,
        "vrf_output": hc.vrf_output,
        "vrf_proof": hc.vrf_proof,
        "vrf_proof_len": hc.vrf_proof_len,
        "ocert_vk_hot": hc.ocert_vk,
        "ocert_counter": hc.ocert_counter,
        "ocert_kes_period": hc.ocert_kes_period,
        "ocert_sigma": sigma,
        "header_end": hc.header_end,
        "body_hash": hc.body_hash,
        "sig_off": hc.sig_off,
        "sig_len": hc.sig_len,
        "kes_off": hc.kes_off,
        "kes_len": hc.kes_len,
        "sgn_off": hc.sgn_off,
        "sgn_len": hc.sgn_len,
    }
    parts = []
    for name, dt, w in _FIXED_COLS:
        a = np.ascontiguousarray(cols[name], dtype=np.dtype(dt))
        if a.shape != ((n,) if w == 1 else (n, w)):
            return None  # shape drift: refuse, never seal a lie
        parts.append(a.tobytes())
    flags = FLAG_WALKED if walked else 0
    if uniform:
        kes = _span_matrix(buf, hc.kes_off, hc.kes_len)
        sgn = _span_matrix(buf, hc.sgn_off, hc.sgn_len)
        if kes is None or sgn is None:
            uniform, kes_w, sgn_w = False, 0, 0
        else:
            flags |= FLAG_UNIFORM
            parts.append(np.ascontiguousarray(kes, np.uint8).tobytes())
            parts.append(np.ascontiguousarray(sgn, np.uint8).tobytes())
    if two.all():
        flags |= FLAG_TWO_CERTS
        parts.append(
            np.ascontiguousarray(hc.vrf_leader_output, np.uint8).tobytes())
        parts.append(
            np.ascontiguousarray(hc.vrf_leader_proof, np.uint8).tobytes())
    payload = b"".join(parts)
    header = _HEADER.pack(
        MAGIC, VERSION, flags, n, kes_w, sgn_w,
        len(chunk_bytes), _crc32(chunk_bytes),
        _crc32(payload), LAYOUT_DIGEST,
    )
    return header + payload


def write_sidecar(fs, db_dir: str, chunk: int, blob: bytes) -> bool:
    """Land one sealed sidecar blob on disk through the PR 13
    tmp+rename durability protocol (``fs.write_atomic``). The chaos
    seam detonates HERE, where the bytes meet the disk: ``sidecar-torn``
    bypasses the protocol and leaves a torn prefix at the final name
    (the probe must reject it by seal); ``sigkill`` dies between the
    tmp write and the rename (only the durable tmp survives)."""
    from ..testing import chaos

    path = sidecar_path(db_dir, chunk)
    kind = chaos.sidecar_fault("sidecar-build", chunk=chunk)
    if kind == "sidecar-torn":
        cut = min(len(blob) - 1, max(HEADER_SIZE + 7, len(blob) // 3))
        fs.write_bytes(path, blob[:cut])
        return False
    if kind == "sigkill":
        import signal

        fs.write_bytes(path + ".tmp", blob)
        os.kill(os.getpid(), signal.SIGKILL)
    fs.write_atomic(path, blob)
    return True


def backfill(fs, db_dir: str, chunk: int, hc, chunk_bytes,
             walked: bool = False) -> bool:
    """Build + write chunk `chunk`'s sidecar from an in-hand parse
    (the first replay of an un-sidecared chunk, forge time, truncater
    regeneration). `walked` stamps FLAG_WALKED — pass True only when
    a full integrity walk of these exact bytes backs the seal. True
    when a sealed sidecar landed."""
    blob = build_bytes(hc, chunk_bytes, walked=walked)
    if blob is None:
        return False
    try:
        return write_sidecar(fs, db_dir, chunk, blob)
    except OSError:
        return False  # an unwritable sidecar is a missed optimization,
        # never an error: the parse path stays correct


def backfill_store(imm, walked: bool = False) -> int:
    """Regenerate every missing/stale sidecar of an open (writer)
    ImmutableDB — db_synthesizer forge time, db_truncater
    --to-last-valid. Chunks already carrying a fresh seal are skipped
    (write-once); chunks the native scanner cannot parse are skipped
    (the parse path owns them). `walked` stamps FLAG_WALKED on every
    seal written — the forge (bytes it just wrote) and the truncater
    (everything ≤ the validated truncation point) qualify; a bare
    writer open does not. Returns the number of sidecars written."""
    from .. import native_loader
    from .immutable import _chunk_name

    if not enabled() or native_loader.load() is None:
        return 0
    wrote = 0
    for n in imm._chunks:
        entries = imm._entries[n]
        if not entries:
            continue
        try:
            data = imm.fs.read_bytes(os.path.join(imm.path, _chunk_name(n)))
        except OSError:
            continue
        sc, outcome = load_sidecar(imm.fs, imm.path, n, data, len(entries))
        if sc is not None:
            continue  # fresh seal: write-once
        try:
            hc = native_loader.extract_headers(data, entries.offset)
        except native_loader.MalformedBlock:
            continue
        if backfill(imm.fs, imm.path, n, hc, data, walked=walked):
            record("rebuilt", n)
            wrote += 1
    return wrote


# ---------------------------------------------------------------------------
# reader
# ---------------------------------------------------------------------------


def _payload_size(n: int, kes_w: int, sgn_w: int, flags: int) -> int:
    size = n * _ROW_BYTES
    if flags & FLAG_UNIFORM:
        size += n * (kes_w + sgn_w)
    if flags & FLAG_TWO_CERTS:
        size += n * _LEADER_CERT_BYTES
    return size


def _map_bytes(fs, path: str):
    """The sidecar bytes as a buffer + keep-alive handles: mmap'd on
    the real filesystem (columns page in lazily; no copy), a plain
    read through the fs seam otherwise (MockFS tests)."""
    from ..utils.fs import RealFS

    if isinstance(fs, RealFS):
        import mmap

        try:
            with open(fs._p(path), "rb") as f:
                mm = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
        except (OSError, ValueError):  # vanished / zero-length file
            return b"", ()
        return memoryview(mm), (mm,)
    try:
        return fs.read_bytes(path), ()
    except OSError:
        return b"", ()


@dataclass
class SidecarColumns:
    """One loaded, seal-verified sidecar: the fixed columns by name
    (zero-copy views over the mapped file) plus — UNIFORM chunks —
    the kes_sig/signed_bytes matrices."""

    n: int
    uniform: bool
    arrays: dict
    kes_sig: np.ndarray | None = None
    signed_bytes: np.ndarray | None = None
    walked: bool = False
    # FLAG_TWO_CERTS: the TPraos leader certificate, [n, 64] / [n, 80]
    vrf_leader_output: np.ndarray | None = None
    vrf_leader_proof: np.ndarray | None = None
    _keepalive: tuple = field(default=(), repr=False)

    def pieces(self, data) -> list | None:
        """The chunk as rectangular `ViewColumns` pieces — the same
        split-at-width-steps contract as
        ``ViewColumns.pieces_from_header_columns`` (a width step of the
        KES signature or the body, or a VRF proof format change), but
        from the sidecar's columns instead of a parse. UNIFORM chunks
        are one piece straight off the mapped matrices; non-uniform
        chunks take the kes/sgn spans from the in-hand chunk bytes (the
        span-gather fallback — still zero parse)."""
        from ..protocol.views import ViewColumns

        a = self.arrays

        def piece(lo, hi, kes, sgn):
            return ViewColumns(
                slot=a["slot"][lo:hi],
                prev_hash=a["prev_hash"][lo:hi],
                has_prev=a["has_prev"][lo:hi],
                vk_cold=a["vk_cold"][lo:hi],
                vrf_vk=a["vrf_vk"][lo:hi],
                vrf_output=a["vrf_output"][lo:hi],
                vrf_proof=a["vrf_proof"][lo:hi],
                vrf_proof_len=a["vrf_proof_len"][lo:hi],
                ocert_vk_hot=a["ocert_vk_hot"][lo:hi],
                ocert_counter=a["ocert_counter"][lo:hi],
                ocert_kes_period=a["ocert_kes_period"][lo:hi],
                ocert_sigma=a["ocert_sigma"][lo:hi],
                kes_sig=kes,
                signed_bytes=sgn,
                **({} if self.vrf_leader_output is None else dict(
                    vrf_leader_output=self.vrf_leader_output[lo:hi],
                    vrf_leader_proof=self.vrf_leader_proof[lo:hi],
                )),
            )

        if self.uniform:
            return [piece(0, self.n, self.kes_sig, self.signed_bytes)]
        from ..native_loader import _span_matrix

        buf = np.frombuffer(data, np.uint8)
        kes_len = a["kes_len"].astype(np.int64)
        sgn_len = a["sgn_len"].astype(np.int64)
        kes_off = a["kes_off"].astype(np.int64)
        sgn_off = a["sgn_off"].astype(np.int64)
        widths = np.stack([kes_len, sgn_len, a["vrf_proof_len"]], axis=1)
        chg = np.flatnonzero((widths[1:] != widths[:-1]).any(axis=1)) + 1
        bounds = [0, *chg.tolist(), self.n]
        out = []
        for k in range(len(bounds) - 1):
            lo, hi = bounds[k], bounds[k + 1]
            kes = _span_matrix(buf, kes_off[lo:hi], kes_len[lo:hi])
            sgn = _span_matrix(buf, sgn_off[lo:hi], sgn_len[lo:hi])
            if kes is None or sgn is None:
                return None  # cannot happen within one width run;
                # refuse rather than mis-shape
            out.append(piece(lo, hi, kes, sgn))
        return out


def load_sidecar(fs, db_dir: str, chunk: int, chunk_bytes,
                 n_entries: int) -> tuple[SidecarColumns | None, str]:
    """Probe + map chunk `chunk`'s sidecar against the LIVE chunk
    bytes. Returns ``(columns, "hit")`` only when every seal matches —
    structural truncation is ``torn``, any seal/layout/count mismatch
    is ``stale``, no file is ``miss``. The chaos seam
    (``sidecar-stale@open:N``) forces a stale verdict to prove the
    fallback path never changes a verdict."""
    from ..testing import chaos

    path = sidecar_path(db_dir, chunk)
    if chaos.sidecar_fault("sidecar-open", chunk=chunk) == "sidecar-stale":
        return None, "stale"
    if not fs.exists(path):
        return None, "miss"
    buf, keep = _map_bytes(fs, path)
    if len(buf) < HEADER_SIZE:
        return None, "torn"
    (magic, version, flags, n, kes_w, sgn_w, chunk_len, chunk_crc,
     payload_crc, digest) = _HEADER.unpack_from(buf, 0)
    if magic != MAGIC or version != VERSION:
        return None, "torn"
    end = HEADER_SIZE + _payload_size(n, kes_w, sgn_w, flags)
    if len(buf) < end:
        return None, "torn"
    if digest != LAYOUT_DIGEST or n != n_entries:
        return None, "stale"
    if chunk_len != len(chunk_bytes) or chunk_crc != _crc32(chunk_bytes):
        return None, "stale"
    payload = buf[HEADER_SIZE:end]
    if payload_crc != _crc32(payload):
        return None, "stale"
    arrays: dict = {}
    off = HEADER_SIZE
    for name, dt, w in _FIXED_COLS:
        dtype = np.dtype(dt)
        count = n * w
        a = np.frombuffer(buf, dtype=dtype, count=count, offset=off)
        arrays[name] = a if w == 1 else a.reshape(n, w)
        off += count * dtype.itemsize
    kes = sgn = None
    if flags & FLAG_UNIFORM:
        kes = np.frombuffer(
            buf, np.uint8, count=n * kes_w, offset=off
        ).reshape(n, kes_w)
        off += n * kes_w
        sgn = np.frombuffer(
            buf, np.uint8, count=n * sgn_w, offset=off
        ).reshape(n, sgn_w)
        off += n * sgn_w
    lout = lprf = None
    if flags & FLAG_TWO_CERTS:
        lout = np.frombuffer(
            buf, np.uint8, count=n * 64, offset=off).reshape(n, 64)
        lprf = np.frombuffer(
            buf, np.uint8, count=n * 80, offset=off + n * 64).reshape(n, 80)
    sc = SidecarColumns(
        n=n, uniform=bool(flags & FLAG_UNIFORM), arrays=arrays,
        kes_sig=kes, signed_bytes=sgn,
        walked=bool(flags & FLAG_WALKED),
        vrf_leader_output=lout, vrf_leader_proof=lprf, _keepalive=keep,
    )
    return sc, "hit"


# ---------------------------------------------------------------------------
# hot-path integrity (tentpole piece 3)
# ---------------------------------------------------------------------------


def integrity_batch_hook(sc: SidecarColumns):
    """``default_check_integrity_batch`` WITHOUT the parse: the
    per-header body-hash compare from the sidecar's
    ``header_end``/``body_hash`` columns via ``ops/blake2b.hash_spans``
    (one native batch call; device batch behind
    ``OCT_SIDECAR_DEVICE_HASH``). Unwalked seals run under
    ``_deep_check_fast``, which adds the native ``crc32_first_bad``
    sweep over the raw chunk bytes; WALKED seals call the hook directly
    — the probe's whole-chunk CRC stands in for the per-blob sweep the
    builder already walked. Same contract and
    same non-canonical-block arbitration as the parse-path hook, so a
    mismatch truncates at the identical point; any truncation sends the
    caller to the exact host walk (``deep_check_loaded``) anyway — the
    anomaly path stays the parse."""

    def hook(data, entries):
        from ..ops.blake2b import hash_spans
        from .open import default_check_integrity

        m = len(entries)
        starts = np.asarray(sc.arrays["header_end"][:m], np.int64)
        digests = hash_spans(data, starts, entries.ends)
        bad = (digests != sc.arrays["body_hash"][:m]).any(axis=1)
        for i in np.flatnonzero(bad):
            e = entries[int(i)]
            if not default_check_integrity(
                data[e.offset : e.offset + e.size]
            ):
                return int(i)
        return m

    return hook


# ---------------------------------------------------------------------------
# era-tagged chunks (a Cardano chain: hardfork/cardano)
# ---------------------------------------------------------------------------
#
# A chunk of era-tagged blocks ([era, inner]) holds runs of Byron headers
# and of Shelley-family headers: its sidecar is the chunk's parse as it
# streams (hardfork/cardano.parse_chunk): each piece's columns by name,
# the piece's era and kind, and each block's txs span and committed hash
# (the deep walk's body-hash compare). Same file name, same seals, same
# tmp+rename protocol and chaos seams as the one-era format; its own
# magic, so neither loader mistakes the other's file for its own.
#
#   header   magic ``OCTCOLSE`` + version + flags + n + chunk_len +
#            chunk_crc32 + payload_crc32 + manifest length
#   payload  the manifest (JSON: each array's name, dtype, shape and
#            offset, by piece), then the arrays, 8-byte aligned

ERA_MAGIC = b"OCTCOLSE"
ERA_VERSION = 2  # 2: a Praos piece's columns hold `signed_len`
_ERA_HEADER = struct.Struct("<8sIIIQIII")
_BYRON_FIELDS = ("kind", "slot", "block_no", "prev_hash", "has_prev",
                 "delegate_vk", "sig", "signed")


@dataclass
class EraSidecar:
    """A loaded, seal-verified era sidecar: the chunk's pieces (zero-copy
    views over the mapped file) and its integrity spans."""

    pieces: list
    spans: tuple  # (txs starts, ends, committed hashes)
    walked: bool
    _keepalive: tuple = field(default=(), repr=False)


def _piece_arrays(cols) -> dict:
    from ..protocol.pbft import ByronColumns

    if isinstance(cols, ByronColumns):
        return {f: getattr(cols, f) for f in _BYRON_FIELDS}
    from dataclasses import fields as dc_fields

    return {f.name: getattr(cols, f.name) for f in dc_fields(cols)}


def build_era_bytes(pieces, spans, chunk_bytes,
                    walked: bool = False) -> bytes | None:
    """Serialize one era-tagged chunk's parse into a sealed blob."""
    import json

    from ..protocol.pbft import ByronColumns

    n = int(len(spans[0]))
    if n == 0:
        return None
    blobs: list = []
    off = 0

    def put(a) -> list:
        nonlocal off
        a = np.ascontiguousarray(a)
        entry = [a.dtype.str, list(a.shape), off]
        raw = a.tobytes()
        pad = (-len(raw)) % 8
        blobs.append(raw + bytes(pad))
        off += len(raw) + pad
        return entry

    manifest = {
        "spans": [put(np.asarray(x)) for x in spans],
        "pieces": [
            {"era": int(p.era),
             "kind": "byron" if isinstance(p.cols, ByronColumns)
             else "praos",
             "cols": {k: put(v) for k, v in _piece_arrays(p.cols).items()}}
            for p in pieces],
    }
    mbytes = json.dumps(manifest, separators=(",", ":")).encode()
    mbytes += b" " * ((-len(mbytes)) % 8)  # JSON ends in blanks
    payload = mbytes + b"".join(blobs)
    header = _ERA_HEADER.pack(
        ERA_MAGIC, ERA_VERSION, FLAG_WALKED if walked else 0, n,
        len(chunk_bytes), _crc32(chunk_bytes), _crc32(payload),
        len(mbytes))
    return header + payload


def backfill_era(fs, db_dir: str, chunk: int, pieces, spans, chunk_bytes,
                 walked: bool = False) -> bool:
    """`backfill` for an era-tagged chunk, from an in-hand parse."""
    blob = build_era_bytes(pieces, spans, chunk_bytes, walked=walked)
    if blob is None:
        return False
    try:
        return write_sidecar(fs, db_dir, chunk, blob)
    except OSError:
        return False  # a missed optimization, never an error


def load_era_sidecar(fs, db_dir: str, chunk: int, chunk_bytes,
                     n_entries: int) -> tuple[EraSidecar | None, str]:
    """`load_sidecar` for an era-tagged chunk: ``hit`` only when every
    seal matches the LIVE chunk bytes."""
    import json

    from ..protocol.batch import EraPiece
    from ..protocol.pbft import ByronColumns
    from ..protocol.views import ViewColumns
    from ..testing import chaos

    path = sidecar_path(db_dir, chunk)
    if chaos.sidecar_fault("sidecar-open", chunk=chunk) == "sidecar-stale":
        return None, "stale"
    if not fs.exists(path):
        return None, "miss"
    buf, keep = _map_bytes(fs, path)
    hs = _ERA_HEADER.size
    if len(buf) < hs:
        return None, "torn"
    (magic, version, flags, n, chunk_len, chunk_crc, payload_crc,
     mlen) = _ERA_HEADER.unpack_from(buf, 0)
    if magic != ERA_MAGIC or version != ERA_VERSION:
        return None, "torn" if magic != MAGIC else "stale"
    if n != n_entries or chunk_len != len(chunk_bytes) \
            or chunk_crc != _crc32(chunk_bytes):
        return None, "stale"
    if payload_crc != _crc32(buf[hs:]):
        return None, "torn"
    manifest = json.loads(bytes(buf[hs:hs + mlen]))
    base = hs + mlen

    def get(entry):
        dt, shape, off = entry
        dtype = np.dtype(dt)
        count = int(np.prod(shape)) if shape else 1
        return np.frombuffer(buf, dtype, count=count,
                             offset=base + off).reshape(shape)

    spans = tuple(get(e) for e in manifest["spans"])
    pieces = []
    for p in manifest["pieces"]:
        cols = {k: get(v) for k, v in p["cols"].items()}
        pieces.append(EraPiece(p["era"], ByronColumns(**cols)
                               if p["kind"] == "byron"
                               else ViewColumns(**cols)))
    return EraSidecar(pieces, spans, bool(flags & FLAG_WALKED), keep), "hit"
