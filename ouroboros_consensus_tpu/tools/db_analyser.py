"""db-analyser: stream a stored chain and validate / benchmark it.

Reference: `Cardano.Tools.DBAnalyser` (Analysis.hs:75-88, Run.hs:42-151).
Implemented analyses:

  * ``only_validation`` — open the ImmutableDB with full integrity
    checking (ValidateAllChunks analog: reparse + body-hash check per
    block, Run.hs:133-143) and run full header revalidation. With the
    ``device`` backend the Praos crypto executes as epoch-segmented
    fused TPU batches (protocol/batch.py); with the ``host`` backend it
    folds the sequential pure-Python reference path — the same work the
    reference's libsodium-backed fold does.
  * ``benchmark_ledger_ops`` — per-block timing of forecast / header
    tick / header apply / ledger tick / ledger apply, CSV rows matching
    the reference's SlotDataPoint columns (Analysis.hs:526-607). Host
    backend only (per-block timing is meaningless inside a fused batch).
  * ``count_blocks`` — CountBlocks analog.

The device path is the north-star benchmark: headers validated/sec over
a db-synthesizer chain.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from dataclasses import dataclass, field

from ..block.praos_block import Block, Header
from ..protocol import batch as pbatch
from ..protocol import praos
from ..protocol.praos import PraosParams, PraosState
from ..protocol.views import LedgerView
from ..storage.immutable import ImmutableDB
from ..storage.open import default_check_integrity


@dataclass
class ValidationResult:
    n_blocks: int = 0
    n_valid: int = 0
    wall_s: float = 0.0
    open_s: float = 0.0  # ImmutableDB open (index load + validation)
    stage_s: float = 0.0  # host SoA staging time (device backend)
    device_s: float = 0.0  # kernel execution time (device backend)
    error: Exception | None = None
    final_state: PraosState | None = None
    resumed_headers: int = 0  # headers skipped by a checkpoint resume
    # (counted INTO n_valid: the record vouches for them — the resumed
    # total equals the uninterrupted run's by the differential suite)
    opened_dirty: bool = False  # the clean-shutdown marker was absent:
    # the validation policy escalated to all-chunks + on-disk repair
    # (storage/guard.py — forced revalidation after a crash)
    repairs: dict | None = None  # {action: count} of the store repairs
    # this open/replay applied (detailed rows ride the warmup report)
    # filled by collect_phases=True (protocol/batch tracer events):
    phases: dict | None = None  # per-phase wall s (stage/dispatch/...)
    h2d_bytes: int = 0  # staged bytes shipped host->device
    d2h_bytes: int = 0  # verdict/nonce bytes shipped device->host
    n_windows: int = 0  # dispatched windows
    packed_windows: int = 0  # windows that staged packed
    # a chain over eras (hardfork/cardano): the era of `final_state`,
    # and at each hard fork (from era, to era, the state before the
    # translation, the state after it)
    era: int = 0
    crossings: list | None = None
    # filled by collect_phases=True: the device's idle intervals of the
    # replay, each put down to the main thread's span then (obs/idle.py):
    # [(start, end, cause)] on time.monotonic(), in time order
    idle_gaps: list | None = None


class _PhaseCollector:
    """Batch tracer aggregating per-phase wall time + boundary bytes
    (Enclose brackets and TransferEvents from protocol/batch.py).
    Events arrive from the staging, reader and prefetch threads too; the
    += updates and the list append are GIL-atomic enough for
    accounting."""

    def __init__(self):
        from collections import defaultdict

        self.wall = defaultdict(float)
        self.spans: list = []  # end edges, for the self times
        self.h2d = 0
        self.d2h = 0
        self.windows = 0
        self.packed = 0

    def __call__(self, ev):
        from ..utils.trace import EncloseEvent, TransferEvent

        if isinstance(ev, EncloseEvent):
            if ev.edge == "end":
                self.wall[ev.label] += ev.duration
                self.spans.append(ev)
        elif isinstance(ev, TransferEvent):
            if ev.phase == "dispatch":
                self.h2d += ev.h2d_bytes
                self.windows += 1
                if ev.packed:
                    self.packed += 1
            else:
                self.d2h += ev.d2h_bytes

    def fill(self, res: "ValidationResult") -> None:
        from ..obs import idle as obs_idle
        from ..obs import spans as obs_spans

        res.phases = dict(self.wall)
        res.phases.setdefault("gc", 0.0)  # no collection is a reading
        # "<label>.self": the label's wall less what its child spans on
        # the same thread cover
        for label, self_s in obs_spans.self_times(self.spans).items():
            res.phases[label + ".self"] = self_s
        # the device's idle time, by the main thread's span at the time
        idle, res.idle_gaps = obs_idle.account(self.spans)
        res.phases["device-idle"] = obs_idle.total(idle)
        for cause, seconds in idle.items():
            res.phases["device-idle." + cause] = seconds
        res.h2d_bytes = self.h2d
        res.d2h_bytes = self.d2h
        res.n_windows = self.windows
        res.packed_windows = self.packed


@dataclass
class SlotDataPoint:
    """One CSV row of benchmark_ledger_ops (SlotDataPoint.hs)."""

    slot: int
    block_no: int
    block_bytes: int
    mut_forecast_us: float
    mut_header_tick_us: float
    mut_header_apply_us: float
    mut_block_tick_us: float
    mut_block_apply_us: float

    CSV_HEADER = (
        "slot,block_no,block_bytes,mut_forecast,mut_headerTick,"
        "mut_headerApply,mut_blockTick,mut_blockApply"
    )

    def csv(self) -> str:
        return (
            f"{self.slot},{self.block_no},{self.block_bytes},"
            f"{self.mut_forecast_us:.1f},{self.mut_header_tick_us:.1f},"
            f"{self.mut_header_apply_us:.1f},{self.mut_block_tick_us:.1f},"
            f"{self.mut_block_apply_us:.1f}"
        )


def open_immutable(db_path: str, validate_all=False,
                   repair: bool = False) -> ImmutableDB:
    """validate_all: False = most-recent-chunk check only; True =
    ValidateAllChunks at open (two disk passes: validation walk, then
    the replay's stream — truncates corrupted tails ON DISK, snipped
    bytes quarantined); "stream" = the SAME all-chunks checks (CRC +
    body-hash integrity, per-blob order) folded into the replay's own
    chunk reads by _stream_views — one disk pass, identical verdicts
    and truncation points. Stream mode is read-only analysis by
    default: pass ``repair=True`` (revalidate's ``--repair`` /
    ``repair=`` lever, forced by a dirty open) to write back the
    truncation the deep read computes, via `ImmutableDB.repair_to`.
    Reference: --only-validation forces ValidateAllChunks
    (Tools/DBAnalyser.hs:133-136); the stream mode is how the replay
    pays for it without reading every chunk twice."""
    import os

    from ..storage.open import default_check_integrity_batch

    stream = validate_all == "stream"
    deep = bool(validate_all) and not stream
    return ImmutableDB(
        os.path.join(db_path, "immutable"),
        check_integrity=default_check_integrity if deep else None,
        validate_all=deep,
        check_integrity_batch=(
            default_check_integrity_batch if deep else None
        ),
        # reader opens (shallow / plain stream) may not mutate the disk
        # AT ALL: truncations and index rebuilds are computed in memory
        # (applied=False rows); only a deep open or an explicit repair
        # lever writes — matching the StoreGuard writer decision
        repair=deep or bool(repair),
        stream_deep=stream,
        stream_repair=stream and bool(repair),
    )


def _epoch_segments(params: PraosParams, headers):
    """Cut a header stream at epoch boundaries (SURVEY.md §5.7: nonce and
    pool distribution are epoch-constant, so a batch spans one epoch)."""
    seg: list = []
    epoch = None
    for h in headers:
        e = params.epoch_of(h.slot)
        if epoch is None or e == epoch:
            seg.append(h)
            epoch = e
        else:
            yield seg
            seg = [h]
            epoch = e
    if seg:
        yield seg


def _columnar_enabled() -> bool:
    """OCT_COLUMNAR (default 1): flow the native chunk scan as
    ViewColumns windows end-to-end (vectorized prechecks, columnar
    packed staging, columnar epilogue — the round-8 host pipeline). =0
    restores the per-HeaderView object stream; read per call so the
    differential tests can A/B both paths in one process."""
    import os

    return os.environ.get("OCT_COLUMNAR", "1") != "0"


def _views_from_columns(cols):
    """native_loader.HeaderColumns -> HeaderViews (no Python CBOR) — the
    per-object stream (`OCT_COLUMNAR=0` and ragged-chunk fallback)."""
    from ..protocol.views import ViewColumns

    vc = ViewColumns.from_header_columns(cols)
    if vc is not None:
        return vc.views()
    # ragged spans (no rectangular column): per-row bytes-list path
    from ..protocol.views import HeaderView, OCert

    n = cols.n
    prev_b = cols.prev_hash.tobytes()
    issuer_b = cols.issuer_vk.tobytes()
    vrf_vk_b = cols.vrf_vk.tobytes()
    vrf_out_b = cols.vrf_output.tobytes()
    vrf_prf_b = cols.vrf_proof.tobytes()  # 128-wide zero-padded rows
    ocert_vk_b = cols.ocert_vk.tobytes()
    has_prev = cols.has_prev.tolist()
    counters = cols.ocert_counter.tolist()
    kes_periods = cols.ocert_kes_period.tolist()
    slots = cols.slot.tolist()
    prf_lens = cols.vrf_proof_len.tolist()
    two = cols.vrf_two.tolist()
    out = []
    for i in range(n):
        o32 = 32 * i
        out.append(
            HeaderView(
                prev_hash=prev_b[o32:o32 + 32] if has_prev[i] else None,
                vk_cold=issuer_b[o32:o32 + 32],
                vrf_vk=vrf_vk_b[o32:o32 + 32],
                vrf_output=vrf_out_b[64 * i:64 * i + 64],
                vrf_proof=vrf_prf_b[128 * i:128 * i + prf_lens[i]],
                ocert=OCert(
                    ocert_vk_b[o32:o32 + 32],
                    counters[i],
                    kes_periods[i],
                    cols.ocert_sigma[i],
                ),
                slot=slots[i],
                signed_bytes=cols.signed_bytes[i],
                kes_sig=cols.kes_sig[i],
                vrf_leader_output=(
                    cols.vrf_leader_output[i].tobytes() if two[i] else None
                ),
                vrf_leader_proof=(
                    cols.vrf_leader_proof[i].tobytes() if two[i] else None
                ),
            )
        )
    return out


def _read_chunk(path: str, chunk_idx: int) -> bytes:
    """One chunk read behind the chaos seam (`chunk-corrupt@epoch:N` —
    the chunk index stands in for the epoch on the synthesized chains,
    one chunk per epoch) with ONE recovery reread: transient I/O (and
    the chaos taxonomy, transient by contract) recovers in place as a
    first-class `chunk-reread` RecoveryEvent; a second failure
    propagates — persistent corruption must truncate loudly, not loop."""
    from ..obs import recovery as _recovery
    from ..testing import chaos

    try:
        chaos.fire("chunk", chunk=chunk_idx)
        with open(path, "rb") as f:
            return f.read()
    except (chaos.ChaosError, OSError) as e:
        if not (_recovery.enabled() and _recovery.recoverable(e)):
            raise
        _recovery.note_recovery_event("chunk-reread", chunk_idx, 0, 1, e)
        with open(path, "rb") as f:
            data = f.read()
        _recovery.note_recovery_event("recovered", chunk_idx, 0, 1, e,
                                      ok=True)
        return data


def _stream_windows(imm: ImmutableDB, res: "ValidationResult"):
    """Per-chunk window stream for revalidation. Three tiers:

    1. **Sidecar fast path** (storage/sidecar.py): a fresh-sealed
       ``NNNNN.cols`` builds `ViewColumns` straight from mmap'd column
       blobs — ZERO per-header parse; stream-deep integrity collapses
       to the one native ``crc32_first_bad`` sweep plus the sidecar's
       body-hash columns (``ops/blake2b.hash_spans``), with the exact
       host walk kept as the anomaly path on any truncation.
    2. **Native parse** (`native_loader.extract_headers` — the C++
       data-loader path, SURVEY.md §7.3 item 5): the miss/stale
       fallback, which also BACKFILLS the sidecar through the PR 13
       tmp+rename protocol — writer opens only; a read-only open never
       writes.
    3. **HeaderView lists** (no native library, OCT_COLUMNAR=0, or
       ragged chunks).

    The mmap-vs-parse wall split rides nested `_enclose` brackets
    ("stream-mmap" / "stream-parse") inside the per-chunk "stream"
    span, so the flight recorder's phase collector banks both."""
    import os

    from .. import native_loader
    from ..protocol.views import ViewColumns
    from ..storage import sidecar as sidecar_mod
    from ..storage.immutable import _chunk_name

    native_ok = native_loader.load() is not None
    columnar = _columnar_enabled()
    stream_deep = getattr(imm, "stream_deep", False)
    # the sidecar produces ViewColumns, so the kill-switch rides BOTH
    # levers: OCT_SIDECAR=0 and OCT_COLUMNAR=0 each restore the parse
    use_sidecar = sidecar_mod.enabled() and native_ok and columnar
    for chunk_idx, n in enumerate(imm._chunks):
        entries = imm._entries[n]
        if not entries:
            continue
        if native_ok and columnar and _era_tagged(imm, n, entries):
            # a chain over eras (hardfork/cardano): era-tagged blocks,
            # each run of one era parsed into its era's columns
            pieces, truncated = _era_chunk_pieces(imm, res, n, chunk_idx,
                                                  entries, stream_deep)
            yield from pieces
            if truncated:
                return  # corruption truncates the chain here
            continue
        # the per-chunk disk read + integrity walk + native column
        # extraction is the "stream" span of the flight recorder (one
        # Enclose bracket per CHUNK — per-window granularity, no object
        # tax); pbatch._enclose is a no-op while no tracer is installed
        with pbatch._enclose("stream", parent="replay"):
            data = _read_chunk(
                os.path.join(imm.path, _chunk_name(n)), chunk_idx
            )
            truncated = False
            sc = None
            if use_sidecar:
                with pbatch._enclose("stream-mmap"):
                    sc, outcome = sidecar_mod.load_sidecar(
                        imm.fs, imm.path, n, data, len(entries)
                    )
                sidecar_mod.record(outcome, n)
            if stream_deep:
                # single-pass validate-all: the open deferred the deep
                # walk to this read (open_immutable "stream" mode) —
                # same checks, same truncation point, no second disk pass
                from ..storage.open import (
                    default_check_integrity,
                    default_check_integrity_batch,
                )

                if sc is not None:
                    # hot path — no parse. WALKED seals (forge/truncater/
                    # deep-replay builds) skip the per-blob CRC sweep:
                    # the probe's whole-chunk CRC proved these are the
                    # build-time bytes, and the build-time walk proved
                    # those bytes pass the sweep; only the body-hash
                    # compare (cryptographic, vs the sealed column)
                    # still runs. Unwalked seals pay the full sweep.
                    if sc.walked:
                        good = sidecar_mod.integrity_batch_hook(sc)(
                            data, entries
                        )
                    else:
                        good = imm.deep_check_loaded(
                            data, entries, default_check_integrity,
                            sidecar_mod.integrity_batch_hook(sc),
                        )
                    if good < len(entries):
                        # anomaly path: recompute with the EXACT host
                        # walk so the truncation point and arbitration
                        # are parse-identical, and drop the sidecar —
                        # its seal dies with the repair anyway
                        sc = None
                        good = imm.deep_check_loaded(
                            data, entries, default_check_integrity,
                            default_check_integrity_batch,
                        )
                else:
                    good = imm.deep_check_loaded(
                        data, entries, default_check_integrity,
                        default_check_integrity_batch,
                    )
                if good < len(entries):
                    entries = entries[:good]
                    truncated = True
                    if getattr(imm, "stream_repair", False):
                        # --repair / dirty-open write-back: apply the
                        # truncation this deep read just computed —
                        # quarantine + on-disk cut, the same repair a
                        # deep open would have taken here
                        imm.repair_to(n, good, data=data)
            pieces = None
            cols = None
            if sc is not None and not truncated:
                with pbatch._enclose("stream-mmap"):
                    pieces = sc.pieces(data)
                if pieces is not None:
                    res.n_blocks += sc.n
            if pieces is None and native_ok and entries:
                with pbatch._enclose("stream-parse"):
                    cols = native_loader.extract_headers(
                        data, entries.offset
                    )
                res.n_blocks += cols.n
                if use_sidecar and sc is None and not truncated \
                        and getattr(imm, "_repair", False):
                    # back-fill: the first replay of an un-sidecared
                    # chunk writes the sidecar it just paid the parse
                    # for (tmp+rename durability; WRITER opens only —
                    # a read-only open leaves the disk untouched).
                    # walked only when THIS replay's deep walk covered
                    # the whole chunk; a shallow replay seals unwalked
                    if sidecar_mod.backfill(imm.fs, imm.path, n, cols,
                                            data, walked=stream_deep):
                        sidecar_mod.record("rebuilt", n)
        if pieces is not None:
            yield from pieces
        elif cols is not None:
            pcs = (
                ViewColumns.pieces_from_header_columns(cols)
                if columnar else None
            )
            if pcs is None:
                yield _views_from_columns(cols)
            else:
                yield from pcs
        else:
            win = []
            for e in entries:
                res.n_blocks += 1
                win.append(Block.from_bytes(
                    data[e.offset : e.offset + e.size]
                ).header.to_view())
            yield win
        if truncated:
            return  # corruption truncates the chain here


def _era_tagged(imm, n, entries) -> bool:
    """Does chunk `n` hold era-tagged blocks ([era, inner]: a 2-array
    whose first item is an integer, where a Praos block's is its header
    array)? Read from the first block's first two bytes."""
    from ..storage.immutable import _chunk_name

    with open(os.path.join(imm.path, _chunk_name(n)), "rb") as f:
        f.seek(int(entries.offset[0]))
        head = f.read(2)
    return len(head) == 2 and head[0] == 0x82 and head[1] <= 0x17


def _era_chunk_pieces(imm, res, n, chunk_idx, entries, stream_deep):
    """One chunk of era-tagged blocks -> (EraPiece runs in chain order,
    whether the deep walk truncated the chunk): Byron blocks as
    pbft.ByronColumns, Shelley-family blocks as ViewColumns. From the
    chunk's era sidecar (storage/sidecar.load_era_sidecar) where a fresh
    seal holds it, else from ONE native parse (hardfork/cardano
    .parse_chunk), which a writer open seals for the next replay. Either
    hands the deep walk (when the open deferred it to this read) each
    block's txs span and committed hash: the batched body-hash compare
    (storage/open.check_spans), behind the per-blob CRC walk unless a
    WALKED seal's whole-chunk CRC stands in for it."""
    from .. import native_loader
    from ..hardfork import cardano
    from ..storage import sidecar as sidecar_mod
    from ..storage.immutable import _chunk_name
    from ..storage.open import (check_spans, default_check_integrity,
                                default_check_integrity_batch)

    truncated = False
    with pbatch._enclose("stream", parent="replay"):
        data = _read_chunk(os.path.join(imm.path, _chunk_name(n)), chunk_idx)
        sc = parsed = None
        if sidecar_mod.enabled():
            with pbatch._enclose("stream-mmap"):
                sc, outcome = sidecar_mod.load_era_sidecar(
                    imm.fs, imm.path, n, data, len(entries))
            sidecar_mod.record(outcome, n)
        if sc is not None:
            parsed = (sc.pieces, sc.spans)
        else:
            with pbatch._enclose("stream-parse"):
                try:
                    parsed = cardano.parse_chunk(data, entries.offset)
                except native_loader.MalformedBlock:
                    pass  # the deep walk names the block
        if stream_deep:
            if sc is not None and sc.walked:
                good = check_spans(data, entries, *sc.spans)
            else:
                hook = default_check_integrity_batch
                if parsed is not None:
                    def hook(data, prefix, p=parsed):
                        return check_spans(data, prefix, *p[1])
                good = imm.deep_check_loaded(data, entries,
                                             default_check_integrity, hook)
            truncated = good < len(entries)
            if truncated:
                entries = entries[:good]
                if getattr(imm, "stream_repair", False):
                    imm.repair_to(n, good, data=data)
        if truncated or parsed is None:
            with pbatch._enclose("stream-parse"):
                parsed = cardano.parse_chunk(data, entries.offset)
        elif sc is None and getattr(imm, "_repair", False):
            # a writer open seals what it just parsed (walked only when
            # this replay's deep walk covered the whole chunk)
            if sidecar_mod.backfill_era(imm.fs, imm.path, n, *parsed, data,
                                        walked=stream_deep):
                sidecar_mod.record("rebuilt", n)
        res.n_blocks += len(entries)
    return parsed[0], truncated


def _stream_views(imm: ImmutableDB, res: "ValidationResult"):
    """Per-header HeaderView stream (the sequential reference fold's
    input; the batched backends consume `_stream_windows`)."""
    from ..protocol.views import ViewColumns

    for win in _stream_windows(imm, res):
        if isinstance(win, ViewColumns):
            yield from win.views()
        else:
            yield from win


def _cap_windows(wins, cap: int):
    """Truncate a window stream to `cap` total headers."""
    left = cap
    for win in wins:
        if left <= 0:
            return
        if len(win) > left:
            yield win[:left]
            return
        left -= len(win)
        yield win


def _skip_headers(wins, n: int):
    """Drop the first `n` headers of a window stream (checkpoint
    resume: the retired prefix is already banked and the fold is
    re-seeded from the host progress record). ViewColumns windows slice
    in place, so the stream stays columnar across the resume point."""
    left = n
    for win in wins:
        if left <= 0:
            yield win
        elif len(win) <= left:
            left -= len(win)
        else:
            yield win[left:]
            left = 0


def _epoch_window_segments(params: PraosParams, wins,
                           cut: int | None = None):
    """Cut a stream of chunk windows at epoch boundaries (SURVEY.md
    §5.7), merging same-epoch pieces: the columnar analog of
    `_epoch_segments`. Consecutive ViewColumns pieces of one KES
    signature width and certificate count merge into ONE columnar
    segment per epoch (one array concat, the bodies zero-padded to the
    widest: a body's length steps with the CBOR widths of the integers
    it holds, and a window takes bodies of any lengths); a KES width
    change inside an epoch yields separate columnar segments rather
    than falling back to objects — validate_chain threads state across
    them identically (the within-epoch tick is a no-op rotation).

    With `cut` (the replay's window size), an epoch's whole windows are
    handed over as soon as the stream has read them: a segment of `cut`
    rows a time from the epoch's start, its rest at the epoch's end. The
    windows cut from the segments are the same, but the replay's first
    one is staged while the rest of the epoch is still being read."""
    from ..protocol.views import ViewColumns

    if getattr(params, "eras", None) is not None:
        yield from _era_epoch_segments(params, wins, cut)
        return

    def pieces():
        import numpy as np

        for win in wins:
            if isinstance(win, ViewColumns):
                epochs = params.epoch_of(win.slot)
                cuts = np.flatnonzero(np.diff(epochs)) + 1
                bounds = [0, *cuts.tolist(), len(win)]
                for k in range(len(bounds) - 1):
                    yield int(epochs[bounds[k]]), win[bounds[k]:bounds[k + 1]]
            else:
                seg: list = []
                e = None
                for hv in win:
                    he = params.epoch_of(hv.slot)
                    if e is None or he == e:
                        seg.append(hv)
                        e = he
                    else:
                        yield e, seg
                        seg, e = [hv], he
                if seg:
                    yield e, seg

    def flush(parts):
        group: list = []
        gw = None
        for p in parts:
            if isinstance(p, ViewColumns):
                wkey = (p.kes_sig.shape[1], p.two_certs)
                if group and gw == wkey:
                    group.append(p)
                    continue
                if group:
                    yield ViewColumns.concat(group)
                group, gw = [p], wkey
            else:
                if group:
                    yield ViewColumns.concat(group)
                    group, gw = [], None
                yield p
        if group:
            yield ViewColumns.concat(group)

    acc: list = []
    epoch = None
    for e, piece in pieces():
        if acc and e != epoch:
            yield from flush(acc)
            acc = []
        acc.append(piece)
        epoch = e
        if cut and sum(len(p) for p in acc) >= cut:
            *done, last = flush(acc)
            yield from done
            whole, acc = _whole_windows(last, cut)
            yield from whole
    if acc:
        yield from flush(acc)


def _whole_windows(seg, cut: int):
    """-> ([the first `cut` x k rows of `seg`, when k > 0], [the rest,
    when there is one]): a columnar run's whole windows, and what waits
    for more of its epoch. A HeaderView list is handed over whole."""
    if isinstance(seg, list):
        return [seg], []
    k = len(seg) // cut * cut
    return [seg[:k]] if k else [], [seg[k:]] if k < len(seg) else []


def _era_epoch_segments(params, wins, cut: int | None = None):
    """`_epoch_window_segments` for a chain over eras: each EraPiece is
    cut at its era's epoch boundaries (the era params' own clock:
    Byron's epochs, then Shelley's, on one count), and consecutive runs
    of one era, one epoch and one KES signature width (Byron: one
    signed width) merge into ONE segment. An era boundary is an epoch
    boundary, so no segment spans two eras. `cut` as there."""
    import numpy as np

    from ..protocol.batch import EraPiece
    from ..protocol.pbft import ByronColumns
    from ..protocol.views import ViewColumns

    def width(cols):
        if isinstance(cols, ByronColumns):
            return ("byron", cols.signed.shape[1])
        return (cols.kes_sig.shape[1], cols.two_certs)

    def concat(group):
        if len(group) == 1:
            return group[0]
        if isinstance(group[0], ByronColumns):
            return ByronColumns.concat(group)
        return ViewColumns.concat(group)

    group: list = []
    key = None
    for ep in wins:
        p = params.era_params(ep.era)
        epochs = p.epoch_of(ep.cols.slot)
        cuts = np.flatnonzero(np.diff(epochs)) + 1
        bounds = [0, *cuts.tolist(), len(ep.cols)]
        for a, b in zip(bounds, bounds[1:]):
            part = ep.cols[a:b]
            k = (ep.era, int(epochs[a]), width(part))
            if group and k != key:
                yield EraPiece(key[0], concat(group))
                group = []
            group.append(part)
            key = k
            if cut and sum(len(g) for g in group) >= cut:
                whole, group = _whole_windows(concat(group), cut)
                yield from (EraPiece(key[0], w) for w in whole)
    if group:
        yield EraPiece(key[0], concat(group))


class _Prefetched:
    """The consumer's end of `_prefetch_iter`'s bounded queue: an
    iterator (`next()` waits for the pump) that can also be POLLED —
    `poll()` hands the next item if the pump has one ready and None
    otherwise — so a consumer with work of its own never waits for the
    stream (protocol/batch.validate_stream). `close()` stops the pump."""

    def __init__(self, q, stop, end, thread):
        self._q, self._stop, self._end = q, stop, end
        self.thread = thread  # the pump, for whoever must see it end

    def __iter__(self):
        return self

    def _take(self, block: bool):
        if self._stop.is_set():
            raise StopIteration
        try:
            item = self._q.get(block)
        except queue.Empty:
            return None
        if item is self._end:
            self._stop.set()
            raise StopIteration
        if isinstance(item, BaseException):
            self._stop.set()
            raise item
        return item

    def __next__(self):
        return self._take(True)

    def poll(self):
        return self._take(False)

    def close(self) -> None:
        self._stop.set()


def _prefetch_iter(gen, depth: int = 2) -> _Prefetched:
    """Pull a generator on a background thread through a bounded queue:
    the view-stream (disk read + integrity walk + native column
    extraction) of the segments ahead runs while the device pipeline
    stages, dispatches and retires the ones before them — part of the
    round-10 threaded staging pipeline (OCT_STAGE_THREAD=0 restores the
    inline pull). The consumer is `validate_stream`'s loop, which polls
    while it has windows staged or in flight and waits only with an
    empty pipeline. Exceptions from the stream are forwarded to the
    consumer; an early consumer exit (first-failure truncation) closes
    the iterator, which stops the pump without blocking and closes the
    generator on the pump's thread. At most `depth` items wait in the
    queue and one more in the pump's hand."""
    q: queue.Queue = queue.Queue(maxsize=max(1, depth))
    stop = threading.Event()
    end = object()

    def _put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.2)
                return True
            except queue.Full:
                continue
        return False

    def pump():
        try:
            for item in gen:
                if not _put(item):
                    return
            _put(end)
        except BaseException as e:  # noqa: BLE001 — forwarded, re-raised
            _put(e)
        finally:
            close = getattr(gen, "close", None)
            if close is not None:
                close()

    t = threading.Thread(target=pump, daemon=True, name="oct-prefetch")
    t.start()
    return _Prefetched(q, stop, end, t)


def revalidate(
    db_path: str,
    params: PraosParams,
    lview: LedgerView,
    backend: str = "device",
    validate_all: bool = True,
    max_batch: int = 8192,
    max_headers: int | None = None,  # replay only the first N headers
    # (bench.py measures the native baseline RATE on a prefix of the 1M
    # chain so the wall budget converts into device measurement)
    trace=lambda s: None,
    ledger=None,  # LEDGER-DERIVED epoch views: replay blocks through
    genesis_state=None,  # this ledger and take the per-epoch pool
    # distribution from its stake snapshots (view_for_epoch) instead of
    # the constant `lview` — Ledger/SupportsProtocol.hs
    # ledgerViewForecastAt driven from Storage/LedgerDB/Update.hs:115
    collect_phases: bool = False,  # per-phase wall + H2D/D2H byte
    # attribution in the result (batch tracer; bench.py json fields)
    resume: bool | None = None,  # resume from the OCT_CHECKPOINT
    # progress record when one matches this chain (None = follow the
    # OCT_RESUME env lever) — obs/recovery.py; batched backends only
    repair: bool = False,  # opt-in ON-DISK write-back of the
    # truncation the deep/stream validation computes (--repair):
    # quarantine + truncate via ImmutableDB.repair_to. Defaults OFF —
    # analysis stays read-only — but a DIRTY open (missing clean-
    # shutdown marker) forces it on, the reference's forced-
    # revalidation-after-crash semantics
    network_magic: int | None = None,  # strict chain-magic check of
    # the DB marker (wrong-chain open refuses with DbMarkerMismatch);
    # None = accept the existing marker, create the default on a
    # virgin store
) -> ValidationResult:
    """only-validation analysis: full chain revalidation from genesis
    — or, with `OCT_CHECKPOINT` set and a resume requested, from the
    last retired window of a killed attempt (crash-consistent progress
    record, obs/recovery.py; proven verdict-identical to the
    uninterrupted replay by tests/test_selfheal.py).

    The open speaks the store crash protocol (storage/guard.py): DB
    lock (a concurrent open refuses loudly with DbLocked), chain-magic
    marker (a wrong-chain open refuses with DbMarkerMismatch), and the
    clean-shutdown marker — an open that cannot prove the last writer
    shut down cleanly escalates its validation policy to all-chunks
    WITH on-disk repair, and the result records `opened_dirty` +
    `repairs` ({action: count}; detailed rows in the warmup report).

    collect_phases=True threads a batch tracer through the replay and
    fills `res.phases` / `res.h2d_bytes` / `res.d2h_bytes` /
    `res.n_windows` / `res.packed_windows` — the per-phase wall and
    device-boundary byte attribution the bench json reports — and the
    device's idle time by cause (`res.phases["device-idle.<cause>"]`,
    `res.idle_gaps`: obs/idle.py; the flight recorder counts the same
    account with or without this flag).

    With OCT_TRACE=1 the obs flight recorder additionally rides the
    replay (per-window spans, gate-decline attribution, Perfetto-
    exportable event stream — ouroboros_consensus_tpu/obs).

    With any live-plane lever set (OCT_HEARTBEAT / OCT_STALL_BUDGET_S /
    OCT_METRICS_PORT) the replay also arms obs/live.py: an atomically
    rewritten heartbeat file, the no-progress stall watchdog, and the
    in-run /metrics /healthz HTTP endpoint — the run stops being a
    black box WHILE it runs.
    """
    from .. import obs
    from ..obs import live as _live

    # arming is exception-safe END TO END: whatever escapes the replay
    # (a validation error, an exhausted recovery ladder, a failure in
    # maybe_arm itself) must release the live plane's ref-count and
    # stop the OCT_METRICS_PORT server thread — a failed replay may
    # never leave an orphan listener behind (tests/test_live.py)
    installed = obs.maybe_install()
    try:
        plane = _live.maybe_arm()
    except BaseException:
        if installed:
            obs.uninstall()
        raise
    pbatch.begin_replay()  # the id every span of this replay carries
    try:
        return _revalidate_traced(
            db_path, params, lview, backend, validate_all, max_batch,
            max_headers, trace, ledger, genesis_state, collect_phases,
            resume, repair, network_magic,
        )
    finally:
        pbatch.end_replay()
        if plane is not None:
            plane.disarm()
        if installed:
            obs.uninstall()


def _revalidate_traced(
    db_path, params, lview, backend, validate_all, max_batch,
    max_headers, trace, ledger, genesis_state, collect_phases, resume,
    repair, network_magic,
) -> ValidationResult:
    args = (db_path, params, lview, backend, validate_all, max_batch,
            max_headers, trace, ledger, genesis_state, resume, repair,
            network_magic)
    if not collect_phases:
        with pbatch._enclose("replay"):
            return _revalidate_impl(*args)
    from ..utils.trace import fanout

    coll = _PhaseCollector()
    prev = pbatch.BATCH_TRACER
    pbatch.set_batch_tracer(coll if prev is None else fanout(prev, coll))
    try:
        # opened once the collector is chained: res.phases holds it
        with pbatch._enclose("replay"):
            res = _revalidate_impl(*args)
    finally:
        pbatch.set_batch_tracer(prev)
    coll.fill(res)
    return res


def _revalidate_impl(
    db_path, params, lview, backend, validate_all, max_batch,
    max_headers, trace, ledger, genesis_state, resume=None,
    repair=False, network_magic=None,
) -> ValidationResult:
    """The store crash protocol around the replay (storage/guard.py):
    lock → marker → clean-shutdown check. A dirty open escalates the
    validation policy to all-chunks (`storage/open.escalate_policy` —
    Recovery.hs's forced revalidation) and forces repair write-back;
    a guard refusal (DbLocked / DbMarkerMismatch) raises BEFORE any
    bytes are read. An exception unwinding out of the replay leaves
    the store dirty (crash shape); a completed replay closes clean
    only when its walk PROVED the whole store (deep open-time
    validation, or an uncapped stream that reached the end of the
    chain — a stream aborted at a validation error checked nothing
    past the error and leaves a dirty store dirty)."""
    from ..storage import guard as _guard_mod
    from ..storage import open as _open_mod
    from ..storage import repair as _repair_mod

    res = ValidationResult()
    t0 = time.monotonic()
    policy = validate_all
    # writer mode iff this open may mutate the store: a deep open
    # repairs on disk (reference ValidateAllChunks), --repair writes
    # back stream truncations; plain stream/shallow analysis is a
    # reader and leaves the markers alone
    guard = _guard_mod.StoreGuard(
        db_path, network_magic=network_magic,
        writer=bool(repair) or policy is True,
    )
    if guard.writer and not os.path.exists(
        os.path.join(db_path, "immutable")
    ):
        # a writer-mode open of a path with no store would FABRICATE
        # one (lock + default-magic marker + clean marker) and report
        # a healthy 0/0 chain — a typo'd --db must refuse loudly
        # first. (A read-only scan of a virgin path stays legal and
        # side-effect-free.)
        raise FileNotFoundError(
            f"no store at {db_path} (refusing to create one — check --db)"
        )
    guard.open()
    try:
        if guard.opened_dirty:
            policy = _open_mod.escalate_policy(policy, True)
            guard.promote_writer()
            _repair_mod.note_repair(
                "dirty-open-escalated",
                detail=f"no clean-shutdown marker: policy {validate_all!r}"
                       f" -> {policy!r}, repair forced on",
            )
            repair = True
        res.opened_dirty = guard.opened_dirty
        with pbatch._enclose("open"):
            imm = open_immutable(db_path, validate_all=policy, repair=repair)
        res.open_s = time.monotonic() - t0
        out = _revalidate_body(
            imm, res, t0, db_path, params, lview, backend, max_batch,
            max_headers, trace, ledger, genesis_state, resume,
        )
        counts: dict = {}
        if res.opened_dirty:
            counts["dirty-open-escalated"] = 1
        # APPLIED rows only: computed-only (read-only scan) rows ride
        # the warmup report, never the applied counts
        counts.update(_repair_mod.count_actions(getattr(imm, "repairs", ())))
        out.repairs = counts or None
    except BaseException:
        guard.close(clean=False)  # the crash shape: store stays dirty
        raise
    # Stamp clean only when this open PROVED store consistency: a deep
    # open walked every chunk at open time (wherever the replay then
    # stopped), but a stream ran its checks only over the chunks it
    # actually consumed: it covers the whole chain only when uncapped
    # AND the replay reached the end — a validation ERROR aborts the
    # stream mid-chain, leaving later chunks unchecked and unrepaired
    # (a checkpoint resume still reads every chunk — the skip is
    # window-level). A capped or error-aborted stream on a DIRTY store
    # must leave it dirty so the next open still force-revalidates the
    # rest (Recovery.hs:24-59 — the promise is ALL chunks, not "the
    # prefix the replay happened to read").
    full_walk = policy is True or (policy == "stream"
                                   and max_headers is None
                                   and out.error is None)
    guard.close(clean=full_walk or not res.opened_dirty)
    return out


def _revalidate_body(
    imm, res, t0, db_path, params, lview, backend, max_batch,
    max_headers, trace, ledger, genesis_state, resume=None,
) -> ValidationResult:
    """The revalidate body (wrapped by `revalidate` for attribution and
    by `_revalidate_impl` for the store crash protocol).

    backend="device": the stream of epoch segments through ONE window
    pipeline a replay (`protocol/batch.validate_stream`: windows split
    at max_batch to bound device memory; the jit caches per padded
    shape). Memory stays bounded: the pipeline holds the segments of at
    most 2 x pipeline_depth windows, the prefetch queue two more and
    its pump one.
    backend="native": same segmentation, one `validate_chain` call a
    segment (one segment buffered at a time), through the C++ verifier
    (native/hostcrypto.cpp) — the measured single-core CPU baseline.
    backend="sharded": multi-chip SPMD — the batch axis sharded over a
    jax.sharding.Mesh of ALL visible devices with psum/pmin verdict
    collectives (parallel/spmd.py); the production multi-chip path.
    backend="host": the sequential fold (reference semantics, pure Python).
    """

    def stream_views(imm, res):
        if max_headers is None:
            return _stream_views(imm, res)
        import itertools

        return itertools.islice(_stream_views(imm, res), max_headers)

    # the chain's protocol: the params say (a DB's own config.json makes
    # them, tools/config.load_config), and the rules give its empty
    # state, its sequential reference and its batched path
    rules = pbatch.rules_of(params)
    st = rules.initial_state()
    # the ledger view of each epoch: a chain over eras has one a era
    # (hardfork/cardano.CardanoViews); the others one for every epoch
    for_epoch = (lview.for_epoch(params) if hasattr(lview, "for_epoch")
                 else (lambda _e: lview))
    if rules.protocol == "Cardano" and backend == "host":
        from ..hardfork import cardano

        wins = _stream_windows(imm, res)
        if max_headers is not None:
            wins = _cap_windows(wins, max_headers)
        cardano.host_replay(params, lview, wins, res)
        res.wall_s = time.monotonic() - t0
        return res
    if ledger is not None and getattr(ledger, "view_for_epoch", None):
        # ledger-derived epoch views: stream BLOCKS (the ledger replay
        # needs tx bodies), segment at epoch boundaries, and feed each
        # segment the pool distribution the ledger's stake snapshots
        # dictate for that epoch
        lst = genesis_state
        seg: list = []
        seg_epoch = None

        def flush(seg, seg_epoch, st, lst):
            first_slot = seg[0].slot
            tls = ledger.tick(lst, first_slot)  # seals due snapshots
            lview_e = ledger.view_for_epoch(tls.state, seg_epoch)
            hvs = [b.header.to_view() for b in seg]
            ts = time.monotonic()
            result = pbatch.validate_chain(
                params, lambda _e: lview_e, st, hvs,
                max_batch=max_batch,
                backend=backend if backend != "host" else "native",
            )
            res.device_s += time.monotonic() - ts
            for b in seg[: result.n_valid]:
                lst = ledger.tick_then_reapply(lst, b)
            return result, lst

        decode = Block.from_bytes
        block_stream = imm.stream_all()
        if max_headers is not None:
            import itertools

            block_stream = itertools.islice(block_stream, max_headers)
        for entry, raw in block_stream:
            res.n_blocks += 1
            b = decode(raw)
            e = params.epoch_of(b.slot)
            if seg_epoch is None or e == seg_epoch:
                seg.append(b)
                seg_epoch = e
                continue
            result, lst = flush(seg, seg_epoch, st, lst)
            st = result.state
            res.n_valid += result.n_valid
            if result.error is not None:
                res.error = result.error
                break
            seg, seg_epoch = [b], e
        if seg and res.error is None:
            result, lst = flush(seg, seg_epoch, st, lst)
            st = result.state
            res.n_valid += result.n_valid
            if result.error is not None:
                res.error = result.error
        res.final_state = st
        res.wall_s = time.monotonic() - t0
        return res
    if backend == "host":
        try:
            for hv in stream_views(imm, res):
                ticked = praos.tick(params, lview, hv.slot, st)
                st = rules.update(params, hv, hv.slot, ticked)
                res.n_valid += 1
        except praos.PraosValidationError as e:
            res.error = e
    elif backend in ("device", "native", "sharded"):
        # crash-consistent checkpoint/resume (obs/recovery.py): when
        # OCT_CHECKPOINT is set, validate_chain's retire path persists
        # a progress record per retired window under this chain's tag;
        # a requested resume re-seeds the fold from the record and
        # skips the already-banked prefix of the window stream.
        from ..obs import recovery as _recovery

        tag = _recovery.chain_tag(db_path, params)
        want_resume = (_recovery.resume_requested()
                       if resume is None else resume)
        rec_doc = _recovery.resume_record(tag) if want_resume else None
        _recovery.arm_writer(
            tag,
            resumed_headers=int(rec_doc["headers"]) if rec_doc else 0,
            resumed_windows=int(rec_doc["windows"]) if rec_doc else 0,
        )
        try:
            if rec_doc is not None:
                st = type(st)(**vars(
                    _recovery.decode_state(rec_doc["state"])))
                res.n_valid = int(rec_doc["headers"])
                res.resumed_headers = int(rec_doc["headers"])
                _recovery.note_resume(rec_doc)
            # Segments flow COLUMNAR (ViewColumns) end-to-end from the
            # native chunk scan; HeaderView lists appear only without
            # the native library / OCT_COLUMNAR=0
            wins = _stream_windows(imm, res)
            if max_headers is not None:
                wins = _cap_windows(wins, max_headers)
            if res.resumed_headers:
                wins = _skip_headers(wins, res.resumed_headers)
            segs = _epoch_window_segments(params, wins, cut=max_batch)
            if backend == "device":
                # ONE window pipeline a replay (validate_stream): the
                # next segment's first windows are staged and
                # dispatched while this one's last are on the device
                if pbatch._stage_thread_enabled():
                    # disk/parse/column work of the segments ahead, on
                    # its own thread; the loop polls it and waits
                    # (span `segment-wait`) only with an empty pipeline
                    segs = _prefetch_iter(segs, depth=2)
                ts = time.monotonic()
                result = pbatch.validate_stream(
                    params, for_epoch, st, segs, max_batch=max_batch,
                )
                res.device_s += time.monotonic() - ts
                st = result.state
                res.n_valid += result.n_valid
                res.error = result.error
                res.era, res.crossings = result.era, result.crossings
                if res.error is None:
                    trace(f"validated {res.n_valid} headers")
            else:
                # native / sharded: one epoch segment buffered at a time
                segs, end = iter(segs), object()
                era_cur, crossings = 0, []
                while True:
                    # the main thread's wait for the stream
                    with pbatch._enclose("segment-wait"):
                        seg = next(segs, end)
                    if seg is end:
                        break
                    seg_params, seg_views = params, for_epoch
                    if isinstance(seg, pbatch.EraPiece):
                        # a chain over eras: the era's params and view,
                        # the state translated at a hard fork
                        if seg.era != era_cur:
                            before = st
                            st = params.cross(st, era_cur, seg.era)
                            crossings.append((era_cur, seg.era, before, st))
                            era_cur = seg.era
                        seg_params = params.era_params(seg.era)
                        seg, era_view = seg.cols, lview.era_view(seg.era)
                        seg_views = lambda _e, v=era_view: v  # noqa: E731
                    ts = time.monotonic()
                    result = pbatch.validate_chain(
                        seg_params, seg_views, st, seg,
                        max_batch=max_batch, backend=backend,
                    )
                    res.device_s += time.monotonic() - ts
                    st = result.state
                    res.n_valid += result.n_valid
                    if result.error is not None:
                        res.error = result.error
                        break
                    trace(f"validated {res.n_valid} headers")
                res.era, res.crossings = era_cur, crossings or None
            w = _recovery._WRITER
            if w is not None:
                # mark the record COMPLETE (cleanly or at a validation
                # error): a later resume never skips a fresh run's work
                # based on a finished one's position
                w.finalize(st, res.error)
        finally:
            _recovery.disarm_writer()
    else:
        raise ValueError(f"unknown backend {backend!r}")

    if max_headers is not None:
        # the native columnar stream counts whole chunks into n_blocks;
        # the cap consumes only the first max_headers of the last one
        res.n_blocks = min(res.n_blocks, max_headers)
    res.final_state = st
    res.wall_s = time.monotonic() - t0
    return res


def benchmark_ledger_ops(
    db_path: str,
    params: PraosParams,
    lview: LedgerView,
    ledger=None,
    genesis_state=None,
    out_csv=None,
) -> list[SlotDataPoint]:
    """Per-block μs timings of the five ledger ops (Analysis.hs:526-607).

    The ledger tick/apply columns use the mock ledger when one is given
    (matching the reference, where ledger cost dwarfs header cost only
    on real eras); header columns always run the host Praos path.
    """
    imm = open_immutable(db_path, validate_all=False)
    rows: list[SlotDataPoint] = []
    st = PraosState()
    lst = genesis_state
    for entry, raw in imm.stream_all():
        block = Block.from_bytes(raw)
        h = block.header
        hv = h.to_view()

        t = time.monotonic()
        # forecast: ledger view at the header's slot (epoch-constant here)
        _ = lview
        forecast_us = (time.monotonic() - t) * 1e6

        t = time.monotonic()
        ticked = praos.tick(params, lview, h.slot, st)
        header_tick_us = (time.monotonic() - t) * 1e6

        t = time.monotonic()
        st = praos.update(params, hv, h.slot, ticked)
        header_apply_us = (time.monotonic() - t) * 1e6

        block_tick_us = block_apply_us = 0.0
        if ledger is not None and lst is not None:
            t = time.monotonic()
            tls = ledger.tick(lst, h.slot)
            block_tick_us = (time.monotonic() - t) * 1e6
            t = time.monotonic()
            lst = ledger.apply_block(tls, block)
            block_apply_us = (time.monotonic() - t) * 1e6

        rows.append(
            SlotDataPoint(
                slot=h.slot,
                block_no=h.block_no,
                block_bytes=len(raw),
                mut_forecast_us=forecast_us,
                mut_header_tick_us=header_tick_us,
                mut_header_apply_us=header_apply_us,
                mut_block_tick_us=block_tick_us,
                mut_block_apply_us=block_apply_us,
            )
        )
    if out_csv is not None:
        with open(out_csv, "w") as f:
            f.write(SlotDataPoint.CSV_HEADER + "\n")
            for r in rows:
                f.write(r.csv() + "\n")
    return rows


def count_blocks(db_path: str) -> int:
    imm = open_immutable(db_path)
    return imm.n_blocks()


def _stream_decoded(db_path: str, decode_block=None):
    """Shared streaming loop of the per-block analyses: yield decoded
    blocks in chain order (one decoder seam for all of them)."""
    decode = decode_block or Block.from_bytes
    for _entry, raw in open_immutable(db_path).stream_all():
        yield decode(raw)


def show_slot_block_no(db_path: str, out=None, decode_block=None) -> int:
    """ShowSlotBlockNo (Analysis.hs:76, showSlotBlockNo): print every
    block's slot and block number while streaming the ImmutableDB."""
    n = 0
    for b in _stream_decoded(db_path, decode_block):
        h = b.header
        if out is not None:
            out(f"slot: {h.slot}, blockNo: {h.block_no}")
        n += 1
    return n


def count_tx_outputs(db_path: str, decode_block=None) -> int:
    """CountTxOutputs (Analysis.hs:77): cumulative count of transaction
    outputs over the whole chain (the reference's per-block running
    total; we return the final total and emit per-block rows via
    `show_slot_block_no`-style streaming on demand)."""
    from ..ledger.mock import decode_tx

    total = 0
    for b in _stream_decoded(db_path, decode_block):
        for tx in getattr(b, "txs", ()):
            try:
                _ins, outs = decode_tx(tx)
                total += len(outs)
            except Exception:
                # opaque (non-mock-ledger) tx bytes count as zero outputs
                pass
    return total


def show_ebbs(db_path: str, decode_block=None, out=None) -> list[dict]:
    """ShowEBBs (Analysis.hs:81, Byron/EBBs.hs): list every epoch
    boundary block with its hash, previous hash, and the "known" flag
    the reference checks against its hard-coded EBB table (we have no
    such table — synthetic chains — so `known` reports whether the EBB
    chains onto the previous block we streamed)."""
    ebbs: list[dict] = []
    prev_hash = None
    for b in _stream_decoded(db_path, decode_block):
        h = b.header
        if getattr(h, "is_ebb", False) or getattr(
            getattr(h, "body", None), "is_ebb", False
        ):
            row = {
                "slot": h.slot,
                "hash": h.hash_.hex(),
                "prev": h.prev_hash.hex() if h.prev_hash else None,
                "known": prev_hash is None or h.prev_hash == prev_hash,
            }
            ebbs.append(row)
            if out is not None:
                out(f"EBB {row['hash']} at slot {row['slot']} "
                    f"(prev {row['prev']}, chains: {row['known']})")
        prev_hash = h.hash_
    return ebbs


def trace_ledger_processing(
    db_path: str,
    params: PraosParams,
    lview: LedgerView,
    ledger,
    genesis_state,
    out=None,
) -> list:
    """TraceLedgerProcessing (Analysis.hs:80): replay the chain applying
    each block to the ledger and emit the InspectLedger events of every
    transition (the reference pipes `inspectLedger old new` to stdout —
    cardano-node's "entering era" family of messages)."""
    from ..ledger.inspect import inspect_ledger

    imm = open_immutable(db_path)
    events: list = []
    lst = genesis_state
    st = PraosState()
    for entry, raw in imm.stream_all():
        block = Block.from_bytes(raw)
        h = block.header
        ticked = praos.tick(params, lview, h.slot, st)
        st = praos.reupdate(params, h.to_view(), h.slot, ticked)
        new_lst = ledger.tick_then_reapply(lst, block)
        for ev in inspect_ledger(ledger, lst, new_lst):
            events.append((h.slot, ev))
            if out is not None:
                out(f"slot {h.slot}: {ev!r}")
        lst = new_lst
    return events


def check_state_growth_every(
    db_path: str,
    params: PraosParams,
    lview: LedgerView,
    ledger,
    genesis_state,
    every: int = 100,
) -> list[dict]:
    """CheckNoThunksEvery analog (Analysis.hs:84,396-412): the reference
    walks the ledger state every N blocks looking for space leaks
    (unforced thunks). Python has no thunks; the equivalent failure mode
    is UNBOUNDED STATE GROWTH — structures that should be pruned (ocert
    counters per retired pool, protocol nonce history, UTxO bookkeeping)
    accreting per block. Samples state sizes every `every` blocks so a
    leak shows as a monotone slope instead of an OOM at block 10M."""
    import sys as _sys

    imm = open_immutable(db_path)
    st = PraosState()
    lst = genesis_state
    samples: list[dict] = []
    for i, (entry, raw) in enumerate(imm.stream_all()):
        block = Block.from_bytes(raw)
        h = block.header
        ticked = praos.tick(params, lview, h.slot, st)
        st = praos.reupdate(params, h.to_view(), h.slot, ticked)
        if ledger is not None:
            lst = ledger.tick_then_reapply(lst, block)
        if i % every == 0:
            samples.append(
                {
                    "block": i,
                    "slot": h.slot,
                    "ocert_counters": len(st.ocert_counters),
                    "utxo_entries": (
                        len(lst.utxo) if hasattr(lst, "utxo") else None
                    ),
                    "chain_dep_bytes": _sys.getsizeof(st.ocert_counters),
                }
            )
    return samples


def show_block_stats(db_path: str) -> dict:
    """GetBlockApplicationMetrics / block-size counts analog
    (Analysis.hs:75-88 counts/sizes family): min/max/total sizes + slot
    span without validating anything."""
    imm = open_immutable(db_path)
    n = 0
    total = 0
    smallest = None
    largest = None
    first_slot = last_slot = None
    # sizes/slots live in the CRC index — no body reads
    for entry in imm.iter_entries():
        n += 1
        total += entry.size
        smallest = entry.size if smallest is None else min(smallest, entry.size)
        largest = entry.size if largest is None else max(largest, entry.size)
        if first_slot is None:
            first_slot = entry.slot
        last_slot = entry.slot
    return {
        "n_blocks": n,
        "total_bytes": total,
        "min_block_bytes": smallest,
        "max_block_bytes": largest,
        "first_slot": first_slot,
        "last_slot": last_slot,
    }


def show_block_header_size(db_path: str, out=None, decode_block=None) -> int:
    """ShowBlockHeaderSize (Analysis.hs:78, showHeaderSize): per-block
    header byte size (HeaderSizeEvent) and the running maximum, which is
    returned (MaxHeaderSizeEvent)."""
    max_size = 0
    for b in _stream_decoded(db_path, decode_block):
        h = b.header
        size = len(h.bytes_)
        max_size = max(max_size, size)
        if out is not None:
            out(f"slot: {h.slot}, blockNo: {h.block_no}, headerSize: {size}")
    if out is not None:
        out(f"maxHeaderSize: {max_size}")
    return max_size


def show_block_txs_size(db_path: str, out=None, decode_block=None) -> tuple[int, int]:
    """ShowBlockTxsSize (Analysis.hs:79, showTxSize): per-block tx count
    and total tx byte size; returns the chain totals."""
    n_txs = 0
    total = 0
    for b in _stream_decoded(db_path, decode_block):
        txs = getattr(b, "txs", ())
        block_bytes = sum(len(tx) for tx in txs)
        n_txs += len(txs)
        total += block_bytes
        if out is not None:
            out(f"slot: {b.header.slot}, numBlockTxs: {len(txs)}, "
                f"blockTxsSize: {block_bytes}")
    if out is not None:
        out(f"total: {n_txs} txs, {total} bytes")
    return n_txs, total


def store_ledger_state_at(
    db_path: str,
    params: PraosParams,
    lview: LedgerView,
    slot: int,
    ledger,
    genesis_state,
    snap_dir: str,
) -> str | None:
    """StoreLedgerStateAt (Analysis.hs:118): replay (reapply, no crypto)
    up to the last block with slot <= `slot` and write that
    ExtLedgerState as a LedgerDB-compatible snapshot — a later
    db-analyser/node run can start from it instead of genesis."""
    from ..ledger.extended import ExtLedgerState
    from ..ledger.header_validation import AnnTip, HeaderState
    from ..storage.ledgerdb import encode_snapshot
    from ..utils.fs import REAL_FS

    imm = open_immutable(db_path)
    st = PraosState()
    lst = genesis_state
    tip = None
    for entry, raw in imm.stream_all():
        if entry.slot > slot:
            break
        block = Block.from_bytes(raw)
        h = block.header
        ticked = praos.tick(params, lview, h.slot, st)
        st = praos.reupdate(params, h.to_view(), h.slot, ticked)
        lst = ledger.tick_then_reapply(lst, block)
        tip = AnnTip(h.slot, h.block_no, h.hash_)
    if tip is None:
        return None
    ext = ExtLedgerState(lst, HeaderState(tip, st))
    import os as _os

    REAL_FS.makedirs(snap_dir)
    name = f"snapshot-{tip.slot}"
    REAL_FS.write_atomic(_os.path.join(snap_dir, name), encode_snapshot(ext))
    return name


def repro_mempool_and_forge(
    db_path: str,
    ledger,
    genesis_state,
    n_blocks: int | None = None,
) -> list[dict]:
    """ReproMempoolAndForge (Analysis.hs:615): replay the chain and, at
    every block, push that block's txs through a mempool against the
    pre-block ledger state and time the two phases the reference
    reports — durTick (snapshot revalidation tick) and durSnap
    (snapshot acquisition) — plus the add time."""
    from ..mempool import Mempool

    imm = open_immutable(db_path)
    rows: list[dict] = []
    lst = genesis_state
    for i, (entry, raw) in enumerate(imm.stream_all()):
        if n_blocks is not None and i >= n_blocks:
            break
        block = Block.from_bytes(raw)
        pool_state = lst
        pool = Mempool(ledger, lambda: (pool_state, block.slot))
        t = time.monotonic()
        accepted, rejected = pool.try_add_txs(list(block.txs))
        add_us = (time.monotonic() - t) * 1e6
        t = time.monotonic()
        ticked = ledger.tick(lst, block.slot)
        tick_us = (time.monotonic() - t) * 1e6
        t = time.monotonic()
        snap = pool.get_snapshot_for(ticked.state, block.slot)
        snap_us = (time.monotonic() - t) * 1e6
        rows.append(
            {
                "slot": block.slot,
                "n_txs": len(block.txs),
                "accepted": len(accepted),
                "rejected": len(rejected),
                "mut_add_us": add_us,
                "dur_tick_us": tick_us,
                "dur_snap_us": snap_us,
            }
        )
        lst = ledger.tick_then_reapply(lst, block)
    return rows


def main(argv=None) -> None:
    """CLI (app/db-analyser.hs + DBAnalyser/Parsers.hs analog)."""
    import argparse

    from .db_synthesizer import default_params, make_credentials

    p = argparse.ArgumentParser(prog="db_analyser", description=__doc__)
    p.add_argument("--db", required=True)
    p.add_argument("--pools", type=int, default=2,
                   help="credential count the chain was synthesized with")
    p.add_argument("--kes-depth", type=int, default=7)
    p.add_argument(
        "--analysis",
        choices=["only-validation", "benchmark-ledger-ops", "count-blocks",
                 "show-block-stats", "show-slot-block-no",
                 "count-tx-outputs", "show-ebbs", "show-block-header-size",
                 "show-block-txs-size"],
        default="only-validation",
    )
    p.add_argument("--backend", choices=["device", "native", "sharded", "host"], default="device")
    p.add_argument("--resume", action="store_true",
                   help="resume only-validation from the OCT_CHECKPOINT "
                        "progress record when one matches this chain "
                        "(default: follow the OCT_RESUME env lever)")
    p.add_argument("--repair", action="store_true",
                   help="write back (quarantine + truncate on disk) the "
                        "corrupted-tail truncation the validation walk "
                        "computes; default off = read-only analysis. A "
                        "dirty open (missing clean-shutdown marker) "
                        "forces this on regardless")
    p.add_argument("--out-csv", default=None)
    p.add_argument("--config", default=None,
                   help="node config.json (defaults to <db>/config/config.json "
                        "when present) instead of --pools/--kes-depth; its "
                        "\"Protocol\" (Praos, TPraos, Cardano) says what "
                        "the chain is (DBAnalyser/Block/Cardano.hs)")
    p.add_argument("--with-ledgers", action="store_true",
                   help="the toy composite forged by db_synthesizer "
                        "--cardano --with-ledgers (hardfork/composite): "
                        "fold its era ledgers too")
    a = p.parse_args(argv)
    from .. import compile_cache

    compile_cache.configure()  # before the first trace
    if a.with_ledgers:
        # the era ledgers' fold is the composite's (ROADMAP Reach 5)
        import json as _json

        from ..hardfork import composite as cardano

        if a.analysis != "only-validation":
            raise SystemExit("--with-ledgers supports only-validation")
        if a.repair or a.resume or a.config is not None:
            # a silently ignored flag would fake a repair/resume that
            # never ran, or revalidate under WRONG parameters
            raise SystemExit(
                "--with-ledgers reads the composite's built-in config "
                "and opens its store read-only: no --config, --repair "
                "or --resume"
            )
        cfg = cardano.CardanoMockConfig(with_ledgers=True)
        res = cardano.revalidate(a.db, cfg, backend=a.backend)
        out = {
            "blocks": res.n_blocks, "valid": res.n_valid,
            "per_era": res.per_era,
            "error": None if res.error is None else repr(res.error),
        }
        if res.error is not None and res.n_valid == res.n_blocks:
            # CONSENSUS passed on every block, only the LEDGER replay
            # failed — most often a flag mismatch, not corruption
            out["hint"] = (
                "ledger replay failed on a consensus-valid chain — was "
                "the DB synthesized with --with-ledgers? (a consensus-"
                "only synthesis forges placeholder tx bytes)"
            )
        print(_json.dumps(out))
        return
    if a.analysis == "count-blocks":
        print(count_blocks(a.db))
        return
    if a.analysis == "show-block-stats":
        import json as _json

        print(_json.dumps(show_block_stats(a.db)))
        return
    if a.analysis == "show-slot-block-no":
        n = show_slot_block_no(a.db, out=print)
        print(f"{n} blocks")
        return
    if a.analysis == "count-tx-outputs":
        print(count_tx_outputs(a.db))
        return
    if a.analysis == "show-ebbs":
        rows = show_ebbs(a.db, out=print)
        print(f"{len(rows)} EBBs")
        return
    if a.analysis == "show-block-header-size":
        # the analysis prints its own summary line through `out`
        show_block_header_size(a.db, out=print)
        return
    if a.analysis == "show-block-txs-size":
        show_block_txs_size(a.db, out=print)
        return
    import os as _os

    config = a.config
    if config is None:
        implicit = _os.path.join(a.db, "config", "config.json")
        if _os.path.exists(implicit):
            config = implicit
    if config:
        from .config import load_config

        params, lview, _pools = load_config(config)
    else:
        params = default_params(kes_depth=a.kes_depth)
        _, lview = make_credentials(a.pools, kes_depth=a.kes_depth)
    if a.analysis == "benchmark-ledger-ops":
        rows = benchmark_ledger_ops(a.db, params, lview, out_csv=a.out_csv)
        print(f"{len(rows)} blocks benchmarked" + (
            f"; CSV at {a.out_csv}" if a.out_csv else ""))
        return
    res = revalidate(a.db, params, lview, backend=a.backend,
                     trace=lambda s: print(s),
                     resume=True if a.resume else None,
                     repair=a.repair)
    status = "OK" if res.error is None else f"INVALID at {res.n_valid}: {res.error!r}"
    if res.repairs:
        acts = ", ".join(f"{k}={v}" for k, v in sorted(res.repairs.items()))
        print(("dirty open — " if res.opened_dirty else "")
              + f"store repairs: {acts}")
    print(
        f"validated {res.n_valid}/{res.n_blocks} headers in {res.wall_s:.1f}s "
        f"(device {res.device_s:.1f}s) -> {status}"
    )


if __name__ == "__main__":
    main()
