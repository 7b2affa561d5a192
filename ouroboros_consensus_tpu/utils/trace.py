"""Tracing: structured event emission threaded through every component.

Reference: contravariant `Tracer`s everywhere (contra-tracer; master
record `Tracers'` at diffusion Node/Tracers.hs:50-64; ChainDB's event
algebra at ChainDB/Impl.hs:10-28) plus `Enclose` start/end brackets for
latency measurement (Util/Enclose.hs).

The TPU build keeps the same shape with plain callables: a Tracer is any
`Callable[[event], None]`; combinators below mirror contramap / nullTracer
/ condTracer; `Enclose` is a context manager stamping monotonic start/end
events. Events are dataclasses (typed, matchable) — rendering is the
embedding application's job, exactly as in the reference (§5.5)."""

from __future__ import annotations

import gc
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable

Tracer = Callable[[Any], None]


def null_tracer(_event: Any) -> None:
    """nullTracer: drop everything."""


def contramap(f: Callable[[Any], Any], tracer: Tracer) -> Tracer:
    """contramap: adapt event type before forwarding."""

    def t(ev):
        tracer(f(ev))

    return t


def cond_tracer(pred: Callable[[Any], bool], tracer: Tracer) -> Tracer:
    def t(ev):
        if pred(ev):
            tracer(ev)

    return t


def fanout(*tracers: Tracer) -> Tracer:
    def t(ev):
        for tr in tracers:
            tr(ev)

    return t


class ListTracer:
    """Test helper: collect events (the recordingTracerIORef analog)."""

    def __init__(self):
        self.events: list = []

    def __call__(self, ev):
        self.events.append(ev)


@dataclass(frozen=True)
class EncloseEvent:
    """Start/end bracket (Util/Enclose.hs RisingEdge/FallingEdge).
    Frozen like every other event dataclass: the end edge is a NEW
    event carrying the duration, never a mutated start event.

    `replay` and `window` are the identifiers the spans of one replay /
    one device window share; `parent` is the label of the span that
    caused this one (the enclosing span on the same thread, else the
    cause the emitter names); `thread` is the emitting thread's name —
    a span's self time counts only children on its own thread
    (obs/spans.self_times)."""

    label: str
    edge: str  # "start" | "end"
    t: float
    duration: float | None = None  # set on the end edge
    replay: int | None = None
    window: int | None = None
    parent: str | None = None
    thread: str = ""


# the open Enclose spans of each thread, innermost last: a span opened
# inside another on the same thread takes it as parent and inherits its
# identifiers
_OPEN = threading.local()

_ANNOTATION: Any = None  # jax.profiler.TraceAnnotation, False without JAX


def _annotation():
    """jax.profiler.TraceAnnotation, imported on the first span of a
    traced run (this module stays importable without JAX); None where
    JAX is not installed."""
    global _ANNOTATION
    if _ANNOTATION is None:
        try:
            from jax.profiler import TraceAnnotation

            _ANNOTATION = TraceAnnotation
        except ImportError:
            _ANNOTATION = False
    return _ANNOTATION or None


class Enclose:
    """Context manager emitting start/end events around an action:

        with Enclose(tracer, "volatile-write"):
            ...

    The same span is written into the profiler's own timeline as the
    `TraceAnnotation` "oct:<label>" (with its replay / window / thread),
    on the profiler's clock and on the line of the thread that did the
    work — so a device trace needs no offset to show what the host was
    doing. Outside a profiler session the annotation costs one flag
    test in native code."""

    __slots__ = ("tracer", "label", "replay", "window", "parent", "thread",
                 "_t0", "_ann")

    def __init__(self, tracer: Tracer, label: str, replay: int | None = None,
                 window: int | None = None, parent: str | None = None):
        self.tracer = tracer
        self.label = label
        self.replay = replay
        self.window = window
        self.parent = parent
        self.thread = ""
        self._t0 = 0.0
        self._ann = None

    def __enter__(self):
        stack = getattr(_OPEN, "stack", None)
        if stack is None:
            stack = _OPEN.stack = []
        if stack:
            outer = stack[-1]
            self.parent = outer.label
            if self.replay is None:
                self.replay = outer.replay
            if self.window is None:
                self.window = outer.window
        stack.append(self)
        self.thread = threading.current_thread().name
        annotation = _annotation()
        if annotation is not None:
            ids = {k: v for k, v in (("replay", self.replay),
                                     ("window", self.window)) if v is not None}
            self._ann = annotation("oct:" + self.label, thread=self.thread,
                                   **ids)
            self._ann.__enter__()
        self._t0 = time.monotonic()
        self.tracer(EncloseEvent(self.label, "start", self._t0, None,
                                 self.replay, self.window, self.parent,
                                 self.thread))
        return self

    def __exit__(self, *exc):
        t1 = time.monotonic()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _OPEN.stack.pop()
        self.tracer(EncloseEvent(self.label, "end", t1, t1 - self._t0,
                                 self.replay, self.window, self.parent,
                                 self.thread))
        return False


class GcSpans:
    """A `gc.callbacks` hook: each collection of the interpreter while
    `replay()` names a replay, as the span `gc` on the thread that ran
    it, whose parent is the `Enclose` span open there; the profiler's
    annotation `oct:gc` carries its generation. The two edges are queued
    and handed to a tracer by `flush`, since a collection can begin
    while its thread holds a tracer's lock."""

    def __init__(self, replay: Callable[[], "int | None"]):
        self._replay = replay
        self._done: deque = deque()
        self._at: list = []  # the collection in progress: t0, replay, ann

    def hook(self, on: bool) -> None:
        """Put the hook in `gc.callbacks` (on) or take it out."""
        hooked = any(cb is self for cb in gc.callbacks)
        if on and not hooked:
            gc.callbacks.append(self)
        elif hooked and not on:
            gc.callbacks.remove(self)

    @property
    def pending(self) -> bool:
        return bool(self._done)

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._at.clear()
            replay = self._replay()
            if replay is None:
                return
            annotation = _annotation()
            ann = None
            if annotation is not None:
                ann = annotation(
                    "oct:gc", thread=threading.current_thread().name,
                    replay=replay, generation=info["generation"])
                ann.__enter__()
            self._at.extend((time.monotonic(), replay, ann))
            return
        if not self._at:
            return
        t1 = time.monotonic()
        t0, replay, ann = self._at
        self._at.clear()
        if ann is not None:
            ann.__exit__(None, None, None)
        stack = getattr(_OPEN, "stack", None)
        parent = stack[-1].label if stack else None
        thread = threading.current_thread().name
        self._done.append(EncloseEvent("gc", "start", t0, None, replay, None,
                                       parent, thread))
        self._done.append(EncloseEvent("gc", "end", t1, t1 - t0, replay, None,
                                       parent, thread))

    def flush(self, tracer: "Tracer | None") -> None:
        """Hand the queued edges to `tracer` (None: drop them); any
        thread may call it at any time."""
        while True:
            try:
                ev = self._done.popleft()
            except IndexError:
                return
            if tracer is not None:
                tracer(ev)


@dataclass(frozen=True)
class TransferEvent:
    """Device-boundary byte accounting for one batch-path phase: H2D
    staged bytes at dispatch, D2H verdict/nonce bytes at materialize.
    Emitted through the same batch tracer as the Enclose brackets so
    bench/profiling runs can report bytes-per-window alongside wall
    time (protocol/batch.py packed-staging contract)."""

    phase: str  # "dispatch" | "materialize"
    lanes: int  # padded window size
    h2d_bytes: int = 0
    d2h_bytes: int = 0
    packed: bool = False  # packed staging / packed verdict path
    window: int | None = None  # the window's id (WindowStaged.index)


# -- per-window pipeline spans (the obs/ flight-recorder vocabulary) ---------
# Per-WINDOW granularity by design: a 100k-header replay emits ~21 of
# these, never one a header (the round-8 object-tax lesson applied to
# telemetry).


@dataclass(frozen=True)
class WindowStaged:
    """One window left dispatch_batch: how it staged and, when the
    packed wire declined, WHICH qualification gate said no (the PR 5
    columnar/packed gates were silent about why a window fell back)."""

    index: int  # the window's id, allotted when it was enqueued for
    # staging (staging order is dispatch order); the `window` of every
    # span and TransferEvent of the window
    lanes: int  # true window size (pre bucket pad)
    lanes_padded: int
    outcome: str  # "packed-agg" | "packed" | "generic"
    gate: str | None  # decline reason when outcome == "generic"
    stage_s: float
    dispatch_s: float


@dataclass(frozen=True)
class StallEvent:
    """The live-plane stall watchdog (obs/live.py) tripped: no
    recorder/warmup progress for `age_s` seconds against the
    OCT_STALL_BUDGET_S budget. `phase` is the live classification at
    trip time (what the run LOOKED like while it hung); `dump_path`
    names the all-thread stack forensics file written. Escalation is
    the parent's job — this event is evidence, never a kill."""

    phase: str
    age_s: float
    budget_s: float
    dump_path: str | None


@dataclass(frozen=True)
class RecoveryEvent:
    """The recovery supervisor (obs/recovery.py) took an action for a
    failing window: one event per LADDER TRANSITION, so the trajectory
    of an episode (retry -> stage-split -> ... -> recovered/exhausted)
    is a readable event sequence and a countable metric
    (oct_recovery_total{action=}). `fault` is the failure class being
    recovered (the exception type, e.g. DeviceChaosError,
    XlaRuntimeError); `ok` is set on the terminal event of the episode."""

    action: str  # "retry" | "restage" | "stage-split" | "xla-twin"
    # | "host-reference" | "chunk-reread" | "recovered" | "exhausted"
    window: int  # retire-order window index (or -1 when unknown)
    lanes: int
    attempt: int  # 1-based position in the episode's ladder
    fault: str  # exception class name of the original failure
    detail: str  # repr of the triggering exception, trimmed
    ok: bool | None = None  # terminal events: did the episode recover?


@dataclass(frozen=True)
class CheckpointEvent:
    """The crash-consistent progress record (obs/recovery.py) moved:
    a per-retired-window atomic write, or a resume that seeded a replay
    from a record instead of genesis."""

    kind: str  # "write" | "resume" | "complete"
    headers: int  # cumulative retired headers at this point
    windows: int  # cumulative retired windows


@dataclass(frozen=True)
class RepairEvent:
    """The durable store mutated (or, dry-run, WOULD have mutated)
    itself back to consistency (storage/repair.py): a corrupted chunk
    tail truncated on disk, a secondary index rebuilt from chunk
    bytes, a wholly corrupt chunk dropped, an orphaned index swept, or
    a dirty open escalating its validation policy. Snipped bytes are
    QUARANTINED (never deleted); `applied=False` marks a read-only /
    --dry-run scan that only computed the action. Counted into
    ``oct_repair_total{action=}``."""

    action: str  # "truncate-chunk" | "rebuild-index" | "drop-chunk"
    # | "sweep-orphan-index" | "sweep-orphan-sidecar"
    # | "dirty-open-escalated"
    chunk: int  # chunk number (-1 for store-level actions)
    blocks_kept: int
    blocks_dropped: int
    bytes_quarantined: int
    applied: bool  # False = dry-run: computed, not written
    detail: str = ""


@dataclass(frozen=True)
class SidecarEvent:
    """One columnar-sidecar probe/build outcome (storage/sidecar.py):
    the stream loader probed a chunk's ``NNNNN.cols`` seal (hit / miss
    / stale / torn) or backfilled one through the tmp+rename protocol
    (rebuilt). Counted into ``oct_sidecar_total{outcome=}``; a
    non-hit outcome costs exactly one parse fallback, never a verdict
    change."""

    outcome: str  # "hit" | "miss" | "stale" | "rebuilt" | "torn"
    chunk: int = -1


@dataclass(frozen=True)
class ShardSpan:
    """Per-shard WindowSpan analogue for one sharded SPMD dispatch
    (parallel/spmd.sharded_run_batch): how one mesh position fared.
    Emitted host-side after the psum/pmin collectives land — one event
    per shard per window, so a pod-scale replay stays per-window cheap.
    `wall_s` is the whole sharded dispatch wall (identical across the
    window's shards: SPMD lockstep)."""

    index: int  # process-wide sharded-dispatch sequence number
    shard: int  # mesh position
    lanes: int  # shard-local padded lane count
    lanes_real: int  # non-pad lanes this shard carried
    n_ok: int  # popcount of ok verdicts over the real lanes
    pad_lanes: int  # bucket-pad waste in this shard
    wall_s: float


@dataclass(frozen=True)
class AggRedispatch:
    """An aggregated (RLC/MSM) window came back dirty: its per-lane
    flags are meaningless, so materialize_verdicts re-dispatched the
    unchanged per-lane stage kernels (one extra round trip)."""

    lanes: int


@dataclass(frozen=True)
class ForgeSpan:
    """One election window retired through the batched forging
    pipeline (protocol/forge.py via tools/db_synthesizer): the
    pools×slots election grid dispatched, the elected set scattered
    back, and the sequential assembly tail signed + appended. Counted
    into oct_forge_windows_total{engine=} / oct_forge_elected_total /
    oct_forge_signed_total. Per-WINDOW granularity like WindowSpan: a
    10⁷-header synthesis emits ~thousands, never per-block."""

    index: int  # process-wide forge-window sequence number
    engine: str  # "device" | "host" (the loop engine emits none)
    slots: int  # window width in slots
    pairs: int  # pools × slots election grid size
    elected: int  # slots won in this window
    signed: int  # blocks forged + appended (a limit may truncate)
    elect_s: float
    assemble_s: float


@dataclass(frozen=True)
class WindowSpan:
    """One window fully retired through validate_chain's pipelined
    loop: the complete per-phase wall plus the dispatch->materialize
    device latency (t_materialized - t_dispatch)."""

    index: int
    lanes: int
    outcome: str  # WindowStaged.outcome
    gate: str | None
    stage_s: float
    dispatch_s: float
    materialize_s: float  # host wait for the device result
    epilogue_s: float
    t_dispatch: float  # monotonic at dispatch return
    t_materialized: float  # monotonic when the device result landed
    t_done: float  # monotonic after the epilogue
    n_valid: int
    failed: bool  # this window carried the chain's first error
    stage_wait_s: float = 0.0  # main thread blocked on the staging future
    tick_s: float = 0.0  # praos.tick between materialize and epilogue
    # read when this window's retire wait began: windows dispatched and
    # not yet retired behind it (did the device have its next window
    # queued?), and windows staged and not yet dispatched
    inflight_behind: int = 0
    staged_ahead: int = 0
    t_stage_start: float = 0.0  # monotonic, on `stage_thread`
    t_stage_end: float = 0.0
    t_dispatch_start: float = 0.0
    stage_thread: str = ""
    # who the window holds: what grows with the number of issuers (a
    # one-pool chain reads 1, 2-3, 1)
    issuers: int = 0  # distinct cold keys
    kes_tails: int = 0  # rows of the KES tail table before padding
    # distinct signed-body layouts (length and field offsets: CBOR width
    # classes) the packed window holds; 0 where it staged generic
    layouts: int = 0
    prechecks_s: float = 0.0  # span `stage.prechecks`, on `stage_thread`
    epilogue_counters_s: float = 0.0  # span `epilogue.counters`
    # lane tiles that hold the window's `lanes` (ops/pk/kernels.live_tiles),
    # where that count bounded its stage kernels: they did no work on the
    # tiles behind them. 0 where it bounded none: a generic or packed-agg
    # window runs every tile, and the XLA twin has no tiles
    tiles_live: int = 0
    # VRF proofs the device verified for the window: one a live lane
    # under Praos, two under TPraos (the nonce and the leader
    # certificate); exact
    vrf_proofs: int = 0
    # TPraos: live lanes whose slot is an active overlay slot (their
    # issuer a genesis delegate, no threshold), and the wall of the
    # columnar overlay pass (span `stage.overlay`, child of
    # `stage.prechecks`, on `stage_thread`); 0 under Praos
    overlay_lanes: int = 0
    overlay_s: float = 0.0
    # PBFT (a Byron window): live regular lanes (EBB lanes excluded), the
    # wall of the columnar signing-window count (span `epilogue.pbft`) and
    # the EBB lanes; 0 in the other eras
    pbft_lanes: int = 0
    pbft_s: float = 0.0
    ebbs: int = 0  # epoch boundary blocks among a Byron window's lanes
    # wall less the thread's CPU time (`time.thread_time`) of `dispatch_s`
    # (main) and of `stage_s` (on `stage_thread`): the time the thread was
    # off the CPU — waiting for the interpreter lock, blocked in the
    # runtime, or descheduled. Where that clock advances in scheduler
    # ticks (10 ms on some hosts) one window's reading is that coarse, and
    # can be below 0: read means over many windows
    dispatch_offcpu_s: float = 0.0
    stage_offcpu_s: float = 0.0


# -- the consensus event vocabulary (Tracers' record, condensed) -------------


@dataclass(frozen=True)
class AddedBlock:
    slot: int
    block_no: int
    hash_: bytes


@dataclass(frozen=True)
class SwitchedToFork:
    n_rollback: int
    new_tip_slot: int


@dataclass(frozen=True)
class InvalidBlockEvent:
    slot: int
    hash_: bytes
    reason: str


# -- the ChainDB event algebra (ChainDB/Impl.hs:10-28) -----------------------
# One dataclass per constructor family: the add-block lifecycle,
# validation verdicts, diffusion pipelining, followers, and the
# copy/snapshot/GC background — typed and matchable so tests assert
# event SEQUENCES, not log strings.


@dataclass(frozen=True)
class IgnoreBlockOlderThanK:
    slot: int
    hash_: bytes


@dataclass(frozen=True)
class IgnoreInvalidBlock:
    slot: int
    hash_: bytes


@dataclass(frozen=True)
class AddedBlockToQueue:
    slot: int
    hash_: bytes
    queue_len: int


@dataclass(frozen=True)
class PoppedBlockFromQueue:
    slot: int
    hash_: bytes


@dataclass(frozen=True)
class AddedBlockToVolatileDB:
    slot: int
    hash_: bytes


@dataclass(frozen=True)
class StoreButDontChange:
    slot: int
    hash_: bytes


@dataclass(frozen=True)
class AddedToCurrentChain:
    n_blocks: int
    new_tip_slot: int


@dataclass(frozen=True)
class SwitchedToAFork:
    n_rollback: int
    n_blocks: int
    new_tip_slot: int


@dataclass(frozen=True)
class ValidCandidate:
    n_blocks: int
    tip_slot: int


@dataclass(frozen=True)
class SetTentativeHeader:
    slot: int
    hash_: bytes


@dataclass(frozen=True)
class TrapTentativeHeader:
    slot: int
    hash_: bytes


@dataclass(frozen=True)
class NewFollowerEvent:
    include_tentative: bool


@dataclass(frozen=True)
class CopiedToImmutableDB:
    n_blocks: int
    up_to_slot: int


@dataclass(frozen=True)
class TookSnapshot:
    n_since_last: int


@dataclass(frozen=True)
class ScheduledGC:
    slot: int


@dataclass(frozen=True)
class PerformedGC:
    slot: int


@dataclass(frozen=True)
class ForgedBlock:
    slot: int
    block_no: int
    adopted: bool


@dataclass(frozen=True)
class ValidatedBatch:
    """The TPU-specific event: one fused device batch completed."""

    n_headers: int
    n_valid: int
    device_s: float


@dataclass
class NodeTracers:
    """Tracers' (Node/Tracers.hs:50): one tracer per subsystem, all
    defaulting to null."""

    chain_db: Tracer = null_tracer
    chain_sync_client: Tracer = null_tracer
    chain_sync_server: Tracer = null_tracer
    block_fetch: Tracer = null_tracer
    mempool: Tracer = null_tracer
    forge: Tracer = null_tracer
    batch_validation: Tracer = null_tracer

    @classmethod
    def all_to(cls, tracer: Tracer) -> "NodeTracers":
        # derive the count from the dataclass fields: a hardcoded arity
        # silently desyncs the moment a tracer field is added (the
        # subsystem after the cut-off would keep its null default)
        import dataclasses

        return cls(**{f.name: tracer for f in dataclasses.fields(cls)})
