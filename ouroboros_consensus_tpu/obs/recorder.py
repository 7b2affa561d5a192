"""FlightRecorder: the batch-tracer sink behind the OCT_TRACE lever.

One process-wide recorder chains into `protocol.batch.BATCH_TRACER`
(preserving whatever tracer an embedding application already set),
keeps the timed event stream for Perfetto export, and folds every
event into the metrics registry:

    oct_windows_total{outcome=}            dispatched windows
    oct_gate_declines_total{gate=}         why packed staging said no
    oct_headers_validated_total            retired lanes
    oct_agg_redispatch_total               dirty aggregate windows
    oct_h2d_bytes_total / oct_d2h_bytes_total
    oct_window_{stage,dispatch,materialize,epilogue}_seconds   histograms
    oct_window_device_latency_seconds      dispatch->materialize wall
    oct_stalls_total{phase=}               stall-watchdog trips (obs/live)
    oct_recovery_total{action=}            recovery-ladder transitions
    oct_checkpoint_events_total{kind=}     progress-record movement
                                           (obs/recovery)
    oct_repair_total{action=}              on-disk store repairs applied
                                           (storage/repair)
    oct_sidecar_total{outcome=}            columnar-sidecar probe/build
                                           outcomes (storage/sidecar)
    oct_shard_{windows,lanes,ok_lanes,pad_lanes}_total{shard=}
                                           per-shard SPMD telemetry
    oct_forge_windows_total{engine=}       election windows dispatched
                                           (protocol/forge ForgeSpan)
    oct_forge_elected_total                slots won across windows
    oct_forge_signed_total                 blocks forged + appended
    oct_device_idle_seconds_total{under=}  the device's idle time of each
                                           replay, by the main thread's
                                           span then (obs/idle.py)

Per-window granularity only — a 1M-header replay emits a few hundred
events, so the host feed ceiling is untaxed."""

from __future__ import annotations

import threading
import time

from ..utils.trace import (
    AggRedispatch, CheckpointEvent, EncloseEvent, ForgeSpan, RecoveryEvent,
    RepairEvent, ShardSpan, SidecarEvent, StallEvent, TransferEvent,
    WindowSpan, WindowStaged,
)
from . import idle as _idle
from . import registry as _registry

# bounded event buffer: a pathological run cannot grow without limit
MAX_EVENTS = 200_000


class FlightRecorder:
    def __init__(self, reg: "_registry.MetricsRegistry | None" = None):
        self.registry = reg if reg is not None else _registry.default_registry()
        self._lock = threading.Lock()
        self.events: list[tuple[float, object]] = []
        self.dropped = 0
        r = self.registry
        self._windows = r.counter(
            "oct_windows_total", "dispatched device windows", ("outcome",)
        )
        self._gates = r.counter(
            "oct_gate_declines_total",
            "packed-staging qualification gate declines", ("gate",),
        )
        self._headers = r.counter(
            "oct_headers_validated_total", "lanes retired valid"
        )
        self._redisp = r.counter(
            "oct_agg_redispatch_total",
            "aggregate windows re-dispatched per-lane",
        )
        self._h2d = r.counter("oct_h2d_bytes_total", "bytes staged to device")
        self._d2h = r.counter("oct_d2h_bytes_total", "bytes returned to host")
        self._phase_h = {
            p: r.histogram(
                f"oct_window_{p}_seconds", f"per-window {p} wall"
            )
            for p in ("stage", "dispatch", "materialize", "epilogue")
        }
        self._latency = r.histogram(
            "oct_window_device_latency_seconds",
            "dispatch->materialize wall per window",
        )
        # live plane (obs/live.py): stall-watchdog trips by the phase
        # the run was wedged in at trip time
        self._stalls = r.counter(
            "oct_stalls_total", "stall-watchdog trips", ("phase",)
        )
        # recovery plane (obs/recovery.py): ladder transitions per
        # action, and checkpoint record movement (write/resume/complete)
        self._recovery = r.counter(
            "oct_recovery_total",
            "recovery-supervisor ladder transitions", ("action",),
        )
        self._checkpoints = r.counter(
            "oct_checkpoint_events_total",
            "progress-record writes/resumes/completions", ("kind",),
        )
        # durable-store repair plane (storage/repair.py): on-disk
        # repairs the open-with-repair scan applied (truncated tails,
        # rebuilt indices, dropped chunks, dirty-open escalations) —
        # dry-run/would-repair events are NOT counted here, they only
        # ride the warmup report's `repairs` rows
        self._repairs = r.counter(
            "oct_repair_total",
            "on-disk store repair actions applied", ("action",),
        )
        # columnar-sidecar plane (storage/sidecar.py): every freshness
        # probe / backfill outcome — hit is the parse-free fast path,
        # everything else costs exactly one parse fallback
        self._sidecar = r.counter(
            "oct_sidecar_total",
            "columnar-sidecar probe/build outcomes", ("outcome",),
        )
        # per-shard SPMD telemetry (parallel/spmd.py ShardSpan events):
        # label cardinality is the mesh size — bounded by hardware
        self._shard_windows = r.counter(
            "oct_shard_windows_total",
            "sharded windows dispatched per mesh position", ("shard",),
        )
        self._shard_lanes = r.counter(
            "oct_shard_lanes_total",
            "real (non-pad) lanes dispatched per shard", ("shard",),
        )
        self._shard_ok = r.counter(
            "oct_shard_ok_lanes_total",
            "lanes retired valid per shard (psum popcount vocabulary)",
            ("shard",),
        )
        self._shard_pad = r.counter(
            "oct_shard_pad_lanes_total",
            "bucket-pad waste lanes per shard", ("shard",),
        )
        # forge plane (protocol/forge.py ForgeSpan events): the batched
        # synthesizer's election windows, elected slots and signed
        # blocks — label cardinality is the engine set (device/host)
        self._forge_windows = r.counter(
            "oct_forge_windows_total",
            "forge election windows dispatched", ("engine",),
        )
        self._forge_elected = r.counter(
            "oct_forge_elected_total", "slots won in forge windows"
        )
        self._forge_signed = r.counter(
            "oct_forge_signed_total", "blocks forged and appended"
        )
        # the device's idle time of each replay, by the main thread's
        # span at the idle instant (obs/idle.py), counted as the replay
        # ends from the end edges of its spans, kept until then
        self._device_idle = r.counter(
            "oct_device_idle_seconds_total",
            "device idle seconds by the main thread's span", ("under",),
        )
        self._replays: dict[int, list] = {}
        # heartbeat source: the most recent event (kept even after the
        # bounded buffer fills) + the latest retired window index
        self._last: "tuple[float, object] | None" = None
        self._last_span_index = -1

    # -- the tracer ---------------------------------------------------------

    def __call__(self, ev) -> None:
        now = time.monotonic()
        ended = None
        with self._lock:
            self._last = (now, ev)
            if len(self.events) < MAX_EVENTS:
                self.events.append((now, ev))
            else:
                self.dropped += 1
            if isinstance(ev, EncloseEvent) and ev.replay is not None:
                ended = self._replay_span(ev)
        if isinstance(ev, EncloseEvent):
            # kept in the event stream (Perfetto slices); a replay's
            # last one closes its idle account
            if ended is not None:
                for cause, seconds in _idle.account(ended)[0].items():
                    self._device_idle.labels(under=cause).inc(seconds)
        elif isinstance(ev, WindowStaged):
            self._windows.labels(outcome=ev.outcome).inc()
            if ev.outcome == "generic":
                self._gates.labels(gate=ev.gate or "packed-off").inc()
        elif isinstance(ev, WindowSpan):
            with self._lock:
                if ev.index > self._last_span_index:
                    self._last_span_index = ev.index
            self._headers.inc(ev.n_valid)
            self._phase_h["stage"].observe(ev.stage_s)
            self._phase_h["dispatch"].observe(ev.dispatch_s)
            self._phase_h["materialize"].observe(ev.materialize_s)
            self._phase_h["epilogue"].observe(ev.epilogue_s)
            self._latency.observe(
                max(0.0, ev.t_materialized - ev.t_dispatch)
            )
        elif isinstance(ev, AggRedispatch):
            self._redisp.inc()
        elif isinstance(ev, TransferEvent):
            if ev.phase == "dispatch":
                self._h2d.inc(ev.h2d_bytes)
            else:
                self._d2h.inc(ev.d2h_bytes)
        elif isinstance(ev, StallEvent):
            self._stalls.labels(phase=ev.phase).inc()
        elif isinstance(ev, RecoveryEvent):
            self._recovery.labels(action=ev.action).inc()
        elif isinstance(ev, CheckpointEvent):
            self._checkpoints.labels(kind=ev.kind).inc()
        elif isinstance(ev, RepairEvent):
            if ev.applied:
                self._repairs.labels(action=ev.action).inc()
        elif isinstance(ev, SidecarEvent):
            self._sidecar.labels(outcome=ev.outcome).inc()
        elif isinstance(ev, ShardSpan):
            s = str(ev.shard)
            self._shard_windows.labels(shard=s).inc()
            self._shard_lanes.labels(shard=s).inc(ev.lanes_real)
            self._shard_ok.labels(shard=s).inc(ev.n_ok)
            self._shard_pad.labels(shard=s).inc(ev.pad_lanes)
            # shards also count as headers retired on the sharded path
            # ONLY through their WindowSpan-carrying replay loop — the
            # per-shard families never double-fold into oct_headers_*
        elif isinstance(ev, ForgeSpan):
            self._forge_windows.labels(engine=ev.engine).inc()
            self._forge_elected.inc(ev.elected)
            self._forge_signed.inc(ev.signed)

    def _replay_span(self, ev: EncloseEvent) -> "list | None":
        """Keep the end edges of each replay in progress (the lock is
        held); -> all of them once the replay's `replay` span ends."""
        if ev.edge == "start":
            if ev.label == "replay":
                self._replays[ev.replay] = []
            return None
        spans = self._replays.get(ev.replay)
        if spans is None:
            return None
        spans.append(ev)
        return self._replays.pop(ev.replay) if ev.label == "replay" else None

    # -- live plane (obs/live.py heartbeat source) --------------------------

    def last_event(self) -> "tuple[float, object] | None":
        """(monotonic t, event) of the newest event seen — kept fresh
        even once the bounded buffer is full, so a week-long run's
        heartbeat never reads a stale phase."""
        with self._lock:
            return self._last

    def progress_fingerprint(self) -> tuple:
        """A cheap value that changes whenever the replay makes ANY
        observable progress (the stall watchdog's no-progress test):
        headers retired, last retired window index, and the timestamp
        of the newest event."""
        with self._lock:
            last_t = self._last[0] if self._last is not None else 0.0
            n = len(self.events) + self.dropped
        return (self._headers.value, self._last_span_index, last_t, n)

    def headers_retired(self) -> int:
        return int(self._headers.value)

    def last_window_index(self) -> int:
        with self._lock:
            return self._last_span_index

    # -- reporting ----------------------------------------------------------

    def timed_events(self) -> list[tuple[float, object]]:
        with self._lock:
            return list(self.events)

    def _warmup_state(self) -> tuple[dict, float]:
        """This process's warmup forensics + the recorder's monotonic
        epoch: the Perfetto export places stage first-execute slices
        (the compile walls) on the same timeline as the window spans."""
        from .warmup import WARMUP

        return WARMUP.report(), WARMUP.t0

    def chrome_trace(self) -> dict:
        from . import perfetto

        report, t0 = self._warmup_state()
        return perfetto.to_chrome_trace(self.timed_events(), report, t0)

    def write_chrome_trace(self, path: str) -> dict:
        from . import perfetto

        report, t0 = self._warmup_state()
        return perfetto.write(path, self.timed_events(), report, t0)

    def self_times(self) -> dict:
        """{label: self seconds} over the recorded spans (obs/spans)."""
        from . import spans

        return spans.self_times(ev for _, ev in self.timed_events())

    def latency_summary(self) -> dict:
        """p50/p99 of the dispatch->materialize device latency plus the
        per-phase p50s — the serving-north-star numbers (ROADMAP #3)."""
        out = {
            "device_latency_p50_s": self._latency.quantile(0.5),
            "device_latency_p99_s": self._latency.quantile(0.99),
            "windows": self._latency.count,
        }
        for p, h in self._phase_h.items():
            out[f"{p}_p50_s"] = h.quantile(0.5)
        return out

    def clear(self) -> None:
        with self._lock:
            self.events.clear()
            self.dropped = 0
            self._last = None
            self._last_span_index = -1
            self._replays.clear()
