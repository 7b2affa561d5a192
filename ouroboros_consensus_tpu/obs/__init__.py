"""obs: pipeline-wide telemetry — the flight recorder.

The reference threads contravariant `Tracer`s through every subsystem
and maps them onto EKG/Prometheus gauges (SURVEY.md layers 4-5); this
package is the TPU build's equivalent surface, all host-side:

  * `registry`  — numpy-backed counters / gauges / fixed-bucket
                  histograms, Prometheus text exposition + JSON snapshot
  * `recorder`  — the FlightRecorder batch tracer: per-window spans
                  through validate_chain's pipelined loop, fed into the
                  registry (see `OCT_TRACE` below)
  * `spans`     — self time of the replay's span tree (a span less
                  its children on the same thread)
  * `idle`      — the device's idle time of a replay, put down to the
                  main thread's span at each idle instant
  * `warmup`    — compile/warmup forensics: per-stage first-execute
                  walls, pk-AOT load/reject attribution, the bench
                  cache probe; crash-safe JSON via $OCT_WARMUP_REPORT
  * `perfetto`  — Chrome trace-event (chrome://tracing / Perfetto)
                  export of a replay's event stream (+ warmup track)
  * `ledger`    — append-only JSONL run ledger (.oct_ledger/): one
                  provenance-complete record per bench / suite /
                  profile run — git rev+dirty, PJRT build id, every
                  OCT_* kill-switch, metrics, warmup, banked result
  * `resources` — device resource accounting: FLOPs / bytes / HBM per
                  dispatched stage program (oct_stage_* gauges, the
                  budgets.json "device_resources" ratchet)
  * `live`      — the LIVE run plane: in-run heartbeat snapshots
                  (OCT_HEARTBEAT), the stall watchdog with all-thread
                  stack forensics (OCT_STALL_BUDGET_S), armed by
                  db_analyser.revalidate / bench / profile_replay
  * `server`    — the one HTTP exposition implementation (/metrics,
                  /metrics.json, /healthz, /progress): asyncio for
                  immdb_server, thread-hosted for replays
                  (OCT_METRICS_PORT)

Env levers:

  OCT_TRACE=1          install the flight recorder for replays
                       (db_analyser.revalidate, profile_replay, bench)
  OCT_WARMUP_REPORT=f  flush warmup forensics to `f` after every note
  OCT_LEDGER=d|0       run-ledger directory override / kill-switch
  OCT_STAGE_RESOURCES  =1 turns per-stage resource capture on; unset
                       or =0 it is off, recorder installed or not
  OCT_HEARTBEAT=f      rewrite a live JSON heartbeat to `f` every ~2 s
  OCT_STALL_BUDGET_S=n stall watchdog: no-progress budget before an
                       all-thread stack dump (+ oct_stalls_total)
  OCT_STALL_DUMP=f     stall forensics file override (default: next to
                       the warmup report)
  OCT_METRICS_PORT=p   serve /metrics /metrics.json /healthz /progress
                       from inside the replay on port p

Everything stays OFF the hot path unless installed: with OCT_TRACE
unset, `protocol.batch.BATCH_TRACER` remains None and the only residual
cost is one module-level assignment per declined packed window."""

from __future__ import annotations

import os
import threading

from .recorder import FlightRecorder
from .registry import MetricsRegistry, default_registry
from .warmup import WARMUP

_ENV = "OCT_TRACE"

_LOCK = threading.Lock()
_RECORDER: FlightRecorder | None = None
_INSTALL_DEPTH = 0
_PREV_TRACER = None


def enabled() -> bool:
    """The OCT_TRACE lever (read per call so tests can flip it)."""
    return os.environ.get(_ENV, "0") not in ("0", "")


def installed() -> bool:
    """True while at least one install() is outstanding."""
    with _LOCK:
        return _INSTALL_DEPTH > 0


def recorder() -> FlightRecorder:
    """The process-wide FlightRecorder (created on first use)."""
    global _RECORDER
    with _LOCK:
        if _RECORDER is None:
            _RECORDER = FlightRecorder()
        return _RECORDER


def install() -> FlightRecorder:
    """Chain the flight recorder into protocol.batch.BATCH_TRACER
    (keeping any tracer an embedding application already set).
    Re-entrant: nested installs share one chain entry."""
    global _INSTALL_DEPTH, _PREV_TRACER
    rec = recorder()
    with _LOCK:
        if _INSTALL_DEPTH == 0:
            from ..protocol import batch as pbatch

            from ..utils.trace import fanout

            prev = pbatch.BATCH_TRACER
            _PREV_TRACER = prev
            pbatch.set_batch_tracer(
                rec if prev is None else fanout(prev, rec)
            )
        _INSTALL_DEPTH += 1
    return rec


def uninstall() -> None:
    """Undo one `install`; the outermost uninstall restores the
    previous tracer."""
    global _INSTALL_DEPTH, _PREV_TRACER
    with _LOCK:
        if _INSTALL_DEPTH == 0:
            return
        _INSTALL_DEPTH -= 1
        if _INSTALL_DEPTH == 0:
            from ..protocol import batch as pbatch

            pbatch.set_batch_tracer(_PREV_TRACER)
            _PREV_TRACER = None


def maybe_install() -> bool:
    """install() iff OCT_TRACE is set; returns whether it installed
    (pair with uninstall())."""
    if enabled():
        install()
        return True
    return False


def reset_for_tests() -> None:
    """Drop the process-wide recorder + registry (test isolation)."""
    global _RECORDER, _INSTALL_DEPTH, _PREV_TRACER
    from .registry import reset_default_registry

    # an armed live plane holds a recorder reference — drop it first
    from . import live as _live

    _live.reset_for_tests()
    with _LOCK:
        if _INSTALL_DEPTH > 0:
            from ..protocol import batch as pbatch

            pbatch.set_batch_tracer(_PREV_TRACER)
        _RECORDER = None
        _INSTALL_DEPTH = 0
        _PREV_TRACER = None
        reset_default_registry()
