"""Compile/warmup flight recorder: which stage ate the wall?

A run that dies inside warmup — a probe timeout, a ~410 s compile wall,
a rejected stored executable — otherwise banks nothing but an exit
code. This module is the black box that survives the crash: every first-execute of a stage jit (ops/pk/kernels._stage_call,
the XLA-twin jits in protocol/batch), every pk-AOT load outcome
(ops/pk/aot.load: loaded / failed / format-rejected / marker-skipped)
and the bench child's persistent-cache startup probe record themselves
here, and — when `OCT_WARMUP_REPORT` names a file — every note is
immediately flushed as atomic JSON. A child killed at the wall mid-
compile leaves a readable per-stage diagnosis on disk; bench.py folds
it into the round JSON as the `warmup_report` block whether or not a
device number was ever banked.

Recording is always-on (a dict insert + a rare atomic file write per
FIRST execute — nothing per warm call), so the forensics need no env
lever to have been enabled before the crash."""

from __future__ import annotations

import json
import os
import threading
import time

_REPORT_ENV = "OCT_WARMUP_REPORT"


class WarmupRecorder:
    """Process-wide warmup/compile forensics accumulator."""

    def __init__(self):
        self._lock = threading.Lock()
        # separate from _lock (report() takes _lock inside a flush):
        # serializes the tmp-write + rename so two threads' first
        # executes (main dispatch + the materialize worker's aggregate
        # re-dispatch) can never interleave on the shared tmp path and
        # publish a truncated report — the one file a crash must leave
        # readable
        self._flush_lock = threading.Lock()
        self.t0 = time.monotonic()
        # stage -> {"wall_s", "via", "t"} — FIRST execute only (the
        # compile happens synchronously inside that call)
        self.stages: dict[str, dict] = {}  # guarded-by: _lock
        # aot outcome counts + the per-stage detail rows
        self.aot: dict[str, int] = {}  # guarded-by: _lock
        self.aot_events: list[dict] = []  # guarded-by: _lock
        self.cache_probe: dict | None = None  # guarded-by: _lock
        self.notes: list[str] = []  # guarded-by: _lock
        # recovery-supervisor episodes (obs/recovery.py): every ladder
        # transition for a failing window — banked with the rest of the
        # forensics so the round JSON and ledger carry the recovery
        # story (perf_report classifies recovered rounds from this)
        self.recovery: list[dict] = []  # guarded-by: _lock
        # durable-store repair plane (storage/repair.py): every
        # on-disk repair (or dry-run would-repair) the open-with-repair
        # scan took — truncated chunk tails, rebuilt indices, dropped
        # chunks, dirty-open escalations — banked with the forensics so
        # perf_report can classify a round `repaired@<action>`
        self.repairs: list[dict] = []  # guarded-by: _lock

    # -- recording ----------------------------------------------------------

    def note_stage(self, stage: str, wall_s: float,
                   via: str = "jit") -> bool:
        """Record a stage's FIRST execute wall (compile-inclusive).
        Returns True when this call was the first for `stage`."""
        with self._lock:
            if stage in self.stages:
                return False
            self.stages[stage] = {
                "wall_s": round(wall_s, 3),
                "via": via,
                "t": round(time.monotonic() - self.t0, 3),
            }
        self._flush()
        return True

    def note_aot(self, stage: str, outcome: str, wall_s: float = 0.0,
                 detail: str = "") -> None:
        """One pk-AOT load outcome: loaded | missing | wrong_build |
        failed | rejected | marker_skip | run_failed | saved."""
        with self._lock:
            self.aot[outcome] = self.aot.get(outcome, 0) + 1
            self.aot_events.append({
                "stage": stage,
                "outcome": outcome,
                "wall_s": round(wall_s, 3),
                "detail": detail[:200],
                "t": round(time.monotonic() - self.t0, 3),
            })
        self._flush()

    def note_cache_probe(self, outcome: str, wall_s: float = 0.0,
                         detail: str = "") -> None:
        """The bench child's startup probe-deserialize of one persistent
        jax-cache entry: ok | stale | inconclusive | empty."""
        with self._lock:
            self.cache_probe = {
                "outcome": outcome,
                "wall_s": round(wall_s, 3),
                "detail": detail[:200],
            }
        self._flush()

    def note_recovery(self, action: str, window: int, attempt: int,
                      fault: str, detail: str = "",
                      ok: bool | None = None) -> None:
        """One recovery-ladder transition (obs/recovery.py): action is
        retry | restage | stage-split | xla-twin | host-reference |
        chunk-reread | recovered | exhausted."""
        row = {
            "action": action,
            "window": window,
            "attempt": attempt,
            "fault": fault,
            "detail": detail[:200],
            "t": round(time.monotonic() - self.t0, 3),
        }
        if ok is not None:
            row["ok"] = ok
        with self._lock:
            self.recovery.append(row)
        self._flush()

    def note_repair(self, action: str, chunk: int = -1, kept: int = 0,
                    dropped: int = 0, bytes_quarantined: int = 0,
                    applied: bool = True, detail: str = "") -> None:
        """One durable-store repair action (storage/repair.py): action
        is truncate-chunk | rebuild-index | drop-chunk |
        sweep-orphan-index | dirty-open-escalated; `applied=False`
        marks a dry-run scan that only computed the action."""
        with self._lock:
            self.repairs.append({
                "action": action,
                "chunk": chunk,
                "kept": kept,
                "dropped": dropped,
                "bytes_quarantined": bytes_quarantined,
                "applied": applied,
                "detail": detail[:200],
                "t": round(time.monotonic() - self.t0, 3),
            })
        self._flush()

    def note(self, msg: str) -> None:
        """Free-form forensic breadcrumb (e.g. 'warmup replay started')."""
        with self._lock:
            self.notes.append(
                f"[{time.monotonic() - self.t0:.1f}s] {msg[:200]}"
            )
        self._flush()

    # -- reporting ----------------------------------------------------------

    def report(self) -> dict:
        """The `warmup_report` block: per-stage compile wall + cache
        hit/miss/reject attribution."""
        with self._lock:
            stages = {k: dict(v) for k, v in self.stages.items()}
            compile_total = sum(v["wall_s"] for v in stages.values())
            return {
                "elapsed_s": round(time.monotonic() - self.t0, 1),
                "compile_total_s": round(compile_total, 1),
                "n_stages": len(stages),
                "stages": stages,
                "aot": dict(self.aot),
                "aot_events": list(self.aot_events),
                # nothing refuses a dispatch since PR 34; the key
                # stays, empty, while benchmark/ reads it
                "refusals": [],
                "cache_probe": self.cache_probe,
                "recovery": [dict(r) for r in self.recovery],
                "repairs": [dict(r) for r in self.repairs],
                "notes": list(self.notes),
            }

    def _flush(self) -> None:
        """Atomic write of the report to $OCT_WARMUP_REPORT (when set):
        a kill mid-warmup leaves the last complete note on disk, never a
        torn file. Notes are first-executes and load outcomes — dozens
        per run, so per-note writes cost nothing measurable."""
        path = os.environ.get(_REPORT_ENV)
        if not path:
            return
        try:
            with self._flush_lock:
                tmp = path + ".tmp"
                with open(tmp, "w", encoding="utf-8") as f:
                    json.dump(self.report(), f)
                os.replace(tmp, path)
        except OSError:
            pass  # forensics are best-effort; never break the pipeline

    def reset(self) -> None:
        with self._lock:
            self.t0 = time.monotonic()
            self.stages.clear()
            self.aot.clear()
            self.aot_events.clear()
            self.cache_probe = None
            self.recovery.clear()
            self.repairs.clear()
            self.notes.clear()


WARMUP = WarmupRecorder()


def read_report(path: str) -> dict | None:
    """Read a (possibly mid-crash) warmup report; None when absent or
    unreadable — callers treat that as 'no forensics banked'."""
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError, ValueError):
        return None
