"""Chrome trace-event (Perfetto / chrome://tracing) export.

Converts the flight recorder's timed event stream — `Enclose` phase
brackets, `TransferEvent` byte accounting, `WindowStaged`/`WindowSpan`
pipeline spans — into the Trace Event Format JSON that Perfetto and
chrome://tracing load directly:

    python scripts/profile_replay.py --trace-out /tmp/replay.json
    # then open ui.perfetto.dev and drag the file in

Layout: one process ("oct replay"), one thread row per phase label
(stage / dispatch / materialize / epilogue / stream), a "windows" row
holding one complete ("X") slice per retired window whose args carry
lanes / outcome / gate / n_valid, counter ("C") tracks for the H2D
and D2H bytes per window, and a "warmup" row rebuilt from the warmup
recorder (obs/warmup.py): one slice per stage FIRST execute (the
compile wall that dominates cold runs — previously invisible in the
very tool meant to visualize walls) plus instants for every pk-AOT
load outcome. The warmup rows need the
recorder's own monotonic t0 to share the event stream's timeline, so
they appear when exporting from a live process (FlightRecorder
.chrome_trace / scripts/profile_replay.py --trace-out), not when
rendering a report file from another process.

`validate_chrome_trace` is the schema gate the tier-1 test runs over a
replay export: structural validation of the JSON object model per the
Trace Event Format spec (required keys, phase vocabulary, numeric
non-negative ts/dur, JSON-serializability)."""

from __future__ import annotations

import json
from typing import Iterable

from ..utils.trace import (
    EncloseEvent, TransferEvent, WindowSpan, WindowStaged,
)

PID = 1
# stable thread ids per track; unknown phase labels allocate past these
_TIDS = {
    "windows": 1, "stage": 2, "dispatch": 3, "materialize": 4,
    "epilogue": 5, "stream": 6, "warmup": 7,
}

_ALLOWED_PH = {"X", "B", "E", "i", "C", "M"}


def _meta(name: str, tid: int | None = None) -> dict:
    ev = {
        "name": "process_name" if tid is None else "thread_name",
        "ph": "M",
        "pid": PID,
        "ts": 0,
        "args": {"name": name},
    }
    if tid is not None:
        ev["tid"] = tid
    else:
        ev["tid"] = 0
    return ev


def to_chrome_trace(timed_events: Iterable[tuple[float, object]],
                    warmup_report: dict | None = None,
                    warmup_t0: float | None = None) -> dict:
    """[(t_monotonic_received, event)] -> Trace Event Format document.

    `EncloseEvent` end edges become complete "X" slices on their label's
    track (their own t/duration stamps, not the receive time);
    `WindowSpan`s become "X" slices on the windows track; dirty-window
    re-dispatches and other events ride as instants on track 0;
    `TransferEvent`s become per-window byte counters.

    `warmup_report` (with `warmup_t0`, the recorder's monotonic epoch —
    report timestamps are relative to it) adds the warmup track:
    per-stage first-execute slices with aot/jit attribution and pk-AOT
    load-outcome instants."""
    timed = list(timed_events)
    tids = dict(_TIDS)

    def tid_of(label: str) -> int:
        t = tids.get(label)
        if t is None:
            t = tids[label] = max(tids.values()) + 1
        return t

    wu = warmup_report if (warmup_report and warmup_t0 is not None) else None

    # normalize all timestamps against the earliest one observed — the
    # warmup slices usually start BEFORE the first window event (the
    # compile precedes the replay), so they join the minimum
    t_zero = None
    for t_recv, ev in timed:
        cand = t_recv
        if isinstance(ev, EncloseEvent):
            cand = ev.t - (ev.duration or 0.0)
        t_zero = cand if t_zero is None else min(t_zero, cand)
    if wu:
        for row in wu.get("stages", {}).values():
            cand = warmup_t0 + float(row.get("t", 0.0)) - float(
                row.get("wall_s", 0.0))
            t_zero = cand if t_zero is None else min(t_zero, cand)
        for ev_row in wu.get("aot_events", []):
            cand = warmup_t0 + float(ev_row.get("t", 0.0))
            t_zero = cand if t_zero is None else min(t_zero, cand)
    if t_zero is None:
        t_zero = 0.0

    def us(t: float) -> float:
        return max(0.0, (t - t_zero) * 1e6)

    events: list[dict] = [_meta("oct replay")]
    for label, t in sorted(_TIDS.items(), key=lambda kv: kv[1]):
        events.append(_meta(label, t))

    n_xfer = 0
    for t_recv, ev in timed:
        if isinstance(ev, EncloseEvent):
            if ev.edge != "end" or ev.duration is None:
                continue  # start edges carry no duration; the end edge
                # alone reconstructs the complete slice
            events.append({
                "name": ev.label, "cat": "phase", "ph": "X",
                "ts": us(ev.t - ev.duration), "dur": ev.duration * 1e6,
                "pid": PID, "tid": tid_of(ev.label),
            })
        elif isinstance(ev, WindowSpan):
            t0 = ev.t_dispatch - ev.dispatch_s - ev.stage_s
            events.append({
                "name": f"window {ev.index} [{ev.outcome}]",
                "cat": "window", "ph": "X",
                "ts": us(t0), "dur": max(0.0, (ev.t_done - t0) * 1e6),
                "pid": PID, "tid": _TIDS["windows"],
                "args": {
                    "lanes": ev.lanes, "outcome": ev.outcome,
                    "gate": ev.gate or "", "n_valid": ev.n_valid,
                    "failed": ev.failed,
                    "device_latency_ms": round(
                        (ev.t_materialized - ev.t_dispatch) * 1e3, 3
                    ),
                },
            })
        elif isinstance(ev, TransferEvent):
            n_xfer += 1
            counter = ("h2d_bytes" if ev.phase == "dispatch"
                       else "d2h_bytes")
            events.append({
                "name": counter, "cat": "transfer", "ph": "C",
                "ts": us(t_recv), "pid": PID, "tid": 0,
                "args": {counter: ev.h2d_bytes or ev.d2h_bytes},
            })
        elif isinstance(ev, WindowStaged):
            # instants only for declined windows — the WindowSpan slice
            # already tells the packed story
            if ev.outcome == "generic":
                events.append({
                    "name": f"gate: {ev.gate or 'packed-off'}",
                    "cat": "gate", "ph": "i", "s": "t",
                    "ts": us(t_recv), "pid": PID, "tid": _TIDS["windows"],
                })

    if wu:
        wtid = _TIDS["warmup"]
        for stage, row in sorted(wu.get("stages", {}).items()):
            wall = float(row.get("wall_s", 0.0))
            end = warmup_t0 + float(row.get("t", 0.0))
            args = {"via": row.get("via", "jit"),
                    "wall_s": wall}
            events.append({
                "name": f"{stage} first-execute [{row.get('via', 'jit')}]",
                "cat": "warmup", "ph": "X",
                "ts": us(end - wall), "dur": max(0.0, wall * 1e6),
                "pid": PID, "tid": wtid, "args": args,
            })
        for ev_row in wu.get("aot_events", []):
            events.append({
                "name": (f"aot {ev_row.get('stage', '?')}: "
                         f"{ev_row.get('outcome', '?')}"),
                "cat": "warmup", "ph": "i", "s": "t",
                "ts": us(warmup_t0 + float(ev_row.get("t", 0.0))),
                "pid": PID, "tid": wtid,
            })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def write(path: str, timed_events, warmup_report: dict | None = None,
          warmup_t0: float | None = None) -> dict:
    doc = to_chrome_trace(timed_events, warmup_report, warmup_t0)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f)
    return doc


def validate_chrome_trace(doc) -> list[str]:
    """Structural validation against the Chrome trace-event JSON object
    model; returns a list of problems (empty = valid)."""
    errs: list[str] = []
    if not isinstance(doc, dict):
        return ["document is not a JSON object"]
    evs = doc.get("traceEvents")
    if not isinstance(evs, list):
        return ["traceEvents missing or not a list"]
    try:
        json.dumps(doc)
    except (TypeError, ValueError) as e:
        errs.append(f"not JSON-serializable: {e}")
    for i, ev in enumerate(evs):
        where = f"traceEvents[{i}]"
        if not isinstance(ev, dict):
            errs.append(f"{where}: not an object")
            continue
        if not isinstance(ev.get("name"), str):
            errs.append(f"{where}: name missing or not a string")
        ph = ev.get("ph")
        if ph not in _ALLOWED_PH:
            errs.append(f"{where}: ph {ph!r} not in {sorted(_ALLOWED_PH)}")
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)) or ts < 0:
            errs.append(f"{where}: ts must be a non-negative number")
        for k in ("pid", "tid"):
            if not isinstance(ev.get(k), int):
                errs.append(f"{where}: {k} missing or not an int")
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                errs.append(f"{where}: X event needs non-negative dur")
        if ph in ("C", "M") and not isinstance(ev.get("args"), dict):
            errs.append(f"{where}: {ph} event needs an args object")
    return errs
