"""The live run plane: in-run heartbeat + stall watchdog.

Every obs layer before this one is post-hoc — the flight recorder,
warmup forensics and run ledger all explain a run AFTER it ended. A
live replay (the r06 proof point) is a black box WHILE it runs: a
400 s compile, a wedged staging thread and a hung AOT deserialize all
look identical to progress until the wall kills the child. The
reference serves its EKG/Prometheus surface live while validating
(cardano-node, SURVEY.md layers 4-5); this module is the equivalent
in-run surface for the batched pipeline:

  * `Heartbeat` — a daemon thread that atomically rewrites a JSON
    snapshot every ~2 s (`OCT_HEARTBEAT=<file>`): current phase from
    the recorder's last event, retired window index, headers retired,
    a rolling headers/s, the compile state from the warmup
    notes, and the age since the last observable progress. The bench
    parent reads it to tell *compiling* / *staging* / *running* /
    *stalled* / *dead* apart in real time.
  * `StallWatchdog` — a monotonic no-progress budget
    (`OCT_STALL_BUDGET_S`). On trip it dumps ALL thread stacks
    (`sys._current_frames` + a raw `faulthandler` twin) plus a
    warmup/metrics snapshot into a forensics file next to the warmup
    report, increments `oct_stalls_total{phase=}` and emits a
    first-class `StallEvent` on the recorder. Escalation stays the
    parent's job — the dump is evidence, not a kill.
  * `maybe_arm()` — the one-call mount used by `db_analyser.revalidate`
    (and through it bench's device child and `profile_replay.py`):
    heartbeat + watchdog + the `obs/server.py` HTTP endpoint
    (`OCT_METRICS_PORT`), ref-counted like `obs.install`.

Everything is host-side and per-beat (one dict build + one atomic
rename every ~2 s): the instrumentation-purity ratchet and the
host-ceiling 2% bound both hold with the full plane armed.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import traceback
from collections import deque

_HB_ENV = "OCT_HEARTBEAT"
_STALL_ENV = "OCT_STALL_BUDGET_S"
_DUMP_ENV = "OCT_STALL_DUMP"

# heartbeat cadence; the dead-vs-alive staleness threshold derives from
# it (classify() below), so parent and child agree on one constant
BEAT_INTERVAL_S = 2.0
# rolling-rate window: long enough to smooth per-window jitter, short
# enough that a rate collapse shows within a few beats
RATE_WINDOW_S = 30.0


def heartbeat_path() -> str | None:
    return os.environ.get(_HB_ENV) or None


def stall_budget_s() -> float | None:
    v = os.environ.get(_STALL_ENV)
    if not v:
        return None
    try:
        budget = float(v)
    except ValueError:
        return None
    return budget if budget > 0 else None


def stall_dump_path() -> str:
    """Where the stall forensics land: `OCT_STALL_DUMP` when set, else
    next to the warmup report (the crash-forensics neighborhood), else
    next to the heartbeat file, else the cwd."""
    explicit = os.environ.get(_DUMP_ENV)
    if explicit:
        return explicit
    for anchor in (os.environ.get("OCT_WARMUP_REPORT"), heartbeat_path()):
        if anchor:
            return os.path.join(
                os.path.dirname(os.path.abspath(anchor)), "stall_dump.json"
            )
    return "stall_dump.json"


# ---------------------------------------------------------------------------
# phase classification
# ---------------------------------------------------------------------------


def phase_of(ev) -> str:
    """Map a recorder event to the live phase vocabulary. Import-free
    of jax; events are plain dataclasses."""
    from ..utils import trace as T

    if isinstance(ev, T.EncloseEvent):
        return ev.label  # stage | dispatch | materialize | epilogue | stream
    if isinstance(ev, T.WindowStaged):
        return "dispatch"
    if isinstance(ev, (T.WindowSpan, T.ShardSpan)):
        return "retired"
    if isinstance(ev, T.TransferEvent):
        return ev.phase
    if isinstance(ev, T.AggRedispatch):
        return "agg-redispatch"
    if isinstance(ev, T.RecoveryEvent):
        return "recovery"
    if isinstance(ev, T.CheckpointEvent):
        return "retired"  # a checkpoint write trails a retired window
    if isinstance(ev, T.StallEvent):
        return "stalled"
    return type(ev).__name__


def _warmup_live(report: dict) -> dict:
    """The compile-side slice of the heartbeat: is a first-execute in
    flight right now?"""
    notes = report.get("notes") or []
    last_note = notes[-1] if notes else None
    # a stage's "<label> first execute starting" note lands BEFORE its
    # compile-inclusive first execute and the completion note_stage
    # after — so "starting" with no matching stage row means a compile
    # is in flight RIGHT NOW (the ~410 s wall, live)
    compiling_now = False
    if last_note and last_note.endswith("first execute starting"):
        label = last_note.split("] ", 1)[-1]
        label = label[: -len(" first execute starting")]
        compiling_now = label not in (report.get("stages") or {})
    return {
        "n_stages": report.get("n_stages", 0),
        "compile_total_s": report.get("compile_total_s", 0.0),
        "last_note": last_note,
        "compiling_now": compiling_now,
    }


def live_snapshot(rec=None, clock=time.monotonic) -> dict:
    """One heartbeat document (also what `/healthz` serves). Cheap by
    construction: counter reads, the recorder's last event, and the
    warmup report dict — no device interaction ever."""
    from .warmup import WARMUP

    from .. import obs

    rec = rec if rec is not None else obs.recorder()
    now = clock()
    last = rec.last_event()
    report = WARMUP.report()
    wu = _warmup_live(report)
    if last is not None:
        phase = phase_of(last[1])
        age = max(0.0, now - last[0])
    else:
        # nothing dispatched yet: the run is warming up (or idle)
        phase = "warmup" if (wu["last_note"] or wu["n_stages"]) else "idle"
        age = report.get("elapsed_s", 0.0)
    doc = {
        "v": 1,
        "pid": os.getpid(),
        "ts_unix": time.time(),
        "t_mono": now,
        "phase": phase,
        "age_s": round(age, 3),
        "headers": rec.headers_retired(),
        "window_index": rec.last_window_index(),
        "stalls": _stall_count(rec),
        "warmup": wu,
    }
    return doc


def _stall_count(rec) -> int:
    try:
        # under the registry lock: the watchdog's trip counter rides
        # label first-touches from other threads, and samples() iterates
        # the child dict that first-touch inserts into
        with rec.registry._lock:
            fam = rec.registry._families.get("oct_stalls_total")
            if fam is None:
                return 0
            return int(sum(child.value for _l, child in fam.samples()))
    except Exception:  # noqa: BLE001 — the heartbeat never raises
        return 0


def classify(doc: dict | None, now_unix: float | None = None,
             interval_s: float = BEAT_INTERVAL_S) -> str:
    """Reader-side classification of a heartbeat document — the
    vocabulary the bench parent banks:

        no-heartbeat   no document (never armed, or never beat)
        dead           the file stopped being rewritten (> 5 beats old)
        stalled        the child's watchdog is tripped RIGHT NOW
                       (`stalled_now`; the cumulative `stalls` count is
                       informational — a recovered run classifies by
                       its live phase again)
        compiling      a stage first-execute is the
                       freshest activity (warmup moving, no spans yet,
                       or the last note names an in-flight compile)
        staging        host-side window prep (stage/stream/prechecks)
        running        device windows dispatching/retiring
        idle           armed but nothing has happened yet
    """
    if not isinstance(doc, dict) or "ts_unix" not in doc:
        return "no-heartbeat"
    now_unix = time.time() if now_unix is None else now_unix
    if now_unix - float(doc["ts_unix"]) > 5 * interval_s:
        return "dead"
    if doc.get("stalled_now"):
        return "stalled"
    phase = doc.get("phase", "idle")
    wu = doc.get("warmup") or {}
    if (
        phase in ("warmup",)
        # a foreground first-execute is compiling RIGHT NOW, whatever
        # phase the dispatch loop froze in when it hit the cold stage
        or wu.get("compiling_now")
    ):
        return "compiling"
    if phase in ("stage", "stream", "prechecks"):
        return "staging"
    if phase in ("dispatch", "materialize", "epilogue", "retired",
                 "agg-redispatch", "recovery"):
        return "running"
    if phase == "stalled":
        return "stalled"
    return "idle" if phase == "idle" else "running"


# ---------------------------------------------------------------------------
# stall watchdog
# ---------------------------------------------------------------------------


class StallWatchdog:
    """Monotonic no-progress budget over the recorder + warmup state.

    `check()` is drive-able with an injected clock (the tier-1 stubbed
    clock test); production calls arrive from the Heartbeat thread each
    beat. One dump per stall episode: after a trip the watchdog stays
    quiet until progress resumes, so a 30-minute hang produces one
    forensics file, not 900."""

    def __init__(self, budget_s: float, rec=None,
                 dump_path: str | None = None, clock=time.monotonic):
        from .. import obs

        self.budget_s = float(budget_s)
        self.rec = rec if rec is not None else obs.recorder()
        self.dump_path = dump_path or stall_dump_path()
        self.clock = clock
        self.tripped = False
        self.dumps = 0
        now = self.clock()
        self._last_progress_t = now
        self._fingerprint = self._current_fingerprint()

    def _current_fingerprint(self) -> tuple:
        from .warmup import WARMUP

        with WARMUP._lock:
            wu = (len(WARMUP.stages), len(WARMUP.notes),
                  len(WARMUP.aot_events),
                  # recovery-ladder transitions ARE progress: a window
                  # being walked down the degradation ladder must not
                  # read as a wedge (and a stall episode re-arms the
                  # moment recovery starts moving)
                  len(WARMUP.recovery))
        return self.rec.progress_fingerprint() + wu

    def check(self, now: float | None = None) -> dict | None:
        """Advance the watchdog; returns the dump document on a trip,
        None otherwise."""
        now = self.clock() if now is None else now
        fp = self._current_fingerprint()
        if fp != self._fingerprint:
            self._fingerprint = fp
            self._last_progress_t = now
            self.tripped = False
            return None
        age = now - self._last_progress_t
        if self.tripped or age <= self.budget_s:
            return None
        self.tripped = True
        return self._dump(age)

    # -- forensics ----------------------------------------------------------

    def _thread_stacks(self) -> dict:
        """{thread name: [frame strings]} for every live thread — the
        wedged stage is IN here by function name (dispatch_batch,
        materialize_verdicts, a blocking device read...)."""
        names = {t.ident: t.name for t in threading.enumerate()}
        out = {}
        for ident, frame in sys._current_frames().items():
            label = f"{names.get(ident, 'thread')}-{ident}"
            out[label] = [
                ln.rstrip("\n")
                for ln in traceback.format_stack(frame)
            ]
        return out

    def _dump(self, age: float) -> dict:
        from .warmup import WARMUP
        from ..utils.trace import StallEvent

        last = self.rec.last_event()
        phase = phase_of(last[1]) if last is not None else "warmup"
        doc = {
            "v": 1,
            "pid": os.getpid(),
            "ts_unix": time.time(),
            "phase": phase,
            "age_s": round(age, 3),
            "budget_s": self.budget_s,
            "threads": self._thread_stacks(),
            "heartbeat": live_snapshot(self.rec, clock=self.clock),
            "warmup_report": WARMUP.report(),
            "metrics_summary": self.rec.latency_summary(),
        }
        path = self.dump_path
        try:
            os.makedirs(os.path.dirname(os.path.abspath(path)),
                        exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(doc, f, indent=1)
            os.replace(tmp, path)
            # the raw faulthandler twin (C-level, signal-safe format):
            # belt-and-braces in case the interpreter state is too
            # wedged for the structured walk above to be trusted
            import faulthandler

            with open(path + ".txt", "w", encoding="utf-8") as f:
                faulthandler.dump_traceback(file=f, all_threads=True)
            doc["_path"] = path
        except OSError:
            doc["_path"] = None  # forensics are best-effort
        self.dumps += 1
        # countable + first-class on the recorder: a later reader of
        # the event stream / metrics snapshot sees the trip without the
        # dump file
        self.rec(StallEvent(
            phase=phase, age_s=age, budget_s=self.budget_s,
            dump_path=doc.get("_path"),
        ))
        # the StallEvent itself just advanced the recorder's event
        # stream — refresh the fingerprint so the watchdog's own
        # evidence never reads as progress (it would re-arm and
        # re-dump the SAME wedge every budget_s, misattributed to
        # phase="stalled")
        self._fingerprint = self._current_fingerprint()
        return doc


# ---------------------------------------------------------------------------
# heartbeat thread
# ---------------------------------------------------------------------------


class Heartbeat:
    """Daemon thread: every `interval_s`, compose `live_snapshot()`,
    fold in the rolling headers/s, atomically rewrite `path` (tmp +
    rename — a SIGKILL mid-rewrite leaves the previous complete beat
    readable, mirroring the warmup recorder's contract), and drive the
    watchdog. `path=None` runs beats without a file (watchdog-only)."""

    def __init__(self, path: str | None, rec=None,
                 interval_s: float = BEAT_INTERVAL_S,
                 watchdog: StallWatchdog | None = None,
                 clock=time.monotonic):
        from .. import obs

        self.path = path
        self.rec = rec if rec is not None else obs.recorder()
        self.interval_s = interval_s
        self.watchdog = watchdog
        self.clock = clock
        self._beat_lock = threading.Lock()
        self.seq = 0  # guarded-by: _beat_lock
        self._samples: deque[tuple[float, int]] = deque()  # guarded-by: _beat_lock
        self.beat_errors = 0  # guarded-by: _beat_lock
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    # -- one beat (unit-testable without the thread) ------------------------

    def beat(self) -> dict:
        # one beat at a time: stop()'s final beat can race a
        # join-timed-out _run still mid-beat — serializing keeps
        # seq/_samples coherent and the tmp+rename below un-torn
        with self._beat_lock:
            now = self.clock()
            doc = live_snapshot(self.rec, clock=self.clock)
            self._samples.append((now, doc["headers"]))
            # age out samples older than the window but ALWAYS keep a
            # two-sample anchor: a silent stretch then reads 0.0
            # headers/s (informative for a stall), never None
            while (len(self._samples) > 2
                   and now - self._samples[1][0] > RATE_WINDOW_S):
                self._samples.popleft()
            t0, h0 = self._samples[0]
            dt = now - t0
            doc["headers_per_s"] = (
                round((doc["headers"] - h0) / dt, 1) if dt > 0.5 else None
            )
            doc["seq"] = self.seq
            doc["interval_s"] = self.interval_s
            if self.beat_errors:
                doc["beat_errors"] = self.beat_errors
            self.seq += 1
            if self.watchdog is not None:
                self.watchdog.check(now)
                doc["stalls"] = _stall_count(self.rec)
                # CURRENT state, not the lifetime count: tripped resets
                # the moment progress resumes, so a run that stalled
                # once at window 10 and recovered classifies by its live
                # phase again instead of reading "stalled" forever
                doc["stalled_now"] = self.watchdog.tripped
            if self.path:
                try:
                    tmp = self.path + ".tmp"
                    with open(tmp, "w", encoding="utf-8") as f:
                        json.dump(doc, f)
                    os.replace(tmp, self.path)
                except OSError:
                    pass  # the heartbeat never breaks the run it describes
            return doc

    # -- thread lifecycle ---------------------------------------------------

    def start(self) -> "Heartbeat":
        if self._thread is not None:
            return self
        try:
            self.beat()  # an armed plane is visible IMMEDIATELY
        except Exception as exc:  # noqa: BLE001 — diagnostics must
            self._note_beat_error(exc)  # never break the run they
            # describe; the thread below keeps trying every interval
        self._thread = threading.Thread(
            target=self._run, name="oct-heartbeat", daemon=True
        )
        self._thread.start()
        return self

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.beat()
            except Exception as exc:  # noqa: BLE001 — keep beating,
                self._note_beat_error(exc)  # but never silently

    def _note_beat_error(self, exc: BaseException) -> None:
        """A failing beat must stay visible without being able to kill
        the plane: count it (the next good beat publishes the count as
        `beat_errors`) and note the FIRST one into the warmup report —
        bounded, so a wedged snapshot source cannot spam a note per
        interval."""
        with self._beat_lock:
            self.beat_errors += 1
            first = self.beat_errors == 1
        if not first:
            return
        try:
            from .warmup import WARMUP

            WARMUP.note(
                f"heartbeat beat failed: {type(exc).__name__}: {exc}"
            )
        except Exception:  # noqa: BLE001 — the seam itself failing
            pass           # must not take the heartbeat thread down

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=self.interval_s + 5)
            self._thread = None
        # final beat so the file's last word reflects the finished run
        try:
            self.beat()
        except Exception as exc:  # noqa: BLE001
            self._note_beat_error(exc)


def read_heartbeat(path: str) -> dict | None:
    """Read a heartbeat document; None when absent/torn — callers treat
    that as 'no heartbeat' (the atomic rewrite makes torn reads rare:
    only a never-completed FIRST write can produce one)."""
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError, ValueError):
        return None


# ---------------------------------------------------------------------------
# the one-call mount (db_analyser.revalidate, profile_replay, bench child)
# ---------------------------------------------------------------------------


class LivePlane:
    """One armed live plane: heartbeat (+watchdog) thread and the HTTP
    endpoint, with the recorder installed underneath so phase events
    actually flow. `disarm()` undoes exactly one `arm`."""

    def __init__(self, heartbeat: Heartbeat, server=None):
        self.heartbeat = heartbeat
        self.server = server

    def disarm(self) -> None:
        _disarm(self)


_LOCK = threading.Lock()
_DEPTH = 0
_PLANE: LivePlane | None = None


def maybe_arm(rec=None) -> LivePlane | None:
    """Arm the live plane iff any of its env levers is set
    (OCT_HEARTBEAT / OCT_STALL_BUDGET_S / OCT_METRICS_PORT). Ref-counted
    like obs.install: nested replays share one plane; the outermost
    disarm stops the thread and the server."""
    from . import server as obs_server

    hb_path = heartbeat_path()
    budget = stall_budget_s()
    port = obs_server.metrics_port()
    if hb_path is None and budget is None and port is None:
        return None
    global _DEPTH, _PLANE
    with _LOCK:
        _DEPTH += 1
        if _PLANE is not None:
            return _PLANE
        from .. import obs

        # install() is re-entrant and ALWAYS paired by _disarm's
        # uninstall — phase events flow even when OCT_TRACE is unset.
        # Arming is exception-SAFE end to end: a failure ANYWHERE past
        # the depth bump (install itself included) must unwind
        # everything it did — a leaked ref-count would pin the recorder
        # (and every later-armed plane) forever, and a bound-but-
        # unowned socket is an orphan listener on OCT_METRICS_PORT no
        # later disarm can ever reach.
        installed = None
        hb = None
        srv = None
        try:
            installed = obs.install()
            rec = rec if rec is not None else installed
            wd = (StallWatchdog(budget, rec=rec)
                  if budget is not None else None)
            hb = Heartbeat(hb_path, rec=rec, watchdog=wd).start()
            if port is not None:
                srv = obs_server.start_in_thread(
                    port=port, registry=rec.registry,
                    live_doc=lambda: live_snapshot(rec),
                )
            _PLANE = LivePlane(hb, srv)
        except BaseException:
            if srv is not None:
                srv.close()
            if hb is not None:
                hb.stop()
            if installed is not None:
                obs.uninstall()
            _DEPTH -= 1
            raise
        return _PLANE


def _disarm(plane: LivePlane) -> None:
    global _DEPTH, _PLANE
    with _LOCK:
        if _PLANE is not plane or _DEPTH == 0:
            return
        _DEPTH -= 1
        if _DEPTH > 0:
            return
        _PLANE = None
    plane.heartbeat.stop()
    if plane.server is not None:
        plane.server.close()
    from .. import obs

    obs.uninstall()


def reset_for_tests() -> None:
    """Drop any armed plane (test isolation)."""
    global _DEPTH, _PLANE
    with _LOCK:
        plane, _PLANE, _DEPTH = _PLANE, None, 0
    if plane is not None:
        plane.heartbeat.stop()
        if plane.server is not None:
            plane.server.close()
