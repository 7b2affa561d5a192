"""From a profiler trace to device busy time, idle gaps and time per stage.

`jax.profiler` writes `<dir>/plugins/profile/<time>/<host>.xplane.pb`;
`jax.profiler.ProfileData` reads it with nothing but JAX. `load()` turns it
into plain lists, `reduce()` works on those alone, so the reduction is
tested on a small recorded trace kept as JSON (tests/fixtures/).

What a v5e trace looks like (looked at by hand, PR 27): one plane
`/device:TPU:0` per chip whose line `XLA Modules` has one event per run of
a jitted program, named `jit_<function>(<fingerprint>)`, and whose line
`XLA Ops` has one event per HLO operation inside it, named by the whole text
of the instruction (`%while.44 = (s32[]...`), a loop's body operations once
for every trip; host threads are lines of the plane `/host:CPU`, where a
`TraceAnnotation` appears under its name. All planes share one clock, in
nanoseconds from the start of the trace. The profiler pays for every event:
a loop's trips make 375k events a traced second, a whole device window of
3.8 s took it 53 to 180 s to write out, and a whole replay overflows the
device's trace buffer (my chip runs, PR 27). So a run traces under a second,
laid over the moment a device window retires and the next one's stage
programs start: `Stretch`.
"""

from __future__ import annotations

import glob
import json
import os
import re
import threading
import time

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SYNC_ANNOTATION = "bench:sync"
NAME_MAX = 64


class Stretch:
    """Trace `seconds` of the run, laid over the moment at which a device
    window retires and the next one's stage programs start.

    `retired()` gives the host-clock times (`time.monotonic()`) at which
    device windows have retired so far. Once two have, the next is due one
    period after the last, and the profiler is started `lead_s` before
    that; where two have not retired within `wait_s`, it starts then. A
    thread does all of it: the main thread is inside the program. The
    stretch is short because the profiler pays for every event (see the
    module's head): what is wholly inside it is read, and a stage whose
    program runs longer than the stretch has no whole run in it and is left
    unread. Once a window is shorter than the stretch it holds whole
    windows. One annotation whose host-clock time is known lets spans on
    `time.monotonic()` be laid on the trace's clock."""

    def __init__(self, trace_dir: str, seconds: float, retired=None,
                 lead_s: float = 0.3, wait_s: float = 15.0):
        self.dir = trace_dir
        self.sync_mono_ns = self.start_mono_ns = self.stop_mono_ns = None
        self.stop_s = None  # how long the profiler took to write it out
        self.error = None
        self._thread = threading.Thread(
            target=self._run, args=(seconds, retired, lead_s, wait_s),
            daemon=True)
        self._thread.start()

    @staticmethod
    def start_time(retired_at, lead_s: float, give_up: float) -> float:
        """When to start the profiler: `lead_s` before the next window is
        due to retire, `give_up` where fewer than two have retired."""
        if len(retired_at) < 2:
            return give_up
        period = retired_at[-1] - retired_at[-2]
        return min(give_up, retired_at[-1] + period - lead_s)

    def _run(self, seconds, retired, lead_s, wait_s):
        import jax

        give_up = time.monotonic() + wait_s
        while True:
            at = self.start_time(retired() if retired else (), lead_s,
                                 give_up)
            if time.monotonic() >= at:
                break
            time.sleep(min(0.005, max(0.0, at - time.monotonic())))
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # no Python frames
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.sync_mono_ns = time.monotonic_ns()
            with jax.profiler.TraceAnnotation(SYNC_ANNOTATION):
                pass
            self.start_mono_ns = time.monotonic_ns()
            time.sleep(seconds)
            self.stop_mono_ns = time.monotonic_ns()
            jax.profiler.stop_trace()
            self.stop_s = (time.monotonic_ns() - self.stop_mono_ns) / 1e9
        except Exception as e:  # noqa: BLE001 - raised by path() on the main thread
            self.error = e

    def path(self) -> str:
        """Wait for the profiler to have written the trace; -> its file."""
        self._thread.join()
        if self.error is not None:
            raise self.error
        found = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError("the profiler left no .xplane.pb under "
                                    + self.dir)
        return found[-1]


def short_name(name: str) -> str:
    """`%while.44 = (s32[]{:T(128)}, ...` -> `%while.44`."""
    return name.split(" = ", 1)[0][:NAME_MAX]


def load(path: str, keep_host=(SYNC_ANNOTATION,)) -> dict:
    """-> {"planes": [{"name", "lines": [{"name", "events": [[name,
    start_ns, duration_ns], ...]}]}]}: every event of the device planes
    (names cut short), and of the host planes only the benchmark's own
    annotations."""
    if path.endswith(".json"):
        with open(path) as f:
            return json.load(f)
    from jax.profiler import ProfileData

    planes = []
    short: dict[str, str] = {}
    for pl in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(pl.name))
        lines = []
        for ln in pl.lines:
            evs = []
            for e in ln.events:
                name = e.name
                if device:
                    cut = short.get(name)
                    if cut is None:
                        cut = short[name] = short_name(name)
                    evs.append([cut, float(e.start_ns),
                                float(e.duration_ns)])
                elif name in keep_host:
                    evs.append([name, float(e.start_ns),
                                float(e.duration_ns)])
            if evs:
                lines.append({"name": ln.name, "events": evs})
        if lines:
            planes.append({"name": pl.name, "lines": lines})
    return {"planes": planes}


def _union(intervals):
    """Sorted, merged [start, end] intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(intervals, lo, hi):
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if min(b, hi) > max(a, lo)]


def _overlap(a, b, intervals) -> float:
    return sum(max(0.0, min(b, y) - max(a, x)) for x, y in intervals)


def host_events(trace: dict, name: str):
    return sorted((s, s + d) for pl in trace["planes"]
                  if not DEVICE_PLANE.match(pl["name"])
                  for ln in pl["lines"] for n, s, d in ln["events"]
                  if n == name)


def stage_of(module: str, stage_map: dict) -> str | None:
    """The stage whose patterns match an XLA module's name."""
    for stage, patterns in stage_map.items():
        if any(re.search(p, module) for p in patterns):
            return stage
    return None


def reduce(trace: dict, stage_map: dict, window, replays=(),
           host_phases: dict | None = None, top: int = 10) -> dict | None:
    """`window` is the traced stretch, [start_ns, end_ns] on the trace's
    clock. -> busy_s (union of the device's operation intervals inside it,
    averaged over the chips), window_s, idle_s, seconds and runs per stage
    (the stage's module events that lie wholly inside the window: the
    window opens after the profiler has started and closes before it is
    stopped, so a program cut at either end of the trace is not inside;
    and the last module event of a device's line is not counted: it is the
    program that was running when the profiler stopped, shown as long as
    it had run by then, or the last before the device went idle, and the
    trace cannot say which), the
    operations that took most time, and the idle gaps by what the host was
    doing.

    `replays`: [[start_ns, end_ns], ...] of the benchmark's calls into the
    program, and `host_phases`: {label: [[start_ns, end_ns], ...]} of the
    program's per-window spans, both laid on the trace's clock by the
    caller. A gap goes to the phase that covers most of it; one that no
    phase covers is "replay: outside the window spans" or "between
    replays". None when the trace holds no device plane."""
    devices = [pl for pl in trace["planes"] if DEVICE_PLANE.match(pl["name"])]
    if not devices:
        return None
    lo, hi = window
    busy_ns, per_stage, unmatched, ops_time, gaps = 0.0, {}, {}, {}, {}
    n_modules: dict[str, int] = {}
    for pl in devices:
        lines = {ln["name"]: ln["events"] for ln in pl["lines"]}
        ops = lines.get(OPS_LINE)
        if ops is None:  # no operation line: every line of the plane
            ops = [e for evs in lines.values() for e in evs]
        busy = _clip(_union([s, s + d] for _, s, d in ops), lo, hi)
        busy_ns += sum(b - a for a, b in busy)
        for n, s, d in ops:
            if lo <= s < hi:
                ops_time[n] = ops_time.get(n, 0.0) + d
        # the profiler keeps an operation once it has finished and a
        # program from its start. The program running at the stop is the
        # last event of the line, as long as it had run by then (17.05 ms
        # of 31.76; 270 ns, after the trace's last operation: traces of
        # PRs 29 and 31), the operation that was running left out. Where
        # the device was idle at the stop the last event is a whole run
        # and looks no different: it is not counted either way
        modules = lines.get(MODULES_LINE, ())
        cut = max(modules, key=lambda e: e[1], default=None)
        for e in modules:
            n, s, d = e
            if not (lo <= s and s + d <= hi) or e is cut:
                continue
            stage = stage_of(n, stage_map)
            if stage is None:
                unmatched[n] = unmatched.get(n, 0.0) + d
            else:
                per_stage[stage] = per_stage.get(stage, 0.0) + d
                n_modules[stage] = n_modules.get(stage, 0) + 1
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            label, best = None, 0.0
            for name, ivs in (host_phases or {}).items():
                o = _overlap(a, b, ivs)
                if o > best:
                    label, best = name, o
            if label is None or best < (b - a) / 2:
                label = ("replay: outside the window spans"
                         if _overlap(a, b, replays) >= (b - a) / 2
                         else "between replays")
            gaps[label] = gaps.get(label, 0.0) + (b - a)
    k = len(devices)

    def ranked(d):
        return [[n, v / k / 1e9] for n, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    window_s = (hi - lo) / 1e9
    busy_s = busy_ns / k / 1e9
    return {
        "window_s": window_s, "busy_s": busy_s,
        "idle_s": window_s - busy_s, "chips": k,
        "stage_s": {s: v / k / 1e9 for s, v in per_stage.items()},
        "stage_runs": {s: n // k for s, n in n_modules.items()},
        "unmatched_modules": ranked(unmatched),
        "device_ops": ranked(ops_time), "idle_gaps": ranked(gaps),
    }


def clock_offset_ns(trace: dict, sync_mono_ns: int) -> float | None:
    """trace clock = time.monotonic_ns() - offset."""
    ev = host_events(trace, SYNC_ANNOTATION)
    return sync_mono_ns - ev[0][0] if ev else None


def phases_on_trace(spans, offset_ns: float) -> dict:
    """The program's per-window spans (obs `WindowSpan`, on
    time.monotonic()) as intervals on the trace's clock. A window's
    phases end at t_dispatch (stage, then dispatch), t_materialized
    (materialize) and t_done (epilogue); where the program stages on its
    own thread, `stage` lies earlier than reckoned here."""
    out = {"stage": [], "dispatch": [], "materialize": [], "epilogue": []}

    def put(label, end_s, dur_s):
        end = end_s * 1e9 - offset_ns
        out[label].append([end - dur_s * 1e9, end])

    for s in spans:
        put("dispatch", s["t_dispatch"], s["dispatch_s"])
        put("stage", s["t_dispatch"] - s["dispatch_s"], s["stage_s"])
        put("materialize", s["t_materialized"], s["materialize_s"])
        put("epilogue", s["t_done"], s["epilogue_s"])
    return out
