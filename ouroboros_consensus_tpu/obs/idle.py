"""The device's idle time of one replay, and what the host was doing then.

Two times a window, from spans the replay records anyway:

  * `t_launch`: the end of its `dispatch` span (main thread), when the
    last of the window's programs has been enqueued: the device cannot
    finish the window before it;
  * `t_ready`: the end of its `materialize.wait` span (`oct-read_0`), when
    `block_until_ready` returned: the device has finished the window (the
    end of the main thread's `materialize` where there is no such span).

Windows run on the device in dispatch order, which is the order of their
ids, so the device is busy on [max(t_launch(j), t_ready(j-1)), t_ready(j)]
and idle on the rest of the replay's `replay` span. A replay starts and
ends with nothing in flight, so each is accounted on its own.

Each idle interval is put down to the main thread's span at that instant:
the outermost one below `replay` / `validate-chain` (`CAUSES`), its children
counting as it (`open.index` as `open`, `dispatch.<stage>` as `dispatch`,
`epilogue.fold` as `epilogue`). Time under none of them, or under a span of
another label, is `unspanned`: `replay`'s and `validate-chain`'s own time,
the store guard's open and close, the pools' set-up and shutdown.

Nothing is in flight while `open`, `segment-wait` or `stage-wait` is open,
so their parts equal their walls: the account's self-check.

Both times are the host's, so the account errs both ways: the device's
work on a window's first programs while the host still enqueues its last
ones counts as idle, and `t_ready` comes after the device finished by the
reader's wake-up. Against the profiler's trace of the same stretch,
PERF.md gives each."""

from __future__ import annotations

from ..utils.trace import EncloseEvent

CONTAINERS = ("replay", "validate-chain")
# every cause, in the order the total adds them up
CAUSES = ("open", "segment-wait", "enqueue", "stage-wait", "dispatch",
          "materialize", "era-cross", "tick", "epilogue", "gc", "unspanned")


def _windows(spans, main: str) -> list[tuple[float, float]]:
    """[(t_launch, t_ready)] of every window the spans launched and saw
    finished, in dispatch order."""
    launch: dict = {}
    ready: dict = {}
    ready_main: dict = {}
    for ev in spans:
        w = ev.window
        if w is None:
            continue
        if ev.label == "materialize.wait":
            ready[w] = max(ready.get(w, ev.t), ev.t)
        elif ev.thread != main:
            continue
        elif ev.label == "dispatch":
            launch[w] = ev.t
        elif ev.label == "materialize":
            ready_main[w] = ev.t
    out = []
    for w in sorted(launch):
        t_ready = ready.get(w, ready_main.get(w))
        if t_ready is not None:
            out.append((launch[w], t_ready))
    return out


def _idle(windows, lo: float, hi: float) -> list[tuple[float, float]]:
    """The complement inside [lo, hi] of the device's busy intervals."""
    gaps = []
    at = lo  # the device is idle from here on
    for t_launch, t_ready in windows:
        start = max(t_launch, at)
        if start > at:
            gaps.append((at, min(start, hi)))
        at = max(at, t_ready)
    if hi > at:
        gaps.append((at, hi))
    return [(a, b) for a, b in gaps if b > a]


def _causes(spans, main: str) -> list[tuple[float, float, str]]:
    """[(start, end, cause)] of the main thread's outermost spans below
    the containers, in time order (they do not overlap)."""
    mine = sorted(((ev.t - ev.duration, ev.t, ev.label) for ev in spans
                   if ev.thread == main), key=lambda s: (s[0], -s[1]))
    out = []
    open_: list[float] = []  # ends of the enclosing non-container spans
    for start, end, label in mine:
        while open_ and open_[-1] <= start:
            open_.pop()
        if label in CONTAINERS:
            continue
        if not open_:
            out.append((start, end, label if label in CAUSES else "unspanned"))
        open_.append(end)
    return out


def account(spans) -> tuple[dict, list]:
    """The idle account of ONE replay from its spans' end edges.

    -> ({cause: idle seconds} for every cause in `CAUSES`, [(start, end,
    cause)] the idle pieces in time order). The parts, added in `CAUSES`
    order, are the total (`total`). Without a `replay` span: zeros."""
    parts = dict.fromkeys(CAUSES, 0.0)
    ends = [ev for ev in spans
            if isinstance(ev, EncloseEvent) and ev.edge == "end"]
    roots = [ev for ev in ends if ev.label == "replay"]
    if not roots:
        return parts, []
    root = roots[-1]
    ends = [ev for ev in ends if ev.replay == root.replay]
    lo, hi = root.t - root.duration, root.t
    gaps = _idle(_windows(ends, root.thread), lo, hi)
    causes = _causes(ends, root.thread)
    pieces = []
    k = 0
    for a, b in gaps:
        while k < len(causes) and causes[k][1] <= a:
            k += 1
        at = a
        j = k
        while j < len(causes) and causes[j][0] < b:
            s, e, cause = causes[j]
            s, e = max(s, a), min(e, b)
            if s > at:
                pieces.append((at, s, "unspanned"))
            if e > s:
                pieces.append((s, e, cause))
                at = e
            j += 1
        if b > at:
            pieces.append((at, b, "unspanned"))
    for s, e, cause in pieces:
        parts[cause] += e - s
    return parts, pieces


def total(parts: dict) -> float:
    """The account's total: its parts added in `CAUSES` order."""
    out = 0.0
    for cause in CAUSES:
        out += parts[cause]
    return out
