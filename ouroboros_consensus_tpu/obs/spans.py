"""Self time of the replay's spans (utils/trace.Enclose brackets).

A span's self time is its duration less what its child spans cover, and
a child is a span that lies inside it ON THE SAME THREAD: a `stage` on
the staging thread takes nothing from the main thread's `validate-chain`,
which went on with its own work meanwhile. Spans of one thread nest (a
thread closes what it opened last first), so the children of a span
never overlap each other and their durations sum to their union.

One function, shared by the flight recorder, db_analyser's phase
collector (`res.phases["<label>.self"]`) and the tests."""

from __future__ import annotations

from collections import defaultdict

from ..utils.trace import EncloseEvent


def self_times(events) -> dict[str, float]:
    """{label: seconds of self time, summed over the label's spans}.

    `events`: any iterable of tracer events; of it the end edges of
    EncloseEvents are read (an end edge carries its start as
    `t - duration`)."""
    by_thread: dict[str, list] = defaultdict(list)
    for ev in events:
        if isinstance(ev, EncloseEvent) and ev.edge == "end":
            by_thread[ev.thread].append((ev.t - ev.duration, ev.t, ev.label))
    out: dict[str, float] = defaultdict(float)
    for spans in by_thread.values():
        # parents before their children: by start, the longer first
        spans.sort(key=lambda s: (s[0], -s[1]))
        open_: list[list] = []  # [end, label, self seconds], innermost last

        def close():
            _end, label, self_s = open_.pop()
            out[label] += self_s

        for start, end, label in spans:
            while open_ and open_[-1][0] <= start:
                close()
            if open_:
                open_[-1][2] -= end - start
            open_.append([end, label, end - start])
        while open_:
            close()
    return dict(out)
