"""Of the device's idle time inside the traced stretch, the share (in per
cent) during which a thread of the program was inside one of its own spans.

The program writes its spans into the profiler's own file (`Enclose` enters
`jax.profiler.TraceAnnotation("oct:<label>")` on the thread that does the
work), so host spans and device operations are on ONE clock: no offset, no
reckoning from durations.

spec: {"spans": [<annotation>, ...]}  the share of the idle time that those
          spans cover, wherever they were recorded;
      {"spans": null}  the share that NO span covers of the dispatching
          thread (the one that recorded `oct:dispatch` or `oct:epilogue`),
          its spans that only wait or enclose the others not counted.

`run.py` keeps the trace's path out of `sources`, so the run's trace is
found here: the newest under benchmark/_cache/trace-*/ (a traced run
deletes its own directory before it traces, and `sources["trace"]` is there
only when it has traced). The stretch is the `window_s` of `sources["trace"]`
counted from the end of the benchmark's own `bench:sync` annotation, where
`xplane.Stretch` reads its start. None where there is no trace or the
program wrote no annotation into it (a program from before they existed);
0.0 where they are there and cover no idle time.
"""

from __future__ import annotations

import functools
import glob
import os

from benchmark import xplane

CACHE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "_cache")
PREFIX = "oct:"
# what marks the dispatching thread, and its spans that do no work of
# their own: the wait for the device, and the two that enclose the rest
DISPATCHING = ("oct:dispatch", "oct:epilogue")
WAITING = ("oct:materialize", "oct:replay", "oct:validate-chain")


class _ProgramSpans:
    """What `xplane.load` keeps of the host planes: the program's own
    annotations, whatever labels a later PR adds, and the benchmark's."""

    def __contains__(self, name: str) -> bool:
        return name.startswith(PREFIX) or name == xplane.SYNC_ANNOTATION


def newest_trace() -> str | None:
    found = glob.glob(os.path.join(CACHE, "trace-*", "plugins", "profile",
                                   "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


@functools.lru_cache(maxsize=2)
def _load(path: str, _mtime: float) -> dict:
    return xplane.load(path, keep_host=_ProgramSpans())


def idle_gaps(trace: dict, lo: float, hi: float) -> list:
    """[start, end] of every stretch inside [lo, hi] in which a chip runs
    no operation, over all chips."""
    gaps = []
    for pl in trace["planes"]:
        if not xplane.DEVICE_PLANE.match(pl["name"]):
            continue
        lines = {ln["name"]: ln["events"] for ln in pl["lines"]}
        ops = lines.get(xplane.OPS_LINE)
        if ops is None:
            ops = [e for evs in lines.values() for e in evs]
        busy = xplane._clip(xplane._union([s, s + d] for _, s, d in ops),
                            lo, hi)
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        gaps += [[a, b] for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    return gaps


def share(trace: dict, spec: dict, window_s: float) -> float | None:
    sync = xplane.host_events(trace, xplane.SYNC_ANNOTATION)
    threads = [ln["events"] for pl in trace["planes"]
               if not xplane.DEVICE_PLANE.match(pl["name"])
               for ln in pl["lines"]]
    if not sync or not any(n.startswith(PREFIX)
                           for evs in threads for n, _, _ in evs):
        return None
    lo = sync[0][1]
    gaps = idle_gaps(trace, lo, lo + window_s * 1e9)
    idle = sum(b - a for a, b in gaps)
    if not idle:
        return 0.0
    names = spec.get("spans")
    if names is None:
        threads = [evs for evs in threads
                   if any(n in DISPATCHING for n, _, _ in evs)]

        def counts(n):
            return n.startswith(PREFIX) and n not in WAITING
    else:
        def counts(n):
            return n in names
    cover = xplane._union([s, s + d] for evs in threads
                          for n, s, d in evs if counts(n))
    covered = sum(xplane._overlap(a, b, cover) for a, b in gaps)
    if names is None:
        covered = idle - covered
    return covered / idle * 100.0


def read(spec: dict, sources: dict) -> float | None:
    tr = sources.get("trace")
    if not tr or not tr.get("window_s"):
        return None
    path = newest_trace()
    if path is None:
        return None
    return share(_load(path, os.path.getmtime(path)), spec, tr["window_s"])
