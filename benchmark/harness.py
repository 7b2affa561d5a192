"""What every traffic kind shares: the clock from process start, the look
for the chip, JAX's own count of programs built, the lines a run prints.

`CompileCounter` and the device seams are copied from `chip_smoke.py` (PR
26), which the benchmark may not import: a later PR may change it.
"""

from __future__ import annotations

import json
import os
import sys
import time

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# JAX's other durations on the way to a program: tracing to a jaxpr, and
# lowering the jaxpr to an MLIR module. No cache saves either.
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

# exit codes of a run that prints no result line
RC_NO_CHIP = 3
RC_FAILED_RUN = 4
RC_REHEARSAL = 2  # a --cpu-rehearsal whose control flow passed: no result


class FailedRun(Exception):
    """The run is not a measurement (a program built inside the window, a
    fallback fired, no chip): exit non-zero and print no result line."""

    def __init__(self, what: str, rc: int = RC_FAILED_RUN, **detail):
        super().__init__(what)
        self.what, self.rc, self.detail = what, rc, detail


def process_age_s() -> float:
    """Seconds since this process was started, by the kernel's record
    (the interpreter's own start-up and the imports count as set-up)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.monotonic() - _IMPORTED


_IMPORTED = time.monotonic()


def emit(line: str, **fields) -> None:
    """One JSON line of context on stdout, before the result line."""
    print(json.dumps({"line": line, **fields}, default=str), flush=True)


def say(text: str) -> None:
    print(text, file=sys.stderr, flush=True)


class CompileCounter:
    """JAX's own record of every program it built (a compile or a
    persistent-cache load), by jitted function name."""

    def __init__(self):
        import jax.monitoring as mon

        self.built: list[tuple[str, float]] = []
        self.cache_hits = 0
        self.trace_s = self.lower_s = 0.0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.built.append((str(kw.get("fun_name", "?")), duration))
        elif event == TRACE_EVENT:
            self.trace_s += duration
        elif event == LOWER_EVENT:
            self.lower_s += duration

    def _on_event(self, event, **kw):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def mark(self) -> tuple:
        return len(self.built), self.cache_hits, self.trace_s, self.lower_s

    def since(self, mark) -> dict:
        built = self.built[mark[0]:]
        by_name: dict[str, list] = {}
        for name, d in built:
            row = by_name.setdefault(name, [0, 0.0])
            row[0] += 1
            row[1] = round(row[1] + d, 1)
        return {
            "programs_built": len(built),
            "cache_hits": self.cache_hits - mark[1],
            "built_s": round(sum(d for _, d in built), 1),
            "trace_s": round(self.trace_s - mark[2], 1),
            "lower_s": round(self.lower_s - mark[3], 1),
            "by_name": dict(sorted(by_name.items(),
                                   key=lambda kv: -kv[1][1])[:16]),
        }


def acquire_device(chips: int, rehearsal: bool) -> dict:
    """The device as JAX reports it. No TPU, or fewer chips than the cell
    asks for, ends the run with RC_NO_CHIP and no result (a rehearsal
    goes on, on whatever JAX has, and never prints a result either)."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs)}
    if not rehearsal and (d0.platform != "tpu" or len(devs) < chips):
        raise FailedRun("no TPU, or fewer chips than the cell asks for",
                        RC_NO_CHIP, wanted_chips=chips, **device)
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 - a version string for the record
        libtpu = None
    emit("device", **device, jax=jax.__version__, libtpu=libtpu,
         oct_env=sorted(k for k in os.environ if k.startswith("OCT_")))
    return device


def memory_peak_bytes() -> int | None:
    """Peak device memory so far on the fullest chip."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
