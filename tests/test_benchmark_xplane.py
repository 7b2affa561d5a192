"""`benchmark/tests/test_xplane.py`, run by tier-1: the reduction from a
profiler trace to busy, idle and per-stage kernel time, on recorded traces.
Imported, not copied; a module of its own because `trace` is a fixture of
`test_trace_idle_in_span.py` too."""

from benchmark.tests.test_xplane import *  # noqa: F401,F403
