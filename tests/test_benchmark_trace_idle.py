"""`benchmark/tests/test_trace_idle_in_span.py`, run by tier-1: the device's
idle time attributed to the program's own spans. Imported, not copied; a
module of its own because `trace` is a fixture of `test_xplane.py` too."""

from benchmark.tests.test_trace_idle_in_span import *  # noqa: F401,F403
