"""`benchmark/tests/test_correct.py`, run by tier-1: `correct` comes out
false when the timed path is broken underneath (the plain kind's sound run,
its three planted faults, the control). Imported, not copied, as
`tests/test_benchmark_guard.py` imports the others; a module of its own
because `on_cpu`, `_run`, `_plant` and two test names are defined again in
`test_correct_stake.py`, and one namespace would keep one of each."""

from benchmark.tests.test_correct import *  # noqa: F401,F403
