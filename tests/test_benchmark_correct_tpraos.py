"""`benchmark/tests/test_correct_tpraos.py`, run by tier-1: `correct` of the
kind `replay_tpraos` comes out false when the timed path is broken underneath
(the sound run with its five wrong headers, three planted faults, the
control, the parent's clean failure). Imported, not copied, as
`tests/test_benchmark_correct.py` imports the plain kind's; a module of its
own because `on_cpu`, `_run`, `_plant` and the test names are defined again
there, and one namespace would keep one of each."""

from benchmark.tests.test_correct_tpraos import *  # noqa: F401,F403
