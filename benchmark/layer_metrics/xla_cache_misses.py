"""Programs the node compiled and WROTE to the persistent compilation cache
over the job (counter ``xla.cache.misses``, jax's ``cache_misses`` event):
0 says the run was warm, and so does ``cache_entries_new`` 0 in the run's
facts.

Read from the chief's counters in this run's ``logs/run_report.json``
(``benchmark/run_report.py``).  A missing or stale report, or a program that
counts no programs (``xla.programs``): nothing to read."""

from benchmark import run_report

LAYER = "entry, lifecycle, compile cache"
UNIT = "programs"
MOVES = "setup_s"


def read(run: dict):
    return run_report.total(run, "xla.cache.misses", witness="xla.programs")
