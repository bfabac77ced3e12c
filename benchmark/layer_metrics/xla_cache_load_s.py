"""Seconds of ``xla_backend_s`` that were retrieval from the persistent
compilation cache (counter ``xla.cache_load.us``, jax's
``cache_retrieval_time_sec``).  A warm run has the two nearly equal; a run
that loaded nothing reads 0.

Read from the chief's counters in this run's ``logs/run_report.json``
(``benchmark/run_report.py``): the job's totals, which a ``correct`` run
spends before its window.  A missing or stale report, or a program without
the counter: nothing to read."""

from benchmark import run_report

LAYER = "entry, lifecycle, compile cache"
UNIT = "s"
MOVES = "setup_s"


def read(run: dict):
    return run_report.seconds(run, "xla.cache_load.us",
                              witness="xla.programs")
