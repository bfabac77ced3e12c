"""Seconds the node spent inside the backend's compile-or-load, over every
program of the job (counter ``xla.backend.us``): a compile on a miss of the
persistent cache, the retrieval and deserialisation on a hit; either stalls
the caller.

Read from the chief's counters in this run's ``logs/run_report.json``
(``benchmark/run_report.py``): the job's totals, which a ``correct`` run
spends before its window.  A missing or stale report, or a program without
the counter: nothing to read."""

from benchmark import run_report

LAYER = "entry, lifecycle, compile cache"
UNIT = "s"
MOVES = "setup_s"


def read(run: dict):
    return run_report.seconds(run, "xla.backend.us",
                              witness="xla.programs")
