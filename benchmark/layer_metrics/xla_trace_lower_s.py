"""Seconds the node spent tracing jaxprs and lowering them to MLIR, over
every program of the job (counters ``xla.trace.us`` + ``xla.lower.us`` of
``telemetry/xla_events.py``, from ``jax.monitoring``): the Python side of
every program before the window, paid warm or cold.

Read from the chief's counters in this run's ``logs/run_report.json``
(``benchmark/run_report.py``): the job's totals, which a ``correct`` run
spends before its window.  A missing or stale report, or a program without
the counters: nothing to read."""

from benchmark import run_report

LAYER = "entry, lifecycle, compile cache"
UNIT = "s"
MOVES = "setup_s"


def read(run: dict):
    return run_report.seconds(run, "xla.trace.us", "xla.lower.us",
                              witness="xla.programs")
