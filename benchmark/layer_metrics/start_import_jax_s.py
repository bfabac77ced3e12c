"""Seconds of the ``import jax`` inside ``tpu_info.device_summary``
(lifecycle stage ``node.import_jax``): the import alone, before the backend
is touched.  0 where the map_fun's module had loaded jax already (the stage
is then tagged ``preloaded`` and the time is ``start_spawn_s``'s).

Read from the chief's counters in this run's ``logs/run_report.json``
(``benchmark/run_report.py``): the job's totals, which a ``correct`` run
spends before its window.  A missing or stale report, or a program without
the counter: nothing to read."""

from benchmark import run_report

LAYER = "process start"
UNIT = "s"
MOVES = "setup_s"


def read(run: dict):
    return run_report.seconds(run, "node.import_jax.us")
