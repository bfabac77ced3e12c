"""Seconds of the backend's initialisation in ``tpu_info.device_summary``
(lifecycle stage ``node.claim``: ``jax.local_devices()`` and
``default_backend()``): the chip claim, the part of the start that holds the
interpreter lock and that the machine, not the program, decides.

Read from the chief's counters in this run's ``logs/run_report.json``
(``benchmark/run_report.py``): the job's totals, which a ``correct`` run
spends before its window.  A missing or stale report, or a program without
the counter: nothing to read."""

from benchmark import run_report

LAYER = "process start"
UNIT = "s"
MOVES = "setup_s"


def read(run: dict):
    return run_report.seconds(run, "node.claim.us")
