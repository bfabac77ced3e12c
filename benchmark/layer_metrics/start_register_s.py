"""Seconds the node spent registering with the coordinator and waiting for
its peers (lifecycle stage ``node.register``: ``client.register`` through
``await_cluster``): the control plane's round trips.

Read from the chief's counters in this run's ``logs/run_report.json``
(``benchmark/run_report.py``): the job's totals, which a ``correct`` run
spends before its window.  A missing or stale report, or a program without
the counter: nothing to read."""

from benchmark import run_report

LAYER = "process start"
UNIT = "s"
MOVES = "setup_s"


def read(run: dict):
    return run_report.seconds(run, "node.register.us")
