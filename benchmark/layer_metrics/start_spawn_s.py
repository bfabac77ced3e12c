"""Seconds from the launcher's spawn of the node process to the entry of
``node_main`` (lifecycle stage ``node.spawn``, recorded by the node from the
stamp the launcher put into its config): the interpreter's start, the
unpickling of the config, which imports the map_fun's modules, and the
package import.

Read from the chief's counters in this run's ``logs/run_report.json``
(``benchmark/run_report.py``): the job's totals, which a ``correct`` run
spends before its window.  A missing or stale report, or a program without
the counter: nothing to read."""

from benchmark import run_report

LAYER = "process start"
UNIT = "s"
MOVES = "setup_s"


def read(run: dict):
    return run_report.seconds(run, "node.spawn.us")
