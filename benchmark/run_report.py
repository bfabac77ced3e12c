"""The run report of a cell's own job, for the readers that split ``setup_s``.

``cluster.shutdown()`` writes ``<log_dir>/run_report.json`` (``telemetry.
build_run_report``); ``run.py`` gives every cell the ``log_dir``
``runs/<workload>/logs`` under the work directory and shuts the cluster
down before it reads a metric.  The readers take the CHIEF's counters from
it: the lifecycle stages' ``<stage>.us`` and the ``xla.*`` totals, which are
the job's totals; a ``correct`` run compiles nothing inside its window, so
they are set-up's.

The run directory is reused from run to run, so a report that was written
before this run's window began is LAST run's: it reads as no report, not as
a value.  No jax: the readers run in the driver.
"""

from __future__ import annotations

import json
import os

from benchmark import common


def chief_counters(run: dict) -> dict | None:
    """The chief node's counters from this run's report, or None: no
    report, one that is not this run's, or a program that writes none."""
    path = os.path.join(common.WORK_DIR, "runs", run["cell"]["workload"],
                        "logs", "run_report.json")
    try:
        with open(path) as f:
            report = json.load(f)
    except (OSError, ValueError):
        return None
    if float(report.get("written_at") or 0.0) < float(
            run["facts"]["window_epoch_start"]):
        return None
    return ((report.get("nodes") or {}).get("0") or {}).get("counters")


def total(run: dict, *counters: str, witness: str | None = None):
    """The sum of the chief's ``counters``, or None: no report of this run,
    or a program without the counters (nothing to read).  A stage that ran
    has its counter; of the ``xla.*`` counters one that never moved (no load
    from the cache in a cold run) is absent and reads 0 once ``witness``,
    the counter every program with the listener has, is there."""
    found = chief_counters(run)
    if found is None or any(name not in found
                            for name in ((witness,) if witness else counters)):
        return None
    return sum(found.get(name, 0) for name in counters)


def seconds(run: dict, *counters: str, witness: str | None = None):
    """:func:`total` of microsecond counters, in seconds."""
    found = total(run, *counters, witness=witness)
    return None if found is None else found / 1e6
