"""The visits of the band's tables as a share of the causal tables' of the
same shapes: what of plain causal's grid steps (and fetches) the window
layers' kernels keep.  From the program's counters ``flash.window.visits``
over ``flash.window.causal_visits``, which every band kernel traced adds to
(``ops/attention._count``), the job's totals in this run's
``logs/run_report.json`` (``benchmark/run_report.py``): a ratio, so the
check's kernels beside the step's change nothing.  At 16,384 positions under
a window of 4,096 in tiles of 512: 252 of 528 visits.  A missing or stale
report, or a program without the counters: nothing to read."""

from benchmark import run_report

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tok_rate"


def read(run: dict):
    visits = run_report.total(run, "flash.window.visits")
    causal = run_report.total(run, "flash.window.causal_visits")
    if not visits or not causal:
        return None
    return 100.0 * visits / causal
