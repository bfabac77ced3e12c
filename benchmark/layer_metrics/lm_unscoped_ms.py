"""Milliseconds per step on the device in ops with NO scope path at all:
what XLA writes without an ``op_name`` whichever scope traced the work.  On
the v5e that is, by size: RoPE's rotation where the compiler merged its two
halves into one multi-output fusion (the merged instruction carries no
metadata), the exposed waits of asynchronous copies (``copy-done``,
``slice-done``), zero-filled buffers (``broadcast``) and layout copies of
parameters.  The program cannot name these; this is their size.  ``python3
-m benchmark.step_account <run directory>`` lists them by XLA op name.

Device self-time from the traced run's xplane, as one bucket of the step's
account (``benchmark/step_account.py``: every scope path of the step lands
in exactly one bucket, first match in its order)."""

from benchmark import step_account

LAYER = "step, model"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    return step_account.bucket_ms(run, "unscoped")
