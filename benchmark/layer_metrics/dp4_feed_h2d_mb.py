"""Megabytes handed to ``shard_batch`` per batch (counter ``batch.h2d_bytes``,
the sum of the host arrays' ``nbytes``).

Read from ``run["counters"]``: what the program's ``telemetry.stage``
counters moved over the untraced window of a ``--trace 1`` run.  Per batch
PRODUCED in the window (``batch.put.calls``), so that batches prefetched
before the window cancel out.  A program without the stage: nothing to read."""

LAYER = "feed, batch to device"
UNIT = "MB"
MOVES = "train_img_rate_dp4"


def read(run: dict):
    counters = run["counters"]
    batches = counters.get("batch.put.calls")
    if not batches:
        return None
    return counters.get("batch.h2d_bytes", 0) / batches / 1e6
