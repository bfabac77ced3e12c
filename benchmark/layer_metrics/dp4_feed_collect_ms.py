"""Milliseconds per batch the prefetch thread spent in ``feed.next_batch``:
waiting for decoded chunks and assembling the row list (stage
``feed.collect``).

Read from ``run["counters"]``: what the program's ``telemetry.stage``
counters moved over the untraced window of a ``--trace 1`` run.  Per batch
PRODUCED in the window (``batch.put.calls``), so that batches prefetched
before the window cancel out.  A program without the stage: nothing to read."""

LAYER = "feed, batch to device"
UNIT = "ms"
MOVES = "train_img_rate_dp4"


def read(run: dict):
    counters = run["counters"]
    batches = counters.get("batch.put.calls")
    if not batches:
        return None
    return counters.get("feed.collect.us", 0) / batches / 1e3
