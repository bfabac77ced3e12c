"""Milliseconds per batch the prefetch thread was blocked on a FULL prefetch
queue (stage ``batch.queue_full``): the feed's slack against the step.

Read from ``run["counters"]``: what the program's ``telemetry.stage``
counters moved over the untraced window of a ``--trace 1`` run.  Per batch
PRODUCED in the window (``batch.put.calls``), so that batches prefetched
before the window cancel out.  A program without the stage: nothing to read."""

LAYER = "feed, batch to device"
UNIT = "ms"
MOVES = "train_img_rate"


def read(run: dict):
    counters = run["counters"]
    batches = counters.get("batch.put.calls")
    if not batches:
        return None
    return counters.get("batch.queue_full.us", 0) / batches / 1e3
