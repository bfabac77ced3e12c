"""The feed's own period for one batch: milliseconds per batch the prefetch
thread spent collecting rows, converting them and putting them on the device
(stages ``feed.collect`` + ``batch.convert`` + ``batch.put``), without the
time it was blocked on a full queue.  Under the step's device time, the
feed keeps up.

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
    busy_us = sum(counters.get(name, 0) for name in (
        "feed.collect.us", "batch.convert.us", "batch.put.us"))
    return busy_us / batches / 1e3
