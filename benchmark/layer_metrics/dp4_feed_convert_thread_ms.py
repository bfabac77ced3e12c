"""Thread-milliseconds per batch the ``batch-convert`` workers spent in
``to_arrays``, one call per device shard of the batch (stage
``batch.convert_slice``).  Beside ``dp4_feed_convert_ms``, the wall time of
the same work: thread-ms near the wall means the shards' first-touch faults
queued behind one another, thread-ms near slices x wall that they ran side
by side.

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
    return counters.get("batch.convert_slice.us", 0) / batches / 1e3
