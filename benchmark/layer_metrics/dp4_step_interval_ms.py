"""Median milliseconds between two step dispatches in the (untraced) window.
With steps in flight and a host that does not keep up, this is the feed's
period for one global batch.  Unlike the rate it is moved neither by the
batches prefetched before the window opens nor by a single stall."""

import statistics

LAYER = "feed, batch to device"
UNIT = "ms"
MOVES = "train_img_rate_dp4"


def read(run: dict):
    offsets = run["facts"].get("dispatched_s") or []
    if len(offsets) < 3:
        return None
    return 1e3 * statistics.median(
        later - earlier for earlier, later in zip(offsets, offsets[1:]))
