"""Times per step the feed was polled and had nothing (see
``feed_starved_polls``), in the cell where one node process feeds four chips."""

LAYER = "feed, batch to device"
UNIT = "count/step"
MOVES = "train_img_rate_dp4"


def read(run: dict):
    if not run["facts"].get("steps"):
        return None
    return run["counters"].get("feed.starved_polls", 0) / run["facts"]["steps"]
