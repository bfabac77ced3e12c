"""Times per step the feed was polled and had nothing
(``feed.starved_polls`` of ``telemetry.snapshot()``, difference over the window)."""

LAYER = "feed, batch to device"
UNIT = "count/step"
MOVES = "train_img_rate"


def read(run: dict):
    if not run["facts"].get("steps"):
        return None
    return run["counters"].get("feed.starved_polls", 0) / run["facts"]["steps"]
