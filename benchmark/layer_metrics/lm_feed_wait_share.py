"""Share of the (untraced) window the step loop spent inside
``next(batches)``, in the cells that count tokens."""

LAYER = "feed, batch to device"
UNIT = "%"
MOVES = "train_tok_rate"


def read(run: dict):
    wait = run["spans"]["seconds"].get("feed_wait")
    if wait is None or not run["facts"].get("window_s"):
        return None
    return 100.0 * wait / run["facts"]["window_s"]
