"""Seconds the node spent compiling the step, or loading it from the
persistent cache, and running it once (node clock)."""

LAYER = "entry, lifecycle, compile cache"
UNIT = "s"
MOVES = "setup_s"


def read(run: dict):
    return run["node"]["seconds"].get("first_step_s")
