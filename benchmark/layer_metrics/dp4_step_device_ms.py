"""Device-busy milliseconds per step (profiler trace, mean over devices), in
the four-chip cell."""

LAYER = "step, model"
UNIT = "ms"
MOVES = "train_img_rate_dp4"


def read(run: dict):
    steps = run["facts"].get("traced_steps")
    if not steps or not run.get("trace"):
        return None
    return 1e3 * run["trace"]["busy_s"] / steps
