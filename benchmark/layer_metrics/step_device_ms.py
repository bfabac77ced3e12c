"""Device-busy milliseconds per step: the union of the intervals in which an
operation ran (profiler trace, mean over devices) over the steps traced."""

LAYER = "step, model"
UNIT = "ms"
MOVES = "train_img_rate"


def read(run: dict):
    steps = run["facts"].get("traced_steps")
    if not steps or not run.get("trace"):
        return None
    return 1e3 * run["trace"]["busy_s"] / steps
