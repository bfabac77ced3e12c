"""Device-busy milliseconds per step (profiler trace, mean over devices), in
the cells that count tokens."""

LAYER = "step, model"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    steps = run["facts"].get("traced_steps")
    if not steps or not run.get("trace"):
        return None
    return 1e3 * run["trace"]["busy_s"] / steps
