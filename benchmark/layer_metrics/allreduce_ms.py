"""Milliseconds per step in collective operations (all-reduce and kin:
start, done and fused forms), profiler trace, mean over devices."""

LAYER = "collectives over ICI"
UNIT = "ms"
MOVES = "train_img_rate_dp4"


def read(run: dict):
    steps, trace = run["facts"].get("traced_steps"), run.get("trace")
    if not steps or not trace or not trace.get("collectives"):
        return None
    return 1e3 * trace["collectives"]["collective_s"] / steps
