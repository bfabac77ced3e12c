"""Share of the collective time during which no compute ran on that device."""

LAYER = "collectives over ICI"
UNIT = "%"
MOVES = "train_img_rate_dp4"


def read(run: dict):
    trace = run.get("trace")
    if not trace or not trace.get("collectives"):
        return None
    c = trace["collectives"]
    return 100.0 * c["exposed_s"] / c["collective_s"]
