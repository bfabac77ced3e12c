"""The flash forward kernel's share of its roofline: the least time the chip
could take for one call (the larger of FLOPs over peak FLOP/s and bytes
over peak bytes/s, from the configuration's ``flash_fwd_cost``) over the
measured time per call.  ``bound(run)`` says which of the two bounds it."""

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tok_rate"


def read(run: dict):
    least = _least_seconds(run)
    trace = run.get("trace")
    if least is None or not trace or not trace.get("pallas_calls"):
        return None
    return 100.0 * max(least) / (trace["pallas_s"] / trace["pallas_calls"])


def _least_seconds(run: dict):
    cost, peaks = run["facts"]["kernels"].get("flash_fwd"), run.get("peaks")
    if not cost or not peaks:
        return None
    return (cost["flops"] / peaks["bf16_flops_per_s"],
            cost["bytes"] / peaks["hbm_bytes_per_s"])


def bound(run: dict):
    least = _least_seconds(run)
    if least is None:
        return None
    return "compute" if least[0] >= least[1] else "memory"
