"""The grouped expert matmul's share of its roofline: the least time the
chip could take for the FLOPs and bytes the ROUTED PAIRS need in one step
(the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s, from the
configuration's ``moe_experts_cost``: padding and gathered copies are the
formulation's own and are not counted) over ``moe_experts_ms``.
``bound(run)`` says which of the two bounds it."""

from benchmark import scope_times

LAYER = "experts: routing and grouped matmul"
UNIT = "%"
MOVES = "train_tok_rate"


def read(run: dict):
    least = _least_seconds(run)
    if least is None:
        return None
    ms = scope_times.ms_per_step(run, "moe/experts")
    if not ms:
        return None
    return 100.0 * max(least) / (ms * 1e-3)


def _least_seconds(run: dict):
    cost, peaks = run["facts"]["kernels"].get("moe_experts"), run.get("peaks")
    if not cost or not peaks:
        return None
    return (cost["flops"] / peaks["bf16_flops_per_s"],
            cost["bytes"] / peaks["hbm_bytes_per_s"])


def bound(run: dict):
    least = _least_seconds(run)
    if least is None:
        return None
    return "compute" if least[0] >= least[1] else "memory"
