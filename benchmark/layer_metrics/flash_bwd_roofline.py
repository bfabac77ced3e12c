"""Attention's backward as a share of its roofline: the least time the chip
could take for the causal backward of ALL layers in one step over
``flash_bwd_ms``.  The backward NEEDS 2.5 times the forward's FLOPs (five
matmuls of the forward's two: dv, dp, dq, dk and the scores once) and twice
its bytes (it reads q, k, v, o and the cotangent and writes three
gradients), from the configuration's ``flash_fwd_cost`` of one call, times
``num_hidden_layers``.  A second recompute of the scores, the pad to 128
lanes, tiles on the diagonal computed whole and layout copies are the
formulation's own and are not counted.  ``bound(run)`` says which of the
two bounds it."""

from benchmark import scope_times

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tok_rate"


def read(run: dict):
    least = _least_seconds(run)
    if least is None:
        return None
    ms = scope_times.ms_per_step(run, "flash_bwd")
    if not ms:
        return None
    return 100.0 * max(least) / (ms * 1e-3)


def _least_seconds(run: dict):
    cost, peaks = run["facts"]["kernels"].get("flash_fwd"), run.get("peaks")
    if not cost or not peaks:
        return None
    layers = run["cell"]["config"]["num_hidden_layers"]
    return (layers * 2.5 * cost["flops"] / peaks["bf16_flops_per_s"],
            layers * 2.0 * cost["bytes"] / peaks["hbm_bytes_per_s"])


def bound(run: dict):
    least = _least_seconds(run)
    if least is None:
        return None
    return "compute" if least[0] >= least[1] else "memory"
