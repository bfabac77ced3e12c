"""Latent attention's backward as a share of its roofline: the least time
the chip could take for the backward of ALL layers in one step (the larger of
FLOPs over peak FLOP/s and bytes over peak bytes/s, from the configuration's
``mla_flash_bwd_cost`` of one call: the scores once and dq and dk at 192, dp
and dv at 128; the forward's operands, the output and its cotangent in, four
gradients out, the rotary key's once; times ``num_hidden_layers``) over
``flash_bwd_ms``.  The second recompute of the scores, the rotary
columns' pad to 128 lanes, diagonal tiles computed whole and the layout ops
are the formulation's own and are not counted.  ``bound(run)`` says which of
the two bounds it."""

from benchmark import scope_times

LAYER = "latent attention: projections and kernels"
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
    cost = run["facts"]["kernels"].get("mla_flash_bwd")
    peaks = run.get("peaks")
    if not cost or not peaks:
        return None
    layers = run["cell"]["config"]["num_hidden_layers"]
    return (layers * cost["flops"] / peaks["bf16_flops_per_s"],
            layers * cost["bytes"] / peaks["hbm_bytes_per_s"])


def bound(run: dict):
    least = _least_seconds(run)
    if least is None:
        return None
    return "compute" if least[0] >= least[1] else "memory"
