"""Attention's forward as a share of its roofline: the least time the chip
could take for the forward of ALL layers in one step (the larger of FLOPs
over peak FLOP/s and bytes over peak bytes/s, from the configuration's
``flash_fwd_cost`` of one call, which counts the VISIBLE pairs of the mask
and the K/V bytes at the K/V head count, times ``num_hidden_layers``) over
``bd_flash_fwd_ms``.  Masked pairs of tiles the mask cuts, the float32
operands and the layout ops are the formulation's own and are not counted.
``bound(run)`` says which of the two bounds it."""

from benchmark import scope_times

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tok_rate"


def read(run: dict):
    least = _least_seconds(run)
    if least is None:
        return None
    ms = scope_times.ms_per_step(run, "flash_fwd")
    if not ms:
        return None
    return 100.0 * max(least) / (ms * 1e-3)


def _least_seconds(run: dict):
    cost, peaks = run["facts"]["kernels"].get("flash_fwd"), run.get("peaks")
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
