"""Latent attention's forward as a share of its roofline: the least time the
chip could take for the forward of ALL layers in one step (the larger of
FLOPs over peak FLOP/s and bytes over peak bytes/s, from the configuration's
``mla_flash_fwd_cost`` of one call: the scores at 192 and the values at 128
over the causal pairs; q, each head's key and value, the ONE rotary key once,
the output and the log-sum-exp; times ``num_hidden_layers``) over
``bd_flash_fwd_ms``.  The rotary columns' pad to 128 lanes, diagonal tiles
computed whole and the layout ops are the formulation's own and are not
counted.  ``bound(run)`` says which of the two bounds it."""

from benchmark import scope_times

LAYER = "latent attention: projections and kernels"
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
    cost = run["facts"]["kernels"].get("mla_flash_fwd")
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
