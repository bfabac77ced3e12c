"""The ONE latent layer's backward attention (scope ``flash_bwd``) as a share
of its roofline: the least time the chip could take for the causal backward
of the layers that ARE latent in one step (the configuration's
``mla1_flash_bwd_cost``: the scores once, dq and dk at 192, dp and dv at 128;
it counts the latent layers itself) over ``flash_bwd_ms``.
``mla_flash_bwd_roofline`` multiplies one layer by every layer of the model.
The second recompute of the scores where the kernel runs in two passes,
``delta`` and the layout ops show as a loss.  ``bound(run)`` says which of
the two bounds it."""

from benchmark import scope_times

LAYER = "latent attention: projections and kernels"
UNIT = "%"
MOVES = "train_tok_rate"
KERNEL, SCOPE = "mla1_flash_bwd", "flash_bwd"


def read(run: dict):
    least = _least_seconds(run)
    if least is None:
        return None
    ms = scope_times.ms_per_step(run, SCOPE)
    if not ms:
        return None
    return 100.0 * max(least) / (ms * 1e-3)


def _least_seconds(run: dict):
    cost, peaks = run["facts"]["kernels"].get(KERNEL), run.get("peaks")
    if not cost or not peaks:
        return None
    return (cost["flops"] / peaks["bf16_flops_per_s"],
            cost["bytes"] / peaks["hbm_bytes_per_s"])


def bound(run: dict):
    least = _least_seconds(run)
    if least is None:
        return None
    return "compute" if least[0] >= least[1] else "memory"
