"""The GLOBAL layers' backward attention (scope ``flash_bwd``; the window
layers keep ``flash_bwd_window``) as a share of its roofline: the least time
the chip could take for the causal backward of the layers WITHOUT a window in
one step (the configuration's ``global_flash_bwd_cost``: 2.5 times one
forward's FLOPs, five matmuls to its two, and twice its bytes, a global layer)
over ``flash_bwd_ms``.  ``flash_bwd_roofline`` multiplies by every layer of
the model; the configuration counts the layers that carry the scope.  Tiles
on the diagonal computed whole, ``delta`` and the layout ops show as a loss.
``bound(run)`` says which of the two bounds it."""

from benchmark import scope_times

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tok_rate"
KERNEL, SCOPE = "global_flash_bwd", "flash_bwd"


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
