"""The window layers' backward attention as a share of its roofline: the
least time the chip could take for the band's backward of all window layers
in one step (from the configuration's ``swa_flash_bwd_cost``: 2.5 times one
forward's FLOPs over the VISIBLE pairs of the band, five matmuls to its two,
and twice its bytes) over ``swa_flash_bwd_ms``.  Tiles computed whole on both
masked edges, ``delta`` and the layout ops are the formulation's own and
show as a loss: no reading can pass 100%.  ``bound(run)`` says which of the
two bounds it."""

from benchmark import scope_times

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tok_rate"
KERNEL, SCOPE = "swa_flash_bwd", "flash_bwd_window"


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
