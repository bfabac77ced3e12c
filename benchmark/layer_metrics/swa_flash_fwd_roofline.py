"""The window layers' forward attention as a share of its roofline: the least
time the chip could take for ONE call of the band's forward (the larger of
FLOPs over peak FLOP/s and bytes over peak bytes/s, from the configuration's
``swa_flash_fwd_cost``, which counts the VISIBLE pairs of the band,
``W(W + 1)/2 + (L - W)W`` a row, and the K/V bytes at the K/V head count),
times the kernel's executions in a traced step under the scope
``flash_fwd_window`` (``scope_calls``: twice a layer while ``remat`` runs the
forward again, once under a policy that keeps its output), over
``swa_flash_fwd_ms``.  Tiles computed whole on both masked edges of a query
block's run, the float32 softmax and the layout ops are the formulation's own
and show as a loss: no reading can pass 100%.  ``bound(run)`` says which of
the two bounds it."""

from benchmark import scope_calls, scope_times

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tok_rate"
KERNEL, SCOPE = "swa_flash_fwd", "flash_fwd_window"


def read(run: dict):
    least = _least_seconds(run)
    if least is None:
        return None
    ms = scope_times.ms_per_step(run, SCOPE)
    calls = scope_calls.kernels_per_step(run, SCOPE)
    if not ms or not calls:
        return None
    return 100.0 * calls * max(least) / (ms * 1e-3)


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
