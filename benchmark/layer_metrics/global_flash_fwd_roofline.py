"""The GLOBAL layers' forward attention (the full causal mask, scope
``flash_fwd``: in a cell whose other layers run under a window, those keep
``flash_fwd_window``) as a share of its roofline: the least time the chip
could take for ONE causal call (the configuration's ``flash_fwd_cost``),
times the kernel's executions in a traced step under the scope
(``scope_calls``), over the scope's device time.  ``flash_fwd_roofline`` and
``bd_flash_fwd_roofline`` count every layer of the model, which holds where
every layer carries the scope; here the trace says how many do.  Tiles on the
diagonal computed whole and the layout ops show as a loss.  ``bound(run)``
says which of the two bounds it."""

from benchmark import scope_calls, scope_times

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tok_rate"
KERNEL, SCOPE = "flash_fwd", "flash_fwd"


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
