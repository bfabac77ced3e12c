"""The ONE latent layer's forward attention (scope ``flash_fwd``) as a share
of its roofline, in a model whose other layers run no attention kernel: the
least time the chip could take for ONE causal call (the configuration's
``mla1_flash_fwd_cost``: the scores at 192 and the values at 128 over the
causal pairs; q, each head's key and value, the ONE shared key once, the
output and the log-sum-exp), times the kernel's executions in a traced step
under the scope (``scope_calls``: a ``remat`` policy runs a forward once or
twice), over the scope's device time.  ``mla_flash_fwd_roofline`` multiplies
one call by every layer of the model, which holds where every layer is
latent.  The shared columns' pad to 128 lanes, diagonal tiles computed whole
and the layout ops show as a loss.  ``bound(run)`` says which of the two
bounds it."""

from benchmark import scope_calls, scope_times

LAYER = "latent attention: projections and kernels"
UNIT = "%"
MOVES = "train_tok_rate"
KERNEL, SCOPE = "mla1_flash_fwd", "flash_fwd"


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
