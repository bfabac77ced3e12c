"""Attention over the kept pairs as a share of its roofline: the least time the
chip could take for the forward and backward of ALL layers in one step (the
larger of FLOPs over peak FLOP/s and bytes over peak bytes/s, from the
configuration's ``dsa_attend_cost``, which counts the KEPT pairs alone)
over ``dsa_attend_ms``.  The formulation's own cost is not counted and
reads as a low share: every tile that holds a kept pair is computed whole
(a scattered selection leaves every causal tile live, 4.3 pairs computed
for one kept at 16k), remat runs the forward twice, and the mask is read a
byte a pair.
``bound(run)`` says which of the two bounds it."""

from benchmark import scope_times

LAYER = "sparse attention: indexer, selection, kernels"
UNIT = "%"
MOVES = "train_tok_rate"


def read(run: dict):
    least = _least_seconds(run)
    if least is None:
        return None
    ms = scope_times.ms_per_step(run, "dsa/attend")
    if not ms:
        return None
    return 100.0 * max(least) / (ms * 1e-3)


def _least_seconds(run: dict):
    cost, peaks = run["facts"]["kernels"].get("dsa_attend"), run.get("peaks")
    if not cost or not peaks:
        return None
    return (cost["flops"] / peaks["bf16_flops_per_s"],
            cost["bytes"] / peaks["hbm_bytes_per_s"])


def bound(run: dict):
    least = _least_seconds(run)
    if least is None:
        return None
    return "compute" if least[0] >= least[1] else "memory"
