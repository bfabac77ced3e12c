"""The indexer's forward as a share of its roofline: the least time the chip
could take for the index scores of the causal pairs and the indexer's
projections of ALL layers in one step (the larger of FLOPs over peak
FLOP/s and bytes over peak bytes/s, from the configuration's
``dsa_index_cost``) over ``dsa_index_ms``.  Remat's second forward is not
counted and halves the share; a contraction over 64 index dims fills half
of the MXU's depth.
``bound(run)`` says which of the two bounds it."""

from benchmark import scope_times

LAYER = "sparse attention: indexer, selection, kernels"
UNIT = "%"
MOVES = "train_tok_rate"


def read(run: dict):
    least = _least_seconds(run)
    if least is None:
        return None
    ms = scope_times.ms_per_step(run, "dsa/index")
    if not ms:
        return None
    return 100.0 * max(least) / (ms * 1e-3)


def _least_seconds(run: dict):
    cost, peaks = run["facts"]["kernels"].get("dsa_index"), run.get("peaks")
    if not cost or not peaks:
        return None
    return (cost["flops"] / peaks["bf16_flops_per_s"],
            cost["bytes"] / peaks["hbm_bytes_per_s"])


def bound(run: dict):
    least = _least_seconds(run)
    if least is None:
        return None
    return "compute" if least[0] >= least[1] else "memory"
