"""The gated delta rule's share of its roofline: the least time the chip
could take for the FLOPs and bytes the rule's MATHEMATICS needs in one step
(the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s, from the
configuration's ``kda_scan_cost``: a chunk's two triangles of scores, the
unit lower triangular solve for ``W`` and ``U``, the three products with the
state and the scores' product with ``Ũ``, at the stated chunk; q, k, v, g, β
and o and their cotangents through HBM once; the sub-blocks that keep the
exponents <= 0, the explicit inverse, layouts, chunk states and the
backward's recompute are the formulation's own and are not counted) over
``kda_scan_ms``.  It counts the same work whatever implements the op.
``bound(run)`` says which of the two bounds it."""

from benchmark import scope_times

LAYER = "KDA mixer: projections, conv, gates, scan, gated norm"
UNIT = "%"
MOVES = "train_tok_rate"


def read(run: dict):
    least = _least_seconds(run)
    if least is None:
        return None
    ms = scope_times.ms_per_step(run, "kda/scan")
    if not ms:
        return None
    return 100.0 * max(least) / (ms * 1e-3)


def _least_seconds(run: dict):
    cost, peaks = run["facts"]["kernels"].get("kda_scan"), run.get("peaks")
    if not cost or not peaks:
        return None
    return (cost["flops"] / peaks["bf16_flops_per_s"],
            cost["bytes"] / peaks["hbm_bytes_per_s"])


def bound(run: dict):
    least = _least_seconds(run)
    if least is None:
        return None
    return "compute" if least[0] >= least[1] else "memory"
