"""The state-space scan's share of its roofline: the least time the chip
could take for the FLOPs and bytes the scan's MATHEMATICS needs in one step
(the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s, from the
configuration's ``ssd_scan_cost``: the four products a chunk of
``chunk_size`` and each of x, B, C, Δ, z, y and their cotangents through HBM
once; decay matrices, chunk states, transposes and the backward's recompute
are the formulation's own and are not counted) over ``ssm_scan_ms``.
``bound(run)`` says which of the two bounds it."""

from benchmark import scope_times

LAYER = "state-space mixer: projections, conv and scan"
UNIT = "%"
MOVES = "train_tok_rate"


def read(run: dict):
    least = _least_seconds(run)
    if least is None:
        return None
    ms = scope_times.ms_per_step(run, "ssm/scan")
    if not ms:
        return None
    return 100.0 * max(least) / (ms * 1e-3)


def _least_seconds(run: dict):
    cost, peaks = run["facts"]["kernels"].get("ssd_scan"), run.get("peaks")
    if not cost or not peaks:
        return None
    return (cost["flops"] / peaks["bf16_flops_per_s"],
            cost["bytes"] / peaks["hbm_bytes_per_s"])


def bound(run: dict):
    least = _least_seconds(run)
    if least is None:
        return None
    return "compute" if least[0] >= least[1] else "memory"
