"""The hyper-connections' share of their roofline: the least time the chip
could take for the FLOPs and bytes their MATHEMATICS needs in one step (the
larger of FLOPs over peak FLOP/s and bytes over peak bytes/s, from the
configuration's ``hc_cost``: a sub-layer's forward reads the ``n`` streams
once, writes them once and moves the ``C``-wide input and output of ``F``;
the backward the same for the cotangents plus one read of the streams) over
``hc_mix_ms`` + ``hc_maps_ms``.  It counts the WORK, so it reads the same
whether XLA fusions or a kernel do it; ``remat``'s second forward, float32
copies and further reads of the streams are time, not work.  ``bound(run)``
says which of the two bounds it."""

from benchmark import scope_times

LAYER = "residual streams: hyper-connection maps and mixing"
UNIT = "%"
MOVES = "train_tok_rate"


def read(run: dict):
    least = _least_seconds(run)
    if least is None:
        return None
    ms = scope_times.ms_per_step(run, "hc/pre", "hc/post", "hc/maps")
    if not ms:
        return None
    return 100.0 * max(least) / (ms * 1e-3)


def _least_seconds(run: dict):
    cost, peaks = run["facts"]["kernels"].get("hc_mix"), run.get("peaks")
    if not cost or not peaks:
        return None
    return (cost["flops"] / peaks["bf16_flops_per_s"],
            cost["bytes"] / peaks["hbm_bytes_per_s"])


def bound(run: dict):
    least = _least_seconds(run)
    if least is None:
        return None
    return "compute" if least[0] >= least[1] else "memory"
