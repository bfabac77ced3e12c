"""Milliseconds per step on the device in the hyper-connections' mixing
(``hc/pre`` + ``hc/post``), all twelve (two a layer, the MTP module's layer
among the six), forward and backward and ``remat``'s second forward: ``H_pre
x``, the ONE mixed stream a sub-layer reads, and ``H_res x + H_postᵀ y``,
what it writes back to all ``n`` streams.

Device self-time by ``jax.named_scope`` from the traced run's xplane
(``benchmark/scope_times.py``).  A program without the scopes: nothing to
read."""

from benchmark import scope_times

LAYER = "residual streams: hyper-connection maps and mixing"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    return scope_times.ms_per_step(run, "hc/pre", "hc/post")
