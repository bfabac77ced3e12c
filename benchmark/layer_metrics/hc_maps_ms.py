"""Milliseconds per step on the device in the hyper-connections' maps
(``hc/maps``), all twelve, forward and backward and ``remat``'s second
forward: the norm of a token's ``n·C`` values, the product with ``phi``,
sigmoid, exp and the 20 Sinkhorn rounds over ``[n, n]`` a token.

Device self-time by ``jax.named_scope`` from the traced run's xplane
(``benchmark/scope_times.py``).  A program without the scope: nothing to
read."""

from benchmark import scope_times

LAYER = "residual streams: hyper-connection maps and mixing"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    return scope_times.ms_per_step(run, "hc/maps")
