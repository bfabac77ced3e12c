"""Milliseconds per step on the device around the expert matmuls: the router
(``moe/router``), the sort, index maps and the gather of tokens into expert
order (``moe/dispatch``) and the weighting and gather back to tokens
(``moe/combine``), forward and backward, all layers.

Device self-time by ``jax.named_scope`` from the traced run's xplane
(``benchmark/scope_times.py``).  A program without the scopes: nothing to
read."""

from benchmark import scope_times

LAYER = "experts: routing and grouped matmul"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    return scope_times.ms_per_step(run, "moe/router", "moe/dispatch",
                                   "moe/combine")
