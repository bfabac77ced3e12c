"""Milliseconds per step on the device in the exact selection (``dsa/select``): the counting passes that find
each query's threshold among its causal scores, the mask's bytes, the tile
map and the kept-pair counts, remat's second forward included, all layers.

Device self-time by ``jax.named_scope`` from the traced run's xplane
(``benchmark/scope_times.py``).  A program without the scope: nothing to
read."""

from benchmark import scope_times

LAYER = "sparse attention: indexer, selection, kernels"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    return scope_times.ms_per_step(run, "dsa/select")
