"""Milliseconds per step on the device in the indexer's loss (``dsa/index_loss``): the walk that
rebuilds the heads' mean attention probabilities tile by tile, takes the KL
to the index scores' softmax and, in remat's second forward, the indexer's
gradient through the scores, all layers.

Device self-time by ``jax.named_scope`` from the traced run's xplane
(``benchmark/scope_times.py``).  A program without the scope: nothing to
read."""

from benchmark import scope_times

LAYER = "sparse attention: indexer, selection, kernels"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    return scope_times.ms_per_step(run, "dsa/index_loss")
