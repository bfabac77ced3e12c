"""Milliseconds per step on the device in LatentMoE's two latent maps
(``moe/latent``), all expert layers, forward and backward: ``d_model`` ->
``latent`` on every token before the dispatch and ``latent`` -> ``d_model``
after the combine (``parallel/ep.MoEMLP.latent``).

Device self-time by ``jax.named_scope`` from the traced run's xplane
(``benchmark/scope_times.py``).  A program without the scope: nothing to
read."""

from benchmark import scope_times

LAYER = "experts: routing and grouped matmul"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    return scope_times.ms_per_step(run, "moe/latent")
