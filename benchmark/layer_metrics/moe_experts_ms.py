"""Milliseconds per step on the device in the three expert matmuls and the
SwiGLU between them (``moe/experts``), forward and backward, all layers:
the grouped matmul, kernel or not.

Device self-time by ``jax.named_scope`` from the traced run's xplane
(``benchmark/scope_times.py``).  A program without the scope: nothing to
read."""

from benchmark import scope_times

LAYER = "experts: routing and grouped matmul"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    return scope_times.ms_per_step(run, "moe/experts")
