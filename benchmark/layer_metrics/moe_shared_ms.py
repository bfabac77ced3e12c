"""Milliseconds per step on the device in the shared experts
(``moe/shared``), all expert layers, forward and backward: ONE SwiGLU of the
shared experts' summed width that every token passes beside its routed
experts, and that every chip of an expert-parallel stage computes whole.

Device self-time by ``jax.named_scope`` from the traced run's xplane
(``benchmark/scope_times.py``).  A program without the scope: nothing to
read."""

from benchmark import scope_times

LAYER = "experts: routing and grouped matmul"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    return scope_times.ms_per_step(run, "moe/shared")
