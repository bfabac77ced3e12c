"""Milliseconds per step on the device in attention's backward
(``flash_bwd``), all layers: the kernels, ``delta`` and every layout op
between the cotangent and the three gradients, kernel or not.

Device self-time by ``jax.named_scope`` from the traced run's xplane
(``benchmark/scope_times.py``).  A program without the scope: nothing to
read."""

from benchmark import scope_times

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    return scope_times.ms_per_step(run, "flash_bwd")
