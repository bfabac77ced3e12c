"""Milliseconds per step on the device in the backward of the WINDOW layers'
attention (scope ``flash_bwd_window``): the band's kernel(s), ``delta`` and
every layout op between the cotangent and the three gradients.  The global
layers' backward keeps the scope ``flash_bwd`` and is read by
``flash_bwd_ms``.

Device self-time by ``jax.named_scope`` from the traced run's xplane
(``benchmark/scope_times.py``).  A program without the scope: nothing to
read."""

from benchmark import scope_times

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    return scope_times.ms_per_step(run, "flash_bwd_window")
