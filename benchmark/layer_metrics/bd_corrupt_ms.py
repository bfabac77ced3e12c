"""Milliseconds per step on the device in block diffusion's forward process
(``diffusion/corrupt``): the draw of the noise levels and of the masked
tokens from the rows' noise words, the masking, and the concatenation of the
noised and the clean copy.

Device self-time by ``jax.named_scope`` from the traced run's xplane
(``benchmark/scope_times.py``).  A program without the scope: nothing to
read."""

from benchmark import scope_times

LAYER = "step, model"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    return scope_times.ms_per_step(run, "diffusion/corrupt")
