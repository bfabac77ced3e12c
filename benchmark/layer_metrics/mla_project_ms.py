"""Milliseconds per step on the device in latent attention's projections
(``mla/project``), all layers, forward and backward: the query projection
(32 heads of 128 + 64), the down-projection to the latent of 512 and the one
rotary key, the latent's RMSNorm, the up-projection to 32 heads of 128 + 128,
RoPE on the rotary columns, and the output projection.

Device self-time by ``jax.named_scope`` from the traced run's xplane
(``benchmark/scope_times.py``).  A program without the scope: nothing to
read."""

from benchmark import scope_times

LAYER = "latent attention: projections and kernels"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    return scope_times.ms_per_step(run, "mla/project")
