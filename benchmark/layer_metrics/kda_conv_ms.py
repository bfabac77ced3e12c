"""Milliseconds per step on the device in the KDA layers' short convs
(``kda/conv``), forward and backward: three depthwise causal convs of 4 taps
over the 4096-wide projections of q, k and v, their SiLU, and the L2 norm of
q and k over each head's channels.

Device self-time by ``jax.named_scope`` from the traced run's xplane
(``benchmark/scope_times.py``).  A program without the scope: nothing to
read."""

from benchmark import scope_times

LAYER = "KDA mixer: projections, conv, gates, scan, gated norm"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    return scope_times.ms_per_step(run, "kda/conv")
