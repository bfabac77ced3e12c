"""Milliseconds per step on the device in the gated delta rule
(``kda/scan``), all KDA layers, forward and backward: the decay's running
sums, the chunk's scores level by level, the inverse of the unit lower
triangular matrix, ``W`` and ``U``, the recurrence over chunk states, the
layout around them and the backward's recompute, kernel or not
(``ops/kda.py``).

Device self-time by ``jax.named_scope`` from the traced run's xplane
(``benchmark/scope_times.py``).  A program without the scope: nothing to
read."""

from benchmark import scope_times

LAYER = "KDA mixer: projections, conv, gates, scan, gated norm"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    return scope_times.ms_per_step(run, "kda/scan")
