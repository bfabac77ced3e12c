"""Milliseconds per step on the device in the state-space scan
(``ssm/scan``), all Mamba-2 layers, forward and backward: ``Δ``'s softplus,
the decay and its running sums, the four products a chunk, the recurrence
over chunk states, the layout around them and the backward's recompute,
kernel or not (``ops/ssd.py``).

Device self-time by ``jax.named_scope`` from the traced run's xplane
(``benchmark/scope_times.py``).  A program without the scope: nothing to
read."""

from benchmark import scope_times

LAYER = "state-space mixer: projections, conv and scan"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    return scope_times.ms_per_step(run, "ssm/scan")
