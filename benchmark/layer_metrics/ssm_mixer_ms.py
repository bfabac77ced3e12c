"""Milliseconds per step on the device in the Mamba-2 mixers (``ssm``), all
state-space layers, forward and backward: the projection to ``[z | xBC |
dt]``, the depthwise causal conv and its SiLU, the chunked scan, the gated
group norm and the output projection (``ssm/in_proj``, ``ssm/conv``,
``ssm/scan``, ``ssm/gate_norm``, ``ssm/out_proj`` inside it).

Device self-time by ``jax.named_scope`` from the traced run's xplane
(``benchmark/scope_times.py``).  A program without the scope: nothing to
read."""

from benchmark import scope_times

LAYER = "state-space mixer: projections, conv and scan"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    return scope_times.ms_per_step(run, "ssm")
