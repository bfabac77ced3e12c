"""Milliseconds per step on the device in the Kimi Delta Attention mixers
(``kda``), all KDA layers, forward and backward: the four big projections
(``q_proj``, ``k_proj``, ``v_proj``, ``o_proj`` inside it), the three short
convs with their SiLU and L2 norm (``kda/conv``), the decay's and the output
gate's low-rank maps and β's (``kda/gates``), the chunked gated delta rule
(``kda/scan``) and the gated output norm (``kda/gate_norm``).

Device self-time by ``jax.named_scope`` from the traced run's xplane
(``benchmark/scope_times.py``).  A program without the scope: nothing to
read."""

from benchmark import scope_times

LAYER = "KDA mixer: projections, conv, gates, scan, gated norm"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    return scope_times.ms_per_step(run, "kda")
