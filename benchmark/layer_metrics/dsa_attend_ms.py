"""Milliseconds per step on the device in attention over the kept pairs (``dsa/attend``): RoPE of q
and k, the visit tables, the forward kernel (twice under remat), the dk/dv
and dq passes, and the layout ops around them, all layers.

Device self-time by ``jax.named_scope`` from the traced run's xplane
(``benchmark/scope_times.py``).  A program without the scope: nothing to
read."""

from benchmark import scope_times

LAYER = "sparse attention: indexer, selection, kernels"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    return scope_times.ms_per_step(run, "dsa/attend")
