"""Milliseconds per step on the device in the router alone
(``moe/router``), all expert layers, forward and backward: the float32
router matmul, the scores, the top-k (under a selection bias also the
count of experts that score higher than each chosen one), the weights'
renormalisation and the load statistics.  ``moe_dispatch_ms`` holds it too,
with the sort and the gathers.

Device self-time by ``jax.named_scope`` from the traced run's xplane
(``benchmark/scope_times.py``).  A program without the scope: nothing to
read."""

from benchmark import scope_times

LAYER = "experts: routing and grouped matmul"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    return scope_times.ms_per_step(run, "moe/router")
