"""Milliseconds per step on the device in the multi-token-prediction module
(``mtp``), forward and backward: the two norms and ``W_eh``, the module's ONE
layer, its final norm, and its pass of the shared head and the loss.

The module's layer is a layer like the trunk's, so its time is ALSO inside
the readings of the scopes it holds (``moe_*``, ``mla_*``, ``flash_*``,
``hc_*``): this reading overlaps those, it does not add to them.

Device self-time by ``jax.named_scope`` from the traced run's xplane
(``benchmark/scope_times.py``).  A program without the scope: nothing to
read."""

from benchmark import scope_times

LAYER = "step, model"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    return scope_times.ms_per_step(run, "mtp")
