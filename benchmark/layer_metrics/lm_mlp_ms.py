"""Milliseconds per step on the device in the dense SwiGLU (``mlp``), all
dense layers, forward, backward and ``remat``'s second forward: the three
products and the gate's elementwise passes.  A shared expert is a SwiGLU
under ``moe/shared`` and is ``moe_shared_ms``'s (the experts' bucket comes
first in the account's order).
Device self-time by ``jax.named_scope`` from the traced run's xplane, as
one bucket of the step's account (``benchmark/step_account.py``: every scope
path of the step lands in exactly one bucket, first match in its order).  A
trace with no op in the bucket: nothing to read."""

from benchmark import step_account

LAYER = "step, model"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    return step_account.bucket_ms(run, "dense MLP")
