"""Milliseconds per step on the device in the embedding (``embed``): the
forward gather of the rows and the gradient's scatter-add (on the v5e a sort
of the ids and a scatter, both under ``embed/jit(_take)/scatter-add``: the
expander keeps the scope here).

Device self-time by ``jax.named_scope`` from the traced run's xplane, as
one bucket of the step's account (``benchmark/step_account.py``: every scope
path of the step lands in exactly one bucket, first match in its order).  A
trace with no op in the bucket: nothing to read."""

from benchmark import step_account

LAYER = "step, model"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    return step_account.bucket_ms(run, "embed")
