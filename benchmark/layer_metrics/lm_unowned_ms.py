"""Milliseconds per step on the device under a scope path that NO bucket
of the step's account owns: the program (or flax, or jax) named a component
the account has not heard of.  ``python3 -m benchmark.step_account <run
directory>`` lists the ops; the cure is a bucket's component, not a reader.
A step with nothing unowned reads 0, not a gap.

Device self-time by ``jax.named_scope`` from the traced run's xplane, as
one bucket of the step's account (``benchmark/step_account.py``: every scope
path of the step lands in exactly one bucket, first match in its order)."""

from benchmark import step_account

LAYER = "step, model"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    return step_account.bucket_ms(run, "unowned")
