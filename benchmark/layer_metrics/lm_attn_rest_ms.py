"""Milliseconds per step on the device in what attention does around its
kernels and projections (``attention``, then anything else under the module
``attn``): RoPE, the head-major layouts, sharding constraints, and under
``remat`` the ``reduce_precision`` of the arrays a policy keeps.  The flash
kernels' scopes, the sparse trio's, ``mla/project`` and the projections come
first in the account's order and are not in it.  Where attention neither
rotates nor keeps anything (Nemotron-3's) or its RoPE sits in ``mla/project``
(Kanana-2's) XLA fuses what is left into its neighbours: nothing to read.
Device self-time by ``jax.named_scope`` from the traced run's xplane, as
one bucket of the step's account (``benchmark/step_account.py``: every scope
path of the step lands in exactly one bucket, first match in its order).  A
trace with no op in the bucket: nothing to read."""

from benchmark import step_account

LAYER = "step, model"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    return step_account.bucket_ms(run, "attention, the rest")
