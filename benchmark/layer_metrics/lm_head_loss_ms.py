"""Milliseconds per step on the device in the head and the loss
(``lm_head_loss``), forward and backward, the multi-token-prediction
module's second pass included (``mtp/lm_head_loss``; ``mtp_ms`` holds that
pass too): the fused walk of ``ops/xent.py`` (a chunk's logits, softmax
passes, ``dh`` and ``dw``: all three products sit in the scope's FORWARD
half) or the plain head matmul, log-softmax and pick, and whatever XLA fuses
behind them (adamw's pass over the head rides in the last chunk's fusion).
Device self-time by ``jax.named_scope`` from the traced run's xplane, as
one bucket of the step's account (``benchmark/step_account.py``: every scope
path of the step lands in exactly one bucket, first match in its order).  A
trace with no op in the bucket: nothing to read."""

from benchmark import step_account

LAYER = "step, model"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    return step_account.bucket_ms(run, "head")
