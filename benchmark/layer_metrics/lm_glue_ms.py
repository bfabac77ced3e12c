"""Milliseconds per step on the device in the norms and the glue between
modules: ``attn_norm``, ``mlp_norm``, ``norm``, ``final_norm``, the
multi-token-prediction module's own ops outside its layer and head
(``mtp_hnorm``, ``mtp_enorm``, ``mtp_norm``, ``mtp_eh_proj``, the roll and
the concatenation under ``mtp``), the residual adds and the carry's
constraints (``residual``) and the loss's auxiliary terms (``loss_terms``).
Device self-time by ``jax.named_scope`` from the traced run's xplane, as
one bucket of the step's account (``benchmark/step_account.py``: every scope
path of the step lands in exactly one bucket, first match in its order).  A
trace with no op in the bucket: nothing to read."""

from benchmark import step_account

LAYER = "step, model"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    return step_account.bucket_ms(run, "norms and glue")
