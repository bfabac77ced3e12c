"""Milliseconds per step on the device in ``jax.checkpoint``'s second
forward: every op whose scope path holds the component
``rematted_computation``, whatever bucket of the step's account owns it.

It OVERLAPS the buckets (and every scope reader) as ``mtp_ms`` overlaps its
layer's: the recomputed projections are in ``lm_attn_proj_ms`` too.  It does
not add to them.

Device self-time from the traced run's xplane (``benchmark/step_account.py``).
A program that rematerialises nothing: nothing to read."""

from benchmark import step_account

LAYER = "step, model"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    return step_account.remat_ms(run)
