"""Milliseconds per step on the device in attention's projections
(``q_proj``, ``k_proj``, ``v_proj``, ``o_proj`` and ``qk_norm``), all layers,
forward, backward and ``remat``'s second forward, with what XLA fuses into
them (RoPE's cotangent rides in ``q_proj``'s backward).  Latent attention's
projections are ``mla_project_ms``'s (the scope ``mla/project`` comes first
in the account's order) and the sparse indexer's ``dsa_index_ms``'s.
Device self-time by ``jax.named_scope`` from the traced run's xplane, as
one bucket of the step's account (``benchmark/step_account.py``: every scope
path of the step lands in exactly one bucket, first match in its order).  A
trace with no op in the bucket: nothing to read."""

from benchmark import step_account

LAYER = "step, model"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    return step_account.bucket_ms(run, "attention projections")
