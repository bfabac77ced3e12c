"""Milliseconds per step on the device in the optimizer's update
(``optimizer_update``): the WHOLE update, adamw over every parameter the chip
holds (``parallel/dp.py``), embedding and head included — not the experts'
part alone.  Of OLMoE's 625.6 M parameters at one layer the experts are
402.7 M, 64% of the bytes the update moves, and embedding and head 206.0 M,
33%: work on the expert layer can move about two thirds of this number.  It
is filed under the experts' layer because all 64 experts' weights swept for
one layer's work, where the state is not sharded, is what makes it large.

Device self-time by ``jax.named_scope`` from the traced run's xplane
(``benchmark/scope_times.py``).  A program without the scope: nothing to
read."""

from benchmark import scope_times

LAYER = "experts: routing and grouped matmul"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    return scope_times.ms_per_step(run, "optimizer_update")
