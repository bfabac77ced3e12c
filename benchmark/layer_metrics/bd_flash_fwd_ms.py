"""Milliseconds per step on the device in attention's forward (``flash_fwd``),
all layers: the kernel and the layout ops around it (head-major, the pad of
the sequence), as ``flash_bwd_ms`` reads the backward.

Device self-time by ``jax.named_scope`` from the traced run's xplane
(``benchmark/scope_times.py``), not every ``tpu_custom_call`` of the step as
``flash_fwd_ms`` sums them (PERF.md section 3): a step of this cell holds the
backward's kernels and the grouped matmuls' too.  A program without the
scope: nothing to read."""

from benchmark import scope_times

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    return scope_times.ms_per_step(run, "flash_fwd")
