"""Milliseconds per step on the device in the forward of the WINDOW layers'
attention (scope ``flash_fwd_window``): the band's kernel and the layout ops
around it, every execution (under ``remat`` the forward runs again in the
backward pass, under the same scope).  The global layers' forward keeps the
scope ``flash_fwd`` and is read by ``bd_flash_fwd_ms``.

Device self-time by ``jax.named_scope`` from the traced run's xplane
(``benchmark/scope_times.py``).  A program without the scope: nothing to
read."""

from benchmark import scope_times

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    return scope_times.ms_per_step(run, "flash_fwd_window")
