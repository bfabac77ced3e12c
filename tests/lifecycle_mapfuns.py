"""Map function and a config rider of ``test_telemetry_lifecycle.py``.

The driver of that test must never import jax, so nothing here does at import
time.  ``Preload("jax")`` rides in the job's arguments: pickled, it is a call
of ``importlib.import_module``, so the node imports jax while it unpickles its
``NodeConfig``: what a map_fun whose module imports jax at its top does to a
node.  The environment's CPU pin of the device summary (``tpu_info.
env_device_summary``) then does not apply and the node takes the path a chip
run takes: ``tpu_info.device_summary`` with its two stages, ``node.import_jax``
(tagged ``preloaded``) and ``node.claim``.
"""

from __future__ import annotations

import importlib


class Preload:
    """Unpickles to the named module, imported where it is unpickled."""

    def __init__(self, module: str):
        self.module = module

    def __reduce__(self):
        return (importlib.import_module, (self.module,))


def jit_once(args, ctx):
    """One jitted program on the node's mesh, so that the XLA listener has
    something to count whichever of the two places installed it."""
    import jax
    import jax.numpy as jnp

    mesh = ctx.make_mesh(dp=-1)
    out = jax.jit(lambda x: jnp.tanh(x) @ x)(jnp.ones((8, 8)))
    ctx.update_meta({"checksum": float(out.sum()), "mesh": mesh.size})
