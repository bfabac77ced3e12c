"""Persistent-XLA-cache bootstrap: the ONE place a compile-cache path is set.

The tests, ``chip_smoke.py``, ``benchmark/run.py`` and ``__graft_entry__``
compile the same XLA programs run after run; the persistent cache turns
those compiles into loads.  Where the cache lives is decided outside the program:

- ``JAX_COMPILATION_CACHE_DIR`` set by the caller is used verbatim;
- otherwise the cache is ``<checkout>/.jax_cache`` — a fixed path, because
  the path is part of the cache key and a directory that moves never hits.

The choice is exported through ``os.environ`` so the node processes that
``tos.run`` spawns inherit it (jax reads both variables at import).  jax is
NOT imported here: the drivers of ``chip_smoke.py`` and ``benchmark/run.py``
must stay off the backend (one process owns the chip).  If the caller has already
imported jax, its config snapshot is re-asserted to match the env.

Kept as a repo-root stdlib-only module so entry points can call it before
anything else is imported.
"""

from __future__ import annotations

import os
import sys


def enable_persistent_cache() -> str:
    """Export the cache settings; returns the cache directory in effect."""
    here = os.path.dirname(os.path.abspath(__file__))
    cache_dir = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                      os.path.join(here, ".jax_cache"))
    min_secs = os.environ.setdefault(
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")
    if "jax" in sys.modules:
        import jax

        if jax.config.jax_compilation_cache_dir != cache_dir:
            jax.config.update("jax_compilation_cache_dir", cache_dir)
        if jax.config.jax_persistent_cache_min_compile_time_secs != float(min_secs):
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              float(min_secs))
    return cache_dir
