"""The three flash kernels at SDAR's widths, compiled for a DESCRIBED v5e
(nothing runs, no chip needed): 32 x 128 query heads over 4 K/V heads, 8,192
positions under the block-diffusion mask of block 4.  What interpret mode
cannot show: that Mosaic takes the walk's scalar-prefetch tables (80 visits
a head; 640 in the dk/dv pass, which walks a group's 8 query heads) and
index maps that read them.  The topology is described inside a fixture,
never at import (only one process may load the TPU library; see the
on-chip-measurement guide)."""

from __future__ import annotations

import os

import pytest


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: not here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("mask", [
    pytest.param(dict(causal=False, block_diffusion=(4096, 4)),
                 id="block-diffusion"),
    pytest.param(dict(causal=True), id="causal"),
])
def test_all_three_flash_kernels_lower_at_sdar_widths(one_chip, mask):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from tensorflowonspark_tpu.ops.attention import flash_attention

    q = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, 8192, 4, 128), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, impl="pallas", **mask)
        return jnp.sum(out.astype(jnp.float32))

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without a chip: keep it out of the cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        hlo = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
            q, k, k).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    # forward, dk/dv pass, dq pass
    assert hlo.count('custom_call_target="tpu_custom_call"') == 3
