"""The three flash kernels at SDAR's widths, compiled for a DESCRIBED v5e
(nothing runs, no chip needed): 32 x 128 query heads over 4 K/V heads, 8,192
positions under the block-diffusion mask of block 4.  What interpret mode
cannot show: that Mosaic takes the walk's scalar-prefetch tables (80 visits
a K/V head in all three kernels), index maps that read them, and a visit's
blocks of a group's 8 query heads (16 MiB in the dq pass, under the raised
limit).  The topology is described inside a fixture, never at import (only
one process may load the TPU library; see the on-chip-measurement guide)."""

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


@pytest.mark.parametrize("case", [
    # positions, query heads, K/V heads, head dim, mask
    pytest.param((8192, 32, 4, 128,
                  dict(causal=False, block_diffusion=(4096, 4))),
                 id="block-diffusion"),
    pytest.param((8192, 32, 4, 128, dict(causal=True)), id="causal"),
    # the dense LM's: one query head a K/V head, 96 wide padded to 128 lanes
    pytest.param((2048, 32, 32, 96, dict(causal=True)),
                 id="group-1-head-dim-96"),
    # a group too large for one visit: four visits of 8 heads a tile
    pytest.param((2048, 32, 1, 128, dict(causal=True)), id="32-over-1"),
])
def test_all_three_flash_kernels_lower_at_sdar_widths(one_chip, case):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from tensorflowonspark_tpu.ops.attention import flash_attention

    length, heads, kv_heads, d, mask = case
    q = jax.ShapeDtypeStruct((1, length, heads, d), jnp.bfloat16,
                             sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, length, kv_heads, d), jnp.bfloat16,
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


def test_the_latent_kernels_lower_at_kanana_widths(one_chip):
    """Kanana-2's latent attention on one 8,192-token row (ISSUE 39): 32
    query heads of 128 + 64 over per-head keys and values of 128 and ONE
    rotary key of 64.  What interpret mode cannot show: that Mosaic takes a
    visit of several (query, K/V) heads beside one block of the shared key
    (64 columns padded to 128 lanes), its second product, and the dk/dv
    pass's third accumulator."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from tensorflowonspark_tpu.ops.attention import flash_attention

    shape = lambda *dims: jax.ShapeDtypeStruct(  # noqa: E731
        dims, jnp.bfloat16, sharding=one_chip)
    q, k, k_shared = (shape(1, 8192, 32, 192), shape(1, 8192, 32, 128),
                      shape(1, 8192, 64))

    def loss(q, k, v, k_shared):
        out = flash_attention(q, k, v, k_shared=k_shared, impl="pallas")
        return jnp.sum(out.astype(jnp.float32))

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        hlo = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3))).lower(
            q, k, k, k_shared).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    # forward, dk/dv pass, dq pass
    assert hlo.count('custom_call_target="tpu_custom_call"') == 3


@pytest.mark.parametrize("step", ["select", "attend", "index_loss"])
def test_the_sparse_attention_kernels_lower_at_keye_widths(one_chip, step):
    """``ops/sparse_attention.py`` on one 16,384-token row at Keye-VL-2.0's
    widths (16 index heads of 64 over one index key, top 2,048; 32 x 128
    query heads over 4 K/V heads).  What interpret mode cannot show: that
    Mosaic takes the int8 mask tiles, the visit tables built on the device
    with a grid whose length is a value of the run, 4 MiB of one block's
    scores and keys in VMEM under the raised limit, and the loss kernel's
    resident gradient of the one index key."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from tensorflowonspark_tpu.ops import sparse_attention as dsa

    length = 16384
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        dims, dtype, sharding=one_chip)
    a, b, c = (shape((length, 16, 64), jnp.bfloat16),
               shape((length, 64), jnp.bfloat16),
               shape((length, 16), jnp.float32))
    q, k = (shape((length, 32, 128), jnp.bfloat16),
            shape((length, 4, 128), jnp.bfloat16))
    mask = shape((length, length), jnp.int8)
    lse, lse_i = shape((32, length), jnp.float32), shape((length,),
                                                         jnp.float32)
    if step == "select":        # score tiles and the exact threshold
        fn, args, calls = (lambda a, b, c: dsa.lightning_select(
            a, b, c, 2048, impl="pallas")), (a, b, c), 2
    elif step == "attend":      # forward, dk/dv pass, dq pass
        fn = jax.value_and_grad(lambda q, k, v, mask: jnp.sum(
            dsa.sparse_attention(q, k, v, mask, impl="pallas")[0].astype(
                jnp.float32)), argnums=(0, 1, 2))
        args, calls = (q, k, k, mask), 3
    else:                       # the loss walk with the indexer's gradient
        fn = jax.value_and_grad(lambda a, b, c, *rest: dsa.index_kl(
            a, b, c, *rest, impl="pallas"), argnums=(0, 1, 2))
        args, calls = (a, b, c, q, k, lse, lse_i, mask), 1
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        hlo = jax.jit(fn).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    assert hlo.count('custom_call_target="tpu_custom_call"') == calls
