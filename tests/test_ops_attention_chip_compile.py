"""The flash kernels at SDAR's widths, compiled for a DESCRIBED v5e (nothing
runs, no chip needed): 32 x 128 query heads over 4 K/V heads, 8,192
positions under the block-diffusion mask of block 4.  What interpret mode
cannot show: that Mosaic takes the walk's scalar-prefetch tables (80 visits
a K/V head in every kernel), index maps that read them, a visit's blocks of
a group's 8 query heads (16 MiB in the dq pass, under the raised limit), and
the one-pass backward's resident dk and dv over the whole key length (16 MiB
of accumulators and output buffers at these widths) with its product over
the tile's other side.  The topology is described inside a fixture, never at
import (only one process may load the TPU library; see the
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


def _two_passes(monkeypatch):
    """No room beside the tile body's: the plan takes the dk/dv pass and the
    dq pass, which stay the path of rows too long for one pass."""
    from tensorflowonspark_tpu.ops import attention

    monkeypatch.setattr(attention, "_VMEM_BODY", attention._VMEM_LIMIT)


@pytest.mark.parametrize("case", [
    # positions, query heads, K/V heads, head dim, mask, backward kernels
    pytest.param((8192, 32, 4, 128,
                  dict(causal=False, block_diffusion=(4096, 4)), 1),
                 id="block-diffusion"),
    pytest.param((8192, 32, 4, 128,
                  dict(causal=False, block_diffusion=(4096, 4)), 2),
                 id="block-diffusion-two-passes"),
    pytest.param((8192, 32, 4, 128, dict(causal=True), 1), id="causal"),
    # the dense LM's: one query head a K/V head, 96 wide padded to 128 lanes;
    # the one-pass backward under Mosaic's default VMEM scope
    pytest.param((2048, 32, 32, 96, dict(causal=True), 1),
                 id="group-1-head-dim-96"),
    pytest.param((2048, 32, 32, 96, dict(causal=True), 2),
                 id="group-1-head-dim-96-two-passes"),
    # its 512-id rows: one tile a row, no branch in the kernel
    pytest.param((512, 32, 32, 96, dict(causal=True), 1),
                 id="group-1-one-tile-a-row"),
    # OLMoE's: one head a visit whose 8 MiB resident set still fits the
    # default scope; at 8,192 positions the limit is raised
    pytest.param((4096, 16, 16, 128, dict(causal=True), 1),
                 id="group-1-4k-row"),
    pytest.param((8192, 16, 16, 128, dict(causal=True), 1),
                 id="group-1-8k-row"),
    # the longest row of SDAR's widths that takes one pass: 64 MiB resident
    pytest.param((32768, 32, 4, 128, dict(causal=True), 1), id="32k-row"),
    # a group too large for one visit: four visits of 8 heads a tile, whose
    # float32 shares of dk and dv are resident and added outside
    pytest.param((2048, 32, 1, 128, dict(causal=True), 1), id="32-over-1"),
    # SmallThinker's 16k row (ISSUE 48): 28 query heads over 4 K/V heads, a
    # group of 7 in one visit (no power of two: blocks of 7 x 512 x 128), the
    # band of a 4,096 window (252 visits a K/V head, masked tiles at both
    # ends of a run) with dk and dv of the whole row resident (32 MiB), the
    # band through the two passes, and its global layers' causal mask
    pytest.param((16384, 28, 4, 128, dict(causal=True, window=4096), 1),
                 id="window-4096-group-7-16k-row"),
    pytest.param((16384, 28, 4, 128, dict(causal=True, window=4096), 2),
                 id="window-4096-group-7-16k-row-two-passes"),
    pytest.param((16384, 28, 4, 128, dict(causal=True), 1),
                 id="group-7-16k-row"),
])
def test_the_flash_kernels_lower_at_sdar_widths(one_chip, case, monkeypatch):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from tensorflowonspark_tpu.ops.attention import flash_attention

    length, heads, kv_heads, d, mask, backward = case
    if backward == 2:
        _two_passes(monkeypatch)
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
    # forward and the one-pass backward, or forward, dk/dv pass, dq pass,
    # under the scopes the benchmark's readers sum by
    kernels = [line for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 1 + backward
    assert sum("flash_bwd" in line for line in kernels) == backward
    assert sum("flash_fwd" in line for line in kernels) == 1
    # a window's kernels carry scopes of their own, the full mask's do not
    assert sum("flash_fwd_window" in line or "flash_bwd_window" in line
               for line in kernels) == ("window" in mask) * (1 + backward)


@pytest.mark.parametrize("backward", [
    pytest.param(1, id="one-pass"), pytest.param(2, id="two-passes")])
def test_the_latent_kernels_lower_at_kanana_widths(one_chip, backward,
                                                   monkeypatch):
    """Kanana-2's latent attention on one 8,192-token row (ISSUE 39): 32
    query heads of 128 + 64 over per-head keys and values of 128 and ONE
    rotary key of 64.  What interpret mode cannot show: that Mosaic takes a
    visit of several (query, K/V) heads beside one block of the shared key
    (64 columns padded to 128 lanes), its second product, the dk/dv pass's
    third accumulator, and in the one-pass backward the resident dk and dv
    of a visit's 4 heads (every head has K and V of its own: 64 MiB) beside
    the shared key's float32 share (12 MiB)."""
    if backward == 2:
        _two_passes(monkeypatch)
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
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1 + backward


@pytest.mark.parametrize("step", ["select", "attend", "index_loss"])
def test_the_sparse_attention_kernels_lower_at_keye_widths(one_chip, step):
    """``ops/sparse_attention.py`` on one 16,384-token row at Keye-VL-2.0's
    widths (16 index heads of 64 over one index key, top 2,048; 32 x 128
    query heads over 4 K/V heads).  What interpret mode cannot show: that
    Mosaic takes the int8 mask tiles, the visit tables built on the device
    with a grid whose length is a value of the run, 4 MiB of one block's
    scores and keys in VMEM under the raised limit, the loss kernel's
    resident gradient of the one index key, and the one-pass backward's dk
    and dv of a K/V head resident over the whole row (16 MiB of float32
    accumulators beside 16 MiB of output buffers: 43 MiB in all by Mosaic's
    count) with the mask tile's bias turned in VMEM and dq's product over the
    tile's other side."""
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
    elif step == "attend":      # forward, the one-pass backward
        fn = jax.value_and_grad(lambda q, k, v, mask: jnp.sum(
            dsa.sparse_attention(q, k, v, mask, impl="pallas")[0].astype(
                jnp.float32)), argnums=(0, 1, 2))
        args, calls = (q, k, k, mask), 2
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


@pytest.mark.parametrize("case", [
    # positions, heads, norm, rotation: SDAR's and Keye's q and k (per-head
    # QK-norm and RoPE), SmallThinker's (28 heads in blocks of 4, rotation
    # only), OLMoE's (the norm over the whole projection stays jnp in front)
    pytest.param((8192, 32, True, True), id="sdar-q"),
    pytest.param((8192, 4, True, True), id="sdar-k"),
    pytest.param((16384, 32, True, True), id="keye-q"),
    pytest.param((16384, 28, False, True), id="smallthinker-q"),
    pytest.param((4096, 16, False, True), id="olmoe-q"),
    pytest.param((8192, 32, True, False), id="norm-only"),
])
def test_the_qk_prep_kernels_lower_at_the_cells_widths(one_chip, case):
    """``ops/qk_prep.py``'s two passes on a token-major ``[1, S, H, 128]``
    bf16 array.  What interpret mode cannot show: that Mosaic takes the lane
    rotation by half a head (``pltpu.roll``), the lane-aligned head slices
    of a ``(512, 4 x 128)`` block, the scale's ``(1, 128)`` block and the
    partial sums' ``(1, 128)`` block of a four-dimensional output."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from tensorflowonspark_tpu.models.transformer import rope_frequencies
    from tensorflowonspark_tpu.ops.qk_prep import qk_prep

    length, heads, norm, rope = case
    x = jax.ShapeDtypeStruct((1, length, heads, 128), jnp.bfloat16,
                             sharding=one_chip)
    scale = jax.ShapeDtypeStruct((128,), jnp.float32, sharding=one_chip)
    freqs, factor = rope_frequencies(1e6, None, 128)

    def loss(x, scale):
        out = qk_prep(x, scale if norm else None,
                      jnp.arange(length) if rope else None,
                      freqs if rope else None, factor=factor)
        return jnp.sum(out.astype(jnp.float32))

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        hlo = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
            x, scale).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    kernels = [line for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 2
    assert sum("qk_prep_fwd" in line for line in kernels) == 1
    assert sum("qk_prep_bwd" in line for line in kernels) == 1


@pytest.mark.parametrize("state_dtype", ["float32", "bfloat16"])
def test_the_kda_kernels_lower_at_kimi_linear_widths(one_chip, state_dtype):
    """``ops/kda.py``'s two kernels on Kimi-Linear's 16,384-token row (ISSUE
    54): 32 heads of 128 key and 128 value channels read as column blocks of
    ``[1, 16384, 32 x 128]``, chunks of 64, four a grid step.  What
    interpret mode cannot show: that Mosaic takes the ``(256, 128)`` blocks
    of a head out of the token-major arrays, ``β``'s ``(4, 64)`` and the
    chunk states' ``(4, 128, 128)`` float32 blocks, the ``[64, 128] -> [8, 8,
    128]`` views of the diagonal blocks' pairwise decays, the products with a
    transposed left operand and those in float32 proper, the dynamic chunk
    offsets of a loop inside a grid step, and the backward's live set under
    the raised VMEM limit; and the check's control, a state held in bf16."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from tensorflowonspark_tpu.ops import kda

    length, heads, d = 16384, 32, 128
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        dims, dtype, sharding=one_chip)
    q = shape((1, length, heads, d), jnp.bfloat16)
    g = shape((1, length, heads, d), jnp.float32)
    beta = shape((1, length, heads), jnp.float32)

    def loss(q, k, v, g, beta):
        out = kda.kda_scan(q, k, v, g, beta, chunk=64, impl="pallas",
                           state_dtype=jnp.dtype(state_dtype))
        return jnp.sum(out.astype(jnp.float32))

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4))).lower(q, q, q, g, beta).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    kernels = [line for line in compiled.as_text().splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 2
    assert sum("kda_fwd" in line for line in kernels) == 1
    assert sum("kda_bwd" in line for line in kernels) == 1
    # beside the operands and their cotangents the op holds the chunk
    # states (0.54 GB) and little else: no stacked or transposed copies of
    # q, k, v or g (134 MB and 268 MB each)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9
