"""The LM cells' kernel at its real widths, compiled for a DESCRIBED v5e
(nothing runs, no chip needed): the Pallas flash forward at head dim 96
must lower, and stay in the gradient program, for both LM mixes' shapes.
The topology is described inside a fixture, never at import (only one
process may load the TPU library; see the on-chip-measurement guide)."""

from __future__ import annotations

import os

import pytest

from benchmark import common


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


@pytest.mark.parametrize("mix", ["token_rows_2k", "token_rows_512"])
def test_flash_forward_lowers_at_phi3_widths(one_chip, mix):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from tensorflowonspark_tpu.ops.attention import flash_attention

    cfg = common.read_json(os.path.join(common.HERE, "configs",
                                        "phi3_mini_d4.json"))
    traffic = common.read_json(os.path.join(common.HERE, "traffic",
                                            f"{mix}.json"))
    heads = cfg["num_attention_heads"]
    shape = (traffic["rows_per_chip"], traffic["seq_len"], heads,
             cfg["hidden_size"] // heads)
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, impl="pallas")
        return jnp.sum(out.astype(jnp.float32))

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without a chip: keep it out of the cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        # value AND gradient: the gradient alone does not need the forward's
        # output, and XLA then drops the kernel (its backward is XLA code)
        hlo = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
            q, q, q).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    assert 'custom_call_target="tpu_custom_call"' in hlo
