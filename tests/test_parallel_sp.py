"""Ring sequence parallelism vs dense attention (8 virtual CPU devices)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.ops import attention as att
from tensorflowonspark_tpu.parallel import mesh as meshlib
from tensorflowonspark_tpu.parallel import sp as splib


def global_qkv(b=4, s=64, h=4, d=16, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_dense(causal):
    mesh = meshlib.make_mesh(dp=2, sp=4)
    q, k, v = global_qkv()
    ref = att.mha_reference(q, k, v, causal=causal)
    out = splib.sequence_parallel_attention(mesh, q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_ring_attention_sp8():
    mesh = meshlib.make_mesh(sp=8)
    q, k, v = global_qkv(b=2, s=64)
    ref = att.mha_reference(q, k, v, causal=True)
    out = splib.sequence_parallel_attention(mesh, q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_ring_attention_grads_match_dense():
    mesh = meshlib.make_mesh(sp=4, dp=2)
    q, k, v = global_qkv(b=2, s=32, h=2, d=8)

    def loss_ring(q, k, v):
        o = splib.sequence_parallel_attention(mesh, q, k, v, causal=True)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(att.mha_reference(q, k, v, causal=True).astype(jnp.float32) ** 2)

    # jit the grads: one cached program instead of op-by-op eager tracing
    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_ring_attention_jit_with_sharded_inputs():
    # Under jit with mesh-sharded operands (the way a model would call it).
    mesh = meshlib.make_mesh(sp=4, dp=2)
    q, k, v = global_qkv()
    sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(("dp", "fsdp"), "sp", "tp", None))
    qs, ks, vs = (jax.device_put(x, sharding) for x in (q, k, v))
    fn = jax.jit(lambda q, k, v: splib.sequence_parallel_attention(
        mesh, q, k, v, causal=True))
    out = fn(qs, ks, vs)
    ref = att.mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
