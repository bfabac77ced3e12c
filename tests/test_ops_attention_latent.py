"""Latent attention through the flash trio (ISSUE 39): a key width that
differs from the value width, and ONE further key head that every query head
meets (``flash_attention(..., k_shared=)``), against ``mha_reference`` on the
keys a plain implementation would build: each head's own key with the shared
one copied beside it.  Forward and all four gradients, the shared key's
summed over the heads; the Pallas kernels in interpret mode (the one-pass
backward, and the two passes it gives way to where its accumulators do not
fit) and the XLA path."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu import telemetry
from tensorflowonspark_tpu.ops import attention
from tensorflowonspark_tpu.ops.attention import flash_attention, mha_reference


def _operands(b, s, h, h_kv, d_k, d_r, d_v, seed=0):
    keys = jax.random.split(jax.random.key(seed), 5)
    return (jax.random.normal(keys[0], (b, s, h, d_k + d_r)),
            jax.random.normal(keys[1], (b, s, h_kv, d_k)),
            jax.random.normal(keys[2], (b, s, h_kv, d_v)),
            jax.random.normal(keys[3], (b, s, d_r)),
            jax.random.normal(keys[4], (b, s, h, d_v)))


def _plain(q, k, v, k_shared, g):
    """What a plain implementation computes: the shared key copied to every
    K/V head and concatenated; autodiff sums its gradient over the copies."""
    b, s, h_kv, _ = k.shape
    keys = jnp.concatenate([k, jnp.broadcast_to(
        k_shared[:, :, None], (b, s, h_kv, k_shared.shape[-1]))], axis=-1)
    return jnp.sum(mha_reference(q, keys, v) * g)


def _impl(impl, monkeypatch):
    """``pallas_interpret-two-passes``: the kernels with no room for the
    one-pass backward's resident accumulators."""
    impl, _, two_passes = impl.partition("-")
    if two_passes:
        monkeypatch.setattr(attention, "_VMEM_BODY", attention._VMEM_LIMIT)
    return impl


def _both(impl, q, k, v, k_shared, g, **blocks):
    def system(q, k, v, k_shared):
        return jnp.sum(flash_attention(q, k, v, k_shared=k_shared, impl=impl,
                                       **blocks) * g)

    got = jax.value_and_grad(system, argnums=(0, 1, 2, 3))(q, k, v, k_shared)
    want = jax.value_and_grad(_plain, argnums=(0, 1, 2, 3))(
        q, k, v, k_shared, g)
    return got, want


def _assert_close(got, want, tol=2e-5):
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5, abs=1e-4)
    for name, a, b in zip(("dq", "dk", "dv", "dk_shared"), got[1], want[1]):
        assert a.shape == b.shape, name
        err = float(jnp.abs(a - b).max() / jnp.abs(b).max())
        assert err < tol, (name, err)


@pytest.mark.parametrize("impl", ["pallas_interpret",
                                  "pallas_interpret-two-passes", "xla"])
@pytest.mark.parametrize("shape", [
    # batch, positions, heads, K/V heads, d_k, d_r, d_v, blocks
    pytest.param((2, 40, 4, 4, 16, 8, 16, 16), id="24-over-16"),
    pytest.param((1, 24, 32, 32, 128, 64, 128, 8), id="192-over-128"),
    pytest.param((2, 32, 8, 2, 16, 8, 16, 16), id="grouped-heads"),
])
def test_latent_kernels_match_the_plain_reference(impl, shape, monkeypatch):
    impl = _impl(impl, monkeypatch)
    b, s, h, h_kv, d_k, d_r, d_v, block = shape
    q, k, v, k_shared, g = _operands(b, s, h, h_kv, d_k, d_r, d_v)
    got, want = _both(impl, q, k, v, k_shared, g, block_q=block,
                      block_k=block)
    _assert_close(got, want)
    assert got[1][3].shape == (b, s, d_r)       # one key head a batch row


@pytest.mark.parametrize("impl", ["pallas_interpret",
                                  "pallas_interpret-two-passes", "xla"])
def test_a_value_width_of_its_own_without_a_shared_key(impl, monkeypatch):
    impl = _impl(impl, monkeypatch)
    q, k, v, _shared, g = _operands(2, 40, 4, 2, 24, 0, 16)
    def system(q, k, v):
        return jnp.sum(flash_attention(q, k, v, impl=impl, block_q=16,
                                       block_k=16) * g)

    got = jax.value_and_grad(system, argnums=(0, 1, 2))(q, k, v)
    want = jax.value_and_grad(
        lambda q, k, v: jnp.sum(mha_reference(q, k, v) * g),
        argnums=(0, 1, 2))(q, k, v)
    assert got[1][2].shape == v.shape and got[1][0].shape == q.shape
    for a, b in zip(got[1], want[1]):
        assert float(jnp.abs(a - b).max() / jnp.abs(b).max()) < 2e-5


@pytest.mark.parametrize("passes", [1, 2])
def test_a_visit_serves_several_heads_and_the_rows_shares_are_summed(
        passes, monkeypatch):
    """With room for two heads a visit, a batch row's eight heads take four
    grid rows: each writes its float32 share of the shared key's gradient
    and the shares are added; the result is the one of a visit of all
    eight."""
    if passes == 2:
        monkeypatch.setattr(attention, "_VMEM_BODY", attention._VMEM_LIMIT)
    q, k, v, k_shared, g = _operands(2, 48, 8, 8, 16, 8, 16)
    whole, want = _both("pallas_interpret", q, k, v, k_shared, g,
                        block_q=16, block_k=16)
    a_head = 6 * 16 * 16 * 4 + 4 * 16 * 128 * 4 + 16 * 16 * 4 \
        + 4 * 16 * 8 * 4 + 16 * 8 * 4 + 4 * 16 * 16 * 4
    monkeypatch.setattr(attention, "_VMEM_BLOCKS", 2 * a_head)
    before = telemetry.snapshot()["counters"]
    parts, _ = _both("pallas_interpret", q, k, v, k_shared, g, block_q=16,
                     block_k=16)
    after = telemetry.snapshot()["counters"]
    moved = {key: after[key] - before.get(key, 0) for key in after
             if key.startswith("flash.latent")}
    # forward, and the backward's one kernel or two
    assert moved["flash.latent_kernels"] == 1 + passes
    assert (moved["flash.latent_visit_heads"]
            == 2 * moved["flash.latent_kernels"])
    _assert_close(parts, want)
    for a, b in zip(parts[1], whole[1]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_the_one_pass_backward_takes_fewer_heads_where_they_do_not_fit(
        monkeypatch):
    """Every head has K and V of its own, so the resident dk and dv grow
    with the heads a visit: with room for two heads' (and the shared key's
    float32 share), the forward still serves all eight in a visit and the
    one-pass backward two, in four grid rows whose shares of the shared
    key's gradient are added; the gradients are those of a visit of all
    eight."""
    q, k, v, k_shared, g = _operands(1, 48, 8, 8, 16, 8, 16)
    whole, want = _both("pallas_interpret", q, k, v, k_shared, g,
                        block_q=16, block_k=16)
    # two heads' blocks; accumulator and two output buffers of their dk and
    # dv and of the shared key's float32 share (float32 operands)
    a_head = 6 * 16 * 16 * 4 + 4 * 16 * 128 * 4 + 16 * 16 * 4 \
        + 4 * 16 * 8 * 4 + 16 * 8 * 4 + 4 * 16 * 16 * 4
    monkeypatch.setattr(
        attention, "_VMEM_BODY", attention._VMEM_LIMIT - (
            2 * a_head + 2 * 48 * (16 + 16) * 12 + 48 * 8 * 12))
    before = telemetry.snapshot()["counters"]
    parts, _ = _both("pallas_interpret", q, k, v, k_shared, g, block_q=16,
                     block_k=16)
    after = telemetry.snapshot()["counters"]
    moved = {key: after[key] - before.get(key, 0) for key in after
             if key.startswith("flash.")}
    assert moved["flash.bwd_fused"] == moved["flash.bwd_calls"] == 1
    assert moved["flash.latent_kernels"] == 2
    assert moved["flash.latent_visit_heads"] == 8 + 2
    _assert_close(parts, want)
    for a, b in zip(parts[1], whole[1]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_the_plain_signature_counts_no_latent_kernel():
    q, k, v, _shared, _g = _operands(1, 32, 2, 2, 16, 0, 16)
    before = telemetry.snapshot()["counters"].get("flash.latent_kernels", 0)
    flash_attention(q, k, v, impl="pallas_interpret", block_q=16, block_k=16)
    assert telemetry.snapshot()["counters"].get(
        "flash.latent_kernels", 0) == before


def test_block_diffusion_mask_with_a_shared_key():
    """The shared key under the other mask the walk knows: the kernels
    against the XLA path."""
    length = 16
    q, k, v, k_shared, g = _operands(1, 2 * length, 4, 4, 16, 8, 16)
    outs = [flash_attention(q, k, v, k_shared=k_shared, causal=False,
                            block_diffusion=(length, 4), impl=impl,
                            block_q=8, block_k=8)
            for impl in ("pallas_interpret", "xla")]
    np.testing.assert_allclose(outs[0], outs[1], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("bad", ["width", "shape"])
def test_widths_that_do_not_add_up_are_refused(bad):
    q, k, v, k_shared, _g = _operands(1, 16, 2, 2, 16, 8, 16)
    if bad == "width":
        q = q[..., :-1]
    else:
        k_shared = k_shared[:, :-1]
    with pytest.raises(ValueError, match="shared key"):
        flash_attention(q, k, v, k_shared=k_shared, impl="xla")
