"""Latent attention with a query latent and YaRN (ISSUE 45): ``Attention(
latent=, q_lora_rank=, rope_scaling=)`` against the plain reference of
``benchmark/configs/xing4_29b_a4b_d5_tp8_ep8.py`` at a small size on the CPU,
float32 on both sides, through the XLA path and the interpret-mode kernels;
the frequencies against the formula written out, at ``factor`` 64 and at
``rope_scaling`` null; and that the softmax scale carries ``m²``."""

from __future__ import annotations

import math
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from tensorflowonspark_tpu.models import transformer as tfm

YARN = {"type": "yarn", "factor": 64, "original_max_position_embeddings": 16,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}
CFG = {"hidden_size": 48, "num_attention_heads": 4, "q_lora_rank": 24,
       "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
       "v_head_dim": 16, "rms_norm_eps": 1e-6, "rope_theta": 10000,
       "rope_scaling": YARN, "num_hidden_layers": 1,
       "num_nextn_predict_layers": 0}


@pytest.fixture(scope="module")
def xing():
    return common.load_module("configs", "xing4_29b_a4b_d5_tp8_ep8")


_as_tuple = tfm.rope_scaling_from_config


def _attention(cfg: dict, impl: str):
    return tfm.Attention(
        cfg["num_attention_heads"], 0, cfg["rope_theta"], impl,
        compute_dtype=jnp.float32, norm_eps=cfg["rms_norm_eps"],
        latent=(cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                cfg["qk_rope_head_dim"], cfg["v_head_dim"]),
        q_lora_rank=cfg["q_lora_rank"],
        rope_scaling=_as_tuple(cfg["rope_scaling"]))


def _seeded(module, u, scale: float = 3.0):
    """Parameters with scores sharp enough that a wrong scale shows, and norm
    weights that are not 1."""
    params = module.init(jax.random.key(1), u)["params"]
    params["q_b_proj"]["kernel"] = params["q_b_proj"]["kernel"] * scale
    for name in ("q_a_norm", "kv_a_norm"):
        shape = params[name]["scale"].shape
        params[name]["scale"] = 1.0 + 0.2 * jax.random.normal(
            jax.random.key(2), shape)
    return params


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_query_latent_and_yarn_match_the_reference(xing, impl):
    """Forward and the gradients of every weight and of the input.  The
    reference turns the published INTERLEAVED pairs on weights permuted by
    ``published_layout``."""
    u = jax.random.normal(jax.random.key(0), (2, 24, CFG["hidden_size"]))
    module = _attention(CFG, impl)
    params = _seeded(module, u)
    assert set(params) == {"q_a_proj", "q_a_norm", "q_b_proj", "kv_a_proj",
                           "kv_a_norm", "kv_b_proj", "o_proj"}
    probe = jax.random.normal(jax.random.key(3), u.shape)
    published = lambda p: xing.published_layout(  # noqa: E731
        CFG, {"block_0": {"attn": p}})["block_0"]["attn"]

    def program(params, u):
        return jnp.sum(module.apply({"params": params}, u) * probe)

    def reference(params, u):
        return jnp.sum(xing._reference_attention(CFG, published(params), u)
                       * probe)

    got = jax.value_and_grad(program, argnums=(0, 1))(params, u)
    want = jax.value_and_grad(reference, argnums=(0, 1))(params, u)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=2e-5, abs=1e-4)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got[1])[0],
                            jax.tree.leaves(want[1])):
        err = float(jnp.abs(a - b).max() / jnp.abs(b).max())
        assert err < 5e-5, (jax.tree_util.keystr(path), err)


def test_the_softmax_scale_carries_m_squared(xing):
    """``m`` = 1 in the scale (the check's control ``no_mscale``) is another
    function: the output parts by far more than rounding."""
    u = jax.random.normal(jax.random.key(0), (1, 24, CFG["hidden_size"]))
    module = _attention(CFG, "xla")
    params = _seeded(module, u)
    right = module.apply({"params": params}, u)
    with mock.patch.object(tfm, "yarn_mscale", lambda *_a: 1.0):
        wrong = module.apply({"params": params}, u)
    m = 0.1 * math.log(64) + 1
    assert tfm.yarn_mscale(64.0, 1.0) == pytest.approx(m)
    assert float(jnp.abs(right - wrong).max() / jnp.abs(right).max()) > 0.05
    # and the kernels are handed the scale: None without a stretch
    seen = {}
    kept = tfm.flash_attention

    def spy(*args, sm_scale=None, **kwargs):
        seen["sm_scale"] = sm_scale
        return kept(*args, sm_scale=sm_scale, **kwargs)

    tfm.flash_attention = spy
    try:
        module.apply({"params": params}, u)
        assert seen["sm_scale"] == pytest.approx(24 ** -0.5 * m * m)
        plain = tfm.Attention(4, 0, attn_impl="xla",
                              compute_dtype=jnp.float32,
                              latent=(32, 16, 8, 16))
        plain.apply(plain.init(jax.random.key(1), u), u)
        assert seen["sm_scale"] is None
    finally:
        tfm.flash_attention = kept


@pytest.mark.parametrize("width,original,theta", [(64, 4096, 10000.0),
                                                  (8, 16, 10000.0)])
def test_yarn_frequencies_are_the_written_out_formula(xing, width, original,
                                                      theta):
    """``inv = θ^(-2i/width)``; the correction range of ``beta_fast`` 32 and
    ``beta_slow`` 1 over the original positions, floored and ceiled; ``ramp =
    clip((i - low) / (high - low), 0, 1)``; ``inv' = inv / 64 · ramp + inv ·
    (1 - ramp)``."""
    scaling = {**YARN, "original_max_position_embeddings": original}
    inv, on_cos_sin = tfm.rope_frequencies(theta, _as_tuple(scaling), width)

    def dim(rotations):
        return (width * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low, high = max(math.floor(dim(32)), 0), min(math.ceil(dim(1)), width - 1)
    want = []
    for i in range(width // 2):
        plain = theta ** (-2 * i / width)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(plain / 64 * ramp + plain * (1 - ramp))
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    assert on_cos_sin == 1.0        # m(mscale) / m(mscale_all_dim)
    if width == 64:                 # the published widths: what moves where
        assert (low, high) == (10, 23)
        np.testing.assert_allclose(inv[:11], [
            theta ** (-2 * i / 64) for i in range(11)], rtol=1e-6)
        np.testing.assert_allclose(inv[23:], [
            theta ** (-2 * i / 64) / 64 for i in range(23, 32)], rtol=1e-6)
    np.testing.assert_allclose(
        inv, xing.yarn_inverse_frequencies(
            {"rope_scaling": scaling, "qk_rope_head_dim": width,
             "rope_theta": theta}), rtol=1e-6)


def test_no_rope_scaling_is_the_rotation_it_was():
    """``rope_scaling`` null: ``theta^(-i / half)``, no factor, and
    ``apply_rope`` is bit for bit the function it was."""
    inv, factor = tfm.rope_frequencies(1e6, None, 64)
    np.testing.assert_array_equal(
        inv, jnp.exp(-math.log(1e6) * jnp.arange(32, dtype=jnp.float32) / 32))
    assert factor == 1.0
    x = jax.random.normal(jax.random.key(0), (2, 12, 3, 16))
    positions = jnp.arange(12)

    def before(x, positions, theta=10000.0):    # the parent commit's, verbatim
        d = x.shape[-1]
        half = d // 2
        freqs = jnp.exp(-math.log(theta)
                        * jnp.arange(half, dtype=jnp.float32) / half)
        angles = positions[:, None].astype(jnp.float32) * freqs[None, :]
        cos = jnp.cos(angles)[None, :, None, :]
        sin = jnp.sin(angles)[None, :, None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)

    np.testing.assert_array_equal(tfm.apply_rope(x, positions, 1e4),
                                  before(x, positions, 1e4))
    # a stretch whose mscale_all_dim is unset scales cos and sin instead
    _inv, factor = tfm.rope_frequencies(
        1e4, ("yarn", 64.0, 16, 32.0, 1.0, 0.0, 0.0), 8)
    assert factor == pytest.approx(0.1 * math.log(64) + 1)
