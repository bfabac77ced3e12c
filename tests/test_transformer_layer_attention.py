"""``Transformer.layer_attention`` (ISSUE 48): a ``(window, rope)`` a layer
reaches each layer's attention and nothing else; the model says where it is
built what it cannot run with it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.models import transformer as tfm
from tensorflowonspark_tpu.ops import attention as att

BASE = {"model": "transformer", "vocab_size": 64, "d_model": 32,
        "n_layers": 2, "n_heads": 4, "n_kv_heads": 2, "d_head": 8,
        "d_ff": 48, "bf16": False, "attn_impl": "xla"}
IDS = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 24)), jnp.int32)


def _logits(config, params=None):
    model = tfm.build_transformer(config)
    if params is None:
        params = jax.jit(model.init)(jax.random.PRNGKey(0), IDS)["params"]
    return jax.jit(model.apply)({"params": params}, IDS), params


def test_no_entry_and_the_default_entry_are_the_model_as_it_was():
    plain, params = _logits(BASE)
    same, same_params = _logits(
        {**BASE, "layer_attention": [[0, True], [0, True]]})
    assert jax.tree.structure(params) == jax.tree.structure(same_params)
    np.testing.assert_array_equal(plain, same)
    # a window that reaches over the row is no window either
    wide, _ = _logits({**BASE, "layer_attention": [[24, True], [99, True]]},
                      params)
    np.testing.assert_array_equal(plain, wide)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_each_layer_gets_its_own_window_and_rotation(remat, monkeypatch):
    """What ``flash_attention`` and ``apply_rope`` are called with, layer by
    layer: layer 0 global without rotation, layer 1 in a window of 6 with
    RoPE; the same under ``remat``, where the window is a module field."""
    seen = []
    flash, rope = tfm.flash_attention, tfm.apply_rope

    def spy_flash(q, k, v, **kwargs):
        seen.append(("flash", kwargs.get("window")))
        return flash(q, k, v, **kwargs)

    def spy_rope(x, *args, **kwargs):
        seen.append(("rope", None))
        return rope(x, *args, **kwargs)

    monkeypatch.setattr(tfm, "flash_attention", spy_flash)
    monkeypatch.setattr(tfm, "apply_rope", spy_rope)
    config = {**BASE, "remat": remat,
              "layer_attention": [[0, False], [6, True]]}
    model = tfm.build_transformer(config)
    params = model.init(jax.random.PRNGKey(0), IDS)["params"]
    seen.clear()
    logits = model.apply({"params": params}, IDS)
    assert seen == [("flash", None), ("rope", None), ("rope", None),
                    ("flash", 6)]
    # and the numbers are the dense reference's under those masks
    monkeypatch.undo()
    want, _ = _logits({**config, "remat": False}, params)
    np.testing.assert_allclose(logits, want, atol=1e-5, rtol=1e-5)
    moved, _ = _logits({**BASE, "layer_attention": [[0, False], [0, True]]},
                       params)
    assert float(jnp.abs(moved - want).max()) > 1e-3


def test_a_window_layer_is_the_reference_s_band():
    q = jnp.asarray(np.random.default_rng(1).normal(size=(1, 24, 4, 8)),
                    jnp.float32)
    attn = tfm.Attention(4, 8, attn_impl="xla", compute_dtype=jnp.float32,
                         n_kv_heads=2, rope=False, window=6)
    x = q.reshape(1, 24, 32)
    params = attn.init(jax.random.PRNGKey(0), x)["params"]
    got = attn.apply({"params": params}, x)
    proj = lambda name: jnp.einsum(        # noqa: E731
        "bsd,dhk->bshk", x, params[name]["kernel"])
    heads = att.mha_reference(proj("q_proj"), proj("k_proj"), proj("v_proj"),
                              window=6)
    want = jnp.einsum("bshk,hkd->bsd", heads, params["o_proj"]["kernel"])
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("change,named", [
    ({"decode": True}, "decode=True"),
    ({"sparse_attention": {"index_heads": 2, "index_head_dim": 4,
                           "topk": 4}}, "sparse=(2, 4, 4)"),
    ({"latent_attention": {"kv_lora_rank": 8, "qk_nope_head_dim": 4,
                           "qk_rope_head_dim": 4, "v_head_dim": 4}},
     "latent=(8, 4, 4, 4)"),
    ({"hyper_connections": {"hc_mult": 2, "hc_sinkhorn_iters": 2,
                            "hc_eps": 1e-6, "mhc_h_res_clamp_min": 0.0,
                            "mhc_h_res_clamp_max": 1.0}}, "hyper=(2, 2"),
    ({"attn_impl": "ring"}, "attn_impl='ring'"),
    ({"num_nextn_predict_layers": 1}, "mtp_layers=1"),
    ({"layer_mixer": ["*", "E"], "moe_capacity_factor": None},
     "layer_mixer=('*', 'E')"),
    ({"n_layers": 3}, "over 3 layers"),
])
def test_what_a_layer_s_window_does_not_run_with_is_refused_by_name(change,
                                                                    named):
    config = {**BASE, "layer_attention": [[0, False], [6, True]], **change}
    with pytest.raises(NotImplementedError, match="layer_attention") as e:
        if "decode" in change:
            tfm.Transformer(64, 32, 2, 4, decode=True,
                            layer_attention=((0, False), (6, True)))
        else:
            tfm.build_transformer(config)
    assert named in str(e.value)


@pytest.mark.parametrize("change", [
    {"moe_router_input": "attention"},
    {"moe_router_input": "layer", "moe_capacity_factor": 1.25},
    {"moe_router_input": "layer", "moe_capacity_factor": None,
     "layer_mixer": ["*", "E"]},
])
def test_a_router_input_is_the_layer_s_or_the_experts_(change):
    with pytest.raises(NotImplementedError, match="moe_router_input"):
        tfm.build_transformer({**BASE, "n_experts": 4, **change})


def test_attention_refuses_a_window_on_the_paths_that_have_none():
    x = jnp.zeros((1, 8, 32))
    for fields in (dict(latent=(8, 4, 4, 4)), dict(sparse=(2, 4, 4)),
                   dict(decode=True, max_decode_len=8),
                   dict(attn_impl="ring")):
        attn = tfm.Attention(4, 8, window=4, **fields)
        with pytest.raises(NotImplementedError, match="window=4"):
            attn.init(jax.random.PRNGKey(0), x)
