"""DeepSeek-V3's router in ``MoEMLP`` (ISSUE 39): sigmoid scores, a selection
bias that is a buffer (a collection of its own, which no gradient and no
optimizer sees), the unbiased scores of the chosen renormalised and scaled,
and ``Block``'s shared SwiGLU beside the routed experts; held and unheld,
against a dense reference written out here.  Small, float32, CPU."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tensorflowonspark_tpu.models import transformer as tfm
from tensorflowonspark_tpu.parallel import dp
from tensorflowonspark_tpu.parallel import ep as eplib

E, K, D, F, SHARED = 32, 3, 16, 8, 24   # experts, choices, widths
SCALE = 2.448
EXPERTS = ("experts_gate", "experts_up", "experts_down")


def _layer(held=None, **over):
    args = dict(norm_topk_prob=True, held=held, scoring="sigmoid",
                selection_bias=True, routed_scale=SCALE)
    return eplib.MoEMLP(D, F, E, K, None, **{**args, **over})


def _whole(seed=0, n=48, bias_std=0.05):
    """The uncut layer's parameters, a bias that has moved, and an input
    ``[1, n, D]``."""
    x = jnp.asarray(np.random.default_rng(seed).standard_normal((1, n, D)),
                    jnp.float32)
    variables = _layer().init(jax.random.PRNGKey(seed), x)
    assert {"params", "buffers"} <= set(variables)
    assert not np.asarray(
        variables["buffers"]["e_score_correction_bias"]).any()
    bias = bias_std * jax.random.normal(jax.random.PRNGKey(seed + 100), (E,))
    return variables["params"], bias, x


def _apply(layer, params, bias, x, **kwargs):
    return layer.apply({"params": params, "buffers": {
        "e_score_correction_bias": bias}}, x, **kwargs)


def _share(params, first, end):
    """What a chip that holds experts ``first .. end-1`` keeps: the whole
    router, its slice of every expert-stacked weight."""
    return {**params, **{name: params[name][first:end] for name in EXPERTS}}


def _routing(params, bias, xf):
    scores = jax.nn.sigmoid(xf @ params["router"]["kernel"])
    _, top_idx = jax.lax.top_k(scores + bias, K)
    chosen = jax.nn.one_hot(top_idx, E).sum(1)
    weight = scores * chosen
    return top_idx, weight / (weight.sum(-1, keepdims=True) + 1e-20) * SCALE


def _dense_reference(params, bias, x, first=0, end=E):
    """Every expert in ``first .. end-1`` on every token, weighted by the
    token's routing weight for it (0 where not chosen)."""
    xf = x.reshape(-1, D)
    _, weight = _routing(params, bias, xf)
    out = jnp.zeros_like(xf)
    for i in range(first, end):
        h = (jax.nn.silu(xf @ params["experts_gate"][i])
             * (xf @ params["experts_up"][i]))
        out = out + weight[:, i:i + 1] * (h @ params["experts_down"][i])
    return out.reshape(x.shape)


def test_eight_shares_add_up_to_the_uncut_layer():
    """Eight layers holding experts 0-3 ... 28-31 of one seeded layer: each
    is the dense reference's for its four, their sum the uncut layer's and
    the dense reference's for all 32, and so are the gradients to the
    input."""
    params, bias, x = _whole()
    w = jnp.asarray(np.random.default_rng(1).standard_normal(x.shape),
                    jnp.float32)

    def run(f):
        y, vjp = jax.vjp(f, x)
        return y, vjp(w)[0]

    whole_y, whole_dx = run(lambda x: _apply(_layer(), params, bias, x))
    ref_y, ref_dx = run(lambda x: _dense_reference(params, bias, x))
    np.testing.assert_allclose(whole_y, ref_y, atol=5e-6)
    np.testing.assert_allclose(whole_dx, ref_dx, atol=5e-6)
    sum_y, sum_dx = jnp.zeros_like(x), jnp.zeros_like(x)
    for first in range(0, E, 4):
        y, dx = run(lambda x: _apply(
            _layer((first, first + 4)), _share(params, first, first + 4),
            bias, x))
        np.testing.assert_allclose(
            y, _dense_reference(params, bias, x, first, first + 4),
            atol=5e-6)
        sum_y, sum_dx = sum_y + y, sum_dx + dx
    np.testing.assert_allclose(sum_y, whole_y, atol=1e-5)
    np.testing.assert_allclose(sum_dx, whole_dx, atol=1e-5)


def test_eight_shares_of_a_block_hold_the_shared_expert_once_each():
    """``Block`` adds the shared SwiGLU beside the routed experts, whole on
    every chip of the stage: the eight shares' outputs sum to the uncut
    block's plus seven times what a block adds without any routed expert
    (the residual, attention and the shared expert: read off a block whose
    experts' down-projections are 0)."""
    def block(held=None):
        return tfm.Block(2, 8, F, E, K, attn_impl="xla",
                         compute_dtype=jnp.float32, moe_capacity_factor=None,
                         moe_held=held, moe_router=("sigmoid", True, SCALE),
                         moe_shared_d_ff=SHARED)

    x = jnp.asarray(np.random.default_rng(5).standard_normal((1, 24, D)),
                    jnp.float32)
    variables = block().init(jax.random.PRNGKey(5), x)
    params = variables["params"]
    assert params["shared"]["gate_proj"]["kernel"].shape == (D, SHARED)
    assert "shared" not in params["moe"]
    buffers = {"moe": {"e_score_correction_bias": 0.05 * jax.random.normal(
        jax.random.PRNGKey(6), (E,))}}

    def run(held, moe):
        return block(held).apply(
            {"params": {**params, "moe": moe}, "buffers": buffers}, x)

    whole = run(None, params["moe"])
    unrouted = run(None, {**params["moe"], "experts_down": jnp.zeros_like(
        params["moe"]["experts_down"])})
    assert float(jnp.max(jnp.abs(whole - unrouted))) > 1e-3
    total = sum(run((first, first + 4), _share(params["moe"], first,
                                               first + 4))
                for first in range(0, E, 4))
    np.testing.assert_allclose(total - 7 * unrouted, whole, atol=2e-5)


def test_every_gradient_of_a_share_is_the_reference_s():
    params, bias, x = _whole(seed=2)
    first, end = 8, 12
    share = _share(params, first, end)
    w = jnp.asarray(np.random.default_rng(3).standard_normal(x.shape),
                    jnp.float32)

    def system(p, x):
        return jnp.sum(_apply(_layer((first, end)), p, bias, x) * w)

    def reference(p, x):
        full = {**p, **{k: params[k].at[first:end].set(p[k])
                        for k in EXPERTS}}
        return jnp.sum(_dense_reference(full, bias, x, first, end) * w)

    got = jax.grad(system, argnums=(0, 1))(share, x)
    want = jax.grad(reference, argnums=(0, 1))(share, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-5)
    assert np.asarray(got[0]["router"]["kernel"]).any()


def test_a_bias_changes_the_choice_and_not_the_weights_formula():
    """With the bias the layer chooses other experts for some tokens
    (``bias_moved`` says how many); the weights of whatever is chosen are the
    UNBIASED scores renormalised and scaled: a token's weights sum to the
    scaling factor with or without the bias, and equal the reference's."""
    params, bias, x = _whole(seed=4, bias_std=0.3)
    outs = {}
    for name, b in (("biased", bias), ("flat", jnp.zeros((E,)))):
        y, sown = _apply(_layer(), params, b, x,
                         mutable=["intermediates", "moe_stats", "aux_loss"])
        outs[name] = (y, sown)
        assert "aux_loss" not in sown       # balance is the bias's business
    moved = float(outs["biased"][1]["moe_stats"]["bias_moved"][0])
    assert 0.05 < moved < 0.95
    assert float(outs["flat"][1]["moe_stats"]["bias_moved"][0]) == 0.0
    top_biased = np.asarray(outs["biased"][1]["intermediates"]["top_idx"][0])
    top_flat = np.asarray(outs["flat"][1]["intermediates"]["top_idx"][0])
    differs = 1.0 - np.mean([len(set(a) & set(b)) / K
                             for a, b in zip(top_biased, top_flat)])
    assert differs == pytest.approx(moved, abs=1e-6)
    xf = x.reshape(-1, D)
    ref_idx, weight = _routing(params, bias, xf)
    np.testing.assert_array_equal(np.sort(top_biased), np.sort(ref_idx))
    np.testing.assert_allclose(weight.sum(-1), SCALE, rtol=1e-5)
    # the chosen experts' weights are their own sigmoid scores' share
    scores = jax.nn.sigmoid(xf @ params["router"]["kernel"])
    picked = jnp.take_along_axis(scores, ref_idx, axis=-1)
    np.testing.assert_allclose(
        jnp.take_along_axis(weight, ref_idx, axis=-1),
        picked / picked.sum(-1, keepdims=True) * SCALE, rtol=1e-5)


def test_softmax_routing_still_sows_its_terms_and_has_no_buffer():
    x = jnp.zeros((1, 8, D))
    layer = eplib.MoEMLP(D, F, E, K, None)
    variables = layer.init(jax.random.PRNGKey(0), x)
    assert "buffers" not in variables
    _, sown = layer.apply({"params": variables["params"]}, x,
                          mutable=["aux_loss", "moe_stats"])
    assert {"load_balance", "router_z"} <= set(sown["aux_loss"])
    assert "bias_moved" not in sown["moe_stats"]


def test_unknown_scoring_and_group_limited_routing_are_refused():
    with pytest.raises(ValueError, match="scoring"):
        _layer(scoring="tanh").init(jax.random.PRNGKey(0),
                                    jnp.zeros((1, 4, D)))
    with pytest.raises(NotImplementedError, match="n_group"):
        tfm.build_transformer({"n_experts": 8, "moe_router": {
            "scoring": "sigmoid", "selection_bias": True, "n_group": 2}})


MODEL = {"model": "transformer", "vocab_size": 64, "d_model": 32,
         "n_layers": 3, "n_heads": 4, "d_ff": 16, "n_experts": 8,
         "moe_top_k": 2, "moe_capacity_factor": None,
         "latent_attention": {"kv_lora_rank": 16, "qk_nope_head_dim": 8,
                              "qk_rope_head_dim": 4, "v_head_dim": 8},
         "moe_router": {"scoring": "sigmoid", "selection_bias": True,
                        "routed_scale": SCALE},
         "moe_shared_d_ff": 32, "layer_ffn": [48, 0, 0], "attn_impl": "xla",
         "bf16": False}


@pytest.mark.parametrize("held", [None, (0, 4)], ids=["unheld", "held"])
def test_a_train_step_leaves_the_bias_bit_identical(held):
    """adamw decays every leaf it is given: it is given the parameters, and
    the routers' bias buffers ride the train state beside them: out of a
    step bit for bit as they went in, with no gradient and no optimizer
    state, while everything else moves; and they are READ: another bias,
    another loss."""
    model = tfm.build_transformer({**MODEL, "moe_held": held})
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 16)),
                      jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), ids)
    params, buffers = variables["params"], variables["buffers"]
    assert "mlp" in params["block_0"] and "moe" not in params["block_0"]
    assert params["block_0"]["mlp"]["gate_proj"]["kernel"].shape == (32, 48)
    assert set(buffers) == {"block_1", "block_2"}
    for layer in (1, 2):
        assert "e_score_correction_bias" not in params[f"block_{layer}"]["moe"]
        assert (params[f"block_{layer}"]["moe"]["experts_gate"].shape[0]
                == (4 if held else 8))
    buffers = jax.tree.map(
        lambda b: 0.3 * jax.random.normal(jax.random.PRNGKey(7), b.shape),
        buffers)
    loss_fn = tfm.make_loss_fn(model, aux_loss_coef=0.0, vocab_chunk=32)
    optimizer = optax.adamw(1e-2, weight_decay=0.1)
    state = dp.TrainState.create(params, optimizer, buffers)
    step = dp.make_train_step(loss_fn, optimizer, donate=False)
    after, metrics = step(state, {"input_ids": ids})
    assert np.isfinite(float(metrics["loss"]))
    assert "moe_bias_moved" in metrics and float(metrics["aux_loss"]) == 0.0
    assert float(metrics["moe_bias_moved"]) > 0.0
    for got, want in zip(jax.tree.leaves(after.buffers),
                         jax.tree.leaves(buffers)):
        np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                      np.asarray(want).view(np.uint32))
    for layer in (1, 2):
        for name in ("moe", "shared"):
            moved = jax.tree.map(lambda a, b: bool(jnp.any(a != b)),
                                 after.params[f"block_{layer}"][name],
                                 params[f"block_{layer}"][name])
            assert all(jax.tree.leaves(moved)), name
    # no moment is kept for a buffer
    moments = [x for x in jax.tree.leaves(after.opt_state)
               if getattr(x, "shape", None) == (8,)]
    assert not moments
    flat, _ = step(state._replace(buffers=jax.tree.map(jnp.zeros_like,
                                                       buffers)),
                   {"input_ids": ids})
    assert float(_["loss"]) != float(metrics["loss"])
    # with accumulation the buffers reach every microbatch's loss
    accum, accum_metrics = dp.make_train_step(
        loss_fn, optimizer, donate=False, accum_steps=2)(
            state, {"input_ids": ids})
    assert np.isfinite(float(accum_metrics["loss"]))
    np.testing.assert_array_equal(
        jax.tree.leaves(accum.buffers)[0], jax.tree.leaves(buffers)[0])


def test_a_model_with_buffers_refuses_a_loss_that_is_not_handed_them():
    model = tfm.build_transformer(MODEL)
    ids = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    with pytest.raises(Exception, match="e_score_correction_bias|buffers"):
        tfm.make_loss_fn(model, aux_loss_coef=0.0)(params, {"input_ids": ids})


def test_the_cache_path_refuses_latent_attention_by_name():
    model = tfm.build_transformer({**MODEL, "n_experts": 0, "layer_ffn": None,
                                   "moe_router": None, "moe_shared_d_ff": 0})
    decoder = model.clone(decode=True, max_decode_len=8)
    with pytest.raises(NotImplementedError, match="latent attention"):
        decoder.init(jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32))


def test_a_pattern_of_the_wrong_length_is_refused():
    model = tfm.build_transformer({**MODEL, "layer_ffn": [48, 0]})
    with pytest.raises(ValueError, match="layer_ffn"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
