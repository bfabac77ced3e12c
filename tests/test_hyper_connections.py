"""Hyper-connections (mHC, ISSUE 45): ``Transformer.hyper`` against the
equations written out in the plain reference of
``benchmark/configs/xing4_29b_a4b_d5_tp8_ep8.py``, at a small size on the
CPU, float32 on both sides: the maps, one wrapped sub-layer, the gradient
through the 20 Sinkhorn rounds, that one stream is today's ``Block``, and
that the eight chips' shares of a layer add up to the uncut layer."""

from __future__ import annotations

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from tensorflowonspark_tpu.models import transformer as tfm

N, C = 4, 32
HYPER = (N, 20, 1e-6, -30.0, 30.0)
CFG = {"hc_mult": N, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
       "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
       "rms_norm_eps": 1e-6}


@pytest.fixture(scope="module")
def xing():
    return common.load_module("configs", "xing4_29b_a4b_d5_tp8_ep8")


def _maps_and_streams(seed: int = 0, tokens: int = 24):
    """A hyper-connection whose maps DEPEND on the token (alpha 1, biases
    drawn) and streams that differ from one another."""
    keys = jax.random.split(jax.random.key(seed), 4)
    x = jax.random.normal(keys[0], (2, tokens, N * C), jnp.float32)
    maps = tfm.HyperConnection(*HYPER[:3], HYPER[3:])
    params = maps.init(keys[1], x)["params"]
    params = {**params, "alpha": jnp.array([1.0, 0.8, 0.6]),
              "bias": 0.5 * jax.random.normal(keys[2], (2 * N + N * N,)),
              "norm_scale": 1.0 + 0.1 * jax.random.normal(keys[3], (N * C,))}
    return maps, params, x


def test_the_maps_are_the_written_out_equations_and_doubly_stochastic(xing):
    maps, params, x = _maps_and_streams()
    (h_pre, h_post, h_res), sown = maps.apply({"params": params}, x,
                                              mutable=["hc_stats"])
    ref_pre, ref_post, ref_res = xing.reference_hyper_maps(
        CFG, params, x.reshape(2, -1, N, C))
    # the program keeps tokens on the minor axes: [n, B, S], [row, column, ..]
    np.testing.assert_allclose(h_pre.transpose(1, 2, 0), ref_pre, atol=2e-6)
    np.testing.assert_allclose(h_post.transpose(1, 2, 0), ref_post, atol=2e-6)
    np.testing.assert_allclose(h_res.transpose(2, 3, 0, 1), ref_res,
                               atol=2e-6)
    assert float(jnp.std(ref_res[..., 0, 1])) > 0.02       # by token
    np.testing.assert_allclose(h_res.sum(1), 1.0, atol=1e-4)    # rows
    np.testing.assert_allclose(h_res.sum(0), 1.0, atol=1e-4)    # columns
    stats = {k: float(v[0]) for k, v in sown["hc_stats"].items()}
    assert stats["res_row_err"] < 1e-4 and stats["res_col_err"] < 1e-4
    assert 0.0 < stats["pre_mean"] < 1.0


def test_a_wrapped_sub_layer_and_its_gradients_are_the_reference_s(xing):
    """``x' = H_res x + H_postᵀ F(H_pre x)`` and the gradients of a scalar
    of it by the streams, by ``phi``, ``alpha``, ``bias`` and the norm's
    weight (so: through the 20 rounds) and by ``F``'s weight."""
    maps, params, x = _maps_and_streams(1)
    w = jax.random.normal(jax.random.key(9), (C, C)) / np.sqrt(C)
    probe = jax.random.normal(jax.random.key(10), x.shape)

    def program(params, w, x):
        h_pre, h_post, h_res = maps.apply({"params": params}, x)
        y = jnp.tanh(tfm.hc_read(x, h_pre) @ w)
        return tfm.hc_write(x, y, h_post, h_res)

    def reference(params, w, x):
        out, _ = xing.reference_hyper(
            CFG, params, x.reshape(2, -1, N, C),
            lambda u: (jnp.tanh(u @ w), None))
        return out.reshape(x.shape)

    np.testing.assert_allclose(program(params, w, x),
                               reference(params, w, x), atol=3e-6)
    grads = [jax.grad(lambda *a: jnp.sum(f(*a) * probe), argnums=(0, 1, 2))(
        params, w, x) for f in (program, reference)]
    for own, ref in zip(jax.tree.leaves(grads[0]), jax.tree.leaves(grads[1])):
        assert float(jnp.max(jnp.abs(ref))) > 1e-3
        np.testing.assert_allclose(own, ref, atol=2e-4, rtol=2e-4)


BLOCK = dict(vocab_size=64, d_model=C, n_layers=2, n_heads=4, d_ff=48,
             attn_impl="xla", bf16=False)
HYPER_CONFIG = {"hc_mult": N, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
                "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30}


def test_one_stream_is_todays_block_and_the_trees_differ_by_the_maps():
    """Without ``hyper`` the model is the one it was (its tree is held to the
    parent's by ``tests/benchmark/test_benchmark_nemotron.py``); with it the
    tree gains ``hc_attn`` and ``hc_mlp`` a layer and nothing else, and maps
    that read nothing (H_pre 1, H_post 1, H_res 1 over ONE stream) give the
    plain layer's output."""
    ids = jax.random.randint(jax.random.key(0), (2, 16), 0, 64)
    plain = tfm.build_transformer(BLOCK)
    hyper = tfm.build_transformer({**BLOCK, "hyper_connections": HYPER_CONFIG})
    p_plain = plain.init(jax.random.key(1), ids)["params"]
    p_hyper = hyper.init(jax.random.key(1), ids)["params"]
    for name in ("block_0", "block_1"):
        extra = set(p_hyper[name]) - set(p_plain[name])
        assert extra == {"hc_attn", "hc_mlp"}
        rest = {k: v for k, v in p_hyper[name].items() if k not in extra}
        assert (jax.tree.map(jnp.shape, rest)
                == jax.tree.map(jnp.shape, p_plain[name]))
        assert p_hyper[name]["hc_attn"]["phi"].shape == (N * C, 2 * N + N * N)
    assert set(p_hyper) == set(p_plain)
    # one stream: sigmoid(big) = 1 = H_pre, 2 sigmoid(0) = 1 = H_post, and
    # the rounds make a 1 x 1 H_res 1
    one = tfm.build_transformer({**BLOCK, "hyper_connections": {
        **HYPER_CONFIG, "hc_mult": 1}})
    p_one = jax.tree.map(lambda x: x, p_plain)
    for name in ("block_0", "block_1"):
        for maps in ("hc_attn", "hc_mlp"):
            p_one[name][maps] = {
                "phi": jnp.zeros((C, 3)), "alpha": jnp.zeros((3,)),
                "bias": jnp.array([40.0, 0.0, 0.0]),
                "norm_scale": jnp.ones((C,))}
    np.testing.assert_allclose(one.apply({"params": p_one}, ids),
                               plain.apply({"params": p_plain}, ids),
                               atol=2e-5)


@pytest.mark.parametrize("what,build,error", [
    ("decode", lambda: tfm.build_transformer(
        {**BLOCK, "hyper_connections": HYPER_CONFIG}).clone(
            decode=True, max_decode_len=8), "no cache of n streams"),
    ("layer_mixer", lambda: tfm.build_transformer(
        {**BLOCK, "hyper_connections": HYPER_CONFIG, "layer_mixer": ["*", "E"],
         "moe_capacity_factor": None}), "no MixerBlock"),
    ("sparse", lambda: tfm.build_transformer(
        {**BLOCK, "hyper_connections": HYPER_CONFIG, "sparse_attention": {
            "index_heads": 2, "index_head_dim": 8, "topk": 4}}),
     "no indexer"),
    ("ring", lambda: tfm.build_transformer(
        {**BLOCK, "hyper_connections": HYPER_CONFIG, "attn_impl": "ring"}),
     "no ring attention"),
    ("block_diffusion", lambda: tfm.build_transformer(
        {**BLOCK, "hyper_connections": HYPER_CONFIG}).init(
            jax.random.key(0), jnp.zeros((1, 16), jnp.int32), None, (8, 4)),
     "block-diffusion loss runs one residual stream"),
    ("block_diffusion_loss", lambda: tfm.make_block_diffusion_loss_fn(
        tfm.build_transformer({**BLOCK, "hyper_connections": HYPER_CONFIG}),
        4, 63), "one residual stream and one pass of the head"),
    ("rope_scaling", lambda: tfm.build_transformer(
        {**BLOCK, "rope_scaling": {"type": "linear", "factor": 4,
                                   "original_max_position_embeddings": 16}}),
     "rope_scaling type 'linear'"),
    ("mtp_block_diffusion_loss", lambda: tfm.make_block_diffusion_loss_fn(
        tfm.build_transformer({**BLOCK, "num_nextn_predict_layers": 1}),
        4, 63), "one residual stream and one pass of the head"),
    ("mtp_sparse", lambda: tfm.build_transformer(
        {**BLOCK, "num_nextn_predict_layers": 1, "sparse_attention": {
            "index_heads": 2, "index_head_dim": 8, "topk": 4}}),
     "ONE multi-token-prediction module"),
    # the builder refuses such a model; the loss must too, whoever built it
    ("mtp_sparse_loss", lambda: tfm.make_sparse_loss_fn(
        types.SimpleNamespace(hyper=None, mtp_layers=1, n_experts=0)),
     "one residual stream and one pass of the head"),
    ("mtp_depth_2", lambda: tfm.build_transformer(
        {**BLOCK, "num_nextn_predict_layers": 2}),
     "ONE multi-token-prediction module"),
    ("query_latent", lambda: tfm.build_transformer(
        {**BLOCK, "q_lora_rank": 16}), "q_lora_rank=16 without latent="),
])
def test_what_the_code_cannot_compute_says_so_by_name(what, build, error):
    """At BUILD time (or at the first line of the call for a mask that is a
    call's argument), not deep in a trace."""
    with pytest.raises(NotImplementedError, match=error):
        build()


def test_the_eight_shares_add_up_to_the_uncut_layer(xing):
    """The deployment's cut under hyper-connections: eight chips share a
    layer, each with 1/8 of the heads (``W_qb``, ``W_kvb`` by columns,
    ``W_o`` by rows) and 1/8 of the routed experts (``moe_held``); ``W_qa``,
    ``W_kva``, the norms, the maps, the router and the shared expert are on
    every chip alike.  A sub-layer's output is a SUM over heads or experts,
    and ``x' = H_res x + H_postᵀ F(H_pre x)`` is linear in ``F``'s output: so
    the chips' parts of ``F``, with ``H_res x`` and the shared expert
    counted ONCE, add up to the uncut layer, sub-layer by sub-layer (the
    stage's exchange is that sum; this chip runs without it)."""
    ranks, heads, experts = 8, 8, 16
    latent = {"kv_lora_rank": 16, "qk_nope_head_dim": 8,
              "qk_rope_head_dim": 4, "v_head_dim": 8}
    router = ("sigmoid", True, 2.0)
    kwargs = dict(
        n_heads=heads, d_head=0, d_ff=24, n_experts=experts, moe_top_k=4,
        attn_impl="xla", compute_dtype=jnp.float32,
        moe_capacity_factor=None, latent=tuple(latent.values()),
        moe_router=router, moe_shared_d_ff=24, q_lora_rank=12,
        rope_scaling=("yarn", 64.0, 16, 32.0, 1.0, 1.0, 1.0), hyper=HYPER)
    whole = tfm.Block(**kwargs)
    x = jax.random.normal(jax.random.key(0), (1, 16, N * C), jnp.float32)
    variables = whole.init(jax.random.key(1), x)
    params = variables["params"]
    for maps in ("hc_attn", "hc_mlp"):      # maps that depend on the token
        params[maps]["alpha"] = jnp.ones((3,))
        params[maps]["bias"] = 0.3 * jax.random.normal(
            jax.random.key(2), (2 * N + N * N,))
    buffers = jax.tree.map(
        lambda b: 0.02 * jax.random.normal(jax.random.key(3), b.shape),
        variables["buffers"])
    uncut = whole.apply({"params": params, "buffers": buffers}, x)

    norm = lambda name, u: tfm.RMSNorm().apply(  # noqa: E731
        {"params": params[name]}, u)
    hc = lambda name, x: tfm.HyperConnection(  # noqa: E731
        *HYPER[:3], HYPER[3:]).apply({"params": params[name]}, x)

    # attention: each chip's heads, summed; H_res x once
    h_pre, h_post, h_res = hc("hc_attn", x)
    u = norm("attn_norm", tfm.hc_read(x, h_pre))
    per = heads // ranks
    attn = params["attn"]
    added = 0.0
    for rank in range(ranks):
        own = slice(rank * per, (rank + 1) * per)
        share = {**attn,
                 "q_b_proj": {"kernel": attn["q_b_proj"]["kernel"][:, own]},
                 "kv_b_proj": {"kernel": attn["kv_b_proj"]["kernel"][:, own]},
                 "o_proj": {"kernel": attn["o_proj"]["kernel"][own]}}
        added = added + tfm.Attention(
            per, 0, attn_impl="xla", compute_dtype=jnp.float32,
            latent=kwargs["latent"], q_lora_rank=12,
            rope_scaling=kwargs["rope_scaling"]).apply({"params": share}, u)
    x = tfm.hc_write(x, added, h_post, h_res)

    # the FFN: each chip's experts, summed; the shared expert and H_res x once
    from tensorflowonspark_tpu.parallel.ep import MoEMLP

    h_pre, h_post, h_res = hc("hc_mlp", x)
    u = norm("mlp_norm", tfm.hc_read(x, h_pre))
    per = experts // ranks
    added = tfm.SwiGLU(24, jnp.float32).apply({"params": params["shared"]}, u)
    for rank in range(ranks):
        own = slice(rank * per, (rank + 1) * per)
        share = {"router": params["moe"]["router"],
                 **{k: params["moe"][k][own] for k in (
                     "experts_gate", "experts_up", "experts_down")}}
        added = added + MoEMLP(
            C, 24, experts, 4, None, compute_dtype=jnp.float32,
            held=(rank * per, (rank + 1) * per), scoring="sigmoid",
            selection_bias=True, routed_scale=2.0).apply(
                {"params": share, "buffers": buffers["moe"]}, u)
    x = tfm.hc_write(x, added, h_post, h_res)
    np.testing.assert_allclose(x, uncut, atol=2e-5, rtol=2e-5)
