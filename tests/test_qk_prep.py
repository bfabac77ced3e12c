"""``ops/qk_prep.py`` (ISSUE 51): per-head RMSNorm and RoPE of q and k as one
op.  The op is held to the ``jnp`` chain it replaces (``RMSNorm`` and
``apply_rope`` of ``models/transformer.py``) forward and in every gradient,
in interpreter mode; a model is the same model with the op engaged and not;
and the paths that must not engage do not."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu import telemetry
from tensorflowonspark_tpu.models import transformer as tfm
from tensorflowonspark_tpu.ops import qk_prep as qp

D = 128
YARN = ("yarn", 4.0, 64, 32.0, 1.0, 0.0, 0.0)


def chain(x, scale, positions, scaling, eps=1e-6):
    """The specification: ``RMSNorm``'s arithmetic over each head, then
    ``apply_rope``; either left out where its argument is None."""
    if scale is not None:
        x = tfm.RMSNorm(eps).apply({"params": {"scale": scale}}, x)
    if positions is not None:
        x = tfm.apply_rope(x, positions, 10000.0, scaling)
    return x


def fused(x, scale, positions, scaling, eps=1e-6, block_s=64):
    freqs, factor = (tfm.rope_frequencies(10000.0, scaling, D)
                     if positions is not None else (None, 1.0))
    return qp.qk_prep(x, scale, positions, freqs, factor=factor, eps=eps,
                      block_s=block_s, interpret=True)


def draw(seed, shape, dtype):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape), dtype)


def counted(fn):
    """``(attn.qk_prep, attn.qk_sites)`` that ``fn`` adds."""
    names = ("attn.qk_prep", "attn.qk_sites")
    before = telemetry.snapshot()["counters"]
    fn()
    after = telemetry.snapshot()["counters"]
    return tuple(after.get(n, 0) - before.get(n, 0) for n in names)


# (query heads, K/V heads): SDAR's and Keye's, SmallThinker's, OLMoE's
HEADS = [(32, 4), (28, 4), (16, 16)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads", HEADS, ids=["32over4", "28over4", "16over16"])
@pytest.mark.parametrize("norm", [True, False], ids=["norm_rope", "rope"])
def test_the_op_is_the_jnp_chain_forward_and_in_every_gradient(
        norm, heads, dtype):
    """q and k through the op and through the chain, and the gradients of
    q, k and both norm scales under one cotangent: 40 positions in tiles of
    16 (the last tile overhangs the sequence), positions that repeat (SDAR's
    ``[x_t | x_0]``)."""
    s = 40
    positions = jnp.tile(jnp.arange(s // 2), 2)
    arrays = [draw(i, (2, s, h, D), dtype) for i, h in enumerate(heads)]
    scales = [1.0 + 0.2 * draw(7 + i, (D,), jnp.float32) if norm else None
              for i in range(2)]
    cts = [draw(3 + i, (2, s, h, D), dtype) for i, h in enumerate(heads)]

    def loss(prep, arrays, scales):
        return sum(jnp.sum(prep(x, w, positions, None).astype(jnp.float32)
                           * ct.astype(jnp.float32))
                   for x, w, ct in zip(arrays, scales, cts))

    run = lambda prep: jax.value_and_grad(  # noqa: E731
        lambda a, w: loss(prep, a, w), argnums=(0, 1))(arrays, scales)
    (got, got_grads), (want, want_grads) = (
        run(lambda *a: fused(*a, block_s=16)), run(chain))
    # bf16: the op's forward is the chain's to an ulp of the output (one
    # multiply-add ordered otherwise); its backward rounds once where the
    # chain rounds the normed q's cotangent in between
    tol = 1e-5 if dtype == jnp.float32 else 1.5e-2
    np.testing.assert_allclose(got, want, rtol=tol)
    for x, w in zip(arrays, scales):
        np.testing.assert_allclose(
            fused(x, w, positions, None, block_s=16).astype(jnp.float32),
            chain(x, w, positions, None).astype(jnp.float32),
            atol=tol, rtol=tol)
    for g, w in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        assert g.shape == w.shape
        assert float(jnp.linalg.norm(g - w)) <= tol * float(
            jnp.linalg.norm(w))
    assert len(jax.tree.leaves(got_grads)) == (4 if norm else 2)


@pytest.mark.parametrize("case", ["yarn", "norm_only", "one_tile",
                                  "default_positions"])
def test_the_op_s_other_arguments(case):
    """YaRN's stretched frequencies and its factor on cos and sin; a norm
    with no rotation; a sequence shorter than a tile; no positions given
    (the tokens' places)."""
    s = 24
    x = draw(0, (1, s, 4, D), jnp.float32)
    scale = 1.0 + 0.2 * draw(1, (D,), jnp.float32)
    ct = draw(2, x.shape, jnp.float32)
    # what the chain is given, and how the op is called
    positions = {"norm_only": None,
                 "default_positions": jnp.arange(s)}.get(case,
                                                         jnp.arange(s) * 5)
    scaling = YARN if case == "yarn" else None
    block_s = 512 if case == "one_tile" else 16

    def op(x, w):
        if case == "default_positions":     # the op is handed none
            freqs, factor = tfm.rope_frequencies(10000.0, None, D)
            return qp.qk_prep(x, w, None, freqs, factor=factor,
                              block_s=block_s, interpret=True)
        return fused(x, w, positions, scaling, block_s=block_s)

    run = lambda prep: jax.value_and_grad(  # noqa: E731
        lambda x, w: jnp.sum(prep(x, w) * ct), argnums=(0, 1))(x, scale)
    (got, got_grads), (want, want_grads) = run(op), run(
        lambda x, w: chain(x, w, positions, scaling))
    np.testing.assert_allclose(op(x, scale),
                               chain(x, scale, positions, scaling),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(g, w, atol=2e-5, rtol=2e-4)
    if case == "yarn":      # the factor is on the tables: it is not 1
        assert tfm.rope_frequencies(10000.0, YARN, D)[1] > 1.1


def test_the_op_refuses_what_the_jnp_path_keeps():
    x = draw(0, (1, 8, 2, 96), jnp.float32)
    freqs, _ = tfm.rope_frequencies(10000.0, None, 96)
    with pytest.raises(ValueError, match="128-lane"):
        qp.qk_prep(x, None, jnp.arange(8), freqs, interpret=True)
    with pytest.raises(ValueError, match="nothing to prepare"):
        qp.qk_prep(draw(0, (1, 8, 2, D), jnp.float32), interpret=True)
    assert not qp.engages(96, True, True)
    assert not qp.engages(D, False, False)
    assert qp.engages(D, False, True) and qp.engages(2 * D, True, False)


def test_the_op_runs_a_shard_under_a_mesh():
    """Under an ambient mesh the kernels run per shard of (batch, heads),
    as the flash kernels do, and the scale's gradient is summed over the
    shards."""
    from tensorflowonspark_tpu.parallel import mesh as meshlib

    mesh = meshlib.make_mesh(dp=2, tp=2, devices=jax.devices()[:4])
    s = 16
    x = draw(0, (2, s, 4, D), jnp.float32)
    scale = 1.0 + 0.2 * draw(1, (D,), jnp.float32)
    ct = draw(2, x.shape, jnp.float32)
    positions = jnp.arange(s)

    def grads(prep):
        return jax.jit(jax.value_and_grad(lambda x, w: jnp.sum(
            prep(x, w, positions, None) * ct), argnums=(0, 1)))(x, scale)

    want = grads(chain)
    with jax.set_mesh(mesh):
        got = grads(fused)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, atol=2e-5, rtol=2e-4)


# -- the model: engaged and not ----------------------------------------------

SMALL = {"vocab_size": 64, "d_model": 32, "n_layers": 2, "n_heads": 4,
         "n_kv_heads": 2, "d_head": D, "d_ff": 16, "n_experts": 4,
         "moe_top_k": 2, "moe_capacity_factor": None, "qk_norm": True,
         "qk_norm_per_head": True, "bf16": False,
         "attn_impl": "pallas_interpret"}
KEYE = {**SMALL, "sparse_attention": {"index_heads": 3, "index_head_dim": 8,
                                      "topk": 6}}
IDS = jnp.asarray(np.random.default_rng(0).integers(0, 63, (2, 16)), jnp.int32)


def _sdar(config):
    model = tfm.build_transformer(config)
    loss = tfm.make_block_diffusion_loss_fn(model, block=4, mask_id=63,
                                            vocab_chunk=32)
    return model, loss, {"input_ids": IDS,
                         "noise_seed": jnp.asarray([3, 4], jnp.uint32)}


def _keye(config):
    model = tfm.build_transformer(config)
    return model, tfm.make_sparse_loss_fn(model, vocab_chunk=32), {
        "input_ids": IDS}


def _smallthinker(config):
    model = tfm.build_transformer(config)
    return model, tfm.make_loss_fn(model, vocab_chunk=32), {"input_ids": IDS}


MODELS = {
    "sdar_like": (_sdar, SMALL, (2, 2)),
    "keye_like": (_keye, KEYE, (2, 2)),
    "keye_like_remat": (_keye, {**KEYE, "remat": True}, (2, 2)),
    # a window layer turns its keys, a global one does not and has no norm:
    # nothing to prepare there (SmallThinker's two kinds of layer)
    "smallthinker_like": (_smallthinker, {
        **SMALL, "qk_norm": False, "qk_norm_per_head": False,
        "layer_attention": [[8, True], [0, False]]}, (1, 2)),
    # the norm over the WHOLE projection stays jnp in front of the op, which
    # rotates only (OLMoE's)
    "olmoe_like": (_smallthinker, {**SMALL, "qk_norm_per_head": False},
                   (2, 2)),
}


@pytest.mark.parametrize("name", list(MODELS))
def test_a_model_is_the_same_model_with_the_op_engaged_and_not(
        name, monkeypatch):
    """Loss and every gradient leaf with the fused pass and, the kernels
    around it unchanged, with the ``jnp`` chain in its place (``engages``
    answering no); the parameters are one tree either way, and the counter
    says which sites took the pass."""
    make, config, (engaged, sites) = MODELS[name]
    model, loss_fn, batch = make(config)
    run = jax.jit(jax.value_and_grad(lambda p: loss_fn(p, batch)[0]))
    init = lambda m: m.init(jax.random.PRNGKey(0), IDS)["params"]  # noqa: E731
    params = init(model)
    assert counted(lambda: jax.eval_shape(
        lambda: init(model))) == (engaged, sites)
    got, got_grads = run(params)

    monkeypatch.setattr(tfm, "engages", lambda *a: False)
    model, loss_fn, batch = make(config)
    plain = jax.jit(jax.value_and_grad(lambda p: loss_fn(p, batch)[0]))
    assert counted(lambda: jax.eval_shape(lambda: init(model))) == (0, sites)
    assert jax.tree.structure(init(model)) == jax.tree.structure(params)
    want, want_grads = plain(params)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    flat = jax.tree_util.tree_flatten_with_path(want_grads)[0]
    for (path, w), g in zip(flat, jax.tree.leaves(got_grads)):
        np.testing.assert_allclose(
            g, w, atol=2e-6 + 2e-4 * float(jnp.abs(w).max()), rtol=2e-4,
            err_msg=jax.tree_util.keystr(path))
    scales = [w for path, w in flat if "q_norm" in jax.tree_util.keystr(path)]
    if config["qk_norm"]:
        assert scales and all(float(jnp.abs(w).max()) > 0 for w in scales)


@pytest.mark.parametrize("overrides", [
    {"d_head": 96},
    {"latent_attention": {"kv_lora_rank": 16, "qk_nope_head_dim": D,
                          "qk_rope_head_dim": 64, "v_head_dim": D},
     "qk_norm": False, "qk_norm_per_head": False},
    {"layer_attention": [[0, False], [0, False]], "qk_norm": False,
     "qk_norm_per_head": False},
    {"attn_impl": "xla"},
], ids=["d_head_96", "latent", "no_rope_no_norm", "xla"])
def test_the_paths_that_must_not_engage_do_not(overrides):
    model = tfm.build_transformer({**SMALL, **overrides})
    took, sites = counted(lambda: jax.eval_shape(
        model.init, jax.random.PRNGKey(0), IDS))
    assert took == 0 and sites == (0 if "latent_attention" in overrides
                                   else 2)


def test_the_cache_path_does_not_engage():
    attn = tfm.Attention(4, D, attn_impl="pallas_interpret", decode=True,
                         max_decode_len=8, qk_norm=True,
                         qk_norm_per_head=True)
    x = jnp.zeros((1, 4, 32), jnp.bfloat16)
    assert counted(lambda: jax.eval_shape(
        attn.init, jax.random.PRNGKey(0), x)) == (0, 1)
