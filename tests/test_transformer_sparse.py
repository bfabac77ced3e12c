"""Learned sparse attention in ``models/transformer.py``: the two losses'
gradients stay apart, with and without ``remat``; the selection decides what
attention sees; ``remat`` with a static and with a data-dependent mask, and
what its policy keeps of either family of kernels (the sparse and, ISSUE 49,
the flash trio)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.models import transformer as tfm

CONFIG = {"vocab_size": 64, "d_model": 32, "n_layers": 2, "n_heads": 4,
          "n_kv_heads": 2, "d_head": 8, "d_ff": 16, "n_experts": 4,
          "moe_top_k": 2, "moe_capacity_factor": None, "qk_norm": True,
          "qk_norm_per_head": True, "bf16": False, "attn_impl": "xla",
          "sparse_attention": {"index_heads": 3, "index_head_dim": 8,
                               "topk": 6}}
IDS = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 32)), jnp.int32)


def build(**overrides):
    model = tfm.build_transformer({**CONFIG, **overrides})
    params = model.init(jax.random.PRNGKey(0), IDS)["params"]
    return model, params


def is_indexer(path) -> bool:
    return any("index_" in str(getattr(p, "key", "")) for p in path)


def split_norms(grads):
    """(largest |gradient| over the indexers' leaves, over all the others)."""
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    own = [float(jnp.max(jnp.abs(g))) for p, g in flat if is_indexer(p)]
    rest = [float(jnp.max(jnp.abs(g))) for p, g in flat if not is_indexer(p)]
    assert len(own) == 2 * 5 and rest     # q, k, norm scale and bias, w
    return max(own), max(rest)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_each_loss_reaches_its_own_parameters_and_no_others(remat):
    model, params = build(remat=remat)
    batch = {"input_ids": IDS}
    # cross-entropy and the routers' term alone: nothing for the indexers
    lm = tfm.make_sparse_loss_fn(model, aux_loss_coef=0.01, vocab_chunk=32,
                                 index_loss_coef=0.0)
    own, rest = split_norms(jax.grad(lambda p: lm(p, batch)[0])(params))
    assert own == 0.0 and rest > 1e-4
    # the indexers' term alone: nothing for anything else
    only = tfm.make_sparse_loss_fn(model, aux_loss_coef=0.01, vocab_chunk=32)
    index_loss = lambda p: only(p, batch)[1]["index_loss"]  # noqa: E731
    own, rest = split_norms(jax.grad(index_loss)(params))
    assert own > 1e-5 and rest == 0.0
    # and the sum's gradient is the two side by side
    total, metrics = only(params, batch)
    np.testing.assert_allclose(
        total, lm(params, batch)[0] + metrics["index_loss"], rtol=1e-6)
    assert float(metrics["index_loss"]) > 0.0
    # 6 keys a query but for the first five rows of each of 32 positions
    assert float(metrics["dsa_selected_pairs"]) == 6 * 32 - 15
    assert float(metrics["dsa_live_tiles"]) == 1.0


def test_topk_of_the_whole_row_is_the_causal_model():
    sparse = {**CONFIG["sparse_attention"], "topk": 32}
    model, params = build(sparse_attention=sparse)
    dense = tfm.build_transformer({k: v for k, v in CONFIG.items()
                                   if k != "sparse_attention"})
    own = jax.tree_util.tree_map_with_path(
        lambda p, x: None if is_indexer(p) else x, params)
    dense_params = jax.tree.map(lambda x: x, own, is_leaf=lambda x: x is None)
    dense_params = _drop_none(dense_params)
    np.testing.assert_allclose(model.apply({"params": params}, IDS),
                               dense.apply({"params": dense_params}, IDS),
                               atol=1e-5, rtol=1e-5)
    narrow, _ = build()
    assert float(jnp.max(jnp.abs(
        narrow.apply({"params": params}, IDS)
        - model.apply({"params": params}, IDS)))) > 1e-3


def _drop_none(tree):
    if isinstance(tree, dict):
        return {k: _drop_none(v) for k, v in tree.items() if v is not None}
    return tree


@pytest.mark.parametrize("mask", ["block_diffusion", "sparse"])
def test_remat_takes_a_static_and_a_data_dependent_mask(mask):
    """``nn.remat(Block, static_argnums=(3,))``: block diffusion's mask is a
    static tuple, the indexer's selection a traced array born in the block;
    under both, loss and gradients are those of the model without remat."""
    if mask == "sparse":
        make = lambda model: tfm.make_sparse_loss_fn(  # noqa: E731
            model, aux_loss_coef=0.01, vocab_chunk=32)
        overrides, batch = {}, {"input_ids": IDS}
    else:
        make = lambda model: tfm.make_block_diffusion_loss_fn(  # noqa: E731
            model, block=4, mask_id=63, aux_loss_coef=0.01, vocab_chunk=32)
        overrides = {"sparse_attention": None}
        batch = {"input_ids": IDS,
                 "noise_seed": jnp.asarray([3, 4], jnp.uint32)}
    plain, params = build(**overrides)
    remat, _ = build(remat=True, **overrides)
    want = jax.value_and_grad(lambda p: make(plain)(p, batch)[0])(params)
    got = jax.jit(jax.value_and_grad(lambda p: make(remat)(p, batch)[0]))(
        params)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-4)


def test_the_cache_and_the_ring_refuse_sparse_attention():
    model, params = build()
    with pytest.raises(NotImplementedError):
        model.clone(decode=True, max_decode_len=8).apply(
            {"params": params}, IDS[:, :1], mutable=["cache"])
    with pytest.raises(NotImplementedError):
        tfm.build_transformer({**CONFIG, "attn_impl": "ring"}).apply(
            {"params": params}, IDS)


def test_the_second_forward_of_a_remat_block_runs_no_sparse_kernel():
    """Five kernels a layer in a step's program (score tiles, selection,
    attention forward, the loss walk with the indexer's gradient, the
    one-pass attention backward): remat saves what
    ``ops/sparse_attention.py`` names and recomputes the rest of the block.
    Without the policy the forward's four would be there twice."""
    model, params = build(remat=True, attn_impl="pallas_interpret")
    loss = tfm.make_sparse_loss_fn(model, aux_loss_coef=0.01, vocab_chunk=32)
    batch = {"input_ids": IDS[:1]}
    program = str(jax.make_jaxpr(jax.grad(lambda p: loss(p, batch)[0]))(
        params))
    assert program.count("pallas_call[") == 5 * CONFIG["n_layers"]
    plain, _ = build(attn_impl="pallas_interpret")
    loss = tfm.make_sparse_loss_fn(plain, aux_loss_coef=0.01, vocab_chunk=32)
    program = str(jax.make_jaxpr(jax.grad(lambda p: loss(p, batch)[0]))(
        params))
    assert program.count("pallas_call[") == 5 * CONFIG["n_layers"]


FLASH = {**CONFIG, "sparse_attention": None, "attn_impl": "pallas_interpret"}
LATENT = {"kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
          "v_head_dim": 8}


def _kernels(jaxpr) -> int:
    """The ``pallas_call``s of a jaxpr, those of the jaxprs its equations
    hold included (two layers of one shape share ONE printed jaxpr)."""
    return sum((eqn.primitive.name == "pallas_call")
               + sum(map(_kernels, jax.core.jaxprs_in_params(eqn.params)))
               for eqn in jaxpr.eqns)


@pytest.mark.parametrize("overrides,block_diffusion", [
    ({"layer_attention": [[0, False], [0, False]]}, False),
    ({"layer_attention": [[8, True], [8, True]]}, False),
    ({"latent_attention": LATENT, "qk_norm": False, "n_kv_heads": 0}, False),
    ({}, True),
], ids=["global", "window", "latent_shared_key", "block_diffusion"])
def test_the_second_forward_of_a_remat_block_runs_no_flash_kernel(
        overrides, block_diffusion):
    """The sparse test's sibling for the flash trio: two kernels a layer in
    a step's program (the forward, the one-pass backward), with ``remat`` as
    without: the policy keeps what ``ops/attention.py`` names (the forward
    kernel's output and log-sum-exp) and the block recomputes the rest.
    Without the names the forward kernel would be there twice a layer.  Loss
    and every gradient leaf are the plain model's."""
    if block_diffusion:
        make = lambda model: tfm.make_block_diffusion_loss_fn(  # noqa: E731
            model, block=4, mask_id=63, aux_loss_coef=0.01, vocab_chunk=32)
        batch = {"input_ids": IDS, "noise_seed": jnp.asarray([3, 4],
                                                             jnp.uint32)}
    else:
        make = lambda model: tfm.make_loss_fn(  # noqa: E731
            model, aux_loss_coef=0.01, vocab_chunk=32)
        batch = {"input_ids": IDS}
    plain = tfm.build_transformer({**FLASH, **overrides})
    remat = tfm.build_transformer({**FLASH, **overrides, "remat": True})
    params = plain.init(jax.random.PRNGKey(0), IDS)["params"]
    results = []
    for model in (plain, remat):
        step = jax.value_and_grad(lambda p: make(model)(p, batch)[0])
        assert _kernels(jax.make_jaxpr(step)(params).jaxpr) == 2 * FLASH[
            "n_layers"]
        results.append(jax.jit(step)(params))
    for a, b in zip(*map(jax.tree.leaves, results)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("overrides,blocks,kept", [
    ({"sparse_attention": None, "attn_impl": "pallas_interpret"}, 2, 2),
    ({"sparse_attention": None, "attn_impl": "pallas_interpret",
      "num_nextn_predict_layers": 1}, 3, 3),
    ({"attn_impl": "pallas_interpret"}, 2, 0),
    ({"sparse_attention": None}, 2, 0),
    ({"sparse_attention": None, "attn_impl": "pallas_interpret",
      "remat": False}, 0, 0),
], ids=["flash", "flash_and_mtp", "sparse", "xla_scan", "no_remat"])
def test_the_run_report_counts_the_remat_blocks_that_keep_a_flash_output(
        overrides, blocks, kept):
    """``remat.blocks`` and ``remat.flash_kept``, counted as a model is
    traced: the policy's effect follows the names a block's attention emits
    (the MTP module's block is one more; a sparse layer and the XLA scan
    emit no flash name; without ``remat`` no block is counted)."""
    from tensorflowonspark_tpu import telemetry

    model = tfm.build_transformer({**CONFIG, "remat": True, **overrides})
    before = telemetry.snapshot()["counters"]
    jax.eval_shape(model.init, jax.random.PRNGKey(0), IDS)
    after = telemetry.snapshot()["counters"]
    counted = [after.get(name, 0) - before.get(name, 0)
               for name in ("remat.blocks", "remat.flash_kept")]
    assert counted == [blocks, kept]
