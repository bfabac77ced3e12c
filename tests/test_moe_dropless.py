"""OLMoE's layer in the program against the plain reference kept with the
benchmark (``benchmark/configs/olmoe_1b_7b_d1.py``): dropless top-k routing
without renormalised weights, QK-norm, the published epsilon, both auxiliary
terms.  Small, float32, on the CPU; the same comparison runs at the
published widths on the chip (the configuration's ``check_train``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from tensorflowonspark_tpu.models import transformer as tfm
from tensorflowonspark_tpu.parallel import ep as eplib
from tensorflowonspark_tpu.parallel import mesh as meshlib
from tensorflowonspark_tpu.parallel import tp as tplib

OLMOE = common.load_module("configs", "olmoe_1b_7b_d1")

# OLMoE's shape in small: 2 layers, 8 experts, 3 a token.
CFG = {"hidden_size": 32, "intermediate_size": 16, "num_attention_heads": 2,
       "num_key_value_heads": 2, "num_hidden_layers": 2, "num_experts": 8,
       "num_experts_per_tok": 3, "vocab_size": 64, "norm_topk_prob": False,
       "qk_norm": True, "rms_norm_eps": 1e-5, "rope_theta": 10000,
       "router_aux_loss_coef": 0.01, "router_z_loss_coef": 0.001,
       "vocab_chunk": 16, "attn_impl": "xla"}

# System and reference both compute in float32 here and differ only in the
# order of their sums (one sort and a batched matmul over blocks against a
# loop over all experts; a fused blockwise loss against whole logits):
# measured 2e-7 to 3e-6 relative on these sizes.  1e-4 leaves that a factor
# of thirty and is a tenth of the smallest change a wrong routing rule makes
# below (the first-choice load-balance term moves the loss by 1.1e-3).
TOL = 1e-4


def _system(**overrides):
    conf = {**OLMOE.system_config(CFG), "bf16": False, **overrides}
    return tfm.build_transformer(conf)


def _ids(seed=0, shape=(2, 24)):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], shape), jnp.int32)


def _params(seed=0, skew=False):
    params = _system().init(jax.random.PRNGKey(seed), _ids())["params"]
    if not skew:
        return params
    # Uneven on purpose: every embedding shares a direction u, and the
    # routers of both layers favour experts 0 and 1 along it and shun
    # expert 7: most tokens send two of their three pairs to the same two
    # experts, and in each layer some expert gets no token at all.
    d = CFG["hidden_size"]
    u = jnp.asarray(np.random.default_rng(5).standard_normal(d), jnp.float32)
    params = jax.tree.map(lambda x: x, params)
    params["embed"]["embedding"] = params["embed"]["embedding"] + 0.1 * u
    for layer in range(CFG["num_hidden_layers"]):
        router = params[f"block_{layer}"]["moe"]["router"]
        kernel = router["kernel"]
        kernel = kernel.at[:, 0].add(0.5 * u).at[:, 1].add(0.4 * u)
        router["kernel"] = kernel.at[:, 7].add(-2.0 * u)
    return params


def _reference(params, ids, cfg=CFG):
    def f(params):
        logits, aux, routing = OLMOE.reference_forward(cfg, params, ids)
        return OLMOE.reference_loss(cfg, logits, aux, ids), (logits, routing)
    (loss, (logits, routing)), grads = jax.value_and_grad(
        f, has_aux=True)(params)
    return loss, logits, grads, routing


def _loss_and_grads(model):
    loss_fn = tfm.make_loss_fn(
        model, aux_loss_coef=CFG["router_aux_loss_coef"],
        router_z_coef=CFG["router_z_loss_coef"],
        vocab_chunk=CFG["vocab_chunk"])
    return jax.jit(jax.value_and_grad(loss_fn, has_aux=True))


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def _errors(model, params, ids, ref, grads=True):
    ref_loss, ref_logits, ref_grads, _ = ref
    (loss, metrics), sys_grads = _loss_and_grads(model)(params,
                                                        {"input_ids": ids})
    logits = model.apply({"params": params}, ids)
    out = {"loss": abs(float(loss) - float(ref_loss)) / abs(float(ref_loss)),
           "logits": _rel(logits, ref_logits)}
    if grads:
        out["grads"] = max(jax.tree.leaves(
            jax.tree.map(_rel, sys_grads, ref_grads)))
    return out, metrics


@pytest.mark.parametrize("skew", [False, True], ids=["even", "uneven"])
def test_system_matches_the_reference(skew):
    """Logits, the loss with both auxiliary terms, and every parameter
    leaf's gradient."""
    params, ids = _params(skew=skew), _ids(1)
    ref = _reference(params, ids)
    errors, metrics = _errors(_system(), params, ids, ref)
    assert max(errors.values()) < TOL, errors
    pairs = np.stack([np.bincount(np.asarray(r).ravel(), minlength=8)
                      for r in ref[3]])                     # [layers, e]
    mean = ids.size * CFG["num_experts_per_tok"] / CFG["num_experts"]
    np.testing.assert_allclose(float(metrics["moe_max_load"]),
                               (pairs.max(1) / mean).mean(), rtol=1e-6)
    np.testing.assert_allclose(float(metrics["moe_min_load"]),
                               (pairs.min(1) / mean).mean(), rtol=1e-6)
    if skew:    # most tokens at two experts, one expert with none
        assert (pairs[:, :2] > 0.7 * ids.size).all(), pairs
        assert (pairs.min(1) == 0).all(), pairs
        assert float(metrics["moe_min_load"]) == 0.0
        assert float(metrics["moe_max_load"]) > 2.3


def test_routing_changes_neither_shapes_nor_the_program():
    """Even and uneven routing run the one compiled program."""
    step = _loss_and_grads(_system())
    ids = _ids(1)
    outs = [step(_params(skew=s), {"input_ids": ids}) for s in (False, True)]
    assert step._cache_size() == 1
    assert (jax.tree.map(jnp.shape, outs[0])
            == jax.tree.map(jnp.shape, outs[1]))
    assert all(bool(jnp.isfinite(loss)) for (loss, _m), _g in outs)


# Each of these is "another model": what the parent computed, or what a
# careless port would.  The system configured that way must FAIL the
# tolerance against the reference: the capacity rule on a batch routed
# unevenly enough to overflow it, the others on the seeded weights as they
# are.
@pytest.mark.parametrize("overrides,skew,drop_qk,moved", [
    ({"moe_capacity_factor": 1.25}, True, False, "logits"),
    ({"moe_norm_topk_prob": True}, False, False, "logits"),
    ({"qk_norm": False}, False, True, "logits"),
    # capacity n: nothing is dropped, so the logits agree, but the capacity
    # rule's load-balance term counts a token's first choice only
    ({"moe_capacity_factor": 8 / 3}, False, False, "loss"),
], ids=["capacity_1.25", "renormalised_weights", "no_qk_norm",
        "first_choice_load_balance"])
def test_another_routing_rule_or_no_qk_norm_fails_the_tolerance(
        overrides, skew, drop_qk, moved):
    params, ids = _params(skew=skew), _ids(1)
    ref = _reference(params, ids)
    sys_params = params
    if drop_qk:
        sys_params = jax.tree.map(lambda x: x, params)
        for layer in range(CFG["num_hidden_layers"]):
            attn = sys_params[f"block_{layer}"]["attn"]
            del attn["q_norm"], attn["k_norm"]
    errors, _ = _errors(_system(**overrides), sys_params, ids, ref,
                        grads=False)
    assert errors[moved] > 10 * TOL, errors
    if moved == "loss":
        assert errors["logits"] < TOL, errors


def test_a_configuration_without_the_new_keys_builds_the_old_model():
    model = tfm.build_transformer({"vocab_size": 64, "d_model": 32,
                                   "n_layers": 1, "n_heads": 2,
                                   "n_experts": 4})
    assert (model.norm_eps, model.qk_norm, model.moe_capacity_factor,
            model.moe_norm_topk_prob) == (1e-6, False, 1.25, True)
    params = model.init(jax.random.PRNGKey(0), _ids())["params"]
    assert "q_norm" not in params["block_0"]["attn"]
    dense = tfm.build_transformer({"vocab_size": 64, "d_model": 32,
                                   "n_layers": 1, "n_heads": 2})
    dense_params = dense.init(jax.random.PRNGKey(0), _ids())["params"]
    _loss, metrics = tfm.make_loss_fn(dense)(dense_params,
                                             {"input_ids": _ids()})
    assert set(metrics) == {"lm_loss", "aux_loss", "router_z_loss"}


def test_prefill_then_decode_through_the_cache_matches_the_full_forward():
    """QK-norm is applied on the cache path too (or decoding silently
    computes another model), and dropless routing takes one token."""
    model = _system()
    params, ids = _params(skew=True), _ids(2, (2, 12))
    full = model.apply({"params": params}, ids)
    decoder = model.clone(decode=True, max_decode_len=12)
    cache = jax.tree.map(jnp.zeros_like, decoder.init(
        jax.random.PRNGKey(0), jnp.zeros((2, 1), jnp.int32))["cache"])

    @jax.jit
    def step(cache, tokens):
        logits, mutated = decoder.apply({"params": params, "cache": cache},
                                        tokens, mutable=["cache"])
        return mutated["cache"], logits

    cache, logits = step(cache, ids[:, :7])                 # prefill
    pieces = [logits]
    for t in range(7, 12):                                  # one at a time
        cache, logits = step(cache, ids[:, t:t + 1])
        pieces.append(logits)
    assert _rel(jnp.concatenate(pieces, axis=1), full) < TOL


@pytest.mark.parametrize("routing", ["random", "two_experts", "one_each"])
def test_block_layout_places_every_pair_once(routing):
    n, k, e, block = 40, 3, 8, 8
    rng = np.random.default_rng(3)
    if routing == "random":
        top_idx = np.stack([rng.permutation(e)[:k] for _ in range(n)])
    elif routing == "two_experts":      # the rest get no pair at all
        top_idx = np.tile(np.array([[5, 2, 6]]), (n, 1))
    else:
        top_idx = (np.arange(n)[:, None] + np.arange(k)[None]) % e
    sizes = np.bincount(top_idx.ravel(), minlength=e)
    block_expert, pair_of_row, valid, row_of_pair = (
        np.asarray(a) for a in eplib._block_layout(
            jnp.asarray(top_idx, jnp.int32), jnp.asarray(sizes, jnp.int32),
            block))
    assert len(block_expert) == n * k // block + e
    # every pair has one row, that row holds it, and the row's block is its
    # expert's; within an expert rows follow token order
    rows = row_of_pair.ravel()
    assert len(set(rows)) == n * k and valid[rows].all()
    assert valid.sum() == n * k
    np.testing.assert_array_equal(pair_of_row[rows], np.arange(n * k))
    np.testing.assert_array_equal(block_expert[rows // block],
                                  top_idx.ravel())
    for expert in range(e):
        mine = np.sort(rows[top_idx.ravel() == expert])
        np.testing.assert_array_equal(pair_of_row[mine] // k,
                                      np.sort(pair_of_row[mine] // k))
        if len(mine):
            assert mine[0] % block == 0
            np.testing.assert_array_equal(np.diff(mine), 1)


def test_dropless_under_tp_matches_and_under_ep_says_what_is_missing():
    model = _system()
    params, ids = _params(skew=True), _ids(1, (4, 16))
    ref = model.apply({"params": params}, ids)
    mesh = meshlib.make_mesh(dp=-1, tp=2)
    shardings = tplib.rule_shardings(mesh, params, tplib.TRANSFORMER_TP_RULES)
    sharded = meshlib.shard_tree(mesh, params, shardings)
    with jax.set_mesh(mesh):
        out = jax.jit(lambda p, x: model.apply({"params": p}, x))(sharded, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    with jax.set_mesh(meshlib.make_mesh(dp=-1, ep=2)):
        with pytest.raises(NotImplementedError, match="ragged all-to-all"):
            jax.jit(lambda p, x: model.apply({"params": p}, x))(params, ids)
