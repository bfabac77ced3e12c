"""OLMoE's layer in the program against the plain reference kept with the
benchmark (``benchmark/configs/olmoe_1b_7b_d1.py``): dropless top-k routing
without renormalised weights, QK-norm, the published epsilon, both auxiliary
terms.  Small, float32, on the CPU; the same comparison runs at the
published widths on the chip (the configuration's ``check_train``).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from tensorflowonspark_tpu.models import transformer as tfm
from tensorflowonspark_tpu.ops.grouped_matmul import (executed_rows,
                                                       grouped_matmul)
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


def _routing(name, n=40, k=3, e=8):
    """``[n, k]`` expert choices: uneven groups that are no multiple of any
    tile, five empty groups of eight, or every group alike."""
    if name == "random":
        rng = np.random.default_rng(3)
        return np.stack([rng.permutation(e)[:k] for _ in range(n)])
    if name == "two_experts":       # the rest get no pair at all
        return np.tile(np.array([[5, 2, 6]]), (n, 1))
    return (np.arange(n)[:, None] + np.arange(k)[None]) % e


ROUTINGS = ["random", "two_experts", "one_each"]


@pytest.mark.parametrize("routing", ROUTINGS)
def test_sort_and_its_inverse_place_every_pair_once(routing):
    top_idx = _routing(routing)
    n, k = top_idx.shape
    order, row_of_pair = (np.asarray(a) for a in eplib._sorted_layout(
        jnp.asarray(top_idx, jnp.int32)))
    # every pair has one row and that row holds it; rows are in expert
    # order and, within an expert, in token order
    rows = row_of_pair.ravel()
    assert sorted(rows) == list(range(n * k))
    np.testing.assert_array_equal(order[rows], np.arange(n * k))
    expert_of_row = top_idx.ravel()[order]
    assert (np.diff(expert_of_row) >= 0).all()
    same = np.diff(expert_of_row) == 0
    assert (np.diff(order // k)[same] >= 0).all()


@pytest.mark.parametrize("impl", ["pallas_interpret", "xla"])
@pytest.mark.parametrize("routing,d,f", [
    *((routing, 32, 48) for routing in ROUTINGS),
    ("random", 4096, 136),      # a contraction in two tiles, odd lanes
], ids=[*ROUTINGS, "wide"])
def test_grouped_matmul_and_both_cotangents_match_a_loop_over_experts(
        routing, d, f, impl):
    """bf16 operands against a dense float32 product per expert: the
    forward, the rows' cotangent and every expert's weight cotangent (zero
    for an expert with no row), each to bf16's rounding of its own size."""
    e = 8
    sizes = np.bincount(_routing(routing, n=100).ravel(), minlength=e)
    m = int(sizes.sum())                       # 300: no multiple of a tile
    rng = np.random.default_rng(4)
    rows, w, ct = (jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
                   for shape in ((m, d), (e, d, f), (m, f)))

    def loop(rows, w):
        rows, w = rows.astype(jnp.float32), w.astype(jnp.float32)
        bounds = np.concatenate([[0], np.cumsum(sizes)])
        return jnp.concatenate([rows[lo:hi] @ w[g] for g, (lo, hi)
                                in enumerate(zip(bounds[:-1], bounds[1:]))])

    def system(rows, w):
        return grouped_matmul(rows, w, jnp.asarray(sizes, jnp.int32),
                              impl=impl)

    want, want_vjp = jax.vjp(loop, rows, w)
    got, got_vjp = jax.vjp(system, rows, w)
    assert got.dtype == jnp.bfloat16 and got.shape == (m, f)
    d_rows, d_w = got_vjp(ct)
    for a, b in zip((got, d_rows, d_w),
                    (want, *want_vjp(ct.astype(jnp.float32)))):
        assert a.shape == b.shape
        assert _rel(a, b) < 2 ** -7, (routing, impl, _rel(a, b))
    assert not np.asarray(d_w, np.float32)[sizes == 0].any()
    assert (sizes == 0).any() == (routing == "two_experts")


@pytest.mark.parametrize("sizes,tile_visits", [
    ([256] * 4, 4), ([100, 0, 156, 768], 6), ([1, 1, 1, 1021], 7)])
def test_executed_rows_counts_a_tile_once_for_every_group_in_it(
        sizes, tile_visits):
    """1024 rows in tiles of 256: four visits when every group ends on a
    tile boundary, one more for each group that starts inside a tile, and
    one for an empty group."""
    assert int(executed_rows(jnp.asarray(sizes, jnp.int32), 1024)) == (
        256 * tile_visits)


@pytest.fixture(scope="module")
def one_chip():
    """A DESCRIBED v5e (nothing runs, no chip needed), inside a fixture and
    never at import: one process at a time may load the TPU library (the
    on-chip-measurement guide)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: not here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("pairs,d,f,dtype", [
    (65536, 2048, 1024, jnp.bfloat16),      # the cell's step
    (32768, 2048, 1024, jnp.bfloat16),      # its reference check
    (65536, 2048, 1024, jnp.float32),       # the same model with bf16 off
    (1000, 4096, 1536, jnp.bfloat16),       # a contraction in two tiles
], ids=["cell", "check", "float32", "wide"])
def test_kernels_fit_a_v5e_at_olmoes_widths(one_chip, pairs, d, f, dtype):
    """All three products of the up and of the down projection are Mosaic
    kernels the chip's compiler accepts (tiles inside VMEM), at 64 experts."""
    from jax.experimental.compilation_cache import compilation_cache

    def spec(*shape, dtype=dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(rows, w_up, w_down, sizes):
        h = grouped_matmul(rows, w_up, sizes, impl="pallas")
        out = grouped_matmul(h, w_down, sizes, impl="pallas")
        return jnp.sum(out.astype(jnp.float32))

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without a chip: keep it out of the cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        hlo = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
            spec(pairs, d), spec(64, d, f), spec(64, f, d),
            spec(64, dtype=jnp.int32)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 6
    assert " while(" not in hlo


def test_kernels_fit_a_v5e_at_sdars_widths(one_chip):
    """ISSUE 31's cell: attention over 8192 positions under the
    block-diffusion mask with 32 query heads on 4 K/V heads (K and V are
    never repeated: the kernels' operands keep 4 heads), and the grouped
    matmul of one piece of held pairs (16,384 rows, 16 experts, gate and up
    as one product), all Mosaic kernels the chip's compiler accepts."""
    from jax.experimental.compilation_cache import compilation_cache

    from tensorflowonspark_tpu.ops.attention import flash_attention

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def attention(q, k, v):
        out = flash_attention(q, k, v, causal=False, impl="pallas",
                              block_diffusion=(4096, 4))
        return jnp.sum(out.astype(jnp.float32))

    def experts(rows, w_gate_up, w_down, sizes):
        gate_up = grouped_matmul(rows, w_gate_up, sizes, impl="pallas")
        h = jax.nn.silu(gate_up[:, :768]) * gate_up[:, 768:]
        out = grouped_matmul(h, w_down, sizes, impl="pallas")
        return jnp.sum(out.astype(jnp.float32))

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        attn = jax.jit(jax.value_and_grad(attention, argnums=(0, 1, 2))).lower(
            spec(1, 8192, 32, 128), spec(1, 8192, 4, 128),
            spec(1, 8192, 4, 128)).compile()
        moe = jax.jit(jax.value_and_grad(experts, argnums=(0, 1, 2))).lower(
            spec(16384, 2048), spec(16, 2048, 1536), spec(16, 768, 2048),
            spec(16, dtype=jnp.int32)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    hlo = attn.as_text()
    kernels = [ln for ln in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    # the forward and the one-pass backward
    assert len(kernels) == 2 and " while(" not in hlo
    # no [2L, 2L] mask or bias, and no K or V at 32 heads: the program's
    # temporaries are a few copies of q (64 MB each), not the 128 MB+ of a
    # mask nor 2 x 64 MB of repeated K and V on top
    assert "8192,8192" not in hlo
    assert all("bf16[4,8192,128]" in ln for ln in kernels), kernels[0][:400]
    assert moe.count('custom_call_target="tpu_custom_call"') == 6


def test_dropless_under_tp_matches_and_under_ep_says_what_is_missing():
    model = _system()
    params, ids = _params(skew=True), _ids(1, (4, 16))
    mesh = meshlib.make_mesh(dp=-1, tp=2)
    shardings = tplib.rule_shardings(mesh, params, tplib.TRANSFORMER_TP_RULES)
    sharded = meshlib.shard_tree(mesh, params, shardings)
    # forward and, through the shard_map around the grouped matmuls, the
    # gradient of every parameter leaf
    def out_and_grads(p, x):
        def f(p):
            out = model.apply({"params": p}, x)
            return jnp.sum(jnp.sin(out)), out
        (_, out), grads = jax.value_and_grad(f, has_aux=True)(p)
        return out, grads

    ref, ref_grads = jax.jit(out_and_grads)(params, ids)
    with jax.set_mesh(mesh):
        out, grads = jax.jit(out_and_grads)(sharded, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    assert max(jax.tree.leaves(jax.tree.map(_rel, grads, ref_grads))) < TOL
    with jax.set_mesh(meshlib.make_mesh(dp=-1, ep=2)):
        with pytest.raises(NotImplementedError, match="ragged all-to-all"):
            jax.jit(lambda p, x: model.apply({"params": p}, x))(params, ids)
