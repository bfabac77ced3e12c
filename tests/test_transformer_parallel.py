"""Transformer + tp/ep/sp parallelism: sharded runs must match unsharded.

All on the 8-device virtual CPU platform (conftest).  float32 compute so
parity tolerances are tight.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tensorflowonspark_tpu.models import transformer as tfm
from tensorflowonspark_tpu.parallel import dp as dplib
from tensorflowonspark_tpu.parallel import ep as eplib
from tensorflowonspark_tpu.parallel import mesh as meshlib
from tensorflowonspark_tpu.parallel import tp as tplib

CFG = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4, bf16=False)


def tiny_model(**over):
    cfg = {**CFG, **over}
    model = tfm.build_transformer(cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 64, (4, 16)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    return model, params, ids


def test_forward_shapes_and_finite():
    model, params, ids = tiny_model()
    logits = model.apply({"params": params}, ids)
    assert logits.shape == (4, 16, 64)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_tp_sharded_matches_replicated():
    model, params, ids = tiny_model()
    ref = model.apply({"params": params}, ids)

    mesh = meshlib.make_mesh(tp=4, dp=2)
    shardings = tplib.rule_shardings(mesh, params, tplib.TRANSFORMER_TP_RULES)
    sharded = meshlib.shard_tree(mesh, params, shardings)
    with jax.set_mesh(mesh):
        out = jax.jit(lambda p, x: model.apply({"params": p}, x))(sharded, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_tp_fsdp_composition():
    model, params, ids = tiny_model()
    ref = model.apply({"params": params}, ids)
    mesh = meshlib.make_mesh(tp=2, fsdp=2, dp=2)
    shardings = tplib.rule_shardings(mesh, params, tplib.TRANSFORMER_TP_RULES)
    shardings = tplib.compose_fsdp(mesh, params, shardings)
    sharded = meshlib.shard_tree(mesh, params, shardings)
    with jax.set_mesh(mesh):
        out = jax.jit(lambda p, x: model.apply({"params": p}, x))(sharded, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ring_attention_model_matches_flash_model():
    mesh = meshlib.make_mesh(dp=2, sp=4)
    cfg = dict(CFG, attn_impl="xla")
    base = tfm.build_transformer(cfg)
    ids = jnp.asarray(np.random.RandomState(1).randint(0, 64, (4, 32)), jnp.int32)
    params = base.init(jax.random.PRNGKey(0), ids)["params"]
    ref = base.apply({"params": params}, ids)

    ring = tfm.Transformer(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4,
        attn_impl="ring", mesh=mesh, compute_dtype=jnp.float32)
    with jax.set_mesh(mesh):
        out = jax.jit(lambda p, x: ring.apply({"params": p}, x))(params, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_moe_forward_and_aux_loss():
    model, params, ids = tiny_model(n_experts=4)
    logits, updates = model.apply({"params": params}, ids, mutable=["aux_loss"])
    assert logits.shape == (4, 16, 64)
    flat = jax.tree_util.tree_flatten_with_path(updates["aux_loss"])[0]
    lb = [leaf for path, leaf in flat
          if not any("router_z" in str(p) for p in path)]
    rz = [leaf for path, leaf in flat
          if any("router_z" in str(p) for p in path)]
    assert len(lb) == 2 and len(rz) == 2  # one of each per layer
    # Perfectly balanced routing gives load-balance loss == 1.0.
    for a in lb:
        assert 0.5 < float(a) < 4.0
    # z-loss = mean(logsumexp(logits)^2) is strictly positive and finite.
    for z in rz:
        assert 0.0 < float(z) < 100.0


def test_moe_ep_sharded_matches_replicated():
    model, params, ids = tiny_model(n_experts=4)
    ref = model.apply({"params": params}, ids, mutable=["aux_loss"])[0]
    mesh = meshlib.make_mesh(ep=4, dp=2)
    shardings = tplib.rule_shardings(mesh, params, tplib.TRANSFORMER_TP_RULES)
    sharded = meshlib.shard_tree(mesh, params, shardings)
    with jax.set_mesh(mesh):
        out = jax.jit(lambda p, x: model.apply(
            {"params": p}, x, mutable=["aux_loss"])[0])(sharded, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def gshard_einsum_moe(params, x, *, top_k, capacity_factor):
    """The classic GShard one-hot formulation of ``MoEMLP``'s capacity rule
    (``[n, e, c]`` dispatch/combine tensors, O(n·e·c) memory): the parity
    reference for the layer's index/sort dispatch, from the layer's params."""
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    n, e = xf.shape[0], params["router"]["kernel"].shape[1]
    probs = jax.nn.softmax(xf @ params["router"]["kernel"], axis=-1)
    top_p, top_idx = jax.lax.top_k(probs, top_k)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)
    capacity = max(1, math.ceil(n * capacity_factor * top_k / e))
    counts = jnp.zeros((e,), jnp.float32)
    dispatch = combine = jnp.zeros((n, e, capacity), jnp.float32)
    for j in range(top_k):
        oh = jax.nn.one_hot(top_idx[:, j], e, dtype=jnp.float32)  # [n, e]
        pos = jnp.cumsum(oh, axis=0) - oh + counts[None, :]       # [n, e]
        keep = (pos < capacity).astype(jnp.float32) * oh
        counts = counts + jnp.sum(keep, axis=0)
        slot = jax.nn.one_hot(jnp.sum(pos * oh, axis=-1).astype(jnp.int32),
                              capacity, dtype=jnp.float32)        # [n, c]
        d_j = keep[:, :, None] * slot[:, None, :]
        dispatch = dispatch + d_j
        combine = combine + d_j * top_p[:, j][:, None, None]
    expert_in = jnp.einsum("nec,nd->ecd", dispatch, xf)
    h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in,
                                params["experts_gate"]))
         * jnp.einsum("ecd,edf->ecf", expert_in, params["experts_up"]))
    out = jnp.einsum("ecf,efd->ecd", h, params["experts_down"])
    return jnp.einsum("nec,ecd->nd", combine, out).reshape(b, s, d)


def test_moe_sort_dispatch_matches_einsum_reference():
    """The index/sort-based dispatch (O(n·k) bookkeeping) must reproduce
    the classic GShard one-hot einsum formulation exactly — including which
    tokens overflow: slot assignment follows the same priority rule
    (round-major, token order, kept-only carryover)."""
    for cap_factor in (1.25, 0.4):  # ample capacity AND forced overflow
        layer = eplib.MoEMLP(d_model=8, d_ff=16, n_experts=4, top_k=2,
                             capacity_factor=cap_factor,
                             compute_dtype=jnp.float32)
        x = jnp.asarray(np.random.RandomState(7).randn(2, 12, 8), jnp.float32)
        params = layer.init(jax.random.PRNGKey(1), x)["params"]
        y_sort = jax.jit(lambda p, v: layer.apply(
            {"params": p}, v, mutable=["aux_loss"])[0])(params, x)
        y_ein = jax.jit(functools.partial(
            gshard_einsum_moe, top_k=2, capacity_factor=cap_factor))(params, x)
        np.testing.assert_allclose(np.asarray(y_sort), np.asarray(y_ein),
                                   rtol=1e-5, atol=1e-6)


def test_moe_sort_dispatch_grads_match_einsum():
    layer = eplib.MoEMLP(d_model=8, d_ff=16, n_experts=2, top_k=2,
                         capacity_factor=1.25, compute_dtype=jnp.float32)
    x = jnp.asarray(np.random.RandomState(3).randn(1, 10, 8), jnp.float32)
    params = layer.init(jax.random.PRNGKey(2), x)["params"]

    def loss_sort(p):
        y = layer.apply({"params": p}, x, mutable=["aux_loss"])[0]
        return jnp.sum(y * y)

    def loss_ein(p):
        y = gshard_einsum_moe(p, x, top_k=2, capacity_factor=1.25)
        return jnp.sum(y * y)

    g_sort, g_ein = jax.grad(loss_sort)(params), jax.grad(loss_ein)(params)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6), g_sort, g_ein)


@pytest.mark.slow
def test_moe_aux_losses_survive_remat():
    """remat=True must thread the MoE aux sows through nn.remat: a silently
    dropped load-balance/z-loss under rematerialization would detune MoE
    training unnoticed (ADVICE r3).  Loss, aux metrics and grads must match
    the remat=False model."""
    ids = jnp.asarray(np.random.RandomState(11).randint(0, 32, (2, 12)),
                      jnp.int32)
    models = {
        r: tfm.Transformer(vocab_size=32, d_model=16, n_layers=1, n_heads=2,
                           n_experts=2, attn_impl="xla",
                           compute_dtype=jnp.float32, remat=r)
        for r in (False, True)
    }
    params = models[False].init(jax.random.PRNGKey(0), ids)["params"]
    results = {}
    for r, model in models.items():
        loss_fn = tfm.make_loss_fn(model)
        (total, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, {"input_ids": ids})
        results[r] = (total, metrics, grads)
    t0, m0, g0 = results[False]
    t1, m1, g1 = results[True]
    assert float(m0["aux_loss"]) > 0.1 and float(m0["router_z_loss"]) > 0.0
    np.testing.assert_allclose(float(t1), float(t0), rtol=1e-5)
    np.testing.assert_allclose(float(m1["aux_loss"]), float(m0["aux_loss"]),
                               rtol=1e-5)
    np.testing.assert_allclose(float(m1["router_z_loss"]),
                               float(m0["router_z_loss"]), rtol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6), g1, g0)


@pytest.mark.slow
def test_tp_sharded_decode_matches_unsharded():
    """Model-parallel SERVING: greedy_generate with Megatron-TP-sharded
    params on a tp mesh must emit exactly the unsharded tokens — GSPMD
    partitions the compiled decode/prefill steps from operand shardings,
    with no decode-specific sharding code."""
    model = tfm.Transformer(vocab_size=32, d_model=16, n_layers=2, n_heads=4,
                            attn_impl="xla", compute_dtype=jnp.float32)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 32, (2, 8)),
                      jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    base = tfm.greedy_generate(model, params, ids[:, :5], max_new_tokens=4)

    mesh = meshlib.make_mesh(dp=-1, tp=4)
    shardings = tplib.rule_shardings(mesh, params, tplib.TRANSFORMER_TP_RULES)
    gparams = meshlib.shard_tree(mesh, params, shardings)
    with jax.set_mesh(mesh):
        out = tfm.greedy_generate(model, gparams, ids[:, :5], max_new_tokens=4)
    np.testing.assert_array_equal(out, base)


def test_moe_capacity_drops_overflow():
    # capacity_factor tiny -> most tokens dropped -> output far from dense,
    # but still finite and mostly zeros for dropped tokens.
    layer = eplib.MoEMLP(d_model=8, d_ff=16, n_experts=2, top_k=1,
                         capacity_factor=0.1, compute_dtype=jnp.float32)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 8, 8), jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    y = layer.apply({"params": params}, x, mutable=["aux_loss"])[0]
    assert bool(jnp.all(jnp.isfinite(y)))
    # capacity = ceil(16 * 0.1 * 1 / 2) = 1 slot per expert -> ≤2 tokens pass
    nonzero_rows = int(jnp.sum(jnp.any(y.reshape(16, 8) != 0, axis=-1)))
    assert nonzero_rows <= 2


def test_train_step_descends():
    model, params, ids = tiny_model()
    loss_fn = tfm.make_loss_fn(model)
    optimizer = optax.adam(1e-2)
    mesh = meshlib.make_mesh(dp=-1)
    state = dplib.TrainState.create(dplib.replicate(params, mesh), optimizer)
    step = dplib.make_train_step(loss_fn, optimizer)
    batch = meshlib.shard_batch(mesh, {"input_ids": np.tile(np.asarray(ids), (2, 1))})
    losses = []
    for _ in range(5):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]


def test_moe_train_step_descends():
    model, params, ids = tiny_model(n_experts=4)
    loss_fn = tfm.make_loss_fn(model)
    optimizer = optax.adam(1e-2)
    mesh = meshlib.make_mesh(dp=-1)
    state = dplib.TrainState.create(dplib.replicate(params, mesh), optimizer)
    step = dplib.make_train_step(loss_fn, optimizer)
    batch = meshlib.shard_batch(mesh, {"input_ids": np.tile(np.asarray(ids), (2, 1))})
    losses = []
    for _ in range(5):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
    assert np.isfinite(losses[-1])


def test_registry_roundtrip():
    from tensorflowonspark_tpu.models import registry

    model = registry.build({"model": "transformer", "vocab_size": 64,
                            "d_model": 32, "n_layers": 1, "n_heads": 2,
                            "bf16": False})
    assert isinstance(model, tfm.Transformer)


@pytest.mark.parametrize("seq", [16, 33])
def test_rope_shift_invariance_of_scores(seq):
    # RoPE property: q·k depends only on relative positions.
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, seq, 2, 8), jnp.float32)
    pos = jnp.arange(seq)
    q1 = tfm.apply_rope(q, pos)
    k1 = tfm.apply_rope(q, pos)
    q2 = tfm.apply_rope(q, pos + 7)
    k2 = tfm.apply_rope(q, pos + 7)
    s1 = jnp.einsum("bqhd,bkhd->bhqk", q1, k1)
    s2 = jnp.einsum("bqhd,bkhd->bhqk", q2, k2)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4)
