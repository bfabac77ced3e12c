"""A ``Block`` whose attention slot differs in KIND layer by layer (ISSUE 52):
Kimi Delta Attention (``KimiDeltaAttention`` over ``ops/kda.py``) and latent
attention without rotation, chosen by ``Transformer.layer_attention``."""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tensorflowonspark_tpu import telemetry
from tensorflowonspark_tpu.models import transformer as tfm
from tensorflowonspark_tpu.parallel import dp as dplib
from tensorflowonspark_tpu.parallel import mesh as meshlib
from tensorflowonspark_tpu.parallel import tp as tplib

LATENT = {"kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
          "v_head_dim": 8}
KDA = {"n_heads": 2, "head_dim": 8, "conv_kernel": 4, "chunk_size": 8}
BASE = {"model": "transformer", "vocab_size": 64, "d_model": 32, "n_layers": 3,
        "n_heads": 2, "d_ff": 48, "attn_impl": "xla", "bf16": False,
        "layer_attention": [[0, False, "kda"], [0, False, "latent"],
                            [0, False, "kda"]],
        "latent_attention": LATENT, "kda": KDA}
EXPERTS = {"layer_ffn": [48, 0, 0], "n_experts": 4, "moe_top_k": 2,
           "moe_capacity_factor": None, "moe_held": [0, 2],
           "moe_router": {"scoring": "sigmoid", "selection_bias": True,
                          "routed_scale": 2.0}, "moe_shared_d_ff": 16}


def _ids(seed: int = 0, shape=(2, 32)):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, 64)


def _hand_rolled_kda(p, u, h: int, d: int, eps: float):
    """The mixer's equations, one position at a time (the class's
    docstring), from its parameter tree."""
    b, length, _ = u.shape
    silu = lambda x: x / (1.0 + jnp.exp(-x))                # noqa: E731
    sigmoid = lambda x: 1.0 / (1.0 + jnp.exp(-x))           # noqa: E731

    def conv(x, kernel):
        taps = kernel.shape[0]
        padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
        return silu(sum(padded[:, t:t + length] * kernel[t]
                        for t in range(taps)))

    def heads(name):
        x = jnp.einsum("bld,dhk->blhk", u, p[f"{name}_proj"]["kernel"])
        return conv(x.reshape(b, length, h * d),
                    p[f"{name}_conv"]).reshape(b, length, h, d)

    unit = lambda x: x / jnp.sqrt(                          # noqa: E731
        jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
    pair = lambda n: (u @ p[f"{n}_a_proj"]["kernel"]        # noqa: E731
                      ) @ p[f"{n}_b_proj"]["kernel"]
    q, k, v = unit(heads("q")) * d ** -0.5, unit(heads("k")), heads("v")
    g = -jnp.exp(p["A_log"])[:, None] * jnp.logaddexp(
        pair("f") + p["dt_bias"], 0.0).reshape(b, length, h, d)
    beta = sigmoid(u @ p["b_proj"]["kernel"])
    state = jnp.zeros((b, h, d, d))
    outs = []
    for t in range(length):
        state = jnp.exp(g[:, t])[..., None] * state
        erased = v[:, t] - jnp.einsum("bhk,bhkv->bhv", k[:, t], state)
        state = state + (beta[:, t, :, None, None] * k[:, t, :, :, None]
                         * erased[:, :, None, :])
        outs.append(jnp.einsum("bhk,bhkv->bhv", q[:, t], state))
    o = jnp.stack(outs, axis=1)
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    o = o * p["o_norm"] * sigmoid(pair("g").reshape(b, length, h, d))
    return jnp.einsum("blhk,hkd->bld", o, p["o_proj"]["kernel"])


def test_the_mixer_matches_its_equations_position_by_position():
    mixer = tfm.KimiDeltaAttention(2, 8, conv=4, chunk=8, norm_eps=1e-5,
                                   compute_dtype=jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 24))
    params = mixer.init(jax.random.PRNGKey(1), u)["params"]
    # the decay's spread: a thousandth to more than a whole e a step
    step = np.asarray(-jnp.exp(params["A_log"])[:, None] * jax.nn.softplus(
        params["dt_bias"]).reshape(2, 8))
    assert step.max() < 0 and step.min() > -2.0
    got = mixer.apply({"params": params}, u)
    want = _hand_rolled_kda(params, u, 2, 8, 1e-5)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_a_model_of_both_kinds_builds_and_its_parameters_are_each_kind_s():
    model = tfm.build_transformer({**BASE, **EXPERTS})
    assert model.layer_attention == ((0, False, "kda"), (0, False, "latent"),
                                     (0, False, "kda"))
    assert model.kda == (2, 8, 4, 8)
    variables = model.init(jax.random.PRNGKey(0), _ids())
    params = variables["params"]
    kda = {"q_proj", "k_proj", "v_proj", "o_proj", "q_conv", "k_conv",
           "v_conv", "f_a_proj", "f_b_proj", "g_a_proj", "g_b_proj",
           "b_proj", "A_log", "dt_bias", "o_norm"}
    assert set(params["block_0"]["attn"]) == kda
    assert set(params["block_2"]["attn"]) == kda
    assert set(params["block_1"]["attn"]) == {"q_proj", "kv_a_proj",
                                              "kv_a_norm", "kv_b_proj",
                                              "o_proj"}
    assert "mlp" in params["block_0"] and "moe" in params["block_1"]
    assert "e_score_correction_bias" in variables["buffers"]["block_2"]["moe"]
    logits = model.apply(variables, _ids())
    assert logits.shape == (2, 32, 64) and bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("remat", [False, True])
def test_a_model_of_both_kinds_trains_a_step(remat):
    model = tfm.build_transformer({**BASE, **EXPERTS, "remat": remat})
    ids = _ids(3)
    variables = model.init(jax.random.PRNGKey(0), ids)
    optimizer = optax.adamw(1e-2)
    start = jax.device_get(variables["params"])     # the step donates them
    state = dplib.TrainState.create(variables["params"], optimizer,
                                    variables["buffers"])
    step = dplib.make_train_step(
        tfm.make_loss_fn(model, aux_loss_coef=0.0, vocab_chunk=32,
                         router_z_coef=0.0), optimizer)
    losses = []
    for _ in range(4):
        state, metrics = step(state, {"input_ids": ids})
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses
    moved = jax.tree.map(lambda a, b: float(np.abs(a - b).max()),
                         jax.device_get(state.params), start)
    for leaf in ("A_log", "dt_bias", "o_norm", "q_conv"):
        assert moved["block_0"]["attn"][leaf] > 0, leaf


def test_remat_changes_no_gradient_and_counts_what_it_keeps():
    ids = _ids(4)
    grads = {}
    counted = {}
    for remat in (False, True):
        model = tfm.build_transformer({**BASE, **EXPERTS, "remat": remat})
        variables = model.init(jax.random.PRNGKey(0), ids)
        loss = tfm.make_loss_fn(model, aux_loss_coef=0.0, router_z_coef=0.0)
        before = telemetry.snapshot()["counters"]
        grads[remat] = jax.grad(lambda p: loss(
            p, {"input_ids": ids}, variables["buffers"])[0])(
                variables["params"])
        after = telemetry.snapshot()["counters"]
        counted[remat] = {k: after.get(k, 0) - before.get(k, 0) for k in (
            "kda.layers", "kda.chunks", "remat.blocks", "remat.kda_kept")}
    worst = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-30)),
        grads[True], grads[False])))
    assert worst < 1e-4, worst
    # two KDA layers of four chunks a row; under remat both keep the op's
    # output (each layer is traced once more for the second forward)
    assert counted[False] == {"kda.layers": 2, "kda.chunks": 8,
                              "remat.blocks": 0, "remat.kda_kept": 0}
    assert counted[True]["remat.blocks"] == 3
    assert counted[True]["remat.kda_kept"] == 2
    assert counted[True]["kda.chunks"] == 4 * counted[True]["kda.layers"]


def test_the_policy_names_the_op_s_output():
    model = tfm.build_transformer({**BASE, "remat": True})
    ids = _ids(5)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    loss = tfm.make_loss_fn(model)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: loss(p, {"input_ids": ids})[0]))(params))
    assert "name=kda_out" in text and "name=flash_out" not in text


# The op as Pallas kernels (interpreter mode; a chunk of 16 is a bf16 tile).
KERNELS = {"attn_impl": "pallas_interpret", "kda": {**KDA, "chunk_size": 16}}


@pytest.mark.parametrize("remat", [False, True])
def test_a_model_of_both_kinds_trains_a_step_on_the_kernels(remat):
    model = tfm.build_transformer({**BASE, **EXPERTS, **KERNELS,
                                   "remat": remat})
    ids = _ids(3)
    variables = model.init(jax.random.PRNGKey(0), ids)
    optimizer = optax.adamw(1e-2)
    start = jax.device_get(variables["params"])     # the step donates them
    state = dplib.TrainState.create(variables["params"], optimizer,
                                    variables["buffers"])
    step = dplib.make_train_step(
        tfm.make_loss_fn(model, aux_loss_coef=0.0, vocab_chunk=32,
                         router_z_coef=0.0), optimizer)
    losses = []
    for _ in range(3):
        state, metrics = step(state, {"input_ids": ids})
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses
    moved = jax.tree.map(lambda a, b: float(np.abs(a - b).max()),
                         jax.device_get(state.params), start)
    for leaf in ("A_log", "dt_bias", "o_norm", "q_conv", "k_conv"):
        assert moved["block_0"]["attn"][leaf] > 0, leaf


def _kernel_grads(remat: bool, impl: str, ids):
    model = tfm.build_transformer({**BASE, **EXPERTS, **KERNELS,
                                   "attn_impl": impl, "remat": remat})
    variables = model.init(jax.random.PRNGKey(0), ids)
    loss = tfm.make_loss_fn(model, aux_loss_coef=0.0, router_z_coef=0.0)
    before = telemetry.snapshot()["counters"]
    grads = jax.grad(lambda p: loss(
        p, {"input_ids": ids}, variables["buffers"])[0])(variables["params"])
    after = telemetry.snapshot()["counters"]
    return grads, {k: after.get(k, 0) - before.get(k, 0) for k in (
        "kda.layers", "kda.kernel_layers", "remat.blocks", "remat.kda_kept")}


def test_remat_changes_no_gradient_on_the_kernels_and_both_counters_count():
    """``kda.kernel_layers``: the layers traced whose op took the kernels,
    of ``kda.layers``; none under ``xla``.  ``remat`` moves no gradient, and
    the kernels' gradients are the XLA form's."""
    ids = _ids(4)
    grads, counted = {}, {}
    for remat, impl in ((False, "pallas_interpret"),
                        (True, "pallas_interpret"), (False, "xla")):
        grads[remat, impl], counted[remat, impl] = _kernel_grads(
            remat, impl, ids)
    worst = lambda a, b: max(jax.tree.leaves(jax.tree.map(  # noqa: E731
        lambda x, y: float(jnp.abs(x - y).max() / (jnp.abs(y).max() + 1e-30)),
        a, b)))
    plain = grads[False, "pallas_interpret"]
    assert worst(grads[True, "pallas_interpret"], plain) < 1e-4
    assert worst(plain, grads[False, "xla"]) < 1e-3
    assert counted[False, "pallas_interpret"] == {
        "kda.layers": 2, "kda.kernel_layers": 2, "remat.blocks": 0,
        "remat.kda_kept": 0}
    assert counted[False, "xla"]["kda.kernel_layers"] == 0
    kept = counted[True, "pallas_interpret"]
    assert kept["remat.blocks"] == 3 and kept["remat.kda_kept"] == 2
    assert kept["kda.kernel_layers"] == kept["kda.layers"]


def test_the_policy_keeps_the_kernels_output_and_chunk_states():
    """Both names reach the policy, and the rematerialised block's second
    forward runs no KDA kernel: one ``kda_fwd`` and one ``kda_bwd`` a KDA
    layer in the whole gradient."""
    model = tfm.build_transformer({**BASE, **KERNELS, "remat": True})
    ids = _ids(5)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    loss = tfm.make_loss_fn(model)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: loss(p, {"input_ids": ids})[0]))(params))
    assert "name=kda_out" in text and "name=kda_states" in text
    assert len(re.findall(r"name=kda_fwd\b", text)) == 2
    assert len(re.findall(r"name=kda_bwd\b", text)) == 2


def test_latent_attention_without_rotation_reads_no_position():
    """``rope=False`` under ``latent``: the logits are a function of the
    tokens alone; with rotation a shift of every position by a constant
    leaves them (RoPE is relative) and a STRETCH of the positions does not."""
    config = {**BASE, "n_layers": 2, "kda": None,
              "layer_attention": [[0, False, "latent"], [0, False, "latent"]]}
    ids = _ids(6)
    still = tfm.build_transformer(config)
    params = still.init(jax.random.PRNGKey(0), ids)["params"]
    def at(model, positions):
        return model.apply({"params": params}, ids, positions)

    base = at(still, None)
    for positions in (jnp.arange(32) + 7, jnp.arange(32) * 3,
                      jnp.zeros((32,), jnp.int32)):
        np.testing.assert_array_equal(at(still, positions), base)
    turning = tfm.build_transformer({**config, "layer_attention": None})
    assert float(jnp.abs(at(turning, None) - base).max()) > 1e-3
    assert float(jnp.abs(at(turning, jnp.arange(32) * 3)
                         - at(turning, None)).max()) > 1e-3


def test_latent_attention_without_rotation_is_its_equation():
    attn = tfm.Attention(2, 8, attn_impl="xla", compute_dtype=jnp.float32,
                         latent=(16, 8, 4, 8), rope=False)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 32))
    p = attn.init(jax.random.PRNGKey(1), x)["params"]
    q = jnp.einsum("bsd,dhk->bshk", x, p["q_proj"]["kernel"])
    kv_a = x @ p["kv_a_proj"]["kernel"]
    c = kv_a[..., :16]
    c = c / jnp.sqrt(jnp.mean(c * c, -1, keepdims=True) + 1e-6) * p[
        "kv_a_norm"]["scale"]
    kv = jnp.einsum("bsr,rhk->bshk", c, p["kv_b_proj"]["kernel"])
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :8], kv[..., :8])
              + jnp.einsum("bqhd,bkd->bhqk", q[..., 8:], kv_a[..., 16:])
              ) / np.sqrt(12.0)
    scores = jnp.where(jnp.tril(jnp.ones((16, 16), bool)), scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1),
                     kv[..., 8:])
    want = jnp.einsum("bqhk,hkd->bqd", out, p["o_proj"]["kernel"])
    np.testing.assert_allclose(attn.apply({"params": p}, x), want,
                               atol=1e-5, rtol=1e-5)


HYPER = {"hc_mult": 2, "hc_sinkhorn_iters": 2, "hc_eps": 1e-6,
         "mhc_h_res_clamp_min": 0.0, "mhc_h_res_clamp_max": 1.0}


@pytest.mark.parametrize("change,named", [
    ({"decode": True}, "decode=True"),
    ({"sparse_attention": {"index_heads": 2, "index_head_dim": 4,
                           "topk": 4}}, "sparse=(2, 4, 4)"),
    ({"hyper_connections": HYPER}, "hyper=(2, 2"),
    ({"attn_impl": "ring"}, "attn_impl='ring'"),
    ({"num_nextn_predict_layers": 1}, "mtp_layers=1"),
    ({"layer_mixer": ["*", "E", "M"], "moe_capacity_factor": None},
     "layer_mixer=('*', 'E', 'M')"),
    ({"n_layers": 4}, "over 4 layers"),
    ({"kda": None}, "kda=None"),
    ({"latent_attention": None}, "latent=None"),
    ({"layer_attention": [[0, True, "kda"], [0, False, "latent"],
                          [0, False, "kda"]]}, "(0, True, 'kda')"),
    ({"layer_attention": [[8, False, "kda"], [0, False, "latent"],
                          [0, False, "kda"]]}, "(8, False, 'kda')"),
    ({"layer_attention": [[0, False, "kda"], [8, False, "latent"],
                          [0, False, "kda"]]}, "(8, False, 'latent')"),
    ({"layer_attention": [[0, False, "kda"], [0, False, "linear"],
                          [0, False, "kda"]]}, "(0, False, 'linear')"),
    ({"layer_attention": [[0, False], [0, False, "latent"], [0, True]]},
     "without a 'kda' entry"),
])
def test_what_a_kda_layer_does_not_run_with_is_refused_by_name(change, named):
    with pytest.raises(NotImplementedError, match="layer_attention") as e:
        if "decode" in change:
            tfm.Transformer(64, 32, 3, 2, decode=True, kda=(2, 8, 4, 8),
                            latent=(16, 8, 4, 8), layer_attention=tuple(
                                tuple(x) for x in BASE["layer_attention"]))
        else:
            tfm.build_transformer({**BASE, **change})
    assert named in str(e.value)


def test_a_block_diffusion_mask_beside_a_kda_layer_is_refused():
    model = tfm.build_transformer(BASE)
    ids = _ids(7)
    with pytest.raises(NotImplementedError, match="beside a KDA layer"):
        model.init(jax.random.PRNGKey(0), ids, jnp.arange(32), (16, 4))


def test_a_row_that_is_no_multiple_of_the_chunk_is_refused_by_name():
    model = tfm.build_transformer(BASE)
    with pytest.raises(ValueError, match="no multiple of chunk 8"):
        model.init(jax.random.PRNGKey(0), _ids(8, (1, 20)))


def test_attention_refuses_no_rotation_on_the_paths_that_turn_their_keys():
    x = jnp.zeros((1, 8, 32))
    for fields in (dict(sparse=(2, 4, 4)),
                   dict(decode=True, max_decode_len=8)):
        attn = tfm.Attention(4, 8, rope=False, **fields)
        with pytest.raises(NotImplementedError, match="rope=False"):
            attn.init(jax.random.PRNGKey(0), x)


def test_tp_rules_cover_every_parameter_of_a_kda_layer():
    model = tfm.build_transformer(BASE)
    params = model.init(jax.random.PRNGKey(0), _ids())["params"]
    compiled = [(re.compile(pat), spec)
                for pat, spec in tplib.TRANSFORMER_TP_RULES]
    flat = jax.tree_util.tree_flatten_with_path(params["block_0"]["attn"])[0]
    assert len(flat) == 15
    for path, leaf in flat:
        name = "block_0/attn/" + tplib._path_str(path)
        spec = next((spec for pat, spec in compiled if pat.search(name)),
                    None)
        assert spec is not None, name
        assert len(spec) <= leaf.ndim, (name, spec, leaf.shape)
        big = name.split("/")[2] in ("q_proj", "k_proj", "v_proj", "o_proj")
        assert ("tp" in spec) == big, (name, spec)


def test_a_kda_model_sharded_over_tp_matches_the_replicated_one():
    model = tfm.build_transformer(BASE)
    ids = _ids(9)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    mesh = meshlib.make_mesh(dp=-1, tp=2)
    shardings = tplib.rule_shardings(mesh, params, tplib.TRANSFORMER_TP_RULES)
    sharded = meshlib.shard_tree(mesh, params, shardings)
    want = jax.jit(lambda p, x: model.apply({"params": p}, x))(params, ids)
    with jax.set_mesh(mesh):
        got = jax.jit(lambda p, x: model.apply({"params": p}, x))(sharded, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
