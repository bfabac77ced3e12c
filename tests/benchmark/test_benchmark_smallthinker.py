"""SmallThinker-21BA3B-Instruct (ISSUE 48): the program against the plain
reference kept with the benchmark
(``benchmark/configs/smallthinker_21b_a3b_d8_ep8.py``) at a small size on the
CPU (logits, loss, every gradient leaf, the routing, over one period of the
layer pattern), the expert layer alone with ``reglu`` and a router input of
its own, the shares of a layer adding up to the uncut reference, the
negative controls of the chip's check, the configuration's file against the
catalog's row and its counts, the seven new readers on a hand-made run and
the count of a scope's kernels on the recorded trace, and the manifest with
eleven cells.  The same comparison runs at the published
widths on the chip (``check_train``)."""

from __future__ import annotations

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common, run_report, scope_calls, scope_times
from tensorflowonspark_tpu.models import transformer as tfm
from tensorflowonspark_tpu.ops import attention
from tensorflowonspark_tpu.parallel.ep import MoEMLP

ST = common.load_module("configs", "smallthinker_21b_a3b_d8_ep8")
CELL = "smallthinker_21b_a3b_d8_ep8_train_16k"
FILE = common.read_json(os.path.join(
    common.HERE, "configs", "smallthinker_21b_a3b_d8_ep8.json"))

# SmallThinker's shape in small: one period (a global layer without rotation,
# three in a window of 12 with RoPE), 14 query heads over 2 K/V heads (groups
# of 7), experts 2-5 of 8 held, 3 a token, ReGLU, the router on the layer's
# input, remat.
CFG = {**FILE, "hidden_size": 32, "moe_ffn_hidden_size": 16,
       "num_attention_heads": 14, "num_key_value_heads": 2, "head_dim": 8,
       "num_hidden_layers": 4, "router_experts": 8, "experts_held": [2, 6],
       "moe_num_primary_experts": 4, "moe_num_active_primary_experts": 3,
       "sliding_window_size": 12, "vocab_size": 64, "vocab_chunk": 24,
       "reference_tokens": [2, 48], "reference_query_block": 16,
       "bf16": False}

# Both sides compute in float32 and differ in the order of their sums (a sort
# and a grouped matmul against a loop over experts, a flash kernel against
# whole scores, softmax-then-pick against pick-then-softmax, a blockwise loss
# against whole logits): measured 7e-7 on these sizes.  1e-4 leaves that a
# hundred times and is far under what a dropped window, a rotated global
# layer or a router on another state move (the negative controls).
TOL = 1e-4


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def _ids(cfg, seed=0):
    rows, length = cfg["reference_tokens"]
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (rows, length)), jnp.int32)


@pytest.mark.parametrize("attn_impl", ["pallas_interpret", "xla"])
def test_system_matches_the_reference(attn_impl):
    """Loss, logits, the gradient of every parameter leaf and every layer's
    routing indices, through the kernels in interpret mode (the band's walk,
    a group of 7) and through the XLA path."""
    cfg = {**CFG, "attn_impl": attn_impl}
    if attn_impl == "pallas_interpret":     # one group of 7, to trace less
        cfg.update(num_attention_heads=7, num_key_value_heads=1,
                   reference_tokens=[1, 32])
    _tfm, model = ST._model(cfg)
    params = jax.jit(lambda key: ST._init_params(cfg, key))(
        jax.random.PRNGKey(1))
    ids = _ids(cfg)
    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        ST._loss_fn(tfm, model, cfg), has_aux=True))(params, {"input_ids": ids})
    logits, sown = jax.jit(lambda params, ids: model.apply(
        {"params": params}, ids, mutable=["intermediates"]))(params, ids)

    def reference(params):
        ref_logits, routing = ST.reference_forward(cfg, params, ids)
        return ST.reference_lm_loss(ref_logits, ids), (ref_logits, routing)

    (ref_loss, (ref_logits, ref_routing)), ref_grads = jax.jit(
        jax.value_and_grad(reference, has_aux=True))(params)
    errors = {"loss": abs(float(loss) - float(ref_loss)) / float(ref_loss),
              "logits": _rel(logits, ref_logits),
              "grads": max(jax.tree.leaves(
                  jax.tree.map(_rel, grads, ref_grads)))}
    assert max(errors.values()) < TOL, errors
    routing = ST._sown(sown, "top_idx")
    assert len(routing) == len(ref_routing) == 4
    for ours, theirs in zip(routing, ref_routing):
        np.testing.assert_array_equal(np.sort(ours, -1), np.sort(theirs, -1))
    # no auxiliary term: the loss is the cross-entropy alone
    assert float(loss) == float(metrics["lm_loss"])
    # 3 choices over 8 experts, 4 held: half the pairs on even routing
    assert 0.2 < float(metrics["moe_held_pairs"]) < 0.8


def _moe_params(key, d, ff, experts):
    keys = jax.random.split(key, 4)
    return {"router": {"kernel": jax.random.normal(keys[0], (d, experts))},
            "experts_gate": jax.random.normal(keys[1], (experts, d, ff)) * 0.2,
            "experts_up": jax.random.normal(keys[2], (experts, d, ff)) * 0.2,
            "experts_down": jax.random.normal(keys[3], (experts, ff, d)) * 0.2}


def _held(params, first, end):
    return {**params, **{name: params[name][first:end] for name in (
        "experts_gate", "experts_up", "experts_down")}}


@pytest.mark.parametrize("held", [None, (2, 6)], ids=["dropless", "held"])
@pytest.mark.parametrize("own_input", [True, False],
                         ids=["router-input", "one-input"])
def test_reglu_experts_and_a_router_input_against_the_reference(held,
                                                                own_input):
    """``MoEMLP(expert_act="reglu")`` with and without ``router_input``
    against the reference's expert layer: the output and the gradient by the
    rows, by the router's input and by every weight, on all the experts and
    on a held range (whose first piece is shorter than the pairs: the loop
    over further pieces and its hand-written backward run)."""
    d, ff, e, k, n = 16, 8, 8, 3, 40
    cfg = {"router_experts": e, "moe_num_active_primary_experts": k,
           "experts_held": list(held or (0, e))}
    full = _moe_params(jax.random.PRNGKey(0), d, ff, e)
    params = _held(full, *cfg["experts_held"])
    rng = np.random.default_rng(1)
    u = jnp.asarray(rng.normal(size=(1, n, d)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(1, n, d)), jnp.float32) if own_input else u
    w = jnp.asarray(rng.normal(size=(1, n, d)), jnp.float32)
    layer = MoEMLP(d, ff, e, k, None, held=held, expert_act="reglu")

    def system(params, u, x):
        out = layer.apply({"params": params}, u,
                          router_input=x if own_input else None)
        return jnp.sum(out * w), out

    def reference(params, u, x):
        out, _top = ST._reference_moe(cfg, params, x[0] if own_input else u[0],
                                      u[0])
        return jnp.sum(out[None] * w), out[None]

    got = jax.jit(jax.value_and_grad(
        system, argnums=(0, 1, 2), has_aux=True))(params, u, x)
    want = jax.jit(jax.value_and_grad(
        reference, argnums=(0, 1, 2), has_aux=True))(params, u, x)
    assert _rel(got[0][1], want[0][1]) < TOL
    errors = jax.tree.map(_rel, got[1], want[1])
    assert max(jax.tree.leaves(errors)) < TOL, errors
    if own_input:       # the rows' cotangent holds no share of the router's
        assert float(jnp.abs(got[1][2]).max()) > 0


def test_relu_is_on_the_gate_and_the_other_paths_refuse_what_they_lack():
    d, ff, e = 8, 4, 4
    params = _moe_params(jax.random.PRNGKey(0), d, ff, e)
    u = jnp.asarray(np.random.default_rng(0).normal(size=(1, 6, d)),
                    jnp.float32)
    outs = {act: jax.jit(MoEMLP(d, ff, e, 2, None, expert_act=act).apply)(
        {"params": params}, u) for act in ("reglu", "swiglu")}
    assert _rel(outs["reglu"], outs["swiglu"]) > 1e-2
    with pytest.raises(ValueError, match="dropless"):
        MoEMLP(d, ff, e, 2, 1.25, expert_act="reglu").apply(
            {"params": params}, u)
    with pytest.raises(ValueError, match="dropless"):
        MoEMLP(d, ff, e, 2, 1.25).apply({"params": params}, u, router_input=u)
    with pytest.raises(ValueError, match="router_input"):
        MoEMLP(d, ff, e, 2, None).apply({"params": params}, u,
                                        router_input=u[:, :3])


def test_the_shares_of_a_layer_add_up_to_the_uncut_reference():
    """A window layer of the model as each chip of its stage computes it
    (``Block`` with a quarter of the 8 experts held: the ranges 0-1, 2-3, 4-5,
    6-7, as the cell's chip holds 0-7 of 64) against the reference's layer
    with all 8: what every chip computes alike (attention, the residual)
    counted once, the held parts add up to the whole layer's output."""
    cfg = {**CFG, "experts_held": [0, 8], "moe_num_primary_experts": 8}
    d, length, window = cfg["hidden_size"], 48, cfg["sliding_window_size"]

    def block(held):
        return tfm.Block(
            cfg["num_attention_heads"], cfg["head_dim"],
            cfg["moe_ffn_hidden_size"], 8, 3, cfg["rope_theta"], "xla",
            compute_dtype=jnp.float32, norm_eps=cfg["rms_norm_eps"],
            moe_capacity_factor=None, n_kv_heads=2, moe_held=held,
            attention=(window, True), moe_expert_act="reglu",
            moe_router_input="layer")

    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, length, d)),
                    jnp.float32)
    params = jax.jit(block((0, 8)).init)(jax.random.PRNGKey(0), x)["params"]
    params["attn"]["q_proj"]["kernel"] *= 1.6
    eps = cfg["rms_norm_eps"]

    @jax.jit
    def reference_layer(p, x):      # the reference's layer, from its pieces
        heads = ST._reference_attention(
            cfg, p["attn"], ST._rms_norm(x, p["attn_norm"]["scale"], eps),
            window, True)
        x1 = x + jnp.einsum("qhk,hkd->qd", heads,
                            p["attn"]["o_proj"]["kernel"])
        moe, _top = ST._reference_moe(
            cfg, p["moe"], x, ST._rms_norm(x1, p["mlp_norm"]["scale"], eps))
        return x1, moe

    x1, moe = reference_layer(params, x[0])
    shares = [jax.jit(block((first, first + 2)).apply)(
        {"params": {**params, "moe": _held(params["moe"], first, first + 2)}},
        x)[0] for first in range(0, 8, 2)]
    assert _rel(sum(share - x1 for share in shares), moe) < TOL
    assert _rel(sum(share - x1 for share in shares) + x1, x1 + moe) < TOL
    # and no share is the whole
    assert _rel(shares[0] - x1, moe) > 0.1


@pytest.mark.parametrize("wrong", [None, "frozen", *ST.WRONG_SYSTEMS])
def test_the_check_passes_the_model_and_fails_its_neighbours(wrong):
    """The comparison that decides the cell's ``correct`` (``check_train``
    with ITS tolerances, set on the chip), at a small size: the system
    passes; a state that the step left as it was reads 1 in the gradients
    and in both readings of the update; a system that drops the window,
    rotates the global layers, or routes on the post-attention state FAILS
    in the logits or the routing AND in the step.  (The fp8 control is the
    chip's: at these widths a leaf's fp8 rounding is coarser than at 2,560.)"""
    out = ST.check_train({**CFG, "attn_impl": "xla"}, {}, 3,
                         degrade_system=wrong or False)
    assert out["tolerance"] == ST.TOLERANCE
    assert set(out["errors"]) == set(ST.TOLERANCE) == {
        "logits_l2", "logits_max", "routing_disagreement",
        "grad_attn_leaf_max", "grad_leaf_max", "update_l2", "update_leaf_max"}
    assert out["ok"] is (wrong is None), out["errors"]
    errors, limits = out["errors"], ST.TOLERANCE
    step = ("grad_attn_leaf_max", "grad_leaf_max", "update_l2",
            "update_leaf_max")
    if wrong is None:
        assert max(v for k, v in errors.items() if "update" not in k) < TOL
        # the change is read off float32 parameters: their rounding, 6e-8 of
        # a norm's scale of 1 beside a step of 1e-5
        assert errors["update_l2"] < 2e-3 and errors["update_leaf_max"] < 5e-3
        assert out["loss"] < TOL and out["grad_l2"] < TOL
        assert len(out["held_pairs_by_layer"]) == 4
        # 96 positions x 3 choices x 4 of 8 experts: 144 pairs a layer, even
        assert 0.5 < out["held_pairs"] / 144 < 1.5
    elif wrong == "frozen":
        assert [errors[k] for k in step] == pytest.approx([1.0] * 4)
        assert errors["logits_l2"] < TOL
    else:       # the step's limits part them too (the attention leaves'
        #         one where the fault is attention's, not the router's)
        attention = wrong != "router_after_attention"
        assert all(errors[k] > limits[k] for k in step[1 - attention:]), errors
        assert (errors["logits_l2"] > limits["logits_l2"]
                or errors["routing_disagreement"]
                > limits["routing_disagreement"]), errors


def test_the_check_fails_a_backward_that_forgets_the_window(monkeypatch):
    """A fault that no forward reading shows: the band's BACKWARD kernel
    builds its masked tiles without the window (the walk is the band's, the
    far edge's pairs all count).  Through the kernels in interpret mode the
    logits and the routing read as the sound system's; the gradients and the
    update fail their limits."""
    real = attention._flash_bwd_pallas

    def no_window_in_the_masks(*args, plan, **kwargs):
        tile = tuple((k, None if k == "window" else v) for k, v in plan.tile)
        return real(*args, plan=plan._replace(tile=tile), **kwargs)

    monkeypatch.setattr(attention, "_flash_bwd_pallas", no_window_in_the_masks)
    jax.clear_caches()      # the sound backward's traces
    out = ST.check_train({**CFG, "attn_impl": "pallas_interpret",
                          "num_attention_heads": 7, "num_key_value_heads": 1,
                          "reference_tokens": [1, 32]}, {}, 3)
    jax.clear_caches()
    errors, limits = out["errors"], ST.TOLERANCE
    assert max(errors[k] for k in ("logits_l2", "logits_max",
                                   "routing_disagreement")) < TOL
    assert not out["ok"]
    assert all(errors[k] > limits[k] for k in (
        "grad_attn_leaf_max", "grad_leaf_max", "update_l2",
        "update_leaf_max")), errors
    assert "attn" in out["grad_leaf_worst"]
    assert out["grad_attn_leaf_worst"] == out["grad_leaf_worst"]


def test_check_and_window_lower_one_step_program():
    """``build_train`` hands the window the jitted step the check stepped
    (ONE a configuration in a process: ``_program``), so the window's
    lowering is the check's module; another configuration gets another."""
    from tensorflowonspark_tpu.parallel import mesh as meshlib

    cfg = {**CFG, "attn_impl": "xla"}
    mesh = meshlib.make_mesh(jax.devices()[:1], dp=-1)
    traffic = {"rows_per_chip": 2, "seq_len": 48}
    built = ST.build_train(cfg, traffic, mesh, 3)
    assert built["step_fn"] is ST._train(cfg, mesh, 4)[2]
    assert built["step_fn"] is not ST.build_train(
        {**cfg, "vocab_chunk": 32}, traffic, mesh, 3)["step_fn"]
    assert (built["rows_per_step"], built["samples_per_row"]) == (2, 48)
    batch = meshlib.shard_batch(mesh, {"input_ids": np.asarray(_ids(CFG))})
    with jax.set_mesh(mesh):
        lowered = built["step_fn"].lower(built["state"], batch)
        again = ST.build_train(cfg, traffic, mesh, 3)["step_fn"].lower(
            built["state"], batch)
        assert lowered.as_text() == again.as_text()
        state, metrics = lowered.compile()(built["state"], batch)
    assert int(state.step) == 1 and np.isfinite(float(metrics["lm_loss"]))


# -- the file and its counts --------------------------------------------------

def test_the_file_keeps_every_published_width():
    """Every key of the catalog's row under the same name, changed only where
    ``reduced`` says; the published values, the deployment and every assumed
    size are stated."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "SmallThinker-21BA3B-Instruct")
        assert FILE["source"] == row["source_url"]
        differs = [k for k, v in row["config"].items() if FILE.get(k) != v]
        assert sorted(differs) == sorted(FILE["reduced"])
    assert FILE["reduced"] == ["num_hidden_layers", "moe_num_primary_experts",
                               "vocab_size"]
    assert FILE["published"] == {"num_hidden_layers": 52,
                                 "moe_num_primary_experts": 64,
                                 "vocab_size": 151936}
    assert (FILE["hidden_size"], FILE["num_attention_heads"],
            FILE["num_key_value_heads"], FILE["head_dim"],
            FILE["moe_ffn_hidden_size"], FILE["router_experts"],
            FILE["moe_num_active_primary_experts"],
            FILE["sliding_window_size"], FILE["rope_theta"],
            FILE["rms_norm_eps"], FILE["max_position_embeddings"]) == (
                2560, 28, 4, 128, 768, 64, 6, 4096, 1.5e6, 1e-6, 16384)
    assert FILE["rope_layout"] == FILE["sliding_window_layout"] == [
        0, 1, 1, 1] * 13
    assert ST.layer_kinds(FILE) == [(0, False), (4096, True), (4096, True),
                                    (4096, True)] * 2
    first, end = FILE["experts_held"]
    assert end - first == FILE["moe_num_primary_experts"] == 8
    assert FILE["vocab_size"] * 8 == FILE["published"]["vocab_size"]
    assert "8 chips" in FILE["deployment"]
    assert "1536 pairs" in FILE["deployment"]
    stated = " ".join(FILE["assumed"])
    for size in ("router_input", "routing weights", "expert_act",
                 "attention_bias", "qk_norm", "window convention", "the job",
                 "vocab_chunk", "learning rate", "remat", "seeded_state",
                 "embedding_std", "qk_proj_scale"):
        assert size in stated, size
    for size in ("router_input", "expert_act", "QK-norm", "window",
                 "optimizer", "remat", "embedding_std", "qk_proj_scale"):
        assert size in ST.__doc__, size
    # the parameters the file counts are the ones the program creates
    shapes = jax.eval_shape(lambda: ST._init_params(
        FILE, jax.random.PRNGKey(0)))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert count == 643_852_800 and "643.9 M" in FILE["deployment"]
    layer = sum(int(np.prod(a.shape))
                for a in jax.tree.leaves(shapes["block_1"]))
    assert layer == 68_326_400 and "68,326,400" in FILE["deployment"]
    assert set(shapes["block_0"]["moe"]) == {
        "router", "experts_gate", "experts_up", "experts_down"}
    assert shapes["block_0"]["moe"]["router"]["kernel"].shape == (2560, 64)
    assert set(shapes["block_0"]["attn"]) == {"q_proj", "k_proj", "v_proj",
                                              "o_proj"}


def test_the_counts_are_the_band_s_and_the_mask_s():
    """Operations and bytes from the shapes: the band's visible pairs against
    a count from the materialised mask, the two kinds of layer of unequal
    cost, three products an expert."""
    cell = common.resolve_cell(CELL)
    cfg, traffic = cell["config"], cell["traffic"]
    length = traffic["seq_len"]
    assert length == 16384 and traffic["rows_per_chip"] == 1
    for n, w in [(64, 24), (64, 5), (40, 40), (30, 100)]:
        i, j = np.arange(n)[:, None], np.arange(n)[None, :]
        assert ST.band_pairs(n, w) == int(((j <= i) & (i - j < w)).sum())
    band, causal = ST.band_pairs(length, 4096), ST.causal_pairs(length)
    assert band == 4096 * 4097 // 2 + (length - 4096) * 4096 == 58_722_304
    assert causal == 134_225_920 and 0.43 < band / causal < 0.44
    assert ST.visible_pairs(cfg, length) == [causal, band, band, band] * 2
    assert ST.held_pairs_per_position(cfg) == 0.75
    pair = 2 * 2 * 28 * 128
    per_position = (2 * 2560 * 3584 + 2 * 2560 * 512 + 2560 * 64
                    + 0.75 * 3 * 2560 * 768)
    want = (8 * 6 * per_position + 3 * pair * (2 * causal + 6 * band) / length
            + 6 * 2560 * 18992)
    assert ST.flops_per_sample(cfg, traffic) == pytest.approx(want)
    # 51.6 TFLOP a step without the recomputed forward
    assert want * length == pytest.approx(51.6e12, rel=5e-3)
    one = ST.flash_fwd_cost(cfg, traffic, 1)
    assert one["flops"] == pytest.approx(1.924e12, rel=1e-3)
    # q and o at 28 heads, k and v at 4, bf16; the log-sum-exp in float32
    call_bytes = length * (2 * 3584 * 2 + 2 * 512 * 2 + 28 * 4)
    assert one["bytes"] == call_bytes
    fwd = ST.swa_flash_fwd_cost(cfg, traffic, 1)
    bwd = ST.swa_flash_bwd_cost(cfg, traffic, 1)
    # ONE call of the band's forward, whatever ``remat`` says: the reader
    # counts the executions in the trace
    assert fwd["flops"] == pair * band == pytest.approx(0.842e12, rel=1e-3)
    assert fwd["bytes"] == call_bytes
    assert ST.swa_flash_fwd_cost({**cfg, "remat": False}, traffic, 1) == fwd
    # a step's backward: the six window layers, the two global ones
    assert bwd["flops"] == 6 * 2.5 * pair * band
    assert bwd["bytes"] == 6 * 2 * call_bytes
    full = ST.global_flash_bwd_cost(cfg, traffic, 1)
    assert full["flops"] == 2 * 2.5 * pair * causal
    assert full["bytes"] == 2 * 2 * call_bytes
    # whole tiles on both masked edges hold more pairs than are visible: the
    # shares count the visible ones, so a reading cannot pass 100%
    assert 252 * 512 * 512 > 1.12 * band
    moe = ST.moe_experts_cost(cfg, traffic, 1)
    assert moe["flops"] == 8 * 3 * 2 * 12288 * 3 * 2560 * 768
    assert moe["bytes"] == 8 * 2 * (5 * 12288 * 2560 + 3 * 8 * 3 * 2560 * 768)


def test_the_seeded_state_has_the_scales_the_file_states():
    seeded = FILE["seeded_state"]
    cfg = {**CFG, "vocab_size": 512, "attn_impl": "xla"}
    params = jax.jit(lambda key: ST._init_params(cfg, key))(
        jax.random.PRNGKey(3))
    assert np.asarray(params["embed"]["embedding"]).std() == pytest.approx(
        seeded["embedding_std"], rel=0.1)
    for layer in range(cfg["num_hidden_layers"]):
        attn = params[f"block_{layer}"]["attn"]
        for name, scale in (("q_proj", seeded["qk_proj_scale"]),
                            ("k_proj", seeded["qk_proj_scale"]),
                            ("v_proj", 1.0)):
            assert np.asarray(attn[name]["kernel"]).std() == pytest.approx(
                scale / np.sqrt(cfg["hidden_size"]), rel=0.2), name


def test_a_program_without_the_mechanisms_is_refused(monkeypatch):
    """The parent commit's program builds SOME model from these keys (its
    builder ignores what it does not know): the configuration says so at
    once instead of timing another model under SmallThinker's name."""
    monkeypatch.setattr(tfm, "build_transformer",
                        lambda config: types.SimpleNamespace(moe_held=None))
    with pytest.raises(NotImplementedError, match="layer_attention"):
        ST._model(CFG)


# -- the readers --------------------------------------------------------------

STEP = "jit(step)/jit(main)/loss_and_grad/"
REMAT = STEP + "transpose(jvp(Transformer))/checkpoint/rematted_computation/"
SUMS = {
    STEP + "jvp(Transformer)/block_0/attn/attention/flash_fwd/pallas_call:":
        1600e-6,
    STEP + "jvp(Transformer)/block_1/attn/attention/flash_fwd_window/"
    "pallas_call:": 800e-6,
    REMAT + "block_1/attn/attention/flash_fwd_window/pallas_call:": 700e-6,
    REMAT + "block_1/attn/attention/flash_fwd_window/transpose:": 100e-6,
    STEP + "transpose(jvp(Transformer))/block_1/attn/attention/"
    "flash_bwd_window/pallas_call:": 2000e-6,
    STEP + "transpose(jvp(Transformer))/block_0/attn/attention/flash_bwd/"
    "pallas_call:": 3000e-6,
    "": 30e-6,
}
COUNTERS = {"flash.window.visits": 18 * 252,
            "flash.window.causal_visits": 18 * 528,
            "flash.window.masked_tiles": 18 * 56, "flash.kernels": 24}


def _kernels_per_step(sums):
    """As ``scope_calls.kernels_per_step`` counts them, of the hand-made
    scope paths: an execution of each ``pallas_call`` a traced step."""
    def count(run, scope):
        calls = sum(1 for path in sums or {} if path.endswith("pallas_call:")
                    and scope_times.in_scope(path, scope))
        return calls or None
    return count


def _run(monkeypatch, sums, counters=COUNTERS):
    monkeypatch.setattr(scope_times, "run_scope_seconds", lambda run: sums)
    monkeypatch.setattr(scope_calls, "kernels_per_step",
                        _kernels_per_step(sums))
    monkeypatch.setattr(run_report, "chief_counters", lambda run: counters)
    # ONE band forward needs 80 us of compute and 15 us of traffic, a step's
    # band backwards 500 and 60; one global forward 400 and 15, a step's
    # global backwards 900 and 60
    return {"cell": {"workload": CELL, "config": {"num_hidden_layers": 8}},
            "trace": {"busy_s": 1.0},
            "facts": {"traced_steps": 2, "window_epoch_start": 0.0,
                      "kernels": {
                "swa_flash_fwd": {"flops": 197e12 * 80e-6,
                                  "bytes": 819e9 * 15e-6},
                "swa_flash_bwd": {"flops": 197e12 * 500e-6,
                                  "bytes": 819e9 * 60e-6},
                "flash_fwd": {"flops": 197e12 * 400e-6,
                              "bytes": 819e9 * 15e-6},
                "global_flash_bwd": {"flops": 197e12 * 900e-6,
                                     "bytes": 819e9 * 60e-6}}},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


NEW = ["swa_flash_fwd_ms", "swa_flash_bwd_ms", "swa_flash_fwd_roofline",
       "swa_flash_bwd_roofline", "swa_visit_share",
       "global_flash_fwd_roofline", "global_flash_bwd_roofline"]


@pytest.mark.parametrize("metric,expected", [
    ("swa_flash_fwd_ms", 0.8),          # 1,600 us over two steps: both
                                        # executions, kernel AND layout
    ("swa_flash_bwd_ms", 1.0),
    ("swa_flash_fwd_roofline", 20.0),   # two executions of 80 us in 800
    ("swa_flash_bwd_roofline", 50.0),   # 500 us against 1,000
    ("global_flash_fwd_roofline", 50.0),    # one execution of 400 us in 800
    ("global_flash_bwd_roofline", 60.0),    # 900 us against 1,500
    ("swa_visit_share", 100.0 * 252 / 528),
    ("bd_flash_fwd_ms", 0.8),           # the GLOBAL layers alone
    ("flash_bwd_ms", 1.5),
])
def test_readers_tell_band_from_full_on_a_hand_made_run(monkeypatch, metric,
                                                        expected):
    reader = common.load_module("layer_metrics", metric)
    run = _run(monkeypatch, SUMS)
    assert reader.read(run) == pytest.approx(expected)
    if metric.endswith("roofline"):
        assert reader.bound(run) == "compute"
        kernel = run["facts"]["kernels"][reader.KERNEL]
        kernel["bytes"] *= 50
        assert reader.bound(run) == "memory"


@pytest.mark.parametrize("metric", NEW)
def test_new_readers_find_nothing_in_the_parent_s_program(monkeypatch, metric):
    """No trace, a trace without scopes, a program that names neither scope
    and keeps no such counter (the parent's, traced under this PR's benchmark
    files): None, no raise."""
    reader = common.load_module("layer_metrics", metric)
    others = {k: v for k, v in SUMS.items()
              if "_window" not in k and "/flash_" not in k}
    parent_counters = {"flash.kernels": 24}
    assert reader.read(_run(monkeypatch, others, parent_counters)) is None
    assert reader.read(_run(monkeypatch, None, None)) is None
    if metric.endswith("roofline"):
        run = _run(monkeypatch, SUMS)
        assert reader.read({**run, "peaks": None}) is None
        run["facts"]["kernels"] = {}
        assert reader.read(run) is None and reader.bound(run) is None
    monkeypatch.undo()
    run = _run(monkeypatch, SUMS)
    monkeypatch.undo()
    if metric != "swa_visit_share":
        assert reader.read({**run, "trace": None}) is None


def test_a_scope_s_kernels_are_counted_on_the_recorded_v5e_trace(monkeypatch):
    """On the xplane kept with the benchmark (a dense LM's four steps, one
    Pallas kernel a step under ``jit(step)``): the decoding and the window
    are real, only the path to the file is handed in."""
    path = os.path.join(common.HERE, "testdata", "tpu_v5e_4steps.xplane.pb")
    monkeypatch.setattr(common, "find_xplane", lambda trace_dir: path)
    run = {"cell": {"workload": CELL}, "trace": {"busy_s": 1.0},
           "facts": {"traced_steps": 4}}
    assert scope_calls.kernels_per_step(run, "jit(step)") == 1.0
    assert scope_calls.kernels_per_step(run, "flash_fwd_window") is None
    assert scope_calls.kernels_per_step({**run, "trace": None},
                                        "jit(step)") is None
    monkeypatch.setattr(common, "find_xplane", lambda trace_dir: None)
    assert scope_calls.kernels_per_step(run, "jit(step)") is None


# -- the manifest with eleven cells -------------------------------------------

def test_manifest_holds_the_cell_its_configuration_and_seven_readers():
    manifest = common.load_manifest()
    cells = [w["name"] for w in manifest["workloads"]]
    assert CELL in cells and len(cells) >= 11
    entry = {c["name"]: c for c in manifest["configs"]}[
        "smallthinker_21b_a3b_d8_ep8"]
    assert entry["reduced"] == FILE["reduced"]
    assert entry["source"] == FILE["source"]
    assert entry["file"] == "benchmark/configs/smallthinker_21b_a3b_d8_ep8.json"
    cell = common.resolve_cell(CELL)
    assert (cell["chips"], cell["traffic_name"]) == (1, "token_rows_16k_x1")
    assert {m["name"] for m in cell["end_to_end"]} == {"train_tok_rate",
                                                       "setup_s"}
    reported = {m["name"] for m in cell["per_layer"]}
    assert reported >= {
        "claim_s", "first_step_s", "lm_feed_wait_share", "lm_step_device_ms",
        "lm_mfu", "moe_dispatch_ms", "moe_experts_ms", "moe_experts_roofline",
        "moe_optimizer_ms", "moe_router_ms", "flash_bwd_ms",
        "bd_flash_fwd_ms", *NEW}
    # the full mask's shares count every layer: not this cell's
    assert not reported & {"flash_bwd_roofline", "bd_flash_fwd_roofline",
                           "flash_fwd_ms", "flash_fwd_roofline"}
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        metric = by_name[name]
        reader = common.load_module("layer_metrics", name)
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
            metric["layer"], metric["unit"], metric["moves"])
        assert metric["workloads"] == [CELL]
        assert metric["layer"] == "kernels"
        assert metric["source"] == ("program_counter"
                                    if name == "swa_visit_share"
                                    else "device_trace")
    # appended after what was there, nothing else moved
    names = [m["name"] for m in manifest["per_layer"]]
    assert names.index("mtp_ms") < min(names.index(name) for name in NEW)
    for metric in manifest["per_layer"] + manifest["end_to_end"]:
        if CELL in metric.get("workloads", []) and metric["name"] not in NEW:
            cut = metric["workloads"][:metric["workloads"].index(CELL)]
            assert cut and CELL not in cut
    # one chip: the four-chip quota stays where it was
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    # the configuration's module counts what the new readers read
    assert set(ST.KERNELS) >= {"swa_flash_fwd", "swa_flash_bwd", "flash_fwd",
                               "global_flash_bwd", "moe_experts"}
