"""Kimi-Linear-48B-A3B-Instruct (ISSUE 52): the program against the plain
reference kept with the benchmark (``benchmark/configs/kimi_linear_48b_a3b_
d5_ep32.py``: KDA as a scan over positions) at a small size on the CPU (loss,
logits, gradients, one adamw step), the controls that must fail, the 32
shares of the expert layer against the uncut one, the configuration's counts
against the issue's arithmetic, its file against the catalog's row, the six
new readers on a hand-made run, and the manifest with its twelfth cell.  The
same comparison runs at the published widths on the chip (``check_train``)."""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common, scope_calls, scope_times
from tensorflowonspark_tpu.models import transformer as tfm

KIMI = common.load_module("configs", "kimi_linear_48b_a3b_d5_ep32")
CONFIG = "kimi_linear_48b_a3b_d5_ep32"
CELL = "kimi_linear_48b_a3b_d5_ep32_train_16k"
FILE = common.read_json(os.path.join(common.HERE, "configs",
                                     f"{CONFIG}.json"))
READERS = ("kda_mixer_ms", "kda_scan_ms", "kda_conv_ms", "kda_scan_roofline",
           "mla1_flash_fwd_roofline", "mla1_flash_bwd_roofline")
# accepted readers whose scope the cell's step carries: it joined their lists
JOINED = ("lm_feed_wait_share", "lm_step_device_ms", "lm_mfu",
          "moe_dispatch_ms", "moe_experts_ms", "moe_experts_roofline",
          "moe_optimizer_ms", "flash_bwd_ms", "bd_flash_fwd_ms",
          "mla_project_ms", "moe_shared_ms", "moe_router_ms",
          "lm_head_loss_ms", "lm_embed_ms", "lm_attn_proj_ms",
          "lm_attn_rest_ms", "lm_mlp_ms", "lm_glue_ms", "lm_unowned_ms",
          "lm_unscoped_ms", "lm_account_closure", "lm_remat_ms")

# Kimi-Linear's shape in small: layers 1-4 of the published lists (KDA, KDA,
# KDA, latent), the first one dense, 4 heads of 8 key and value channels in
# chunks of 8, latent attention of 8 + 4 over values of 8 from a latent of 16,
# experts 2-5 of 8 held, 3 a token, one shared expert.
CFG = {**{k: FILE[k] for k in (
    "first_k_dense_replace", "moe_renormalize", "moe_router_activation_func",
    "num_expert_group", "topk_group", "num_shared_experts",
    "routed_scaling_factor", "rms_norm_eps", "rope_theta", "optimizer")},
    "hidden_size": 32, "intermediate_size": 48, "moe_intermediate_size": 16,
    "num_attention_heads": 4, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 4, "v_head_dim": 8, "num_hidden_layers": 4,
    "linear_attn_config": {**FILE["linear_attn_config"], "head_dim": 8,
                           "num_heads": 4},
    "kda_chunk": 8, "router_experts": 8, "experts_held": [2, 6],
    "num_experts": 4, "num_experts_per_token": 3, "vocab_size": 64,
    "vocab_chunk": 24, "bf16": False, "attn_impl": "xla", "remat": True,
    "reference_tokens": [1, 48], "reference_query_block": 16,
    "seeded_state": {**FILE["seeded_state"], "selection_bias_std": 0.1}}

# Both sides compute in float32 and differ in the order of their sums (the
# chunked form against the recurrence, a sort and a grouped matmul against a
# loop over experts, a blockwise loss against whole logits): measured 1e-6 to
# 3e-6 on these sizes.  1e-4 leaves that thirty times and is far under what a
# missing erase, a bf16 state or fp8 weights move (the tests below).
TOL = 1e-4


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def _ids(seed=0, cfg=CFG):
    rows, length = cfg["reference_tokens"]
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (rows, length)), jnp.int32)


def _both_sides(cfg, ids, erase=True):
    _tfm, model = KIMI._model(cfg)
    params, buffers = KIMI._init_state(cfg, jax.random.PRNGKey(1))
    (loss, _metrics), grads = jax.jit(jax.value_and_grad(
        KIMI._loss_fn(tfm, model, cfg), has_aux=True))(
            params, {"input_ids": ids}, buffers)
    logits = model.apply({"params": params, "buffers": buffers}, ids)

    def reference(params):
        out, routing = KIMI.reference_forward(cfg, params, buffers, ids,
                                              erase=erase)
        return KIMI.reference_loss(out, ids), (out, routing)

    (ref_loss, (ref_logits, routing)), ref_grads = jax.jit(
        jax.value_and_grad(reference, has_aux=True))(params)
    return (params, (loss, logits, grads),
            (ref_loss, ref_logits, ref_grads, routing))


def test_system_matches_the_reference():
    ids = _ids()
    params, (loss, logits, grads), (ref_loss, ref_logits, ref_grads,
                                    routing) = _both_sides(CFG, ids)
    assert KIMI.layer_kinds(CFG) == ["kda", "kda", "kda", "latent"]
    assert len(routing) == 3            # the first layer is dense
    assert abs(float(loss) - float(ref_loss)) < TOL * float(ref_loss)
    assert _rel(logits, ref_logits) < TOL
    worst = jax.tree.map(_rel, grads, ref_grads)
    assert max(jax.tree.leaves(worst)) < TOL, worst
    # one adamw step, written out, against optax's on the system's gradients
    import optax

    optimizer = optax.adamw(CFG["optimizer"]["learning_rate"])
    change, _ = optimizer.update(grads, optimizer.init(params), params)
    want = KIMI.reference_adamw_step(CFG, params, ref_grads)
    assert max(jax.tree.leaves(jax.tree.map(_rel, change, want))) < 2e-2
    for layer in range(3):
        assert "A_log" in grads[f"block_{layer}"]["attn"]
    assert "kv_a_proj" in grads["block_3"]["attn"]


@pytest.mark.parametrize("change,least", [
    ({"kda_state_dtype": "bfloat16"}, 1e-3),    # the op's state in bf16
    ({"experts_held": [0, 4]}, 1e-3),           # another chip's share
    ({"routed_scaling_factor": 1.0}, 1e-3),
    ({"kda_chunk": 16}, None),                  # the same model, other chunks
])
def test_another_system_fails_the_tolerance(change, least):
    ids = _ids()
    _p, (_l, _lg, _g), (_rl, ref_logits, _rg, _r) = _both_sides(CFG, ids)
    _tfm, model = KIMI._model({**CFG, **change})
    params, buffers = KIMI._init_state(CFG, jax.random.PRNGKey(1))
    logits = model.apply({"params": params, "buffers": buffers}, ids)
    if least is None:
        assert _rel(logits, ref_logits) < TOL
    else:
        assert _rel(logits, ref_logits) > least


def test_the_seeded_state_tells_the_delta_rule_from_its_absence():
    """The reference without the erase (a plain gated linear attention) is
    far from the system: the comparison guards the term."""
    ids = _ids()
    _p, (loss, logits, grads), (ref_loss, ref_logits, ref_grads,
                                _r) = _both_sides(CFG, ids, erase=False)
    assert _rel(logits, ref_logits) > 1e-2
    worst = jax.tree.map(_rel, grads["block_0"]["attn"],
                         ref_grads["block_0"]["attn"])
    assert max(jax.tree.leaves(worst)) > 1e-2


def test_check_train_passes_small_and_every_control_fails():
    sound = KIMI.check_train(CFG, {}, seed=3)
    assert sound["ok"], sound
    assert set(sound["errors"]) == set(KIMI.TOLERANCE)
    assert max(v for k, v in sound["errors"].items()
               if not k.startswith("update")) < TOL
    # the change is read off float32 parameters at a rate of 1e-6
    assert sound["errors"]["update_l2"] < 0.05
    assert sound["held_pairs_by_layer"] and sound["loss"] < TOL
    assert "['attn']" in sound["grad_kda_leaf_worst"]
    for control in ("fp8", "no_erase", "frozen"):
        failed = KIMI.check_train(CFG, {}, seed=3, degrade_system=control)
        assert not failed["ok"], (control, failed)
        over = [k for k, v in failed["errors"].items()
                if not v < KIMI.TOLERANCE[k]]
        assert over, control
    # the limits are the chip's, set beside bf16 operands' rounding; here
    # both sides are float32, and a state held in bf16 reads a thousand
    # times the sound system's readings (on the chip it has to pass a limit:
    # TOLERANCE's comment)
    rounded = KIMI.check_train(CFG, {}, seed=3, degrade_system="bf16_state")
    for key in ("logits_l2", "grad_kda_leaf_max"):
        assert rounded["errors"][key] > 1000 * sound["errors"][key], key
    assert set(KIMI.CONTROLS) == {"fp8", "no_erase", "frozen", "bf16_state"}
    frozen = KIMI.check_train(CFG, {}, seed=3, degrade_system="frozen")
    for key in ("grad_kda_leaf_max", "grad_leaf_max", "update_l2",
                "update_leaf_max"):
        assert frozen["errors"][key] == pytest.approx(1.0, abs=1e-3)


def test_thirty_two_shares_of_the_expert_layer_add_up_to_the_uncut_one():
    """The routed parts that all 32 ranges of 8 experts give, with the
    shared expert counted once, add up to the uncut reference's expert
    layer; the SYSTEM's held part is the matching share."""
    from tensorflowonspark_tpu.parallel.ep import MoEMLP

    cfg = {**CFG, "router_experts": 256, "num_experts_per_token": 8,
           "experts_held": [0, 8]}
    d, ff = cfg["hidden_size"], cfg["moe_intermediate_size"]
    keys = jax.random.split(jax.random.PRNGKey(5), 8)
    y = jax.random.normal(keys[0], (40, d))
    whole = {"router": {"kernel": jax.random.normal(keys[1], (d, 256))},
             "experts_gate": jax.random.normal(keys[2], (256, d, ff)) / 6,
             "experts_up": jax.random.normal(keys[3], (256, d, ff)) / 6,
             "experts_down": jax.random.normal(keys[4], (256, ff, d)) / 4}
    bias = 0.1 * jax.random.normal(keys[5], (256,))
    shared = {name: {"kernel": jax.random.normal(key, shape) / 6}
              for name, key, shape in (("gate_proj", keys[6], (d, ff)),
                                       ("up_proj", keys[7], (d, ff)),
                                       ("down_proj", keys[5], (ff, d)))}

    def share(first, end):
        return {"router": whole["router"],
                **{k: whole[k][first:end]
                   for k in ("experts_gate", "experts_up", "experts_down")}}

    # the uncut layer: every expert summed over, the shared expert once
    uncut, top_idx = KIMI._reference_moe(cfg, whole, bias, y, held=(0, 256))
    uncut = uncut + KIMI._swiglu(shared, y)
    shares = [KIMI._reference_moe(
        {**cfg, "experts_held": [8 * i, 8 * i + 8]},
        share(8 * i, 8 * i + 8), bias, y)[0] for i in range(32)]
    np.testing.assert_allclose(sum(shares) + KIMI._swiglu(shared, y), uncut,
                               atol=2e-5)
    assert top_idx.shape == (40, 8)
    # the program's layer, told it holds experts 8-15 of 256, gives share 1
    layer = MoEMLP(d, ff, 256, 8, None, compute_dtype=jnp.float32,
                   norm_topk_prob=True, held=(8, 16), scoring="sigmoid",
                   selection_bias=True,
                   routed_scale=cfg["routed_scaling_factor"])
    variables = {"params": share(8, 16),
                 "buffers": {"e_score_correction_bias": bias}}
    got = layer.apply(variables, y[None])
    np.testing.assert_allclose(got[0], shares[1], atol=2e-5)


def test_records_are_ids_of_the_held_slice():
    traffic = common.read_json(os.path.join(common.HERE, "traffic",
                                            "token_rows_16k_x1.json"))
    rows = KIMI.train_records(FILE, traffic, common.seeded_rng(7, "records"),
                              3)
    ids = np.stack(rows)
    assert ids.shape == (3, 16384) and ids.dtype == np.int32
    assert 0 <= ids.min() and FILE["vocab_size"] - 64 < ids.max() < 20480
    again = KIMI.train_records(FILE, traffic, common.seeded_rng(7, "records"),
                               3)
    np.testing.assert_array_equal(ids, np.stack(again))
    # a large seed, as the driver's are
    KIMI.train_records(FILE, traffic,
                       common.seeded_rng(2 ** 31 + 12345, "records"), 2)


def test_the_cell_s_counts_are_this_chip_s_work_and_no_more():
    """``flops_per_sample`` and the kernels' costs at the cell's sizes
    against the ISSUE's own arithmetic."""
    cell = common.resolve_cell(CELL)
    cfg, traffic = cell["config"], cell["traffic"]
    length = traffic["seq_len"]
    assert length == 16384 and traffic["rows_per_chip"] == 1
    assert KIMI.layer_kinds(cfg) == ["kda", "kda", "kda", "latent", "kda"]
    assert KIMI.held_pairs_per_position(cfg) == 0.25    # 4,096 pairs a layer
    assert KIMI._kda_weights(cfg) == (4 * 2304 * 4096
                                      + 2 * (2304 * 128 + 128 * 4096)
                                      + 2304 * 32) == 39_460_864
    assert KIMI._latent_weights(cfg) == 29_114_368
    # the rule at chunk 64, a position and head, in multiply-adds: two
    # triangles of scores, the solve at 2 x 128 columns, three products with
    # the state and the scores' product with the values
    macs = (63 / 2 * 128 + 65 / 2 * 128 + 63 / 2 * 256 + 3 * 128 * 128
            + 65 / 2 * 128)
    assert macs == 69_568
    scan = KIMI.kda_scan_cost(cfg, traffic, 1)
    assert scan["flops"] == 4 * 3 * 2 * 16384 * 32 * macs
    # the issue's "about 6 M a token a layer, forward" (in FLOPs: 4.5 M)
    assert scan["flops"] / (4 * 3 * 16384) == pytest.approx(4.45e6, rel=0.01)
    assert scan["bytes"] == 4 * 2 * 16384 * (2 * 4 * 4096 + 4 * 4096 + 4 * 32)
    pairs = KIMI.causal_pairs(length)
    fwd = KIMI.mla1_flash_fwd_cost(cfg, traffic, 1)
    bwd = KIMI.mla1_flash_bwd_cost(cfg, traffic, 1)
    assert fwd["flops"] == 2 * pairs * 32 * (192 + 128)
    assert bwd["flops"] == 2 * pairs * 32 * (3 * 192 + 2 * 128)
    assert fwd["bytes"] == 16384 * (2 * (32 * 192 + 32 * 128 + 64
                                         + 2 * 32 * 128) + 32 * 4)
    moe = KIMI.moe_experts_cost(cfg, traffic, 1)
    held = 16384 * 0.25
    assert moe["flops"] == 4 * 3 * 2 * held * 3 * 2304 * 1024
    assert moe["bytes"] == 4 * 2 * (5 * held * 2304 + 3 * 8 * 3 * 2304 * 1024)
    expert_layer = 2304 * 256 + 3 * 2304 * 1024 + 0.25 * 3 * 2304 * 1024
    want = (6 * (4 * 39_460_864 + 29_114_368 + 3 * 2304 * 9216
                 + 4 * expert_layer + 2304 * 20480)
            + (fwd["flops"] + bwd["flops"] + scan["flops"]) / length)
    assert KIMI.flops_per_sample(cfg, traffic) == pytest.approx(want)
    # the issue's shares of a token's forward FLOPs: the KDA mixers about
    # two fifths, the one latent layer's kernels about a fifth
    forward = (2 * (want - (fwd["flops"] + bwd["flops"] + scan["flops"])
                    / length) / 6
               + (fwd["flops"] + scan["flops"] / 3) / length)
    kda = 4 * 2 * 39_460_864 + scan["flops"] / 3 / length
    assert kda / forward == pytest.approx(0.39, abs=0.03)
    assert fwd["flops"] / length / forward == pytest.approx(0.19, abs=0.03)


def test_the_file_keeps_every_published_width():
    """Every key of the catalog's row under the same name, changed only where
    ``reduced`` says; the published values, the deployment and every assumed
    size are stated."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
        assert FILE["source"] == row["source_url"]
        differs = [k for k, v in row["config"].items() if FILE.get(k) != v]
        assert sorted(differs) == sorted(FILE["reduced"])
    assert FILE["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    assert FILE["published"] == {"num_hidden_layers": 27, "num_experts": 256,
                                 "vocab_size": 163840}
    linear = FILE["linear_attn_config"]
    assert (FILE["hidden_size"], linear["num_heads"], linear["head_dim"],
            linear["short_conv_kernel_size"], FILE["kv_lora_rank"],
            FILE["qk_nope_head_dim"], FILE["qk_rope_head_dim"],
            FILE["v_head_dim"], FILE["q_lora_rank"], FILE["mla_use_nope"],
            FILE["intermediate_size"], FILE["moe_intermediate_size"],
            FILE["num_shared_experts"], FILE["router_experts"],
            FILE["num_experts_per_token"], FILE["routed_scaling_factor"],
            FILE["moe_router_activation_func"], FILE["first_k_dense_replace"],
            FILE["rms_norm_eps"]) == (
                2304, 32, 128, 4, 512, 128, 64, 128, None, True, 9216, 1024,
                1, 256, 8, 2.446, "sigmoid", 1, 1e-5)
    # the published lists whole; the held layers are their entries up to 5
    assert linear["kda_layers"][:4] == [1, 2, 3, 5]
    assert linear["full_attn_layers"][:1] == [4]
    assert KIMI.layer_kinds(FILE) == ["kda", "kda", "kda", "latent", "kda"]
    first, end = FILE["experts_held"]
    assert end - first == FILE["num_experts"] == 8
    assert FILE["vocab_size"] * 8 == FILE["published"]["vocab_size"]
    # the floors: the dense layer and four layers after it, 8 experts, 1/8
    assert FILE["num_hidden_layers"] - FILE["first_k_dense_replace"] >= 4
    for said in ("32 chips", "512 pairs", "1/32", "602,433,408", "9.64 GB"):
        assert said in FILE["deployment"], said
    for key in ("source", "published", "reduced", "assumed", "deployment",
                "rehearsal", "compute"):
        assert FILE[key], key
    stated = " ".join(FILE["assumed"])
    for size in ("A_log", "dt_bias", "softplus", "low-rank", "no bias",
                 "1e-6", "initialis", "mla_use_nope",
                 "e_score_correction_bias", "no auxiliary", "learning rate",
                 "vocab_chunk", "remat", "kda_chunk", "embedding_std",
                 "q_proj_scale", "selection_bias_std", "num_expert_group",
                 "linear_attn_config"):
        assert size in stated, size
    # the parameters the file counts are the ones the program creates
    params, buffers = jax.eval_shape(lambda: KIMI._init_state(
        FILE, jax.random.PRNGKey(0)))
    count = [sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
             for tree in (params, buffers)]
    assert count == [602_433_408, 4 * 256]
    kda = sum(int(np.prod(a.shape))
              for a in jax.tree.leaves(params["block_0"]["attn"]))
    latent = sum(int(np.prod(a.shape))
                 for a in jax.tree.leaves(params["block_3"]["attn"]))
    assert (kda, latent) == (39_514_272, 29_114_880)
    # the rehearsal has both kinds of layer, a dense first one, >= 2 chunks
    small = {**FILE, **FILE["rehearsal"], "linear_attn_config": {
        **linear, **FILE["rehearsal"]["linear_attn_config"]}}
    assert set(KIMI.layer_kinds(small)) == {"kda", "latent"}
    assert small["reference_tokens"][1] >= 2 * small["kda_chunk"]
    assert small["attn_impl"] == "xla" and small["bf16"] is False


def test_the_seeded_state_has_the_scales_the_file_states():
    params, buffers = KIMI._init_state(CFG, jax.random.PRNGKey(2))
    twin = tfm.build_transformer({**KIMI.system_config(CFG),
                                  "attn_impl": "xla", "remat": False})
    plain = twin.init(jax.random.PRNGKey(2), jnp.zeros((1, 8), jnp.int32))
    assert float(jnp.std(params["embed"]["embedding"])) == pytest.approx(
        CFG["seeded_state"]["embedding_std"], rel=0.1)
    np.testing.assert_allclose(
        params["block_3"]["attn"]["q_proj"]["kernel"],
        plain["params"]["block_3"]["attn"]["q_proj"]["kernel"]
        * CFG["seeded_state"]["q_proj_scale"], rtol=1e-6)
    np.testing.assert_array_equal(
        params["block_0"]["attn"]["q_proj"]["kernel"],
        plain["params"]["block_0"]["attn"]["q_proj"]["kernel"])
    bias = buffers["block_1"]["moe"]["e_score_correction_bias"]
    assert float(jnp.std(bias)) == pytest.approx(0.1, rel=0.6)
    assert "block_0" not in buffers
    # beta near a half, the decay over decades
    beta = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(0), (64, 32))
                          @ params["block_0"]["attn"]["b_proj"]["kernel"])
    assert 0.3 < float(beta.mean()) < 0.7
    rate = (jnp.exp(params["block_0"]["attn"]["A_log"])[:, None]
            * jax.nn.softplus(params["block_0"]["attn"]["dt_bias"]
                              ).reshape(4, 8))
    assert float(rate.max() / rate.min()) > 30


def test_a_program_without_the_mechanisms_is_refused(monkeypatch):
    """The parent commit's program builds SOME model from these keys (its
    builder ignores what it does not know): the configuration says so at
    once instead of timing another model under Kimi-Linear's name."""
    monkeypatch.delattr(tfm, "KimiDeltaAttention")
    with pytest.raises(NotImplementedError, match="Kimi Delta Attention"):
        KIMI._model(CFG)


# -- the readers --------------------------------------------------------------

STEP = "jit(step)/jit(main)/loss_and_grad/"
FWD = "jvp(Transformer)/checkpoint/"
BWD = "transpose(jvp(Transformer))/checkpoint/rematted_computation/"
SUMS = {
    STEP + FWD + "block_0/attn/kda/q_proj/dot_general:": 300e-6,
    STEP + BWD + "block_0/attn/kda/kda/conv/mul:": 200e-6,
    STEP + BWD + "block_0/attn/kda/kda/gates/f_b_proj/dot_general:": 100e-6,
    STEP + FWD + "block_0/attn/kda/kda/scan/checkpoint/kda_op/intra/"
    "dot_general:": 1000e-6,
    STEP + BWD + "block_2/attn/kda/kda/scan/checkpoint/kda_op/inter/"
    "while:": 3000e-6,
    STEP + BWD + "block_0/attn/kda/kda/gate_norm/mul:": 400e-6,
    STEP + FWD + "block_3/attn/attention/flash_fwd/jit(_flash_fwd_pallas)/"
    "pallas_call:": 700e-6,
    STEP + FWD + "block_3/attn/attention/flash_fwd/transpose:": 100e-6,
    STEP + BWD + "block_3/attn/attention/flash_bwd/jit(_flash_bwd_pallas)/"
    "pallas_call:": 1600e-6,
    STEP + FWD + "block_3/attn/mla/project/q_proj/dot_general:": 150e-6,
    "": 30e-6,
}


def _kernels_per_step(sums):
    """As ``scope_calls.kernels_per_step`` counts them, of the hand-made
    scope paths: an execution of each ``pallas_call`` a traced step."""
    def count(run, scope):
        calls = sum(1 for path in sums or {} if path.endswith("pallas_call:")
                    and scope_times.in_scope(path, scope))
        return calls or None
    return count


def _run(monkeypatch, sums):
    monkeypatch.setattr(scope_times, "run_scope_seconds", lambda run: sums)
    monkeypatch.setattr(scope_calls, "kernels_per_step",
                        _kernels_per_step(sums))
    # a step's rule needs 100 us of compute and 200 us of memory traffic; ONE
    # latent forward 80 and 15, the step's latent backward 200 and 30
    us = lambda flops, bytes_: {"flops": 197e12 * flops * 1e-6,  # noqa: E731
                                "bytes": 819e9 * bytes_ * 1e-6}
    return {"cell": {"workload": CELL, "config": {"num_hidden_layers": 5}},
            "trace": {"busy_s": 1.0},
            "facts": {"traced_steps": 2, "kernels": {
                "kda_scan": us(100, 200), "mla1_flash_fwd": us(80, 15),
                "mla1_flash_bwd": us(200, 30)}},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


@pytest.mark.parametrize("metric,expected,bound", [
    ("kda_mixer_ms", 2.5, None),        # 5,000 us over two steps, all of it
    ("kda_scan_ms", 2.0, None),         # the op, forward and backward
    ("kda_conv_ms", 0.1, None),
    ("kda_scan_roofline", 10.0, "memory"),      # 200 us against 2,000
    ("mla1_flash_fwd_roofline", 20.0, "compute"),   # ONE call, 80 of 400 us
    ("mla1_flash_bwd_roofline", 25.0, "compute"),   # 200 of 800 us
    ("mla_project_ms", 0.075, None),
    ("bd_flash_fwd_ms", 0.4, None),
    ("flash_bwd_ms", 0.8, None),
])
def test_new_readers_on_a_hand_made_run(monkeypatch, metric, expected, bound):
    reader = common.load_module("layer_metrics", metric)
    run = _run(monkeypatch, SUMS)
    assert reader.read(run) == pytest.approx(expected)
    if bound:
        assert reader.bound(run) == bound
        assert reader.read(run) <= 100.0


@pytest.mark.parametrize("metric", READERS)
def test_new_readers_find_nothing_in_the_parent_s_program(monkeypatch, metric):
    """No trace, a trace without scopes, a program that names none of the
    scopes (the parent's, traced under this PR's benchmark files): None, no
    raise."""
    reader = common.load_module("layer_metrics", metric)
    run = _run(monkeypatch, SUMS)
    monkeypatch.undo()
    assert reader.read({**run, "trace": None}) is None
    assert reader.read(_run(monkeypatch, None)) is None
    others = {"": 30e-6, STEP + FWD + "block_0/mlp/dot_general:": 50e-6}
    assert reader.read(_run(monkeypatch, others)) is None
    if metric.endswith("_roofline"):
        run = _run(monkeypatch, SUMS)
        assert reader.read({**run, "peaks": None}) is None
        run["facts"]["kernels"] = {}
        assert reader.read(run) is None and reader.bound(run) is None


def test_the_account_books_a_kda_layer_s_scopes_and_leaves_nothing_unowned():
    from benchmark import step_account

    owner = {path: step_account.bucket_of(path) for path in SUMS}
    assert owner[STEP + FWD + "block_0/attn/kda/q_proj/dot_general:"] == \
        "attention projections"
    for part in ("conv/mul:", "gates/f_b_proj/dot_general:", "gate_norm/mul:",
                 "intra/dot_general:", "inter/while:"):
        path = next(p for p in SUMS if p.endswith(part))
        assert owner[path] == "attention, the rest", path
    assert owner[next(p for p in SUMS if "mla/project" in p)] == \
        "latent projections"
    assert step_account.UNOWNED not in owner.values()


# -- the manifest with its twelfth cell ---------------------------------------

def test_manifest_holds_the_cell_its_configuration_and_six_readers():
    manifest = common.load_manifest()
    cells = [w["name"] for w in manifest["workloads"]]
    assert CELL in cells and len(cells) >= 12
    assert len(manifest["configs"]) >= 10
    # one chip: the four-chip quota stays where it was
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == FILE["reduced"]
    assert entry["source"] == FILE["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    names = [m["name"] for m in manifest["per_layer"]]
    first = names.index(READERS[0])
    assert tuple(names[first:first + len(READERS)]) == READERS
    cell = common.resolve_cell(CELL)
    assert (cell["chips"], cell["traffic_name"]) == (1, "token_rows_16k_x1")
    assert cell["traffic"]["input_mode"] == "streaming"
    assert {m["name"] for m in cell["end_to_end"]} == {"train_tok_rate",
                                                       "setup_s"}
    reported = {m["name"] for m in cell["per_layer"]}
    assert reported >= {"claim_s", "first_step_s", *JOINED, *READERS}
    # one layer in five is latent: the readers that multiply one call by
    # every layer of the model are not this cell's, nor the band's
    assert not reported & {"mla_flash_fwd_roofline", "mla_flash_bwd_roofline",
                           "flash_fwd_ms", "flash_fwd_roofline",
                           "flash_bwd_roofline", "bd_flash_fwd_roofline",
                           "swa_flash_fwd_ms", "ssm_mixer_ms"}
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in READERS:
        metric = by_name[name]
        reader = common.load_module("layer_metrics", name)
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
            metric["layer"], metric["unit"], metric["moves"])
        assert metric["workloads"] == [CELL]
        assert metric["source"] == "device_trace"
    for name in JOINED:     # appended after what was there
        assert by_name[name]["workloads"][-1] == CELL or \
            CELL in by_name[name]["workloads"]
    tok = next(m for m in manifest["end_to_end"]
               if m["name"] == "train_tok_rate")
    assert CELL in tok["workloads"] and tok["bound"] == 0.01
