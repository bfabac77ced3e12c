"""Kanana-2-30B-A3B (ISSUE 39): the program against the plain reference kept
with the benchmark (``benchmark/configs/kanana2_30b_a3b_d5_ep8.py``) at a small
size on the CPU, the published pairing of the rotary columns, the
configuration's counts against the issue's arithmetic, its file against the
catalog's row, the five new readers on a hand-made run (and the two accepted
readers of the flash scopes that the cell joins), and the manifest with its
eighth cell.  The same comparison runs at the published widths on the
chip (``check_train``)."""

from __future__ import annotations

import json
import os
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common, scope_times
from tensorflowonspark_tpu.models import transformer as tfm

KANANA = common.load_module("configs", "kanana2_30b_a3b_d5_ep8")
CELL = "kanana2_30b_a3b_d5_ep8_train_8k"
FILE = common.read_json(os.path.join(common.HERE, "configs",
                                     "kanana2_30b_a3b_d5_ep8.json"))
READERS = ("mla_project_ms", "mla_flash_fwd_roofline",
           "mla_flash_bwd_roofline", "moe_shared_ms", "moe_router_ms")
# accepted readers of the scopes ``flash_fwd`` / ``flash_bwd``: the cell runs
# the same three kernels under the same scopes and joins their lists
JOINED = ("bd_flash_fwd_ms", "flash_bwd_ms")

# Kanana-2's shape in small: a dense layer and two expert layers, 4 heads of
# 8 + 4 over values of 8 from a latent of 16, experts 2-5 of 8 held, 3 a
# token, two shared experts.
CFG = {"hidden_size": 32, "intermediate_size": 48, "moe_intermediate_size": 16,
       "num_attention_heads": 4, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
       "qk_rope_head_dim": 4, "v_head_dim": 8, "num_hidden_layers": 3,
       "first_k_dense_replace": 1, "router_experts": 8,
       "experts_held": [2, 6], "n_routed_experts": 4, "n_shared_experts": 2,
       "num_experts_per_tok": 3, "n_group": 1, "topk_group": 1,
       "norm_topk_prob": True, "scoring_func": "sigmoid",
       "topk_method": "noaux_tc", "routed_scaling_factor": 2.448,
       "vocab_size": 64, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
       "vocab_chunk": 24, "bf16": False, "reference_tokens": [2, 24],
       "optimizer": FILE["optimizer"],
       "seeded_state": {**FILE["seeded_state"], "selection_bias_std": 0.1}}

# Both sides compute in float32 and differ in the order of their sums (a sort
# and a grouped matmul against a loop over experts, a flash kernel with two
# products against whole scores over concatenated keys, a blockwise loss
# against whole logits): measured 2e-7 to 2e-6 on these sizes (relative to
# the largest entry).  1e-4 leaves that fifty times and is far under what the
# wrong pairing, a biased weight or a missing scale move (the tests below).
TOL = 1e-4


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def _ids(seed=0, cfg=CFG):
    rows, length = cfg["reference_tokens"]
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (rows, length)), jnp.int32)


def _state(cfg, key=1):
    return KANANA._init_state(cfg, jax.random.PRNGKey(key))


def _both_sides(cfg, ids):
    _tfm, model = KANANA._model(cfg)
    params, buffers = _state(cfg)
    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        KANANA._loss_fn(tfm, model, cfg), has_aux=True))(
            params, {"input_ids": ids}, buffers)
    logits, sown = model.apply({"params": params, "buffers": buffers}, ids,
                               mutable=["intermediates"])

    def reference(params):      # gradients come back in the program's layout
        ref_logits, routing = KANANA.reference_forward(
            cfg, KANANA.published_layout(cfg, params), buffers, ids)
        return KANANA.reference_loss(ref_logits, ids), (ref_logits, routing)

    (ref_loss, (ref_logits, ref_routing)), ref_grads = jax.value_and_grad(
        reference, has_aux=True)(params)
    assert jax.tree.structure(grads) == jax.tree.structure(ref_grads)
    errors = {"loss": abs(float(loss) - float(ref_loss))
              / abs(float(ref_loss)),
              "logits": _rel(logits, ref_logits),
              "grads": max(_rel(a, b) for a, b in zip(
                  jax.tree.leaves(grads), jax.tree.leaves(ref_grads)))}
    routing = [np.sort(np.asarray(r)) for r in KANANA._sown_routing(sown)]
    same = all(np.array_equal(a, np.sort(np.asarray(b)))
               for a, b in zip(routing, ref_routing))
    return errors, metrics, grads, same and len(routing) == len(ref_routing)


@pytest.mark.parametrize("attn_impl", ["pallas_interpret", "xla"])
def test_system_matches_the_reference(attn_impl):
    """Loss, logits, the routing and the gradient of every parameter leaf,
    through the kernels in interpret mode and through the XLA path; the
    bias buffers are no parameters: no gradient has a leaf for them."""
    errors, metrics, grads, same_routing = _both_sides(
        {**CFG, "attn_impl": attn_impl}, _ids())
    assert max(errors.values()) < TOL, errors
    assert same_routing
    # 3 choices over 8 experts, 4 held: half the pairs on even routing
    assert 0.2 < float(metrics["moe_held_pairs"]) < 0.8
    assert 0.0 < float(metrics["moe_bias_moved"]) < 1.0
    assert float(metrics["aux_loss"]) == 0.0
    for layer in (1, 2):
        assert set(grads[f"block_{layer}"]["moe"]) == {
            "router", "experts_gate", "experts_up", "experts_down"}
        assert "shared" in grads[f"block_{layer}"]
    assert "moe" not in grads["block_0"] and "shared" not in grads["block_0"]


def test_the_published_pairing_is_a_permutation_no_score_sees():
    """``published_layout`` moves the rotary columns of ``W_q`` and
    ``W_kva`` and nothing else; the reference fed the program's layout
    instead turns the wrong pairs and leaves the tolerance."""
    cfg = {**CFG, "attn_impl": "xla"}
    params, buffers = _state(cfg)
    published = KANANA.published_layout(cfg, params)
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    q, q_pub = (p["block_1"]["attn"]["q_proj"]["kernel"]
                for p in (params, published))
    np.testing.assert_array_equal(q[..., :nope], q_pub[..., :nope])
    np.testing.assert_array_equal(q[..., nope:nope + rope // 2],
                                  q_pub[..., nope::2])
    np.testing.assert_array_equal(q[..., nope + rope // 2:],
                                  q_pub[..., nope + 1::2])
    assert jax.tree.structure(params) == jax.tree.structure(published)
    moved = [jax.tree_util.keystr(path) for (path, a), b in zip(
        jax.tree_util.tree_flatten_with_path(params)[0],
        jax.tree.leaves(published)) if not np.array_equal(a, b)]
    assert len(moved) == 2 * cfg["num_hidden_layers"]
    assert all("q_proj" in name or "kv_a_proj" in name for name in moved)
    ids = _ids()
    logits = KANANA._model(cfg)[1].apply(
        {"params": params, "buffers": buffers}, ids)
    right, _ = KANANA.reference_forward(cfg, published, buffers, ids)
    wrong, _ = KANANA.reference_forward(cfg, params, buffers, ids)
    assert _rel(logits, right) < TOL < 1e-3 < _rel(logits, wrong)


@pytest.mark.parametrize("change,least", [
    ({"scoring_func": "softmax"}, 1e-3),
    ({"routed_scaling_factor": 1.0}, 1e-3),
    ({"experts_held": [0, 4]}, 1e-3),           # another chip's share
    ({"norm_topk_prob": False}, 1e-3),
    ({"n_shared_experts": 1}, None),            # other shapes
    ({"first_k_dense_replace": 0}, None),
    ({"kv_lora_rank": 8}, None),
])
def test_another_model_fails_the_tolerance(change, least):
    """The reference is Kanana-2's and no neighbour's: each change to the
    system alone moves it out of tolerance (or cannot even load the
    parameters)."""
    cfg = {**CFG, "attn_impl": "xla"}
    params, buffers = _state(cfg)
    ids = _ids()
    _tfm, wrong = KANANA._model({**cfg, **change})
    if least is None:
        with pytest.raises(Exception):
            wrong.apply({"params": params, "buffers": buffers}, ids)
        return
    logits = wrong.apply({"params": params, "buffers": buffers}, ids)
    ref_logits, _routing = KANANA.reference_forward(
        cfg, KANANA.published_layout(cfg, params), buffers, ids)
    assert _rel(logits, ref_logits) > least


def test_a_flat_bias_is_another_choice():
    """The system with its bias buffers zeroed chooses other experts: the
    check's routing limit would see a program that drops the bias."""
    cfg = {**CFG, "attn_impl": "xla"}
    params, buffers = _state(cfg)
    flat = jax.tree.map(jnp.zeros_like, buffers)
    ids = _ids()
    model = KANANA._model(cfg)[1]
    tops = [KANANA._sown_routing(model.apply(
        {"params": params, "buffers": b}, ids, mutable=["intermediates"])[1])
        for b in (buffers, flat)]
    chosen = [KANANA._chosen(t, cfg["router_experts"]) for t in tops]
    assert 0.02 < 1 - (chosen[0] & chosen[1]).sum() / chosen[0].sum() < 0.9


def test_check_train_passes_small_and_fails_degraded():
    """``check_train`` itself at the small size: ok on the true weights, and
    with the system's weights rounded to fp8 at least one limit fails."""
    cfg = {**CFG, "attn_impl": "xla"}
    good = KANANA.check_train(cfg, {"seq_len": 24}, 3)
    assert good["ok"], good
    assert set(good["errors"]) == set(good["tolerance"]) == {
        "logits_l2", "logits_max", "routing_disagreement", "update_l2",
        "update_leaf_max"}
    # float32 on both sides: the two changes differ where a gradient is
    # within rounding of 0
    assert good["errors"]["update_l2"] < 0.01
    assert good["loss"] < 1e-5 and good["grad_norm"] < 1e-4
    assert len(good["held_pairs_by_layer"]) == 2
    assert good["routing_agreement"] == 1.0
    bad = KANANA.check_train(cfg, {"seq_len": 24}, 3, degrade_system=True)
    assert not bad["ok"]
    assert any(bad["errors"][k] >= bad["tolerance"][k] for k in bad["errors"])


def test_records_are_ids_of_the_held_slice():
    traffic = {"seq_len": 64}
    rows = KANANA.train_records(FILE, traffic,
                                common.seeded_rng(7, "records"), 50)
    batch = KANANA.rows_to_arrays(FILE)(rows[:5])
    assert set(batch) == {"input_ids"}
    assert batch["input_ids"].shape == (5, 64)
    assert batch["input_ids"].dtype == np.int32
    ids = np.stack(rows)
    assert ids.min() >= 0 and ids.max() < FILE["vocab_size"]
    again = KANANA.train_records(FILE, traffic,
                                 common.seeded_rng(7, "records"), 50)
    np.testing.assert_array_equal(ids, np.stack(again))
    # a large seed, as the driver's are
    KANANA.train_records(FILE, traffic,
                         common.seeded_rng(2 ** 31 + 12345, "records"), 2)


def test_the_cell_s_counts_are_this_chip_s_work_and_no_more():
    """``flops_per_sample`` and the kernels' costs at the cell's sizes
    against the ISSUE's own arithmetic."""
    cell = common.resolve_cell(CELL)
    cfg, traffic = cell["config"], cell["traffic"]
    length = traffic["seq_len"]
    assert length == 8192 and traffic["rows_per_chip"] == 1
    pairs = KANANA.causal_pairs(length)
    assert pairs == 33_558_528
    assert KANANA.held_pairs_per_position(cfg) == 0.75
    attention = (2048 * 32 * 192 + 2048 * 576 + 512 * 32 * 256
                 + 32 * 128 * 2048)
    assert attention == KANANA._attention_params(cfg) == 26_345_472
    fwd = KANANA.mla_flash_fwd_cost(cfg, traffic, 1)
    bwd = KANANA.mla_flash_bwd_cost(cfg, traffic, 1)
    assert fwd["flops"] == 2 * pairs * 32 * (192 + 128)
    assert fwd["flops"] == pytest.approx(687e9, rel=1e-3)
    assert bwd["flops"] == 2 * pairs * 32 * (3 * 192 + 2 * 128)
    # q, k_nope, v, o at 32 heads, the rotary key ONCE, the lse in float32
    assert fwd["bytes"] == 8192 * (2 * (32 * 192 + 32 * 128 + 64
                                        + 2 * 32 * 128) + 32 * 4)
    assert bwd["bytes"] == 8192 * (
        2 * (32 * 192 + 32 * 128 + 64 + 3 * 32 * 128) + 32 * 4
        + 2 * (32 * 192 + 32 * 128 + 64 + 32 * 128))
    # a copy of the rotary key a head would be 31 x 64 x 2 bytes a position
    # more, each way
    assert fwd["bytes"] < 8192 * (2 * (32 * 192 + 32 * 192 + 2 * 32 * 128)
                                  + 32 * 4)
    moe = KANANA.moe_experts_cost(cfg, traffic, 1)
    held = 8192 * 0.75                                  # 6,144 pairs a layer
    assert moe["flops"] == 4 * 3 * 2 * held * 3 * 2048 * 768
    assert moe["bytes"] == 4 * 2 * (5 * held * 2048 + 3 * 16 * 3 * 2048 * 768)
    expert_layer = 2048 * 128 + 3 * 2048 * 1536 + 0.75 * 3 * 2048 * 768
    want = (6 * (5 * attention + 3 * 2048 * 6144 + 4 * expert_layer
                 + 2048 * 16032)
            + 5 * (fwd["flops"] + bwd["flops"]) / length)
    assert KANANA.flops_per_sample(cfg, traffic) == pytest.approx(want)
    # the issue's shares of an expert layer's forward: the kernels 52%,
    # latent attention (kernels and projections) 84%
    layer = 2 * length * (attention + expert_layer) + fwd["flops"]
    assert fwd["flops"] / layer == pytest.approx(0.52, abs=0.01)
    assert ((fwd["flops"] + 2 * length * attention) / layer
            == pytest.approx(0.84, abs=0.01))


def test_the_file_keeps_every_published_width():
    """Every key of the catalog's row under the same name, changed only where
    ``reduced`` says; the published values, the deployment and every assumed
    size are stated."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "kanana-2-30b-a3b-instruct-2601")
        assert FILE["source"] == row["source_url"]
        differs = [k for k, v in row["config"].items() if FILE.get(k) != v]
        assert sorted(differs) == sorted(FILE["reduced"])
    assert FILE["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    assert FILE["published"] == {"num_hidden_layers": 48,
                                 "n_routed_experts": 128,
                                 "vocab_size": 128256}
    assert (FILE["hidden_size"], FILE["num_attention_heads"],
            FILE["kv_lora_rank"], FILE["qk_nope_head_dim"],
            FILE["qk_rope_head_dim"], FILE["v_head_dim"], FILE["q_lora_rank"],
            FILE["intermediate_size"], FILE["moe_intermediate_size"],
            FILE["n_shared_experts"], FILE["router_experts"],
            FILE["num_experts_per_tok"], FILE["routed_scaling_factor"],
            FILE["scoring_func"], FILE["first_k_dense_replace"],
            FILE["rope_theta"], FILE["rms_norm_eps"]) == (
                2048, 32, 512, 128, 64, 128, None, 6144, 768, 2, 128, 6,
                2.448, "sigmoid", 1, 1e6, 1e-6)
    first, end = FILE["experts_held"]
    assert end - first == FILE["n_routed_experts"] == 16
    assert FILE["vocab_size"] * 8 == FILE["published"]["vocab_size"]
    # the floors: the dense layer and four expert layers, 8 experts, 1/8
    assert FILE["num_hidden_layers"] - FILE["first_k_dense_replace"] >= 4
    assert "8 chips" in FILE["deployment"] and "384 pairs" in FILE["deployment"]
    for key in ("source", "published", "reduced", "assumed", "deployment",
                "rehearsal"):
        assert FILE[key], key
    stated = " ".join(FILE["assumed"])
    for size in ("rope_interleave", "no auxiliary", "learning rate",
                 "vocab_chunk", "seeded_state", "embedding_std",
                 "q_proj_scale", "selection_bias_std", "n_group"):
        assert size in stated, size
    # the parameters the file counts are the ones the program creates
    params, buffers = jax.eval_shape(lambda: KANANA._init_state(
        FILE, jax.random.PRNGKey(0)))
    count = [sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
             for tree in (params, buffers)]
    assert count == [575_955_456, 4 * 128] and sum(count) == 575_955_968
    assert "576.0 M" in FILE["deployment"]


def test_the_seeded_state_has_the_scales_the_file_states():
    """The program's own initialisers but for three scales: the embedding's
    rows at ``embedding_std``, ``W_q`` times ``q_proj_scale``, the routers'
    bias buffers at ``selection_bias_std``; everything else as flax draws
    it."""
    seeded = CFG["seeded_state"]
    params, buffers = _state(CFG, 3)
    assert np.asarray(params["embed"]["embedding"]).std() == pytest.approx(
        seeded["embedding_std"], rel=0.1)
    for layer in range(CFG["num_hidden_layers"]):
        block = params[f"block_{layer}"]
        np.testing.assert_allclose(block["attn_norm"]["scale"], 1.0)
        np.testing.assert_allclose(block["attn"]["kv_a_norm"]["scale"], 1.0)
        assert np.asarray(block["attn"]["q_proj"]["kernel"]).std() == \
            pytest.approx(seeded["q_proj_scale"]
                          / np.sqrt(CFG["hidden_size"]), rel=0.15)
        assert np.asarray(block["attn"]["o_proj"]["kernel"]).std() == \
            pytest.approx(1 / np.sqrt(32), rel=0.2)
        if layer:
            bias = np.asarray(
                buffers[f"block_{layer}"]["moe"]["e_score_correction_bias"])
            assert 0.2 * seeded["selection_bias_std"] < bias.std() \
                < 3 * seeded["selection_bias_std"]
    assert set(buffers) == {"block_1", "block_2"}
    other = _state(CFG, 4)[1]
    assert not np.array_equal(
        buffers["block_1"]["moe"]["e_score_correction_bias"],
        other["block_1"]["moe"]["e_score_correction_bias"])
    assert not np.array_equal(
        buffers["block_1"]["moe"]["e_score_correction_bias"],
        buffers["block_2"]["moe"]["e_score_correction_bias"])


def test_a_program_without_the_mechanisms_is_refused(monkeypatch):
    """The parent commit's program builds SOME model from these keys (its
    builder ignores what it does not know): the configuration says so at
    once instead of timing another model under Kanana's name."""
    from tensorflowonspark_tpu.parallel import dp as dplib

    class Parent(NamedTuple):
        params: Any
        opt_state: Any
        step: Any

    monkeypatch.setattr(dplib, "TrainState", Parent)
    with pytest.raises(NotImplementedError, match="TrainState.buffers"):
        KANANA._model(CFG)


# -- the readers --------------------------------------------------------------

STEP = "jit(step)/jit(main)/loss_and_grad/"
FWD, BWD = "jvp(Transformer)/block_1/", "transpose(jvp(Transformer))/block_1/"
SUMS = {
    STEP + FWD + "attn/mla/project/q_proj/dot_general:": 300e-6,
    STEP + BWD + "attn/mla/project/o_proj/dot_general:": 500e-6,
    STEP + FWD + "attn/attention/flash_fwd/jit(_flash_fwd_pallas)/"
    "pallas_call:": 700e-6,
    STEP + FWD + "attn/attention/flash_fwd/transpose:": 100e-6,
    STEP + BWD + "attn/attention/flash_bwd/jit(_flash_bwd_pallas)/"
    "pallas_call:": 1600e-6,
    STEP + FWD + "moe/shared/shared/dot_general:": 60e-6,
    STEP + FWD + "moe/moe/router/top_k:": 20e-6,
    STEP + FWD + "moe/moe/dispatch/gather:": 200e-6,
    "": 30e-6,
}


def _run(monkeypatch, sums):
    monkeypatch.setattr(scope_times, "run_scope_seconds", lambda run: sums)
    # a layer's forward needs 20 us of compute and 4 us of memory traffic,
    # its backward 50 and 8
    kernels = {"mla_flash_fwd": {"flops": 197e12 * 20e-6,
                                 "bytes": 819e9 * 4e-6},
               "mla_flash_bwd": {"flops": 197e12 * 50e-6,
                                 "bytes": 819e9 * 8e-6}}
    return {"cell": {"workload": CELL, "config": {"num_hidden_layers": 5}},
            "trace": {"busy_s": 1.0},
            "facts": {"traced_steps": 2, "kernels": kernels},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


@pytest.mark.parametrize("metric,expected", [
    ("mla_project_ms", 0.4),            # 800 us over two steps, both halves
    ("bd_flash_fwd_ms", 0.4),           # kernel AND layout, not the backward
    ("mla_flash_fwd_roofline", 25.0),   # five layers x 20 us against 400 us
    ("flash_bwd_ms", 0.8),
    ("mla_flash_bwd_roofline", 31.25),  # five layers x 50 us against 800 us
    ("moe_shared_ms", 0.03),
    ("moe_router_ms", 0.01),            # the router alone, not the gathers
])
def test_new_readers_on_a_hand_made_run(monkeypatch, metric, expected):
    reader = common.load_module("layer_metrics", metric)
    run = _run(monkeypatch, SUMS)
    assert reader.read(run) == pytest.approx(expected)
    if metric.endswith("_roofline"):
        assert reader.bound(run) == "compute"
        kernel = metric[:-len("_roofline")]
        run["facts"]["kernels"][kernel]["bytes"] *= 20
        assert reader.bound(run) == "memory"


@pytest.mark.parametrize("metric", READERS + JOINED)
def test_new_readers_find_nothing_in_the_parent_s_program(monkeypatch, metric):
    """No trace, a trace without scopes, a program that names none of the
    scopes (the parent's, traced under this PR's benchmark files): None, no
    raise."""
    reader = common.load_module("layer_metrics", metric)
    run = _run(monkeypatch, SUMS)
    monkeypatch.undo()
    assert reader.read({**run, "trace": None}) is None
    assert reader.read(_run(monkeypatch, None)) is None
    others = {"": 30e-6, STEP + FWD + "mlp/dot_general:": 50e-6}
    assert reader.read(_run(monkeypatch, others)) is None
    if metric.endswith("_roofline"):
        run = _run(monkeypatch, SUMS)
        assert reader.read({**run, "peaks": None}) is None
        run["facts"]["kernels"] = {}
        assert reader.read(run) is None and reader.bound(run) is None


# -- the manifest with its eighth cell -----------------------------------------

def test_manifest_holds_the_cell_its_configuration_and_five_readers():
    manifest = common.load_manifest()
    cells = [w["name"] for w in manifest["workloads"]]
    assert CELL in cells and len(cells) >= 8
    # one chip: the four-chip quota stays where it was
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "kanana2_30b_a3b_d5_ep8")
    assert entry["reduced"] == FILE["reduced"]
    assert entry["source"] == FILE["source"]
    assert entry["file"] == "benchmark/configs/kanana2_30b_a3b_d5_ep8.json"
    names = [m["name"] for m in manifest["per_layer"]]
    first = names.index(READERS[0])
    assert tuple(names[first:]) == READERS
    cell = common.resolve_cell(CELL)
    assert (cell["chips"], cell["traffic_name"]) == (1, "token_rows_8k_x1")
    assert cell["traffic"]["input_mode"] == "streaming"
    assert cell["traffic"]["warm_steps"] == 2
    assert {m["name"] for m in cell["end_to_end"]} == {"train_tok_rate",
                                                       "setup_s"}
    reported = {m["name"] for m in cell["per_layer"]}
    assert reported >= {
        "claim_s", "first_step_s", "lm_feed_wait_share", "lm_step_device_ms",
        "lm_mfu", "moe_dispatch_ms", "moe_experts_ms", "moe_experts_roofline",
        "moe_optimizer_ms", *JOINED, *READERS}
    # the cost models of the dense LM's and SDAR's rooflines are not this
    # cell's (2 and 2.5 times a forward of one width), nor the sum of every
    # custom call
    assert not reported & {"flash_fwd_ms", "flash_fwd_roofline",
                           "flash_bwd_roofline", "bd_flash_fwd_roofline"}
    for metric in manifest["per_layer"][first:]:
        reader = common.load_module("layer_metrics", metric["name"])
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
            metric["layer"], metric["unit"], metric["moves"])
        assert metric["workloads"] == [CELL]
        assert metric["source"] == "device_trace"
        assert metric["moves"] == "train_tok_rate"
    # appended after what was there in each list it joined
    for metric in manifest["per_layer"][:first] + manifest["end_to_end"]:
        cells_of = metric.get("workloads", [])
        if CELL in cells_of:
            assert cells_of.count(CELL) == 1
            assert cells_of.index(CELL) > cells_of.index(
                "sdar_30b_a3b_d4_ep8_train_bd4k") if (
                    "sdar_30b_a3b_d4_ep8_train_bd4k" in cells_of) else True
    for kernel in ("mla_flash_fwd", "mla_flash_bwd", "moe_experts"):
        assert kernel in KANANA.KERNELS
