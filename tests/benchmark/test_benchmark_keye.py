"""Keye-VL-2.0-30B-A3B's decoder with learned sparse attention (ISSUE 33): the
program against the plain reference kept with the benchmark
(``benchmark/configs/keye_vl2_30b_a3b_d4_ep8.py``) at a small size on the CPU
(both loss terms, logits, every gradient, the selection), the reference's own
selection, the configuration's counts against hand counts, its file against
the catalog's row, eight shares of the expert layer against the uncut one, the
six new readers on a hand-made run, and the manifest's new entries.  The same
comparison runs at the published widths on the chip (``check_train``)."""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common, scope_times
from tensorflowonspark_tpu.models import transformer as tfm

KEYE = common.load_module("configs", "keye_vl2_30b_a3b_d4_ep8")
CELL = "keye_vl2_30b_a3b_d4_ep8_train_16k"
FILE = common.read_json(os.path.join(common.HERE, "configs",
                                     "keye_vl2_30b_a3b_d4_ep8.json"))
LAYER = "sparse attention: indexer, selection, kernels"
READERS = ["dsa_index_ms", "dsa_select_ms", "dsa_attend_ms",
           "dsa_index_loss_ms", "dsa_attend_roofline", "dsa_index_roofline"]

# Keye's shape in small: 2 layers, 8 query heads over 2 K/V heads, experts 2-5
# of 8 held, 3 a token, an indexer of 3 heads of 8 that keeps 10 keys of 48.
CFG = {"hidden_size": 32, "moe_intermediate_size": 16,
       "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 8,
       "num_hidden_layers": 2, "router_experts": 8, "experts_held": [2, 6],
       "num_experts": 4, "num_experts_per_tok": 3, "vocab_size": 64,
       "norm_topk_prob": True, "qk_norm": True, "qk_norm_per_head": True,
       "rms_norm_eps": 1e-6, "rope_theta": 1e7, "remat": True,
       "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 3,
                     "topk": 10},
       "router_aux_loss_coef": 0.001, "index_loss_coef": 1.0,
       "vocab_chunk": 24, "bf16": False, "reference_tokens": [2, 48],
       "reference_query_block": 16, "seeded_state": FILE["seeded_state"]}

# Both sides compute in float32 and differ in the order of their sums; the
# selection is exact on both, so a pair flips only on a tie of rounding.
TOL = 1e-4


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def _ids(cfg=CFG, seed=0):
    rows, length = cfg["reference_tokens"]
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (rows, length)), jnp.int32)


def _both_sides(cfg, ids):
    _tfm, model = KEYE._model(cfg)
    params = KEYE._init_params(cfg, jax.random.PRNGKey(1))
    (_loss, metrics), grads = jax.jit(jax.value_and_grad(
        KEYE._loss_fn(tfm, model, cfg), has_aux=True))(
            params, {"input_ids": ids})
    logits, sown = model.apply({"params": params}, ids,
                               mutable=["intermediates"])
    masks = KEYE._sown(sown, "dsa_mask")

    def reference(params):
        ref_logits, aux, index_loss, _routing, ref_masks = \
            KEYE.reference_forward(cfg, params, ids)
        lm_loss = KEYE.reference_lm_loss(ref_logits, ids)
        return (lm_loss + cfg["router_aux_loss_coef"] * aux
                + cfg["index_loss_coef"] * index_loss,
                (lm_loss, index_loss, ref_logits, ref_masks))

    (_ref_loss, (ref_lm, ref_index, ref_logits, ref_masks)), ref_grads = \
        jax.value_and_grad(reference, has_aux=True)(params)
    errors = {"lm_loss": _rel(metrics["lm_loss"], ref_lm),
              "index_loss": _rel(metrics["index_loss"], ref_index),
              "logits": _rel(logits, ref_logits),
              "grads": max(jax.tree.leaves(
                  jax.tree.map(_rel, grads, ref_grads)))}
    flipped = sum(int(np.sum((np.asarray(m) != 0) != np.asarray(r)))
                  for m, r in zip(masks, ref_masks))
    return errors, flipped, metrics, (grads, ref_grads)


@pytest.mark.parametrize("attn_impl", ["pallas_interpret", "xla"])
def test_system_matches_the_reference(attn_impl):
    """Both loss terms, the logits, the gradient of every parameter leaf and
    the selection itself, through the kernels in interpret mode and through
    the dense path, under remat."""
    errors, flipped, metrics, (grads, ref_grads) = _both_sides(
        {**CFG, "attn_impl": attn_impl}, _ids())
    assert max(errors.values()) < TOL, errors
    assert flipped == 0
    assert float(metrics["index_loss"]) > 0.01
    # 10 keys a query but for the first nine of 48
    assert float(metrics["dsa_selected_pairs"]) == 10 * 48 - 45
    # the reference keeps the two gradients apart by itself
    flat = jax.tree_util.tree_flatten_with_path(ref_grads)[0]
    assert sum(KEYE.is_indexer(p) for p, _g in flat) == 2 * 5
    assert all(float(jnp.max(jnp.abs(g))) > 0 for p, g in flat
               if KEYE.is_indexer(p))


@pytest.mark.parametrize("change,least", [
    ({"sa_config": {**CFG["sa_config"], "topk": 20}}, 1e-3),   # other keys
    ({"sa_config": {**CFG["sa_config"], "topk": 48}}, 1e-3),   # causal
    ({"experts_held": [0, 4]}, 1e-3),       # another chip's share
    ({"qk_norm_per_head": False}, None),    # OLMoE's placement: other shapes
])
def test_another_model_fails_the_tolerance(change, least):
    """The reference is Keye's and no neighbour's: each change to the system
    alone moves it out of tolerance (or cannot even load the parameters)."""
    cfg = {**CFG, "attn_impl": "xla"}
    params = KEYE._init_params(cfg, jax.random.PRNGKey(1))
    ids = _ids()
    _tfm, wrong = KEYE._model({**cfg, **change})
    if least is None:
        with pytest.raises(Exception):
            wrong.apply({"params": params}, ids)
        return
    ref_logits = KEYE.reference_forward(cfg, params, ids)[0]
    assert _rel(wrong.apply({"params": params}, ids), ref_logits) > least


def test_the_reference_selects_by_a_sort_with_ties_to_the_lower_position():
    rng = np.random.default_rng(2)
    scores = rng.choice(np.array([-1.0, 0.0, 0.5, 3.0], np.float32), (24, 40))
    got = np.asarray(KEYE.reference_selection(jnp.asarray(scores), 16, 7))
    for row in range(24):
        t = 16 + row
        order = np.argsort(-scores[row, :t + 1], kind="stable")[:7]
        want = np.zeros(40, bool)
        want[order] = True
        np.testing.assert_array_equal(got[row], want)


def test_the_cell_s_counts_are_this_chip_s_work_and_no_more():
    """``flops_per_sample`` and the kernels' costs at the cell's sizes
    against hand counts: the KEPT pairs (31,458,304 of 134,225,920 causal
    ones at 16k), the expected held pairs (one a position), the head over the
    slice."""
    cell = common.resolve_cell(CELL)
    cfg, traffic = cell["config"], cell["traffic"]
    length = traffic["seq_len"]
    assert length == 16384
    kept = KEYE.selected_pairs(length, 2048)
    assert kept == 2_098_176 + 14_336 * 2_048 == 31_458_304
    assert KEYE.causal_pairs(length) == 134_225_920
    assert kept / KEYE.causal_pairs(length) == pytest.approx(0.234, abs=1e-3)
    assert KEYE.selected_pairs(512, 2048) == KEYE.causal_pairs(512)
    assert KEYE.held_pairs_per_position(cfg) == 1.0
    indexer = 2048 * 1024 + 2048 * 64 + 2048 * 16
    assert KEYE._indexer_params(cfg) == indexer
    dense = 18_874_368 + 2048 * 128 + indexer + 3 * 2048 * 768
    pairs = 3 * 4 * 32 * 128 * kept + 2 * 16 * 64 * (134_225_920 + 2 * kept)
    want = 4 * (6 * dense + pairs / length) + 6 * 2048 * 18992
    assert KEYE.flops_per_sample(cfg, traffic) == pytest.approx(want)
    attend = KEYE.dsa_attend_cost(cfg, traffic, 1)
    # 0.515 TFLOP a layer forward, 3.5 times with the backward, four layers
    assert attend["flops"] == pytest.approx(4 * 3.5 * 0.5154e12, rel=1e-3)
    assert attend["bytes"] == 4 * 16384 * (
        (2 * 4096 * 2 + 2 * 512 * 2 + 32 * 4)
        + (4 * 4096 * 2 + 4 * 512 * 2 + 32 * 4))
    index = KEYE.dsa_index_cost(cfg, traffic, 1)
    assert index["flops"] == pytest.approx(
        4 * (0.2749e12 + 2 * indexer * 16384), rel=1e-3)
    moe = KEYE.moe_experts_cost(cfg, traffic, 1)
    assert moe["flops"] == pytest.approx(4 * 3 * 2 * 16384 * 3 * 2048 * 768)
    # all causal tiles computed whole would be 4.27 times the kept pairs
    assert 528 * 512 * 512 / kept == pytest.approx(4.4, abs=0.1)


def test_the_file_keeps_every_published_width():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Keye-VL-2.0-30B-A3B")
        assert FILE["source"] == row["source_url"]
        differs = [k for k, v in row["config"].items() if FILE.get(k) != v]
        assert sorted(differs) == sorted(FILE["reduced"])
        assert FILE["sa_config"] == row["config"]["sa_config"]
    assert FILE["reduced"] == ["num_hidden_layers", "num_experts",
                               "num_local_experts", "vocab_size"]
    assert FILE["published"] == {
        "num_hidden_layers": 48, "num_experts": 128,
        "num_local_experts": 128, "vocab_size": 151936}
    assert (FILE["hidden_size"], FILE["num_attention_heads"],
            FILE["num_key_value_heads"], FILE["head_dim"],
            FILE["moe_intermediate_size"], FILE["router_experts"],
            FILE["num_experts_per_tok"], FILE["rope_theta"],
            FILE["rms_norm_eps"]) == (2048, 32, 4, 128, 768, 128, 8, 1e7,
                                      1e-6)
    assert FILE["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}
    first, end = FILE["experts_held"]
    assert end - first == FILE["num_experts"] == FILE["num_local_experts"] == 16
    assert FILE["vocab_size"] * 8 == FILE["published"]["vocab_size"]
    assert FILE["remat"] is True and FILE["reference_tokens"] == [1, 16384]
    assert "8 chips" in FILE["deployment"]
    assert "1024 pairs" in FILE["deployment"]
    for key in ("compute", "optimizer", "rehearsal", "deployment"):
        assert key in FILE
    stated = " ".join(FILE["assumed"])
    for size in ("qk_norm", "query latent", "LayerNorm", "RoPE over ALL 64",
                 "16^-1/2", "Hadamard", "FP8", "q_chunk_size", "Every layer",
                 "index_loss_coef", "router_aux_loss_coef", "adamw",
                 "text-only", "remat", "seeded_state", "lower position"):
        assert size in stated, size
    # the parameters the file counts are the ones the program creates
    shapes = jax.eval_shape(lambda: KEYE._init_params(
        FILE, jax.random.PRNGKey(0)))
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    count = sum(int(np.prod(a.shape)) for _p, a in flat)
    assert count == 4 * 96_899_456 + 2 * 38_895_616 + 2_048 == 465_391_104
    assert "465.4 M" in FILE["deployment"]
    own = sum(int(np.prod(a.shape)) for p, a in flat if KEYE.is_indexer(p))
    assert own == 4 * 2_261_120


def test_eight_shares_of_the_expert_layer_add_up_to_the_uncut_one():
    """What each of eight chips' held experts add, summed, is what the layer
    with all experts gives (the reference's own, and the program's)."""
    from tensorflowonspark_tpu.parallel.ep import MoEMLP

    cfg = {**CFG, "router_experts": 16, "num_experts_per_tok": 4}
    rng = np.random.default_rng(3)
    y = jnp.asarray(rng.standard_normal((40, 32)), jnp.float32)
    layer = MoEMLP(32, 16, 16, 4, None, compute_dtype=jnp.float32,
                   norm_topk_prob=True, held=None)
    p = layer.init(jax.random.PRNGKey(4), y[None])["params"]
    def share(first, end):
        return {"router": p["router"],
                **{name: p[name][first:end] for name in
                   ("experts_gate", "experts_up", "experts_down")}}

    whole, _lb, _idx = KEYE._reference_moe({**cfg, "experts_held": [0, 16]},
                                           p, y)
    shares = [KEYE._reference_moe(
        {**cfg, "experts_held": [2 * i, 2 * i + 2]},
        share(2 * i, 2 * i + 2), y)[0] for i in range(8)]
    np.testing.assert_allclose(sum(shares), whole, atol=1e-5)
    program = [MoEMLP(32, 16, 16, 4, None, compute_dtype=jnp.float32,
                      norm_topk_prob=True, held=(2 * i, 2 * i + 2)).apply(
                          {"params": share(2 * i, 2 * i + 2)}, y[None])[0]
               for i in range(8)]
    np.testing.assert_allclose(sum(program), whole, atol=1e-5)


def test_records_are_rows_of_ids_over_the_held_slice():
    traffic = {"seq_len": 64}
    rows = KEYE.train_records(FILE, traffic, common.seeded_rng(7, "records"),
                              50)
    batch = KEYE.rows_to_arrays(FILE)(rows[:5])
    assert set(batch) == {"input_ids"}
    assert batch["input_ids"].shape == (5, 64)
    assert batch["input_ids"].dtype == np.int32
    ids = np.stack(rows)
    assert ids.min() >= 0 and ids.max() < FILE["vocab_size"]
    again = KEYE.train_records(FILE, traffic,
                               common.seeded_rng(7, "records"), 50)
    np.testing.assert_array_equal(ids, np.stack(again))


def test_a_program_without_the_mechanisms_is_refused(monkeypatch):
    """The parent commit's program builds SOME model from these keys: the
    configuration says so at once instead of timing SDAR's body under causal
    attention under this model's name."""
    monkeypatch.delattr(tfm, "make_sparse_loss_fn")
    with pytest.raises(NotImplementedError, match="sparse"):
        KEYE._model(CFG)


def test_fp8_weights_and_rounded_scores_move_the_readings():
    """The two degraded systems the limits were set against, at the small
    size: fp8 weights move the logits and the selection, index scores
    rounded to bf16 move the selection."""
    cfg = {**CFG, "attn_impl": "xla", "reference_tokens": [1, 48]}
    clean = KEYE.check_train(cfg, {}, 5)
    assert clean["ok"] and clean["errors"]["selection_disagreement"] == 0.0
    fp8 = KEYE.check_train(cfg, {}, 5, degrade_system="fp8")
    assert fp8["errors"]["logits_l2"] > 100 * clean["errors"]["logits_l2"]
    rounded = KEYE.check_train(cfg, {}, 5, degrade_system="bf16_index")
    assert rounded["errors"]["selection_disagreement"] > 0.0
    assert set(clean["errors"]) == set(KEYE.TOLERANCE)


# -- the readers --------------------------------------------------------------

STEP = "jit(step)/jit(main)/loss_and_grad/"
SUMS = {
    STEP + "jvp(sparse_lm)/Transformer/block_0/attn/dsa/index/dot_general:":
        300e-6,
    STEP + "checkpoint/rematted_computation/sparse_lm/Transformer/block_0/"
    "attn/dsa/index/pallas_call:": 500e-6,
    STEP + "jvp(sparse_lm)/Transformer/block_0/attn/dsa/select/pallas_call:":
        600e-6,
    STEP + "jvp(sparse_lm)/Transformer/block_1/attn/dsa/attend/pallas_call:":
        1000e-6,
    STEP + "transpose(jvp(sparse_lm))/Transformer/block_1/attn/dsa/attend/"
    "pallas_call:": 3000e-6,
    STEP + "transpose(jvp(sparse_lm))/Transformer/block_1/attn/dsa/"
    "index_loss/mul:": 200e-6,
    STEP + "jvp(sparse_lm)/lm_head_loss/dot_general:": 50e-6,
    "": 30e-6,
}


def _run(monkeypatch, sums):
    monkeypatch.setattr(scope_times, "run_scope_seconds", lambda run: sums)
    # a step's kept-pair attention needs 400 us of compute, its index scores
    # 100 us of compute
    kernels = {"dsa_attend": {"flops": 197e12 * 400e-6, "bytes": 819e9 * 40e-6},
               "dsa_index": {"flops": 197e12 * 100e-6, "bytes": 819e9 * 10e-6}}
    return {"cell": {"workload": CELL, "config": {"num_hidden_layers": 4}},
            "trace": {"busy_s": 1.0},
            "facts": {"traced_steps": 2, "kernels": kernels},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


@pytest.mark.parametrize("metric,expected", [
    ("dsa_index_ms", 0.4),          # forward AND remat's second forward
    ("dsa_select_ms", 0.3),
    ("dsa_attend_ms", 2.0),         # forward and backward
    ("dsa_index_loss_ms", 0.1),
    ("dsa_attend_roofline", 20.0),  # 400 us a step against 2 ms
    ("dsa_index_roofline", 25.0),   # 100 us against 0.4 ms
])
def test_new_readers_on_a_hand_made_run(monkeypatch, metric, expected):
    reader = common.load_module("layer_metrics", metric)
    run = _run(monkeypatch, SUMS)
    assert reader.read(run) == pytest.approx(expected)
    if metric.endswith("roofline"):
        assert reader.bound(run) == "compute"
        kernel = metric[:-len("_roofline")]
        run["facts"]["kernels"][kernel]["bytes"] *= 20
        assert reader.bound(run) == "memory"
        assert reader.read(run) == pytest.approx(2 * expected)


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
    others = {k: v for k, v in SUMS.items() if "dsa/" not in k}
    assert reader.read(_run(monkeypatch, others)) is None
    if metric.endswith("roofline"):
        run = _run(monkeypatch, SUMS)
        assert reader.read({**run, "peaks": None}) is None
        run["facts"]["kernels"] = {}
        assert reader.read(run) is None and reader.bound(run) is None


# -- the manifest's new entries -----------------------------------------------

def test_manifest_holds_the_cell_its_configuration_and_six_readers():
    manifest = common.load_manifest()
    names = [w["name"] for w in manifest["workloads"]]
    assert names.count(CELL) == 1
    assert names.index(CELL) > names.index("sdar_30b_a3b_d4_ep8_train_bd4k")
    config = next(c for c in manifest["configs"]
                  if c["name"] == "keye_vl2_30b_a3b_d4_ep8")
    assert config["reduced"] == FILE["reduced"]
    assert config["source"] == FILE["source"]
    assert not any(key.endswith(("_dim", "_rank")) or "size" in key
                   for key in config["reduced"] if key != "vocab_size")
    cell = common.resolve_cell(CELL)
    assert (cell["chips"], cell["traffic_name"]) == (1, "token_rows_16k_x1")
    assert cell["traffic"]["rows_per_chip"] == 1
    assert cell["traffic"]["seq_len"] == 16384
    assert {m["name"] for m in cell["end_to_end"]} == {"train_tok_rate",
                                                       "setup_s"}
    assert {m["name"] for m in cell["per_layer"]} == {
        "claim_s", "first_step_s", "lm_feed_wait_share", "lm_step_device_ms",
        "lm_mfu", "moe_dispatch_ms", "moe_experts_ms", "moe_experts_roofline",
        "moe_optimizer_ms", *READERS}
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in READERS:
        metric = by_name[name]
        reader = common.load_module("layer_metrics", name)
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
            metric["layer"], metric["unit"], metric["moves"])
        assert metric["layer"] == LAYER
        assert metric["workloads"] == [CELL]
        assert metric["source"] == "device_trace"
    # appended at the end of each list it joined
    for metric in manifest["per_layer"] + manifest["end_to_end"]:
        if CELL in metric.get("workloads", []):
            assert metric["workloads"].count(CELL) == 1
            assert (metric["workloads"].index(CELL)
                    >= metric["workloads"].index(
                        "sdar_30b_a3b_d4_ep8_train_bd4k")
                    if "sdar_30b_a3b_d4_ep8_train_bd4k" in metric["workloads"]
                    else True)
    # one chip: the four-chip quota stays where it was
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    for entry in (config, manifest["workloads"][names.index(CELL)]):
        assert len(entry["why"]) <= 200
