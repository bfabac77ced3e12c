"""SDAR-30B-A3B under block diffusion (ISSUE 31): the program against the
plain reference kept with the benchmark
(``benchmark/configs/sdar_30b_a3b_d4_ep8.py``) at a small size on the CPU,
the corruption's statistics, the configuration's counts against a count from
the materialised mask, its file against the catalog's row, the three new
readers on a hand-made run, and the manifest with six cells.  The same
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

SDAR = common.load_module("configs", "sdar_30b_a3b_d4_ep8")
CELL = "sdar_30b_a3b_d4_ep8_train_bd4k"
FILE = common.read_json(os.path.join(common.HERE, "configs",
                                     "sdar_30b_a3b_d4_ep8.json"))

# SDAR's shape in small: 2 layers, 8 query heads over 2 K/V heads (group 4),
# experts 2-5 of 8 held, 3 a token, blocks of 4.
CFG = {"hidden_size": 32, "moe_intermediate_size": 16,
       "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 8,
       "num_hidden_layers": 2, "router_experts": 8, "experts_held": [2, 6],
       "num_experts": 4, "num_experts_per_tok": 3, "vocab_size": 64,
       "mask_token_id": 63, "norm_topk_prob": True, "qk_norm": True,
       "qk_norm_per_head": True, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
       "block_length": 4, "noise_level_min": 1e-3,
       "router_aux_loss_coef": 0.001, "vocab_chunk": 24, "bf16": False,
       "reference_tokens": [2, 24], "seeded_state": FILE["seeded_state"]}

# Both sides compute in float32 and differ in the order of their sums (a sort
# and a grouped matmul against a loop over experts, a flash kernel against
# whole scores, a blockwise loss against whole logits): measured 1e-7 to 6e-7
# on these sizes (relative to the largest entry).  1e-4 leaves that two
# hundred times and is far under what a wrong mask, a shifted target or the
# whole-projection QK-norm move (the last test).
TOL = 1e-4


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def _batch(seed=0, cfg=CFG):
    rows, length = cfg["reference_tokens"]
    rng = np.random.default_rng(seed)
    return {"input_ids": jnp.asarray(rng.integers(
                0, cfg["mask_token_id"], (rows, length)), jnp.int32),
            "noise_seed": jnp.asarray(rng.integers(
                0, 2 ** 32, (rows,), dtype=np.uint32))}


def _both_sides(cfg, batch, params=None):
    _tfm, model = SDAR._model(cfg)
    if params is None:
        params = SDAR._init_params(cfg, jax.random.PRNGKey(1))
    ids, words = batch["input_ids"], batch["noise_seed"]
    length, block = ids.shape[1], cfg["block_length"]
    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        SDAR._loss_fn(tfm, model, cfg), has_aux=True))(params, batch)
    noised, masked, t = tfm.corrupt_blocks(
        ids, words, block, cfg["mask_token_id"], cfg["noise_level_min"])
    logits = model.apply(
        {"params": params}, jnp.concatenate([noised, ids], axis=1),
        jnp.tile(jnp.arange(length), 2), (length, block))[:, :length]

    def reference(params):
        ref_logits, aux, _routing = SDAR.reference_forward(
            cfg, params, noised, ids)
        return (SDAR.reference_loss(cfg, ref_logits, aux, ids, masked, t),
                ref_logits)

    (ref_loss, ref_logits), ref_grads = jax.value_and_grad(
        reference, has_aux=True)(params)
    errors = {"loss": abs(float(loss) - float(ref_loss))
              / abs(float(ref_loss)),
              "logits": _rel(logits, ref_logits),
              "grads": max(jax.tree.leaves(
                  jax.tree.map(_rel, grads, ref_grads)))}
    return errors, metrics


@pytest.mark.parametrize("attn_impl", ["pallas_interpret", "xla"])
def test_system_matches_the_reference(attn_impl):
    """Loss, noised-half logits and the gradient of every parameter leaf,
    through the kernels in interpret mode and through the XLA path."""
    errors, metrics = _both_sides({**CFG, "attn_impl": attn_impl}, _batch())
    assert max(errors.values()) < TOL, errors
    assert 0.0 < float(metrics["masked_share"]) < 1.0
    # 3 choices over 8 experts, 4 held: half the pairs on even routing
    assert 0.2 < float(metrics["moe_held_pairs"]) < 0.8
    assert float(metrics["moe_executed_rows"]) >= 1.0


@pytest.mark.parametrize("change,least", [
    ({"qk_norm_per_head": False}, None),    # OLMoE's placement: other shapes
    ({"block_length": 8}, 1e-3),            # another mask
    ({"experts_held": [0, 4]}, 1e-3),       # another chip's share
    ({"norm_topk_prob": False}, 1e-3),
])
def test_another_model_fails_the_tolerance(change, least):
    """The reference is SDAR's and no neighbour's: each change to the system
    alone moves it out of tolerance (or cannot even load the parameters)."""
    cfg = {**CFG, "attn_impl": "xla"}
    params = SDAR._init_params(cfg, jax.random.PRNGKey(1))
    batch = _batch()
    _tfm, wrong = SDAR._model({**cfg, **change})
    length, block = batch["input_ids"].shape[1], cfg["block_length"]
    noised, masked, t = tfm.corrupt_blocks(
        batch["input_ids"], batch["noise_seed"], block, cfg["mask_token_id"],
        cfg["noise_level_min"])
    both = jnp.concatenate([noised, batch["input_ids"]], axis=1)
    positions = jnp.tile(jnp.arange(length), 2)
    mask = (length, {**cfg, **change}["block_length"])
    if least is None:
        with pytest.raises(Exception):
            wrong.apply({"params": params}, both, positions, mask)
        return
    logits = wrong.apply({"params": params}, both, positions, mask)
    ref_logits, _aux, _routing = SDAR.reference_forward(
        cfg, params, noised, batch["input_ids"])
    assert _rel(logits[:, :length], ref_logits) > least


def test_corruption_draws_what_the_objective_says():
    """Per block one level t in [t_min, 1]; a token is masked with its
    block's probability; the clean copy is untouched and unmasked tokens
    keep their id; the draw is a function of the row's noise word."""
    rows, length, block, mask_id = 8, 4096, 4, 999
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, mask_id, (rows, length)), jnp.int32)
    words = jnp.asarray(rng.integers(0, 2 ** 32, (rows,), dtype=np.uint32))
    noised, masked, t = (np.asarray(a) for a in tfm.corrupt_blocks(
        ids, words, block, mask_id, 1e-3))
    ids = np.asarray(ids)
    assert (noised[masked] == mask_id).all()
    assert (noised[~masked] == ids[~masked]).all()
    per_block = t.reshape(rows, -1, block)
    assert (per_block == per_block[..., :1]).all()
    assert t.min() >= 1e-3 and t.max() <= 1.0
    assert abs(t.mean() - 0.5) < 0.02           # 8192 uniform levels
    # the masked share follows the level: overall, and bin by bin
    assert abs(masked.mean() - t.mean()) < 0.01
    for lo in (0.0, 0.25, 0.5, 0.75):
        sel = (t >= lo) & (t < lo + 0.25)
        assert abs(masked[sel].mean() - t[sel].mean()) < 0.02, lo
    again = tfm.corrupt_blocks(jnp.asarray(ids), words, block, mask_id, 1e-3)
    np.testing.assert_array_equal(np.asarray(again[1]), masked)
    other = tfm.corrupt_blocks(jnp.asarray(ids), words + 1, block, mask_id,
                               1e-3)
    assert (np.asarray(other[1]) != masked).mean() > 0.2
    # rows differ from one another: each has its own word
    assert (masked[0] != masked[1]).mean() > 0.2


def test_records_never_hold_the_mask_id_and_carry_a_noise_word():
    traffic = {"seq_len": 64}
    rows = SDAR.train_records(FILE, traffic, common.seeded_rng(7, "records"),
                              50)
    batch = SDAR.rows_to_arrays(FILE)(rows[:5])
    assert batch["input_ids"].shape == (5, 64)
    assert batch["input_ids"].dtype == np.int32
    assert batch["noise_seed"].shape == (5,)
    assert batch["noise_seed"].dtype == np.uint32
    ids = np.stack(rows)[:, :-1]
    assert ids.min() >= 0 and ids.max() < FILE["mask_token_id"]
    assert FILE["mask_token_id"] == FILE["vocab_size"] - 1
    words = np.stack(rows)[:, -1].astype(np.uint32)
    assert len(set(words.tolist())) == 50 and words.max() > 2 ** 31
    again = SDAR.train_records(FILE, traffic,
                               common.seeded_rng(7, "records"), 50)
    np.testing.assert_array_equal(np.stack(rows), np.stack(again))


@pytest.mark.parametrize("length,block", [(64, 4), (96, 32), (512, 4)])
def test_visible_pairs_is_the_materialised_mask_s_count(length, block):
    mask = np.asarray(SDAR.reference_mask(length, block))
    assert SDAR.visible_pairs(length, block) == int(mask.sum())
    # the reference's mask and the program's are one function of the indices
    from tensorflowonspark_tpu.ops.attention import block_diffusion_visible

    idx = jnp.arange(2 * length)
    np.testing.assert_array_equal(mask, np.asarray(block_diffusion_visible(
        idx[:, None], idx[None, :], length, block)))


def test_the_cell_s_counts_are_this_chip_s_work_and_no_more():
    """``flops_per_sample`` and both kernels' costs at the cell's sizes
    against the ISSUE's own arithmetic: the visible pairs (not 2L causal),
    the EXPECTED held pairs (one a position, not eight), the head over the
    L noised positions of the slice."""
    cell = common.resolve_cell(CELL)
    cfg, traffic = cell["config"], cell["traffic"]
    length = traffic["seq_len"]
    pairs = SDAR.visible_pairs(length, cfg["block_length"])
    assert pairs == 16_793_600 and SDAR.held_pairs_per_position(cfg) == 1.0
    attention = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048      # 18,874,368
    per_layer_fwd = (2 * 2 * length * (attention + 2048 * 128)  # both copies
                     + 2 * 2 * length * 1.0 * 3 * 2048 * 768    # held pairs
                     + 2 * 2 * 32 * 128 * pairs)                # QK^T and PV
    want = (3 * 4 * per_layer_fwd + 3 * 2 * length * 2048 * 18992) / length
    assert SDAR.flops_per_sample(cfg, traffic) == pytest.approx(want)
    fwd = SDAR.flash_fwd_cost(cfg, traffic, 1)
    assert fwd["flops"] == pytest.approx(275.1e9, rel=1e-3)
    # q and o at 32 heads, k and v at 4, bf16; the log-sum-exp in float32
    assert fwd["bytes"] == 8192 * (2 * 4096 * 2 + 2 * 512 * 2 + 32 * 4)
    moe = SDAR.moe_experts_cost(cfg, traffic, 1)
    assert moe["flops"] == pytest.approx(4 * 3 * 77.3e9, rel=1e-3)
    assert moe["bytes"] == 4 * 2 * (5 * 8192 * 2048 + 3 * 16 * 3 * 2048 * 768)
    # a causal mask over 2L would count twice the pairs
    assert 2 * length * (2 * length + 1) / 2 > 1.99 * pairs


def test_the_file_keeps_every_published_width():
    """Every key of the catalog's row under the same name, changed only where
    ``reduced`` says; the published values, the deployment and every assumed
    size are stated."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "SDAR-30B-A3B-Chat")
        assert FILE["source"] == row["source_url"]
        differs = [k for k, v in row["config"].items() if FILE.get(k) != v]
        assert sorted(differs) == sorted(FILE["reduced"])
    assert FILE["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    assert FILE["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                 "vocab_size": 151936}
    assert (FILE["hidden_size"], FILE["num_attention_heads"],
            FILE["num_key_value_heads"], FILE["head_dim"],
            FILE["moe_intermediate_size"], FILE["router_experts"],
            FILE["num_experts_per_tok"], FILE["rope_theta"],
            FILE["rms_norm_eps"]) == (2048, 32, 4, 128, 768, 128, 8, 1e6,
                                      1e-6)
    first, end = FILE["experts_held"]
    assert end - first == FILE["num_experts"] == 16
    assert FILE["vocab_size"] * 8 == FILE["published"]["vocab_size"]
    assert "8 chips" in FILE["deployment"] and "512 pairs" in FILE["deployment"]
    stated = " ".join(FILE["assumed"])
    for size in ("qk_norm", "block_length", "noise law", "mask_token_id",
                 "no shift", "normaliser", "router_aux_loss_coef",
                 "learning rate", "seeded_state"):
        assert size in stated, size
    for size in ("QK-norm", "block_length", "noise law", "mask_token_id",
                 "no shift", "normaliser", "router_aux_loss_coef",
                 "learning rate", "seeded_state"):
        assert size in SDAR.__doc__, size
    # the parameters the file counts are the ones the program creates
    shapes = jax.eval_shape(lambda: SDAR._init_params(
        FILE, jax.random.PRNGKey(0)))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert count == 456_346_624 and "456.3 M" in FILE["deployment"]


def test_the_seeded_state_has_the_scales_the_file_states():
    """The program's own initialisers but for three scales: the embedding's
    rows at ``embedding_std``, the mask token's at ``mask_embedding_std``,
    the QK-norm scales at ``qk_norm_scale``; everything else as flax draws
    it (the attention norm's scale 1, a projection's lecun-normal)."""
    seeded = FILE["seeded_state"]
    params = SDAR._init_params(CFG, jax.random.PRNGKey(3))
    table = np.asarray(params["embed"]["embedding"])
    mask_row = table[CFG["mask_token_id"]]
    others = np.delete(table, CFG["mask_token_id"], axis=0)
    assert others.std() == pytest.approx(seeded["embedding_std"], rel=0.1)
    assert mask_row.std() == pytest.approx(seeded["mask_embedding_std"],
                                           rel=0.5)
    for layer in range(CFG["num_hidden_layers"]):
        block = params[f"block_{layer}"]
        for name in ("q_norm", "k_norm"):
            np.testing.assert_allclose(block["attn"][name]["scale"],
                                       seeded["qk_norm_scale"])
        np.testing.assert_allclose(block["attn_norm"]["scale"], 1.0)
        kernel = np.asarray(block["attn"]["q_proj"]["kernel"])
        assert kernel.std() == pytest.approx(
            1 / np.sqrt(CFG["hidden_size"]), rel=0.15)


def test_a_program_without_the_mechanisms_is_refused(monkeypatch):
    """The parent commit's program builds SOME model from these keys (its
    builder ignores what it does not know): the configuration says so at
    once instead of timing another model under SDAR's name."""
    monkeypatch.delattr(tfm, "make_block_diffusion_loss_fn")
    with pytest.raises(NotImplementedError, match="block_diffusion"):
        SDAR._model(CFG)


# -- the readers --------------------------------------------------------------

STEP = "jit(step)/jit(main)/loss_and_grad/"
SUMS = {
    STEP + "jvp(Transformer)/block_0/attn/attention/flash_fwd/pallas_call:":
        700e-6,
    STEP + "jvp(Transformer)/block_1/attn/attention/flash_fwd/transpose:":
        100e-6,
    STEP + "diffusion/corrupt/threefry2x32:": 30e-6,
    STEP + "diffusion/corrupt/concatenate:": 10e-6,
    STEP + "transpose(jvp(Transformer))/block_0/attn/attention/flash_bwd/"
    "pallas_call:": 900e-6,
    "": 30e-6,
}


def _run(monkeypatch, sums):
    monkeypatch.setattr(scope_times, "run_scope_seconds", lambda run: sums)
    # one forward call needs 20 us of compute and 4 us of memory traffic
    cost = {"flops": 197e12 * 20e-6, "bytes": 819e9 * 4e-6}
    return {"cell": {"workload": CELL, "config": {"num_hidden_layers": 4}},
            "trace": {"busy_s": 1.0},
            "facts": {"traced_steps": 2, "kernels": {"flash_fwd": cost}},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


@pytest.mark.parametrize("metric,expected", [
    ("bd_flash_fwd_ms", 0.4),           # 800 us over two steps: the scope,
                                        # kernel AND layout, not the backward
    ("bd_flash_fwd_roofline", 20.0),    # four layers x 20 us against 400 us
    ("bd_corrupt_ms", 0.02),
])
def test_new_readers_on_a_hand_made_run(monkeypatch, metric, expected):
    reader = common.load_module("layer_metrics", metric)
    run = _run(monkeypatch, SUMS)
    assert reader.read(run) == pytest.approx(expected)
    if metric == "bd_flash_fwd_roofline":
        assert reader.bound(run) == "compute"
        run["facts"]["kernels"]["flash_fwd"]["bytes"] *= 10   # 40 us a layer
        assert reader.bound(run) == "memory"
        assert reader.read(run) == pytest.approx(40.0)


@pytest.mark.parametrize("metric", ["bd_flash_fwd_ms", "bd_flash_fwd_roofline",
                                    "bd_corrupt_ms"])
def test_new_readers_find_nothing_in_the_parent_s_program(monkeypatch, metric):
    """No trace, a trace without scopes, a program that names neither scope
    (the parent's, traced under this PR's benchmark files): None, no raise."""
    reader = common.load_module("layer_metrics", metric)
    run = _run(monkeypatch, SUMS)
    monkeypatch.undo()
    assert reader.read({**run, "trace": None}) is None
    assert reader.read(_run(monkeypatch, None)) is None
    others = {k: v for k, v in SUMS.items()
              if "flash_fwd" not in k and "corrupt" not in k}
    assert reader.read(_run(monkeypatch, others)) is None
    if metric == "bd_flash_fwd_roofline":
        run = _run(monkeypatch, SUMS)
        assert reader.read({**run, "peaks": None}) is None
        run["facts"]["kernels"] = {}
        assert reader.read(run) is None and reader.bound(run) is None


def test_new_readers_read_the_recorded_v5e_trace(monkeypatch):
    """On the xplane kept with the benchmark (a dense LM's four steps, from
    before either scope existed... ``flash_fwd`` it has): the decoding is
    real, only the path to the file is handed in."""
    path = os.path.join(common.HERE, "testdata", "tpu_v5e_4steps.xplane.pb")
    monkeypatch.setattr(common, "find_xplane", lambda trace_dir: path)
    run = {"cell": {"workload": CELL, "config": {"num_hidden_layers": 4}},
           "trace": {"busy_s": 1.0}, "facts": {"traced_steps": 4,
                                               "kernels": {}}, "peaks": None}
    sums = scope_times.run_scope_seconds(run)
    fwd = common.load_module("layer_metrics", "bd_flash_fwd_ms").read(run)
    if sums and any(scope_times.in_scope(p, "flash_fwd") for p in sums):
        assert fwd > 0
    else:
        assert fwd is None
    assert common.load_module("layer_metrics", "bd_corrupt_ms").read(run) is None


# -- the manifest with six cells ----------------------------------------------

def test_manifest_holds_the_cell_its_configuration_and_three_readers():
    manifest = common.load_manifest()
    assert [w["name"] for w in manifest["workloads"]][-1] == CELL
    assert len(manifest["workloads"]) == 6
    assert manifest["configs"][-1]["name"] == "sdar_30b_a3b_d4_ep8"
    assert manifest["configs"][-1]["reduced"] == FILE["reduced"]
    assert manifest["configs"][-1]["source"] == FILE["source"]
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[-3:] == ["bd_flash_fwd_ms", "bd_flash_fwd_roofline",
                          "bd_corrupt_ms"]
    cell = common.resolve_cell(CELL)
    assert (cell["chips"], cell["traffic_name"]) == (1, "token_rows_4k_x1")
    assert cell["traffic"]["rows_per_chip"] == 1
    assert cell["traffic"]["seq_len"] == 4096
    assert {m["name"] for m in cell["end_to_end"]} == {"train_tok_rate",
                                                       "setup_s"}
    reported = {m["name"] for m in cell["per_layer"]}
    assert reported == {
        "claim_s", "first_step_s", "lm_feed_wait_share", "lm_step_device_ms",
        "lm_mfu", "flash_bwd_ms", "flash_bwd_roofline", "moe_dispatch_ms",
        "moe_experts_ms", "moe_experts_roofline", "moe_optimizer_ms",
        "bd_flash_fwd_ms", "bd_flash_fwd_roofline", "bd_corrupt_ms"}
    for metric in manifest["per_layer"][-3:]:
        reader = common.load_module("layer_metrics", metric["name"])
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
            metric["layer"], metric["unit"], metric["moves"])
        assert metric["workloads"] == [CELL]
        assert metric["source"] == "device_trace"
    # appended at the end of each list it joined, nothing else moved
    for metric in manifest["per_layer"][:-3] + manifest["end_to_end"]:
        if CELL in metric.get("workloads", []):
            assert metric["workloads"][-1] == CELL
            assert metric["workloads"].count(CELL) == 1
    # one chip: the four-chip quota stays where it was
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
