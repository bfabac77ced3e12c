"""Xing4.0-29B-A4B (ISSUE 45): the program against the plain reference kept
with the benchmark (``benchmark/configs/xing4_29b_a4b_d5_tp8_ep8.py``) at a
small size on the CPU, the configuration's file against the catalog's row, the
parameters the built model creates against the issue's count, the cost
functions against hand counts, the seeded state's maps, the four new readers
on a hand-made run, the manifest with its tenth cell, and Nemotron-3's
parameter tree against the parent commit's (the five older ones are held by
``test_benchmark_nemotron.py``).  The same comparison runs at the published
widths on the chip (``check_train``)."""

from __future__ import annotations

import json
import math
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common, scope_times
from tensorflowonspark_tpu.models import transformer as tfm

NAME = "xing4_29b_a4b_d5_tp8_ep8"
CELL = NAME + "_train_4k"
XING = common.load_module("configs", NAME)
FILE = common.read_json(os.path.join(common.HERE, "configs", NAME + ".json"))
READERS = ("hc_mix_ms", "hc_maps_ms", "hc_mix_roofline", "mtp_ms")
# accepted readers whose lists the cell joins: it runs their scopes
JOINED = ("lm_feed_wait_share", "lm_step_device_ms", "lm_mfu",
          "moe_dispatch_ms", "moe_experts_ms", "moe_experts_roofline",
          "moe_optimizer_ms", "moe_shared_ms", "moe_router_ms", "flash_bwd_ms",
          "bd_flash_fwd_ms", "mla_project_ms", "mla_flash_fwd_roofline",
          "mla_flash_bwd_roofline")


def _small(**over) -> dict:
    cfg = dict(FILE)
    for key, value in {**FILE["rehearsal"], **over}.items():
        cfg[key] = ({**cfg[key], **value}
                    if isinstance(value, dict) and isinstance(cfg.get(key),
                                                              dict)
                    else value)
    return cfg


CFG = _small(attn_impl="xla")


def test_check_train_passes_small_and_fails_on_fp8_weights():
    """``check_train`` itself at the rehearsal size, float32 on both sides:
    both heads' logits, the routing and the update agree; with the system's
    weights rounded to fp8 at least one limit fails."""
    good = XING.check_train(CFG, {"seq_len": 64}, 3)
    assert good["ok"], good
    assert set(good["errors"]) == set(good["tolerance"]) == {
        "logits_l2", "logits_max", "mtp_logits_l2", "mtp_logits_max",
        "routing_disagreement", "update_l2", "update_leaf_max",
        "hc_res_row_err", "hc_res_col_err"}
    for key in ("logits_l2", "logits_max", "mtp_logits_l2", "mtp_logits_max"):
        assert good["errors"][key] < 1e-4, key
    assert good["loss"] < 1e-5 and good["mtp_loss_error"] < 1e-5
    assert good["grad_norm"] < 1e-4
    assert len(good["held_pairs_by_layer"]) == 2    # one trunk layer, the MTP's
    assert good["routing_agreement"] == 1.0
    assert (good["errors"]["hc_res_row_err"] < 1e-3
            and good["errors"]["hc_res_col_err"] < 1e-5)
    assert len(good["update_leaf_top"]) == 6
    assert not [leaf for leaf, _ in good["update_leaf_top"]
                if XING._reads_copies(leaf)]
    assert XING._reads_copies("['block_0']['hc_attn']['phi']")
    assert XING._reads_copies("['mtp_block']['hc_attn']['bias']")
    assert not XING._reads_copies("['block_0']['hc_mlp']['phi']")
    assert not XING._reads_copies("['block_1']['hc_attn']['phi']")
    assert 0.2 < good["hc_pre_mean"] < 0.8 and good["mtp_loss"] > 1.0
    bad = XING.check_train(CFG, {"seq_len": 64}, 3, degrade_system="fp8")
    assert not bad["ok"]
    assert any(bad["errors"][k] >= bad["tolerance"][k] for k in bad["errors"])


@pytest.mark.parametrize("impl", ["pallas_interpret"])
def test_the_kernels_path_matches_the_reference_too(impl):
    """The same through the flash kernels in interpret mode (the query
    latent's heads, YaRN's scale as their static ``sm_scale``), forward
    only: the logits of both heads."""
    cfg = _small(attn_impl=impl)
    _tfm, model = XING._model(cfg)
    params, buffers = XING._init_state(cfg, jax.random.key(2))
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (1, 64)), jnp.int32)
    logits, logits_mtp = model.apply({"params": params, "buffers": buffers},
                                     ids)
    ref, ref_mtp, _routing = XING.reference_forward(
        cfg, XING.published_layout(cfg, params), buffers, ids)
    for own, want in ((logits, ref), (logits_mtp[:, :-1], ref_mtp)):
        assert float(jnp.abs(own - want).max()
                     / jnp.abs(want).max()) < 1e-4


def test_the_other_controls_build_other_programs():
    """``bf16_maps`` and ``sinkhorn2`` are configurations the builder takes,
    ``no_mscale`` a constant patched for the control's trace alone (each is
    run against the limits on the chip: PERF.md section 6)."""
    assert set(XING.CONTROLS) == {"fp8", "bf16_maps", "sinkhorn2",
                                  "no_mscale"}
    model = XING._model(CFG)[1]
    assert model.hyper == (4, 20, 1e-6, -30.0, 30.0)
    assert model.hyper_dtype == jnp.float32
    assert XING._model({**CFG, "hyper_dtype": "bfloat16"})[
        1].hyper_dtype == jnp.bfloat16
    assert XING._model({**CFG, "hc_sinkhorn_iters": 2})[1].hyper[1] == 2
    m = 0.1 * math.log(64) + 1
    assert tfm.yarn_mscale(64.0, 1.0) == pytest.approx(m)
    with mock.patch.object(tfm, "yarn_mscale", lambda *_a: 1.0):
        assert tfm.yarn_mscale(64.0, 1.0) == 1.0
    assert tfm.yarn_mscale(64.0, 1.0) == pytest.approx(m)
    assert XING._mscale(FILE) == pytest.approx(m)


def test_records_are_ids_of_the_held_slice():
    cell = common.resolve_cell(CELL)
    rows = XING.train_records(cell["config"], cell["traffic"],
                              common.seeded_rng(2**31 + 5, "records"), 4)
    assert len(rows) == 4 and rows[0].shape == (4096,)
    assert rows[0].dtype == np.int32
    assert 0 <= min(r.min() for r in rows)
    assert max(r.max() for r in rows) < 16384
    batch = XING.rows_to_arrays(cell["config"])(rows[:1])
    assert batch["input_ids"].shape == (1, 4096)


def test_the_cell_s_counts_are_this_chip_s_work_and_no_more():
    """``flops_per_sample`` and the kernels' costs at the cell's sizes
    against hand counts (the issue's arithmetic)."""
    cell = common.resolve_cell(CELL)
    cfg, traffic = cell["config"], cell["traffic"]
    length = traffic["seq_len"]
    assert length == 4096 and traffic["rows_per_chip"] == 1
    assert XING.held_pairs_per_position(cfg) == 4 * 8 / 64 == 0.5
    assert 4096 * 0.5 == 2048 and 2048 / 8 == 256      # pairs an expert
    attention = (3584 * 768 + 768 * 4 * 192 + 3584 * 576 + 512 * 4 * 256
                 + 4 * 128 * 3584)
    assert XING._attention_weights(cfg) == attention == 7_766_016
    hyper = 14336 * 24 + (16 + 8) * 3584
    assert XING._hyper_weights(cfg) == hyper == 430_080
    expert_layer = 3584 * 64 + 3 * 3584 * 1024 + 0.5 * 3 * 3584 * 1024
    pairs = 4096 * 4097 // 2
    fwd = XING.mla_flash_fwd_cost(cfg, traffic, 1)
    bwd = XING.mla_flash_bwd_cost(cfg, traffic, 1)
    # ONE call's cost times 6/5: the readers multiply by num_hidden_layers,
    # the step runs six layers
    assert fwd["flops"] == pytest.approx(1.2 * 2 * pairs * 4 * 320)
    assert bwd["flops"] == pytest.approx(1.2 * 2 * pairs * 4 * (576 + 256))
    assert fwd["bytes"] == pytest.approx(1.2 * 4096 * (
        2 * (4 * 192 + 4 * 128 + 64 + 2 * 4 * 128) + 16))
    want = (6 * (6 * (attention + 2 * hyper) + 3 * 3584 * 9216
                 + 5 * expert_layer + 2 * 3584 * 3584 + 2 * 3584 * 16384)
            + 5 * (fwd["flops"] + bwd["flops"]) / length)
    assert XING.flops_per_sample(cfg, traffic) == pytest.approx(want)
    # the issue's count: a position's forward ≈ 0.79 GFLOP, the dense layer
    # 28%, the two head passes 30%
    forward = XING.flops_per_sample(cfg, traffic) / 3
    assert forward == pytest.approx(0.79e9, rel=0.03)
    assert 2 * 3 * 3584 * 9216 / forward == pytest.approx(0.25, abs=0.04)
    assert 2 * 2 * 3584 * 16384 / forward == pytest.approx(0.30, abs=0.02)
    moe = XING.moe_experts_cost(cfg, traffic, 1)
    assert moe["flops"] == 5 * 3 * 2 * 2048 * 3 * 3584 * 1024
    assert moe["bytes"] == 5 * 2 * (5 * 2048 * 3584 + 3 * 8 * 3 * 3584 * 1024)
    # twelve hyper-connections: (2n + 2) C values forward, the same and one
    # more read of the n streams backward, in bf16
    hc = XING.hc_cost(cfg, traffic, 1)
    assert hc["bytes"] == 12 * 4096 * 2 * (10 + 10 + 4) * 3584
    assert hc["bytes"] == pytest.approx(8.46e9, rel=0.01)
    assert hc["flops"] == 12 * 4096 * 6 * hyper
    assert hc["bytes"] / 819e9 > 10 * hc["flops"] / 197e12     # memory-bound
    assert set(XING.KERNELS) == {"mla_flash_fwd", "mla_flash_bwd",
                                 "moe_experts", "hc_mix"}


def test_the_file_keeps_every_published_width():
    """Every key of the catalog's row under the same name, changed only where
    ``reduced`` says; the published values, the deployment and every assumed
    size are stated; the built model holds the parameters the file counts."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Xing4.0-29B-A4B")
        assert FILE["source"] == row["source_url"]
        differs = [k for k, v in row["config"].items() if FILE.get(k) != v]
        assert sorted(differs) == sorted(FILE["reduced"])
        assert FILE["published"] == {k: row["config"][k]
                                     for k in FILE["reduced"]}
    assert FILE["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "num_attention_heads",
        "num_key_value_heads", "n_routed_experts", "vocab_size"]
    published = FILE["published"]
    assert [published[k] for k in FILE["reduced"]] == [40, 2, 32, 32, 64,
                                                       131072]
    # no width differs
    assert (FILE["hidden_size"], FILE["q_lora_rank"], FILE["kv_lora_rank"],
            FILE["qk_nope_head_dim"], FILE["qk_rope_head_dim"],
            FILE["v_head_dim"], FILE["intermediate_size"],
            FILE["moe_intermediate_size"], FILE["num_experts_per_tok"],
            FILE["router_experts"], FILE["n_shared_experts"],
            FILE["routed_scaling_factor"], FILE["hc_mult"],
            FILE["hc_sinkhorn_iters"], FILE["num_nextn_predict_layers"]) == (
                3584, 768, 512, 128, 64, 128, 9216, 1024, 4, 64, 1, 2, 4, 20,
                1)
    assert FILE["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    # the cut: the floors
    assert FILE["num_hidden_layers"] - FILE["first_k_dense_replace"] == 4
    assert FILE["num_attention_heads"] * 8 == published["num_attention_heads"]
    first, end = FILE["experts_held"]
    assert end - first == FILE["n_routed_experts"] == 8
    assert FILE["vocab_size"] * 8 == published["vocab_size"]
    for key in ("source", "published", "reduced", "assumed", "deployment",
                "rehearsal", "compute", "seeded_state"):
        assert FILE[key], key
    stated = " ".join(FILE["assumed"])
    for size in ("SUM of the streams", "stream-major", "interleaved",
                 "mtp_loss_weight 0.1", "no auxiliary", "learning rate 1e-6",
                 "vocab_chunk", "remat", "seeded_state", "embedding_std",
                 "q_proj_scale", "selection_bias_std", "hc_alpha",
                 "hc_bias_std", "hc_res_diagonal", "rolls the row"):
        assert size in stated, size
    assert "float32" in FILE["compute"] and "Sinkhorn" in FILE["compute"]
    assert "8 chips" in FILE["deployment"]
    assert "256 an expert" in FILE["deployment"]
    assert "789,782,340" in FILE["deployment"]
    assert "12.64 GB" in FILE["deployment"]
    # the parameters the file counts are the ones the program creates
    params, buffers = jax.eval_shape(lambda: XING._init_state(
        FILE, jax.random.PRNGKey(0)))
    size = lambda tree: sum(  # noqa: E731
        int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    assert [size(params), size(buffers)] == [789_782_340, 5 * 64]
    assert [size(params[name]) for name in (
        "block_0", "block_1", "mtp_block")] == [107_581_750, 107_811_126,
                                                107_811_126]
    assert size(params["block_1"]["attn"]) == 7_767_296
    assert size(params["block_1"]["hc_attn"]) == 358_427
    mtp = sum(size(params[name]) for name in params if name.startswith("mtp_"))
    assert mtp == 133_511_990
    assert round(size(params) * 16 / 1e9, 2) == 12.64


def test_the_seeded_state_works_the_dynamic_path():
    """The issue's conditions on the seeded maps, at the rehearsal widths
    (the statistics are the widths' own: ``x̃ φ ~ N(0, 1)`` whatever
    ``n·C``): ``H̃_res`` differs across tokens by a standard deviation of at
    least 0.5, ``H_pre`` and ``H_post`` are not all equal, the rounds
    converge, and the other scales are the file's."""
    seeded = CFG["seeded_state"]
    params, buffers = XING._init_state(CFG, jax.random.PRNGKey(3))
    assert np.asarray(params["embed"]["embedding"]).std() == pytest.approx(
        seeded["embedding_std"], rel=0.1)
    n = CFG["hc_mult"]
    streams = jax.random.normal(jax.random.key(0),
                                (1, 512, n * CFG["hidden_size"]))
    for name in ("block_0", "block_1", "mtp_block"):
        for maps in ("hc_attn", "hc_mlp"):
            hc = params[name][maps]
            np.testing.assert_allclose(hc["alpha"], seeded["hc_alpha"])
            normed = streams / jnp.sqrt(jnp.mean(streams ** 2, -1,
                                                 keepdims=True))
            raw = hc["alpha"][2] * (normed @ hc["phi"])[..., 2 * n:]
            assert float(jnp.std(raw, axis=1).mean()) >= 0.5
            h_pre, h_post, h_res = tfm.HyperConnection(n).apply(
                {"params": hc}, streams)
            assert float(jnp.std(h_pre)) > 0.05 and float(
                jnp.std(h_post)) > 0.05
            assert float(jnp.abs(h_res.sum(1) - 1).max()) < 1e-3
            # a stream keeps the largest part of itself
            diagonal = float(jnp.mean(jnp.stack(
                [h_res[i, i] for i in range(n)])))
            assert 0.3 < diagonal < 0.7
        if name != "block_0":
            bias = np.asarray(buffers[name]["moe"]["e_score_correction_bias"])
            assert 0.2 * seeded["selection_bias_std"] < bias.std() \
                < 3 * seeded["selection_bias_std"]
    assert "block_0" not in buffers
    assert not np.array_equal(params["block_0"]["hc_attn"]["bias"],
                              params["block_0"]["hc_mlp"]["bias"])


def test_a_program_without_the_mechanisms_is_refused(monkeypatch):
    """The parent commit's program builds SOME model from these keys (its
    builder ignores what it does not know): the configuration says so at
    once instead of timing another model under this one's name."""
    import flax.linen as nn

    class Parent(nn.Module):        # a model class from before the fields
        vocab_size: int = 8

    monkeypatch.setattr(tfm, "build_transformer", lambda config: Parent())
    with pytest.raises(NotImplementedError, match="hyper"):
        XING._model(CFG)


# -- the readers --------------------------------------------------------------

STEP = "jit(step)/jit(main)/loss_and_grad/"
FWD = "jvp(Transformer)/block_1/block_1._hyper_connected/"
BWD = "transpose(jvp(Transformer))/block_1/block_1._hyper_connected/"
MTP = "jvp(Transformer)/mtp/mtp_block/mtp_block._hyper_connected/"
SUMS = {
    STEP + FWD + "hc_attn/hc/maps/div:": 100e-6,
    STEP + BWD + "hc_attn/hc/maps/dot_general:": 300e-6,
    STEP + FWD + "hc/pre/add:": 200e-6,
    STEP + BWD + "hc/post/concatenate:": 500e-6,
    STEP + MTP + "hc/post/concatenate:": 100e-6,
    STEP + MTP + "attn/mla/project/q_b_proj/dot_general:": 150e-6,
    STEP + "jvp(Transformer)/mtp/mtp_eh_proj/dot_general:": 50e-6,
    STEP + "jvp(mtp_loss)/mtp/lm_head_loss/dot_general:": 100e-6,
    STEP + "jvp(Transformer)/hc/ends/tile:": 40e-6,
    STEP + FWD + "moe/moe/experts/pallas_call:": 50e-6,
    "": 30e-6,
}


def _run(monkeypatch, sums):
    monkeypatch.setattr(scope_times, "run_scope_seconds", lambda run: sums)
    # a step's hyper-connections need 12 us of compute and 300 us of traffic
    kernels = {"hc_mix": {"flops": 197e12 * 12e-6, "bytes": 819e9 * 300e-6}}
    return {"cell": {"workload": CELL, "config": {}},
            "trace": {"busy_s": 1.0},
            "facts": {"traced_steps": 2, "kernels": kernels},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


@pytest.mark.parametrize("metric,expected", [
    ("hc_mix_ms", 0.4),         # pre + post, the MTP layer's too: 800 us / 2
    ("hc_maps_ms", 0.2),        # the maps alone
    ("hc_mix_roofline", 50.0),  # 300 us of bytes against 600 us a step
    ("mtp_ms", 0.2),            # the module: its layer, W_eh, its head pass
])
def test_new_readers_on_a_hand_made_run(monkeypatch, metric, expected):
    reader = common.load_module("layer_metrics", metric)
    run = _run(monkeypatch, SUMS)
    assert reader.read(run) == pytest.approx(expected)
    if metric.endswith("_roofline"):
        assert reader.bound(run) == "memory"
        run["facts"]["kernels"]["hc_mix"]["flops"] *= 50
        assert reader.bound(run) == "compute"
        assert reader.read(run) == pytest.approx(100.0)


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
    others = {"": 30e-6, STEP + "jvp(Transformer)/block_0/mlp/dot_general:":
              50e-6}
    assert reader.read(_run(monkeypatch, others)) is None
    if metric.endswith("_roofline"):
        run = _run(monkeypatch, SUMS)
        assert reader.read({**run, "peaks": None}) is None
        run["facts"]["kernels"] = {}
        assert reader.read(run) is None and reader.bound(run) is None


# -- the manifest with its tenth cell -------------------------------------------

def test_manifest_holds_the_cell_its_configuration_and_four_readers():
    manifest = common.load_manifest()
    cells = [w["name"] for w in manifest["workloads"]]
    assert CELL in cells and len(cells) >= 10
    entry = next(c for c in manifest["configs"] if c["name"] == NAME)
    assert entry["reduced"] == FILE["reduced"]
    assert entry["source"] == FILE["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    # no width among the reduced keys
    assert not [k for k in entry["reduced"]
                if k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"]
    names = [m["name"] for m in manifest["per_layer"]]
    first = names.index(READERS[0])
    assert tuple(names[first:first + 4]) == READERS
    assert first > names.index("moe_latent_ms")     # appended after PR 41's
    cell = common.resolve_cell(CELL)
    assert (cell["chips"], cell["traffic_name"]) == (1, "token_rows_4k_x1")
    assert {m["name"] for m in cell["end_to_end"]} == {"train_tok_rate",
                                                       "setup_s"}
    reported = {m["name"] for m in cell["per_layer"]}
    assert reported >= {"claim_s", "first_step_s", *JOINED, *READERS}
    # other cost models and other layers are not this cell's
    assert not reported & {"flash_fwd_ms", "flash_fwd_roofline",
                           "flash_bwd_roofline", "bd_flash_fwd_roofline",
                           "dsa_index_ms", "bd_corrupt_ms", "ssm_scan_ms",
                           "moe_latent_ms"}
    for metric in manifest["per_layer"][first:first + 4]:
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
            assert cells_of[-1] == CELL and cells_of.count(CELL) == 1
            assert metric["name"] in JOINED + ("train_tok_rate",)
    # a name and a why within the manifest's limits
    workload = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert len(workload["why"]) <= 200 and len(entry["why"]) <= 200
    assert len(CELL) <= 64


# -- Nemotron-3 keeps its program's parameter tree -----------------------------

TREE = common.read_json(os.path.join(os.path.dirname(__file__),
                                     "lm_param_tree_nemotron_parent.json"))


def test_nemotron_keeps_its_parameter_tree():
    """The sixth LM configuration (``lm_param_trees_parent.json`` holds the
    five before it): the paths, shapes and dtypes of every variable its model
    creates at its rehearsal size, as the PARENT commit's program created
    them (written from the parent's archive): a configuration that sets none
    of the new fields builds the model it built before."""
    (name, tree), = TREE.items()
    mod = common.load_module("configs", name)
    cfg = common.read_json(os.path.join(common.HERE, "configs",
                                        f"{name}.json"))
    cfg = {**cfg, **cfg["rehearsal"]}
    model = tfm.build_transformer({**mod.system_config(cfg),
                                   "attn_impl": "xla", "remat": False})
    assert model.hyper is None and model.mtp_layers == 0
    assert model.q_lora_rank == 0 and model.rope_scaling is None
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32)))
    now = {c: {jax.tree_util.keystr(p): [list(a.shape), str(a.dtype)]
               for p, a in jax.tree_util.tree_flatten_with_path(shapes[c])[0]}
           for c in ("params", "buffers") if c in shapes}
    assert now == tree
