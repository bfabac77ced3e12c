"""NVIDIA-Nemotron-3-Super-120B-A12B (ISSUE 41): the program against the plain
reference kept with the benchmark
(``benchmark/configs/nemotron3_super_d11_tp8_ep64.py``) at a small size on
the CPU, the configuration's file against the catalog's row, the parameters
the built model creates against the issue's count, the cost functions against
hand counts, the four new readers on a hand-made run, the manifest with its
ninth cell, and the five older LM configurations' parameter trees against
the parent commit's.  The same comparison runs at the published widths on
the chip (``check_train``)."""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common, scope_times
from tensorflowonspark_tpu.models import transformer as tfm

NEMOTRON = common.load_module("configs", "nemotron3_super_d11_tp8_ep64")
NAME = "nemotron3_super_d11_tp8_ep64"
CELL = NAME + "_train_8k"
FILE = common.read_json(os.path.join(common.HERE, "configs", NAME + ".json"))
READERS = ("ssm_mixer_ms", "ssm_scan_ms", "ssm_scan_roofline",
           "moe_latent_ms")
# accepted readers whose lists the cell joins: it runs their scopes
JOINED = ("lm_feed_wait_share", "lm_step_device_ms", "lm_mfu",
          "moe_dispatch_ms", "moe_experts_ms", "moe_experts_roofline",
          "moe_optimizer_ms", "moe_shared_ms", "moe_router_ms", "flash_bwd_ms",
          "bd_flash_fwd_ms")

# the model's shape in small: seven layers of the three kinds, 4 Mamba heads
# in 2 groups, 4 query heads over 1 K/V head, experts 2-5 of 16 held, 5 a
# token, float32
CFG = {**FILE, **FILE["rehearsal"], "hybrid_override_pattern": "MEM*EME",
       "num_hidden_layers": 7, "n_groups": 2, "experts_held": [2, 6],
       "reference_tokens": [2, 64],
       "seeded_state": {**FILE["seeded_state"], "selection_bias_std": 0.1}}
# a chunked scan, a sort with a grouped matmul and a blockwise loss against
# a scan over positions, a loop over experts and whole logits, float32 on
# both sides: measured 4e-7 to 2e-6 (relative to the largest entry)
TOL = 1e-4


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def _ids(cfg=CFG, seed=0):
    rows, length = cfg["reference_tokens"]
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (rows, length)), jnp.int32)


@pytest.mark.parametrize("attn_impl", ["pallas_interpret", "xla"])
def test_system_matches_the_reference(attn_impl):
    """Loss, logits, the routing and the gradient of every parameter leaf;
    the bias buffers are no parameters."""
    cfg = {**CFG, "attn_impl": attn_impl}
    _tfm, model = NEMOTRON._model(cfg)
    params, buffers = NEMOTRON._init_state(cfg, jax.random.PRNGKey(1))
    ids = _ids(cfg)
    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        NEMOTRON._loss_fn(tfm, model, cfg), has_aux=True))(
            params, {"input_ids": ids}, buffers)
    logits, sown = model.apply({"params": params, "buffers": buffers}, ids,
                               mutable=["intermediates"])

    def reference(params):
        ref_logits, routing = NEMOTRON.reference_forward(cfg, params, buffers,
                                                         ids)
        return NEMOTRON.reference_loss(ref_logits, ids), (ref_logits, routing)

    (ref_loss, (ref_logits, ref_routing)), ref_grads = jax.value_and_grad(
        reference, has_aux=True)(params)
    assert jax.tree.structure(grads) == jax.tree.structure(ref_grads)
    assert abs(float(loss) - float(ref_loss)) / float(ref_loss) < TOL
    assert _rel(logits, ref_logits) < TOL
    for (path, got), want in zip(
            jax.tree_util.tree_flatten_with_path(grads)[0],
            jax.tree.leaves(ref_grads)):
        assert _rel(got, want) < TOL, jax.tree_util.keystr(path)
    routing = NEMOTRON._sown_routing(sown)
    assert len(routing) == len(ref_routing) == 3
    for got, want in zip(routing, ref_routing):
        np.testing.assert_array_equal(np.sort(np.asarray(got)),
                                      np.sort(np.asarray(want)))
    assert 0.1 < float(metrics["moe_held_pairs"]) < 0.5     # 4 of 16 held
    assert 0.0 < float(metrics["moe_bias_moved"]) < 1.0
    assert float(metrics["aux_loss"]) == 0.0
    # one norm and one mixer a layer, by the pattern
    assert set(grads["block_0"]) == {"norm", "ssm"}
    assert set(grads["block_1"]) == {"norm", "moe", "shared"}
    assert set(grads["block_3"]) == {"norm", "attn"}
    assert set(grads["block_1"]["moe"]) == {
        "router", "latent_down", "latent_up", "experts_up", "experts_down"}
    assert set(buffers) == {"block_1", "block_4", "block_6"}


@pytest.mark.parametrize("change", [
    {"rope": True},                             # attention that turns
    {"moe_expert_act": "swiglu"},               # gated experts: other shapes
    {"moe_latent": 0},                          # experts of the hidden width
    {"layer_mixer": list("MEM*EEM")},           # another pattern
    {"moe_router": {"scoring": "softmax", "selection_bias": True,
                    "routed_scale": 5.0}},
])
def test_another_model_fails_the_tolerance(change):
    """The reference is this model's and no neighbour's: each change to the
    system alone moves it out of tolerance or cannot load the parameters."""
    cfg = {**CFG, "attn_impl": "xla"}
    params, buffers = NEMOTRON._init_state(cfg, jax.random.PRNGKey(1))
    ids = _ids(cfg)
    wrong = tfm.build_transformer({**NEMOTRON.system_config(cfg), **change})
    try:
        logits = wrong.apply({"params": params, "buffers": buffers}, ids)
    except Exception:       # noqa: BLE001 - the tree does not fit the model
        return
    ref_logits, _ = NEMOTRON.reference_forward(cfg, params, buffers, ids)
    assert _rel(logits, ref_logits) > 1e-3


def test_check_train_passes_small_and_fails_degraded():
    """``check_train`` itself at the rehearsal size: ok on the true weights;
    with the system's weights rounded to fp8 at least one limit fails."""
    cfg = {**CFG, "attn_impl": "xla"}
    good = NEMOTRON.check_train(cfg, {"seq_len": 64}, 3)
    assert good["ok"], good
    assert set(good["errors"]) == set(good["tolerance"]) == {
        "logits_l2", "logits_max", "routing_disagreement", "update_l2",
        "update_leaf_max"}
    assert good["errors"]["update_l2"] < 0.01
    assert good["loss"] < 1e-5 and good["grad_norm"] < 1e-4
    assert len(good["held_pairs_by_layer"]) == 3
    assert good["routing_agreement"] == 1.0
    bad = NEMOTRON.check_train(cfg, {"seq_len": 64}, 3, degrade_system=True)
    assert not bad["ok"]
    assert any(bad["errors"][k] >= bad["tolerance"][k] for k in bad["errors"])
    # the other control builds another program: the scan's state in bf16
    model = NEMOTRON._model({**cfg, "ssm_state_dtype": "bfloat16"})[1]
    assert model.ssm_state_dtype == jnp.bfloat16
    assert NEMOTRON._model(cfg)[1].ssm_state_dtype == jnp.float32


def test_records_are_ids_of_the_held_slice():
    traffic = {"seq_len": 64}
    rows = NEMOTRON.train_records(FILE, traffic,
                                  common.seeded_rng(7, "records"), 50)
    batch = NEMOTRON.rows_to_arrays(FILE)(rows[:5])
    assert set(batch) == {"input_ids"}
    assert batch["input_ids"].shape == (5, 64)
    assert batch["input_ids"].dtype == np.int32
    ids = np.stack(rows)
    assert ids.min() >= 0 and ids.max() < FILE["vocab_size"]
    again = NEMOTRON.train_records(FILE, traffic,
                                   common.seeded_rng(7, "records"), 50)
    np.testing.assert_array_equal(ids, np.stack(again))
    # a large seed, as the driver's are
    NEMOTRON.train_records(FILE, traffic,
                           common.seeded_rng(2 ** 31 + 12345, "records"), 2)


def test_the_cell_s_counts_are_this_chip_s_work_and_no_more():
    """``flops_per_sample`` and the kernels' costs at the cell's sizes
    against hand counts (the issue's arithmetic)."""
    cell = common.resolve_cell(CELL)
    cfg, traffic = cell["config"], cell["traffic"]
    length = traffic["seq_len"]
    assert length == 8192 and traffic["rows_per_chip"] == 1
    assert NEMOTRON.held_pairs_per_position(cfg) == 22 * 8 / 512 == 0.34375
    assert [NEMOTRON.layers_of(cfg, k) for k in "M*E"] == [5, 1, 5]
    mamba = 4096 * 2320 + 1024 * 4096 + 4 * 1280
    assert NEMOTRON._mamba_weights(cfg) == mamba == 13_702_144
    attention = 4096 * 128 * 2 * (4 + 1)
    assert NEMOTRON._attention_weights(cfg) == attention == 5_242_880
    expert = (4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
              + 0.34375 * 2 * 1024 * 2688)
    assert NEMOTRON._expert_layer_weights(cfg) == expert
    # the scan: per position and layer, forward, the causal half of C·Bᵀ
    # (64.5 x 128 a group) and of its product with x (64.5 x 64 x 16 heads),
    # the chunk's state and the carried state's product (16 x 64 x 128 each)
    scan = NEMOTRON.ssd_scan_cost(cfg, traffic, 1)
    forward = 2 * 8192 * (64.5 * (128 + 16 * 64) + 2 * 16 * 64 * 128)
    assert scan["flops"] == 5 * 3 * forward
    assert scan["flops"] == pytest.approx(82.3e9, rel=5e-3)
    # x, z, y of 1024, B and C of 128 in bf16 and Δ of 16 in float32, once
    # forward and their cotangents once backward, five layers
    assert scan["bytes"] == 5 * 2 * 8192 * (2 * (3 * 1024 + 2 * 128) + 4 * 16)
    assert scan["bytes"] / 819e9 > scan["flops"] / 197e12      # memory-bound
    moe = NEMOTRON.moe_experts_cost(cfg, traffic, 1)
    held = 8192 * 0.34375                               # 2,816 pairs a layer
    assert held == 2816 and held / 8 == 352             # an expert's pairs
    assert moe["flops"] == 5 * 3 * 2 * held * 2 * 1024 * 2688
    assert moe["bytes"] == 5 * 2 * (5 * held * 1024
                                    + 3 * 8 * 2 * 1024 * 2688)
    attn_flops = 3.5 * 2 * (8192 * 8193 // 2) * 4 * 256
    want = (6 * (5 * mamba + attention + 5 * expert + 4096 * 16384)
            + attn_flops / length + scan["flops"] / length)
    assert NEMOTRON.flops_per_sample(cfg, traffic) == pytest.approx(want)
    # the issue's count: a position's forward is 858 MFLOP (a third of the
    # training count), of which the shared experts are 51%
    forward = NEMOTRON.flops_per_sample(cfg, traffic) / 3
    assert forward == pytest.approx(858e6, rel=0.01)
    assert 5 * 2 * 2 * 4096 * 5376 / forward == pytest.approx(0.51, abs=0.01)
    assert set(NEMOTRON.KERNELS) == {"ssd_scan", "moe_experts"}


def test_the_file_keeps_every_published_width():
    """Every key of the catalog's row under the same name, changed only where
    ``reduced`` says; the published values, the deployment and every assumed
    size are stated; the built model holds the parameters the file counts."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
        assert FILE["source"] == row["source_url"]
        differs = [k for k, v in row["config"].items() if FILE.get(k) != v]
        assert sorted(differs) == sorted(FILE["reduced"])
        assert FILE["published"] == {k: row["config"][k]
                                     for k in FILE["reduced"]}
    assert FILE["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "mamba_num_heads",
        "n_groups", "num_attention_heads", "num_key_value_heads",
        "n_routed_experts", "vocab_size"]
    published = FILE["published"]
    assert (published["num_hidden_layers"], published["mamba_num_heads"],
            published["n_groups"], published["num_attention_heads"],
            published["num_key_value_heads"], published["n_routed_experts"],
            published["vocab_size"]) == (88, 128, 8, 32, 2, 512, 131072)
    # no width differs
    assert (FILE["hidden_size"], FILE["head_dim"], FILE["mamba_head_dim"],
            FILE["ssm_state_size"], FILE["conv_kernel"], FILE["chunk_size"],
            FILE["moe_latent_size"], FILE["moe_intermediate_size"],
            FILE["moe_shared_expert_intermediate_size"],
            FILE["num_experts_per_tok"], FILE["router_experts"],
            FILE["routed_scaling_factor"], FILE["mlp_hidden_act"],
            FILE["layer_norm_epsilon"], FILE["expand"]) == (
                4096, 128, 64, 128, 4, 128, 1024, 2688, 5376, 22, 512, 5,
                "relu2", 1e-5, 2)
    # the cut: the first period of the published pattern, one of eight B/C
    # groups with its heads, a K/V head with four of its query heads, the
    # floors of experts and vocabulary
    assert published["hybrid_override_pattern"].startswith(
        FILE["hybrid_override_pattern"])
    assert len(FILE["hybrid_override_pattern"]) == FILE["num_hidden_layers"]
    assert sorted(FILE["hybrid_override_pattern"]) == sorted(
        "M" * 5 + "E" * 5 + "*")
    assert FILE["mamba_num_heads"] * 8 == published["mamba_num_heads"]
    assert FILE["n_groups"] * 8 == published["n_groups"]
    assert FILE["num_attention_heads"] * 8 == published["num_attention_heads"]
    first, end = FILE["experts_held"]
    assert end - first == FILE["n_routed_experts"] == 8
    assert FILE["vocab_size"] * 8 == published["vocab_size"]
    for key in ("source", "published", "reduced", "assumed", "deployment",
                "rehearsal", "compute"):
        assert FILE[key], key
    stated = " ".join(FILE["assumed"])
    for size in ("rope_theta", "latent maps", "router reads",
                 "num_nextn_predict_layers", "no auxiliary", "learning rate",
                 "vocab_chunk", "remat", "seeded_state", "embedding_std",
                 "selection_bias_std", "n_group", "sigmoid"):
        assert size in stated, size
    assert "64 chips" in FILE["deployment"]
    assert "352 pairs" in FILE["deployment"]
    assert "700,862,960" in FILE["deployment"]
    # the parameters the file counts are the ones the program creates
    params, buffers = jax.eval_shape(lambda: NEMOTRON._init_state(
        FILE, jax.random.PRNGKey(0)))
    count = [sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
             for tree in (params, buffers)]
    assert count == [700_862_960, 5 * 512]
    by_layer = [sum(int(np.prod(a.shape)) for a in jax.tree.leaves(
        params[f"block_{i}"])) for i in (0, 1, 7)]
    assert by_layer == [13_708_592, 98_570_240, 5_246_976]


def test_the_seeded_state_has_the_scales_the_file_states():
    seeded = CFG["seeded_state"]
    params, buffers = NEMOTRON._init_state(CFG, jax.random.PRNGKey(3))
    assert np.asarray(params["embed"]["embedding"]).std() == pytest.approx(
        seeded["embedding_std"], rel=0.1)
    for layer, kind in enumerate(CFG["hybrid_override_pattern"]):
        block = params[f"block_{layer}"]
        np.testing.assert_allclose(block["norm"]["scale"], 1.0)
        if kind == "E":
            bias = np.asarray(
                buffers[f"block_{layer}"]["moe"]["e_score_correction_bias"])
            assert 0.2 * seeded["selection_bias_std"] < bias.std() \
                < 3 * seeded["selection_bias_std"]
        else:
            assert f"block_{layer}" not in buffers
    assert not np.array_equal(
        buffers["block_1"]["moe"]["e_score_correction_bias"],
        buffers["block_4"]["moe"]["e_score_correction_bias"])


def test_a_program_without_the_mechanisms_is_refused(monkeypatch):
    """The parent commit's program builds SOME model from these keys (its
    builder ignores what it does not know): the configuration says so at
    once instead of timing another model under this one's name."""
    import flax.linen as nn

    class Parent(nn.Module):        # a model class from before the fields
        vocab_size: int = 8

    monkeypatch.setattr(tfm, "build_transformer", lambda config: Parent())
    with pytest.raises(NotImplementedError, match="layer_mixer"):
        NEMOTRON._model(CFG)


# -- the readers --------------------------------------------------------------

STEP = "jit(step)/jit(main)/loss_and_grad/"
FWD, BWD = "jvp(Transformer)/block_0/", "transpose(jvp(Transformer))/block_0/"
SUMS = {
    STEP + FWD + "ssm/ssm/ssm/in_proj/in_proj/dot_general:": 300e-6,
    STEP + FWD + "ssm/ssm/ssm/scan/checkpoint/ssd/intra/dot_general:": 400e-6,
    STEP + BWD + "ssm/ssm/ssm/scan/checkpoint/ssd/state/while:": 1200e-6,
    STEP + BWD + "ssm/ssm/ssm/out_proj/out_proj/dot_general:": 100e-6,
    STEP + "jvp(Transformer)/block_1/moe/moe/latent/latent_down/"
    "dot_general:": 60e-6,
    STEP + "transpose(jvp(Transformer))/block_1/moe/moe/latent/latent_up/"
    "dot_general:": 140e-6,
    STEP + "jvp(Transformer)/block_1/moe/moe/experts/pallas_call:": 50e-6,
    "": 30e-6,
}


def _run(monkeypatch, sums):
    monkeypatch.setattr(scope_times, "run_scope_seconds", lambda run: sums)
    # a step's scans need 40 us of compute and 160 us of memory traffic
    kernels = {"ssd_scan": {"flops": 197e12 * 40e-6, "bytes": 819e9 * 160e-6}}
    return {"cell": {"workload": CELL, "config": {}},
            "trace": {"busy_s": 1.0},
            "facts": {"traced_steps": 2, "kernels": kernels},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


@pytest.mark.parametrize("metric,expected", [
    ("ssm_mixer_ms", 1.0),          # 2,000 us over two steps, both halves
    ("ssm_scan_ms", 0.8),           # the scan alone, not the projections
    ("ssm_scan_roofline", 20.0),    # 160 us of bytes against 800 us
    ("moe_latent_ms", 0.1),         # the two maps, not the experts
])
def test_new_readers_on_a_hand_made_run(monkeypatch, metric, expected):
    reader = common.load_module("layer_metrics", metric)
    run = _run(monkeypatch, SUMS)
    assert reader.read(run) == pytest.approx(expected)
    if metric.endswith("_roofline"):
        assert reader.bound(run) == "memory"
        run["facts"]["kernels"]["ssd_scan"]["flops"] *= 20
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
    others = {"": 30e-6, STEP + FWD + "mlp/dot_general:": 50e-6}
    assert reader.read(_run(monkeypatch, others)) is None
    if metric.endswith("_roofline"):
        run = _run(monkeypatch, SUMS)
        assert reader.read({**run, "peaks": None}) is None
        run["facts"]["kernels"] = {}
        assert reader.read(run) is None and reader.bound(run) is None


# -- the manifest with its ninth cell -------------------------------------------

def test_manifest_holds_the_cell_its_configuration_and_four_readers():
    manifest = common.load_manifest()
    cells = [w["name"] for w in manifest["workloads"]]
    assert CELL in cells and len(cells) >= 9
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
    assert first > names.index("moe_router_ms")     # appended after PR 39's
    cell = common.resolve_cell(CELL)
    assert (cell["chips"], cell["traffic_name"]) == (1, "token_rows_8k_x1")
    assert {m["name"] for m in cell["end_to_end"]} == {"train_tok_rate",
                                                       "setup_s"}
    reported = {m["name"] for m in cell["per_layer"]}
    assert reported >= {"claim_s", "first_step_s", *JOINED, *READERS}
    # other cost models and other layers are not this cell's
    assert not reported & {"flash_fwd_ms", "flash_fwd_roofline",
                           "flash_bwd_roofline", "bd_flash_fwd_roofline",
                           "mla_project_ms", "dsa_index_ms", "bd_corrupt_ms"}
    for metric in manifest["per_layer"][first:first + 4]:
        reader = common.load_module("layer_metrics", metric["name"])
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
            metric["layer"], metric["unit"], metric["moves"])
        assert metric["workloads"][0] == CELL
        assert metric["source"] == "device_trace"
        assert metric["moves"] == "train_tok_rate"
    # appended after what was there in each list it joined
    for metric in manifest["per_layer"][:first] + manifest["end_to_end"]:
        cells_of = metric.get("workloads", [])
        if CELL in cells_of:
            assert cells_of.count(CELL) == 1
            assert metric["name"] in JOINED + ("train_tok_rate",)
            assert cells_of.index(CELL) > cells_of.index(
                "kanana2_30b_a3b_d5_ep8_train_8k")


# -- the accepted LM configurations keep their programs' parameter trees -------

TREES = common.read_json(os.path.join(os.path.dirname(__file__),
                                      "lm_param_trees_parent.json"))


@pytest.mark.parametrize("name", sorted(TREES))
def test_an_older_configuration_keeps_its_parameter_tree(name):
    """The paths, shapes and dtypes of every variable the five older LM
    configurations' models create at their rehearsal sizes, as the PARENT
    commit's program created them (``lm_param_trees_parent.json``, written
    from the parent's archive): a configuration that sets none of the new
    fields builds the model it built before."""
    mod = common.load_module("configs", name)
    cfg = common.read_json(os.path.join(common.HERE, "configs",
                                        f"{name}.json"))
    cfg = {**cfg, **cfg["rehearsal"]}
    model = tfm.build_transformer({**mod.system_config(cfg),
                                   "attn_impl": "xla", "remat": False})
    assert model.layer_mixer is None and model.ssm is None
    assert model.rope and model.moe_expert_act == "swiglu"
    assert model.moe_latent == 0
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)))
    now = {c: {jax.tree_util.keystr(p): [list(a.shape), str(a.dtype)]
               for p, a in jax.tree_util.tree_flatten_with_path(shapes[c])[0]}
           for c in ("params", "buffers") if c in shapes}
    assert now == TREES[name]
