"""The configuration ``olmoe_1b_7b_d1`` (ISSUE 25): its file against the
catalog, its FLOPs and bytes against values worked by hand at the published
widths, its reference check at the rehearsal sizes, and the four ``moe_*``
readers with the scope helper they share, on the recorded v5e trace and on
a small xplane written here."""

from __future__ import annotations

import os
import struct

import pytest

from benchmark import common, scope_times, trace_reduce

CELL = "olmoe_1b_7b_d1_train_4k"
RECORDED = os.path.join(common.HERE, "testdata", "tpu_v5e_4steps.xplane.pb")

# model-configs/architectures.jsonl, "OLMoE-1B-7B-0125-Instruct", "config"
CATALOG = {"attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
           "hidden_size": 2048, "intermediate_size": 1024,
           "max_position_embeddings": 4096, "model_type": "olmoe",
           "norm_topk_prob": False, "num_attention_heads": 16,
           "num_experts": 64, "num_experts_per_tok": 8,
           "num_hidden_layers": 16, "num_key_value_heads": 16,
           "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
           "tie_word_embeddings": False, "vocab_size": 50304}


@pytest.fixture(scope="module")
def cell():
    return common.resolve_cell(CELL)


@pytest.fixture(scope="module")
def module():
    return common.load_module("configs", "olmoe_1b_7b_d1")


def test_file_is_the_catalog_config_with_only_depth_reduced(cell):
    cfg = cell["config"]
    changed = {k for k, v in CATALOG.items() if cfg[k] != v}
    assert changed == {"num_hidden_layers"} == set(cfg["reduced"])
    assert cfg["published"] == {"num_hidden_layers": 16}
    assert cfg["num_hidden_layers"] == 1
    assert cell["traffic"]["seq_len"] == cfg["max_position_embeddings"]
    assert cell["traffic"]["rows_per_chip"] * cell["traffic"]["seq_len"] == 8192
    assert cell["chips"] == 1
    assert {m["name"] for m in cell["end_to_end"]} == {"train_tok_rate",
                                                       "setup_s"}
    assert {m["name"] for m in cell["per_layer"]} >= {
        "lm_step_device_ms", "lm_mfu", "flash_fwd_ms", "flash_fwd_roofline",
        "moe_dispatch_ms", "moe_experts_ms", "moe_experts_roofline",
        "moe_optimizer_ms"}


@pytest.mark.parametrize("what,by_hand", [
    # 6 x (attention 4 d^2 + router d e + 8 experts x 3 d f + head d V)
    #   + 3 x causal attention 2 d s: 1.072 GFLOP a token at 4096 positions
    ("flops_per_token", 6 * (4 * 2048 ** 2 + 2048 * 64
                              + 8 * 3 * 2048 * 1024 + 2048 * 50304)
     + 3 * 2 * 2048 * 4096),
    # 3 passes x 2 x 65,536 pairs x 3 d f: 2.47 TFLOP a step
    ("experts_flops_per_step", 3 * 2 * 65536 * 3 * 2048 * 1024),
    # bf16: 5 x pairs x d rows read or written, the weights three times
    ("experts_bytes_per_step", 2 * (5 * 65536 * 2048
                                    + 3 * 64 * 3 * 2048 * 1024)),
    # one layer, 2 rows: 32 heads x (2 matmuls x 2 x s^2 x 128) / 2
    ("flash_fwd_flops_per_call", 2 * 16 * 2 * 2 * 4096 ** 2 * 128 / 2),
])
def test_flops_and_bytes_equal_values_worked_by_hand(cell, module, what,
                                                     by_hand):
    cfg, traffic = cell["config"], cell["traffic"]
    experts = module.KERNELS["moe_experts"](cfg, traffic, 2)
    got = {"flops_per_token": module.flops_per_sample(cfg, traffic),
           "experts_flops_per_step": experts["flops"],
           "experts_bytes_per_step": experts["bytes"],
           "flash_fwd_flops_per_call":
           module.KERNELS["flash_fwd"](cfg, traffic, 2)["flops"]}[what]
    assert got == by_hand
    assert 1.0715e9 < module.flops_per_sample(cfg, traffic) < 1.0725e9
    assert 2.47e12 < experts["flops"] < 2.48e12
    # the head is 58% of the step's FLOPs at one layer, the experts 28%
    per_token = module.flops_per_sample(cfg, traffic)
    assert round(100 * 6 * 2048 * 50304 / per_token) == 58
    assert round(100 * 6 * 8 * 3 * 2048 * 1024 / per_token) == 28


def test_reference_check_passes_at_the_rehearsal_sizes(cell, module):
    cfg = dict(cell["config"])
    cfg.update(cfg["rehearsal"])
    out = module.check_train(cfg, {"seq_len": 64}, seed=3)
    # bf16 system against the float32 reference: inside the check's limits
    assert out["ok"], out
    assert set(out["errors"]) == set(module.TOLERANCE)
    assert out["routing_agreement"] > 0.95
    assert out["routing"]["pairs_min"] >= 0 < out["routing"]["max_over_mean"]


def test_check_refuses_a_program_without_the_keys(module, monkeypatch):
    """The parent commit's builder ignores keys it does not know; the
    configuration refuses to call what it builds OLMoE."""
    from tensorflowonspark_tpu.models import transformer as tfm

    class Old:
        n_experts = 64

    monkeypatch.setattr(tfm, "build_transformer", lambda conf: Old())
    with pytest.raises(NotImplementedError, match="cannot build OLMoE"):
        module.check_train({**common.resolve_cell(CELL)["config"]}, {}, 0)


# -- the scope helper -------------------------------------------------------

def test_decoding_by_hand_equals_the_profiler_s_own_reader():
    """``scope_times.load`` on the recorded v5e trace: the same planes,
    lines and events as ``trace_reduce.load`` (jax's ``ProfileData``), plus
    the scope of each device op from the event metadata's ``tf_op`` stat."""
    mine, theirs = scope_times.load(RECORDED), trace_reduce.load(RECORDED)
    assert [p["name"] for p in mine["planes"]] == [
        p["name"] for p in theirs["planes"]]
    for a, b in zip(mine["planes"], theirs["planes"]):
        assert [(ln["name"], ln["events"]) for ln in a["lines"]] == [
            (ln["name"], ln["events"]) for ln in b["lines"]]
    device = trace_reduce.device_planes(mine)[0]
    assert set(device["scopes"].values()) >= {"jit(step)/mm/dot_general:",
                                              "jit(step)/pallas_call:"}
    sums = scope_times.scope_seconds(mine)
    ops = trace_reduce.line_events(device, trace_reduce.OPS_LINE)
    assert sum(sums.values()) == pytest.approx(
        sum(t for _n, t in trace_reduce.self_times(ops)) * 1e-9)
    # that recording named one scope, "mm": four 12.6 us matmuls
    mm = sum(t for path, t in sums.items() if scope_times.in_scope(path, "mm"))
    assert mm == pytest.approx(50.392e-6)
    assert not any(scope_times.in_scope(path, "moe/experts") for path in sums)


def _varint(n: int) -> bytes:
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _xplane(name: str, lines, scopes=None) -> bytes:
    """One ``XPlane``: ``lines`` is ``[(line name, [(event name, start_ns,
    duration_ns)])]``; ``scopes`` maps event names to a ``tf_op`` stat."""
    names = sorted({e[0] for _ln, events in lines for e in events})
    ids = {n: i + 1 for i, n in enumerate(names)}
    out = _field(2, name)
    for line_name, events in lines:
        body = _field(2, line_name) + _field(3, 1000)
        for event_name, start_ns, duration_ns in events:
            body += _field(4, _field(1, ids[event_name])
                           + _field(2, (start_ns - 1000) * 1000)
                           + _field(3, duration_ns * 1000))
        out += _field(3, body)
    for event_name, key in ids.items():
        meta = _field(1, key) + _field(2, event_name)
        if scopes and event_name in scopes:
            meta += _field(5, _field(1, 7) + _field(5, scopes[event_name]))
        out += _field(4, _field(1, key) + _field(2, meta))
    out += _field(5, _field(1, 7) + _field(2, _field(1, 7)
                                           + _field(2, "tf_op")))
    return out


@pytest.fixture()
def small_run(tmp_path, monkeypatch):
    """A traced run's directory with a two-step xplane of this test's own:
    per step a ``while`` of 60 us that holds two expert matmuls of 20 us
    (so 20 us of its own), a dispatch gather of 10 us, its backward of 5 us,
    an adamw fusion of 30 us and an op without a scope; one more step lies
    outside the traced window."""
    step = "jit(step)/jit(main)/"
    scopes = {
        "%while.1": step + "loss_and_grad/jvp(T)/block_0/moe/moe/experts/while:",
        "%dot.1": step + "loss_and_grad/jvp(T)/block_0/moe/moe/experts/while/body/dot_general:",
        "%gather.1": step + "loss_and_grad/jvp(T)/block_0/moe/moe/dispatch/gather:",
        "%gather.2": step + "loss_and_grad/transpose(jvp(T))/block_0/moe/moe/dispatch/gather:",
        "%fusion.9": step + "optimizer_update/mul:",
    }
    ops = []
    for start in (10_000, 210_000, 910_000):        # the third: outside
        ops += [("%while.1", start, 60_000), ("%dot.1", start + 5_000, 20_000),
                ("%dot.1", start + 30_000, 20_000),
                ("%gather.1", start + 70_000, 10_000),
                ("%gather.2", start + 85_000, 5_000),
                ("%fusion.9", start + 100_000, 30_000),
                ("%copy.3", start + 140_000, 7_000)]
    space = _field(1, _xplane("/device:TPU:0", [("XLA Ops", ops)], scopes))
    space += _field(1, _xplane("/host:CPU", [
        ("main/1", [(scope_times.WINDOW_SPAN, 5_000, 800_000)])]))
    monkeypatch.setattr(common, "WORK_DIR", str(tmp_path))
    folder = tmp_path / "runs" / CELL / "trace" / "plugins" / "profile" / "t0"
    folder.mkdir(parents=True)
    (folder / "node.xplane.pb").write_bytes(space)
    cost = {"flops": 197e12 * 20e-6, "bytes": 819e9 * 5e-6}   # 20 us, 5 us
    return {"cell": {"workload": CELL}, "trace": {"busy_s": 1.0},
            "facts": {"traced_steps": 2, "kernels": {"moe_experts": cost}},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


@pytest.mark.parametrize("metric,expected", [
    ("moe_experts_ms", 0.060),          # the while and its body, per step
    ("moe_dispatch_ms", 0.015),         # forward and backward gather
    ("moe_optimizer_ms", 0.030),
    ("moe_experts_roofline", 100 * 20e-6 / 60e-6),   # compute-bound
])
def test_moe_readers_on_a_small_trace(small_run, metric, expected):
    reader = common.load_module("layer_metrics", metric)
    assert reader.read(small_run) == pytest.approx(expected)
    if metric == "moe_experts_roofline":
        assert reader.bound(small_run) == "compute"


@pytest.mark.parametrize("metric", ["moe_experts_ms", "moe_dispatch_ms",
                                    "moe_optimizer_ms",
                                    "moe_experts_roofline"])
def test_moe_readers_find_nothing_without_a_trace_or_a_scope(
        small_run, metric, tmp_path):
    """An untraced run, a run whose trace directory is gone, and a traced
    program that names no such scope (the parent commit): None, no raise."""
    reader = common.load_module("layer_metrics", metric)
    assert reader.read({**small_run, "trace": None}) is None
    assert reader.read({**small_run,
                        "cell": {"workload": "no_such_cell"}}) is None
    bare = _field(1, _xplane("/device:TPU:0", [
        ("XLA Ops", [("%fusion.1", 10_000, 5_000)])],
        {"%fusion.1": "jit(step)/jit(main)/loss_and_grad/mlp/dot_general:"}))
    path = next((tmp_path / "runs" / CELL).rglob("*.xplane.pb"))
    path.write_bytes(bare)
    scope_times._LOADED.clear()
    assert reader.read(small_run) is None


def test_signed_and_double_stats_decode():
    stat = _field(1, 3) + _varint(2 << 3 | 1) + struct.pack("<d", 2.5)
    assert scope_times._stat(memoryview(stat)) == (3, 2.5)
    negative = _field(1, 4) + _varint(4 << 3) + _varint((1 << 64) - 2)
    assert scope_times._stat(memoryview(negative)) == (4, -2)
