"""The yardstick's arithmetic: operations and bytes from shapes against
hand counts, the table of peaks, seeded inputs.  No jax, no cluster."""

from __future__ import annotations

import os

import numpy as np
import pytest

from benchmark import common


def _cfg(name: str) -> dict:
    return common.read_json(os.path.join(common.HERE, "configs",
                                         f"{name}.json"))


def test_resnet50_forward_is_4_1_g_multiply_adds():
    mod = common.load_module("configs", "resnet50")
    cfg = _cfg("resnet50")
    shapes = mod.conv_shapes(cfg)
    # 1 stem + 16 blocks x 3 + 4 projections + the classifier
    assert len(shapes) == 1 + 16 * 3 + 4 + 1
    assert shapes[0] == (112, 112, 7, 3, 64)
    assert shapes[-1] == (1, 1, 1, 2048, 1000)
    by_hand_stem = 112 * 112 * 49 * 3 * 64
    assert by_hand_stem == 118_013_952
    macs = mod.forward_macs(cfg)
    assert 4.05e9 < macs < 4.15e9          # the "4.1 GFLOPs" of the paper
    assert mod.flops_per_sample(cfg) == 6.0 * macs
    assert 24.3e9 < mod.flops_per_sample(cfg) < 24.9e9


def test_resnet50_first_stage_by_hand():
    mod = common.load_module("configs", "resnet50")
    shapes = mod.conv_shapes(_cfg("resnet50"))
    # conv2_1: 1x1 64->64, 3x3 64->64, 1x1 64->256, projection 64->256 at 56x56
    assert shapes[1:5] == [(56, 56, 1, 64, 64), (56, 56, 3, 64, 64),
                           (56, 56, 1, 64, 256), (56, 56, 1, 64, 256)]
    # conv3_1 carries the stride on its 3x3 (v1.5): 1x1 at 56, 3x3 at 28
    assert shapes[11:13] == [(56, 56, 1, 256, 128), (28, 28, 3, 128, 128)]


def test_phi3_flops_per_token_by_hand():
    mod = common.load_module("configs", "phi3_mini_d4")
    cfg = _cfg("phi3_mini_d4")
    per_layer = 4 * 3072 * 3072 + 3 * 3072 * 8192
    assert per_layer == 113_246_208
    assert mod.matmul_params(cfg) == 4 * per_layer + 3072 * 32064
    at_2k = mod.flops_per_sample(cfg, {"seq_len": 2048})
    attention = 3 * 4 * (2 * 2 * 3072 * 2048 / 2)
    assert at_2k == 6 * mod.matmul_params(cfg) + attention
    assert 3.45e9 < at_2k < 3.47e9
    # attention's share: about 4% at 2048 positions, about 1% at 512
    assert 0.04 < attention / at_2k < 0.05
    at_512 = mod.flops_per_sample(cfg, {"seq_len": 512})
    assert 0.01 < (at_512 - 6 * mod.matmul_params(cfg)) / at_512 < 0.012
    # the head's share of matmul FLOPs at this depth (18%; 2.6% at 32 layers)
    assert 0.17 < 3072 * 32064 / mod.matmul_params(cfg) < 0.19


def test_flash_forward_cost_by_hand():
    mod = common.load_module("configs", "phi3_mini_d4")
    cost = mod.KERNELS["flash_fwd"](_cfg("phi3_mini_d4"),
                                    {"seq_len": 2048}, 4)
    bh = 4 * 32
    assert cost["flops"] == bh * 2 * 2048 * 2048 * 96
    assert cost["bytes"] == bh * (4 * 2048 * 96 * 2 + 2048 * 4)
    peaks = common.peaks_for("TPU v5 lite")
    # compute-bound on this chip, by a factor of two
    assert (cost["flops"] / peaks["bf16_flops_per_s"]
            > 2 * cost["bytes"] / peaks["hbm_bytes_per_s"])


def test_peaks_table_and_unknown_device():
    peaks = common.peaks_for("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert peaks["hbm_bytes"] == 16e9 and peaks["source"]
    with pytest.raises(KeyError):
        common.peaks_for("TPU v99")
    with pytest.raises(KeyError):
        common.peaks_for("cpu")


@pytest.mark.parametrize("config,traffic", [
    ("resnet50", {}), ("phi3_mini_d4", {"seq_len": 16})])
def test_seeded_inputs_repeat_and_differ(config, traffic):
    mod = common.load_module("configs", config)
    cfg = _cfg(config)
    if config == "resnet50":
        cfg["architecture"]["image_size"] = 8

    def draw(seed):
        rng = common.seeded_rng(seed, "records")
        return [np.asarray(memoryview(r)) if isinstance(r, bytes)
                else np.asarray(r)
                for r in mod.train_records(cfg, traffic, rng, 6)]

    a, b, c = draw(5), draw(5), draw(6)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not all(np.array_equal(x, y) for x, y in zip(a, c))
    assert len(a) == 6


def test_seeded_streams_are_independent():
    a = common.seeded_rng(1, "records").integers(0, 1 << 30, 4)
    b = common.seeded_rng(1, "weights").integers(0, 1 << 30, 4)
    assert not np.array_equal(a, b)


def test_prepare_writes_once_per_seed(tmp_path):
    """DIRECT shards: the same seed finds them again, another replaces them."""
    kind = common.load_module("kinds", "fed_train")
    cell = common.resolve_cell("resnet50_train_tfrecord")
    cell["config"]["architecture"]["image_size"] = 8
    cell["traffic"].update({"records": 16, "shards": 2})
    opts = {"seed": 3, "work_dir": str(tmp_path)}
    first = kind.prepare(cell, opts)
    assert first["written"] and len(os.listdir(first["path"])) == 3
    stat = os.stat(os.path.join(first["path"], "part-00000.tfrecord"))
    again = kind.prepare(cell, opts)
    assert not again["written"] and again["path"] == first["path"]
    assert os.stat(os.path.join(first["path"],
                                "part-00000.tfrecord")).st_mtime_ns == stat.st_mtime_ns
    other = kind.prepare(cell, {**opts, "seed": 4})
    assert other["written"] and other["path"] == first["path"]


def test_counter_delta_keeps_only_what_moved():
    before = {"counters": {"feed.batches": 5, "x": 1}}
    after = {"counters": {"feed.batches": 9, "x": 1, "new": 2}}
    assert common.counter_delta(before, after) == {"feed.batches": 4, "new": 2}


def test_step_interval_is_a_median_that_head_start_and_stalls_do_not_move():
    """Three batches prefetched before the window (dispatched at once) and
    one stall: the rate moves, the median interval does not."""
    reader = common.load_module("layer_metrics", "dp4_step_interval_ms")
    steady = [0.01, 0.02, 0.03] + [0.03 + 0.22 * k for k in range(1, 40)]
    stalled = [t + (0.1 if t > 4.0 else 0.0) for t in steady]
    assert reader.read({"facts": {"dispatched_s": steady}}) == \
        pytest.approx(220.0)
    assert reader.read({"facts": {"dispatched_s": stalled}}) == \
        pytest.approx(220.0)
    assert reader.read({"facts": {"dispatched_s": [0.1, 0.3]}}) is None
    assert reader.read({"facts": {}}) is None
