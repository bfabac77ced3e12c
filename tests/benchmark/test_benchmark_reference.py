"""Each configuration's builder (the program's model) against its plain
float32 reference, at a tiny width on the CPU.  In float32 the two must
agree to rounding; in the bf16 the cells run, within the check's limits."""

from __future__ import annotations

import copy
import os

import pytest

from benchmark import common


def _rehearsal(name: str) -> dict:
    cfg = common.read_json(os.path.join(common.HERE, "configs",
                                        f"{name}.json"))
    for key, value in cfg["rehearsal"].items():
        if isinstance(value, dict) and isinstance(cfg.get(key), dict):
            cfg[key] = {**cfg[key], **value}
        else:
            cfg[key] = value
    return cfg


@pytest.fixture(scope="module")
def tiny_resnet():
    cfg = _rehearsal("resnet50")
    cfg["architecture"].update({"image_size": 32, "stage_sizes": [1, 1, 1, 1],
                                "width": 8})
    cfg["system"].update({"model": "resnet50", "width": 8})
    cfg["reference_images"] = 16
    return cfg


def test_resnet_reference_matches_the_model_in_float32(tiny_resnet,
                                                      monkeypatch):
    mod = common.load_module("configs", "resnet50")
    cfg = copy.deepcopy(tiny_resnet)
    cfg["system"]["bf16"] = False
    # the program's builder has fixed stage sizes; give it the tiny ones
    from tensorflowonspark_tpu.models import registry, resnet

    monkeypatch.setitem(
        registry._REGISTRY, "resnet50",
        lambda c: resnet.ResNet(stage_sizes=(1, 1, 1, 1), width=c["width"],
                                num_classes=c["num_classes"],
                                **resnet._dtypes(c)))
    out = mod.check_train(cfg, {}, seed=3)
    assert out["errors"]["loss"] < 1e-4
    assert out["errors"]["logits_max"] < 1e-3
    assert out["errors"]["logits_l2"] < 1e-3
    assert out["errors"]["grad_norm"] < 1e-3


def test_resnet_check_catches_a_wrong_epsilon(tiny_resnet, monkeypatch):
    mod = common.load_module("configs", "resnet50")
    cfg = copy.deepcopy(tiny_resnet)
    cfg["system"]["bf16"] = False
    from tensorflowonspark_tpu.models import registry, resnet

    monkeypatch.setitem(
        registry._REGISTRY, "resnet50",
        lambda c: resnet.ResNet(stage_sizes=(1, 1, 1, 1), width=c["width"],
                                num_classes=c["num_classes"],
                                **resnet._dtypes(c)))
    cfg["architecture"]["batch_norm"]["epsilon"] = 0.1
    out = mod.check_train(cfg, {}, seed=3)
    assert not out["ok"]


def test_phi3_reference_matches_the_model():
    mod = common.load_module("configs", "phi3_mini_d4")
    cfg = _rehearsal("phi3_mini_d4")
    out = mod.check_train(cfg, {"seq_len": 64}, seed=3)
    # bf16 system against the float32 reference: inside the check's limits
    assert out["ok"], out
    assert out["errors"]["loss"] < 2e-3


def test_phi3_system_config_maps_published_keys():
    mod = common.load_module("configs", "phi3_mini_d4")
    cfg = common.read_json(os.path.join(common.HERE, "configs",
                                        "phi3_mini_d4.json"))
    system = mod.system_config(cfg)
    assert (system["d_model"], system["n_heads"], system["d_head"],
            system["d_ff"], system["n_layers"], system["vocab_size"]) == (
        3072, 32, 96, 8192, 4, 32064)
    with pytest.raises(ValueError):
        mod.system_config({**cfg, "num_key_value_heads": 8})


def test_phi3_check_catches_a_wrong_rope_base():
    mod = common.load_module("configs", "phi3_mini_d4")
    cfg = _rehearsal("phi3_mini_d4")
    real = mod.reference_forward

    def wrong(cfg_, params, ids):
        return real({**cfg_, "rope_theta": 100.0}, params, ids)

    mod.reference_forward = wrong
    assert not mod.check_train(cfg, {"seq_len": 64}, seed=3)["ok"]
