"""BENCHMARK.json against the contract it was written to, and against the
files it names.  No jax, no cluster."""

from __future__ import annotations

import json
import os
import re

import pytest

from benchmark import common

ROOT = common.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return common.load_manifest()


def _one_line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(common.MANIFEST) <= 64 * 1024
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in manifest["paths"])
    assert len(manifest["command"]) <= 32
    assert all(_one_line(w) for w in manifest["command"])
    # the check's budget with the full 24 cells (builder's contract)
    runs = 2 + 14 * 24
    assert (runs * (manifest["run_seconds"] + 60) + 24 * 2 * 90 + 1200
            <= 43200)


def test_command_names_only_files_under_paths(manifest):
    for word in manifest["command"][1:]:
        if os.path.exists(os.path.join(ROOT, word)):
            assert any(word.startswith(p + "/") for p in manifest["paths"])
        assert not word.startswith("/") and ".." not in word


def test_names_units_and_entry_keys(manifest):
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _one_line(c["source"])
        assert _one_line(c["why"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and _one_line(w["why"])
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and _one_line(m["layer"])
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for group in ("configs", "workloads"):
        names = [e["name"] for e in manifest[group]]
        assert len(names) == len(set(names))
    metric_names = [m["name"] for m in
                    manifest["end_to_end"] + manifest["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    assert 2 <= len(manifest["workloads"]) <= 24
    assert 1 <= len(manifest["end_to_end"]) <= 16
    assert 1 <= len(manifest["per_layer"]) <= 128


def test_four_chip_quota_and_unique_pairs(manifest):
    cells = manifest["workloads"]
    four = [w for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(pairs) == len(set(pairs))
    used = {w["config"] for w in cells}
    assert used == {c["name"] for c in manifest["configs"]}


def test_setup_metric_and_moves_targets(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    assert "workloads" not in e2e["setup_s"]
    cell_names = {w["name"] for w in manifest["workloads"]}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert set(m.get("workloads", cell_names)) <= cell_names
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e
        # a per-layer metric is reported only where the metric it moves is
        target = set(e2e[m["moves"]].get("workloads", cell_names))
        assert set(m.get("workloads", cell_names)) <= target


@pytest.mark.parametrize("workload", [
    w["name"] for w in common.load_manifest()["workloads"]])
def test_every_cell_resolves_to_its_files(workload, manifest):
    cell = common.resolve_cell(workload)
    assert cell["config"]["name"] == cell["config_name"]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config_name"]]
    assert cell["config"]["reduced"] == entry["reduced"]
    assert any(entry["file"].startswith(p + "/") for p in manifest["paths"])
    kind = common.load_module("kinds", cell["traffic"]["kind"])
    for name in ("cluster_options", "prepare", "drive", "node", "facts",
                 "end_to_end"):
        assert callable(getattr(kind, name))
    config_mod = common.load_module("configs", cell["config_name"])
    for name in ("train_records", "feed_options", "rows_to_arrays",
                 "build_train", "check_train", "flops_per_sample"):
        assert callable(getattr(config_mod, name))
    # every cell reports setup_s, another end-to-end metric and a layer metric
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell["per_layer"]


@pytest.mark.parametrize("workload", [
    w["name"] for w in common.load_manifest()["workloads"]])
def test_the_kind_reports_every_end_to_end_metric_of_the_cell(workload):
    """What the cell lists besides ``setup_s`` is a name its kind gives the
    rate: ``train_<unit>_rate``, or ``..._dp<chips>`` on several chips."""
    cell = common.resolve_cell(workload)
    kind = common.load_module("kinds", cell["traffic"]["kind"])
    unit = common.load_module("configs", cell["config_name"]).SAMPLE_UNIT
    given = kind.end_to_end(cell, {"sample_unit": unit, "rate_per_chip": 7.0,
                                   "chips": cell["chips"]})
    assert set(given.values()) == {7.0}
    assert f"train_{unit}_rate" in given
    assert (f"train_{unit}_rate_dp{cell['chips']}" in given) == (
        cell["chips"] > 1)
    listed = {m["name"] for m in cell["end_to_end"]} - {"setup_s"}
    assert listed and listed <= set(given)


def test_every_layer_metric_has_a_reader_that_agrees(manifest):
    for m in manifest["per_layer"]:
        reader = common.load_module("layer_metrics", m["name"])
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
            m["layer"], m["unit"], m["moves"])
        assert callable(reader.read)
    # metrics of one layer give the same layer, letter for letter: the set
    # of layers is small and each is PERF.md's name
    perf = open(os.path.join(ROOT, "PERF.md")).read()
    for layer in {m["layer"] for m in manifest["per_layer"]}:
        assert layer in perf


def test_files_under_paths_have_plain_names(manifest):
    for base in manifest["paths"]:
        for folder, _dirs, files in os.walk(os.path.join(ROOT, base)):
            if "__pycache__" in folder:
                continue
            for f in files:
                if f.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(folder, f), ROOT)
                assert PATH.match(rel), rel


def test_phi3_file_keeps_published_widths():
    """Widths are never reduced: the file holds the public config's sizes,
    and only the keys in ``reduced`` differ from ``published``."""
    cfg = common.read_json(os.path.join(common.HERE, "configs",
                                        "phi3_mini_d4.json"))
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["vocab_size"]) == (3072, 8192, 32, 32, 32064)
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert set(cfg["published"]) == set(cfg["reduced"])
    assert cfg["published"]["num_hidden_layers"] == 32
    assert json.dumps(cfg["rope_theta"]) == "10000.0"
