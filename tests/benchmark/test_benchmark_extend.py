"""Driven by data: a later PR adds a configuration, a traffic mix and a
per-layer metric as NEW files plus entries in BENCHMARK.json, and edits no
file that is there.  Shown on a throw-away copy; and the result line's keys
as the contract has them.  No cluster, no backend."""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from benchmark import common

ROOT = common.ROOT


def _digest_tree(base: str) -> dict:
    out = {}
    for folder, _dirs, files in os.walk(base):
        if "__pycache__" in folder:
            continue
        for f in files:
            path = os.path.join(folder, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, base)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture()
def copy(tmp_path):
    shutil.copy(common.MANIFEST, tmp_path / "BENCHMARK.json")
    shutil.copytree(common.HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def _load_run_module():
    """benchmark/run.py as a module (it is a script: loaded by path)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "benchmark_run_under_test", os.path.join(common.HERE, "run.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_a_new_config_mix_and_metric_are_files_and_entries_only(copy):
    before = _digest_tree(copy / "benchmark")
    bench = copy / "benchmark"
    # a configuration: its file of sizes and its module beside it
    (bench / "configs" / "toy.json").write_text(json.dumps({
        "name": "toy", "source": "a paper", "reduced": [], "width": 3}))
    (bench / "configs" / "toy.py").write_text(
        "SAMPLE_UNIT = 'img'\n"
        "def flops_per_sample(cfg, traffic):\n"
        "    return 2.0 * cfg['width'] * traffic['rows_per_chip']\n")
    # a traffic mix: a data file read by the general generator of its kind
    (bench / "traffic" / "toy_rows.json").write_text(json.dumps({
        "kind": "fed_train", "input_mode": "streaming", "rows_per_chip": 5,
        "records": 10, "partitions": 1, "epochs": 1, "warm_steps": 1,
        "trace_seconds": 1}))
    # a per-layer metric: a reader of its own
    (bench / "layer_metrics" / "toy_dispatch_ms.py").write_text(
        "LAYER = 'step, model'\nUNIT = 'ms'\nMOVES = 'train_img_rate'\n"
        "def read(run):\n"
        "    spans = run['spans']\n"
        "    if 'step_dispatch' not in spans['seconds']:\n"
        "        return None\n"
        "    return (1e3 * spans['seconds']['step_dispatch']\n"
        "            / spans['counts']['step_dispatch'])\n")
    manifest = json.loads((copy / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "toy", "source": "a paper",
        "file": "benchmark/configs/toy.json", "reduced": [], "why": "test"})
    manifest["workloads"].append({
        "name": "toy_cell", "config": "toy", "traffic": "toy_rows",
        "chips": 1, "why": "test"})
    for m in manifest["end_to_end"]:
        if m["name"] == "train_img_rate":
            m["workloads"].append("toy_cell")
    manifest["per_layer"].append({
        "name": "toy_dispatch_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "step, model",
        "moves": "train_img_rate", "workloads": ["toy_cell"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(manifest))

    # no file that was there has changed
    after = _digest_tree(bench)
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {
        "configs/toy.json", "configs/toy.py", "traffic/toy_rows.json",
        "layer_metrics/toy_dispatch_ms.py"}

    # the harness finds each by name
    cell = common.resolve_cell("toy_cell", str(copy / "BENCHMARK.json"))
    assert cell["base"] == str(bench)
    assert cell["config"]["width"] == 3
    assert cell["traffic"]["rows_per_chip"] == 5
    assert {m["name"] for m in cell["end_to_end"]} == {"train_img_rate",
                                                       "setup_s"}
    layer_names = {m["name"] for m in cell["per_layer"]}
    assert "toy_dispatch_ms" in layer_names and "claim_s" in layer_names
    assert "allreduce_ms" not in layer_names and "lm_mfu" not in layer_names

    kind = common.load_module("kinds", cell["traffic"]["kind"], cell["base"])
    node_result = _fake_node_result()
    facts = kind.facts(cell, node_result, {})
    assert facts["flops_per_sample"] == 30.0 and facts["sample_unit"] == "img"
    assert kind.end_to_end(cell, facts) == {
        "train_img_rate": 100 * 8 / 10.0 / 1}
    run_py = _load_run_module()
    facts.update({"claim_s": 9.0, "setup_s": 30.0})
    result = run_py.assemble_result(cell, kind, node_result, facts, True,
                                    _fake_reduced(), {"bf16_flops_per_s": 1e12})
    assert result["metrics"]["toy_dispatch_ms"] == {"value": 2.0, "unit": "ms"}
    assert result["metrics"]["claim_s"]["value"] == 9.0
    # no Pallas call and no collective in the fake trace: those readers
    # return nothing and the harness leaves the metrics out
    assert "flash_fwd_ms" not in result["metrics"]


def _fake_node_result() -> dict:
    return {
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        "check": {"ok": True}, "failed": 0, "attempted": 100,
        "memory_peak_bytes": 9_600_000_000, "chips": 1,
        "samples_per_step": 8, "rows_per_step": 8,
        "seconds": {"first_step_s": 6.5},
        "measured": {"epoch_start": 1000.0, "window_s": 10.0, "steps": 100,
                     "out_of_data": False, "compilations": 0,
                     "span_seconds": {"feed_wait": 0.5, "step_dispatch": 0.2},
                     "span_counts": {"feed_wait": 100, "step_dispatch": 100},
                     "counters": {"feed.starved_polls": 50}},
        "traced": {"epoch_start": 990.0, "steps": 30, "compilations": 0},
    }


def _fake_reduced() -> dict:
    return {"busy_s": 2.7, "window_s": 3.0, "per_device_busy_s": [2.7],
            "devices": 1, "program_runs": 30,
            "device_ops": [[f"op{i}", 0.1] for i in range(12)],
            "idle_gaps": [["feed_wait", 0.2], ["unattributed:unknown", 0.1]],
            "collectives": None, "pallas_s": 0.0, "pallas_calls": 0}


def test_result_line_keys_are_the_contract_s():
    run_py = _load_run_module()
    cell = common.resolve_cell("resnet50_train_tfrecord")
    kind = common.load_module("kinds", "fed_train")
    node_result = _fake_node_result()
    facts = kind.facts(cell, node_result, {})
    facts.update({"claim_s": 9.0, "setup_s": 30.0})
    peaks = common.peaks_for("TPU v5 lite")

    plain = run_py.assemble_result(cell, kind, node_result, facts, False,
                                   None, peaks)
    assert set(plain) == {"correct", "attempted", "failed", "metrics",
                          "device"}
    assert set(plain["metrics"]) == {"train_img_rate", "setup_s"}
    assert set(plain["device"]) == {"platform", "kind", "count",
                                    "memory_peak_bytes"}
    assert plain["correct"] is True and plain["attempted"] == 100
    for value in plain["metrics"].values():
        assert set(value) == {"value", "unit"} and value["value"] > 0

    traced = run_py.assemble_result(cell, kind, node_result, facts, True,
                                    _fake_reduced(), peaks)
    assert set(traced) == {"correct", "attempted", "failed", "metrics",
                           "device", "breakdown"}
    assert set(traced["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes", "busy_s", "window_s"}
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(traced["breakdown"]["device_ops"]) == 10
    names = {m["name"] for m in cell["per_layer"]}
    assert set(traced["metrics"]) <= names
    assert traced["metrics"]["step_device_ms"]["value"] == pytest.approx(90.0)
    assert traced["metrics"]["feed_wait_share"]["value"] == pytest.approx(5.0)
    assert traced["metrics"]["feed_starved_polls"]["value"] == 0.5
    # 24.6 GFLOP x 80 img/s over 197 TFLOP/s
    assert traced["metrics"]["mfu"]["value"] == pytest.approx(
        100 * facts["flops_per_sample"] * 80.0 / 197e12)
    json.dumps(traced)


def test_a_compilation_in_the_window_or_a_failed_step_is_not_correct():
    run_py = _load_run_module()
    cell = common.resolve_cell("phi3_mini_d4_train_2k")
    kind = common.load_module("kinds", "fed_train")
    for spoil in ("compilations", "failed", "check"):
        node_result = _fake_node_result()
        if spoil == "compilations":
            node_result["measured"]["compilations"] = 1
        elif spoil == "failed":
            node_result["failed"] = 2
        else:
            node_result["check"] = {"ok": False}
        facts = kind.facts(cell, node_result, {})
        facts["setup_s"] = 30.0
        result = run_py.assemble_result(cell, kind, node_result, facts, False,
                                        None, None)
        assert result["correct"] is False
        assert set(result["metrics"]) == {"train_tok_rate", "setup_s"}


def test_reader_that_disagrees_with_the_manifest_is_refused(copy):
    manifest = json.loads((copy / "BENCHMARK.json").read_text())
    for m in manifest["per_layer"]:
        if m["name"] == "claim_s":
            m["unit"] = "ms"
    (copy / "BENCHMARK.json").write_text(json.dumps(manifest))
    cell = common.resolve_cell("resnet50_train_tfrecord",
                               str(copy / "BENCHMARK.json"))
    run_py = _load_run_module()
    with pytest.raises(SystemExit):
        run_py.read_layer_metrics(cell, {"facts": {"claim_s": 1.0}})


def test_driver_modules_do_not_import_jax():
    """The driver never touches the backend: importing the harness's
    driver-side modules in a fresh interpreter leaves jax unimported."""
    code = ("import sys; sys.path.insert(0, %r);"
            "from benchmark import common;"
            "common.load_module('kinds', 'fed_train');"
            "common.resolve_cell('resnet50_train_tfrecord_dp4');"
            "import importlib.util as u;"
            "s = u.spec_from_file_location('r', %r);"
            "m = u.module_from_spec(s); s.loader.exec_module(m);"
            "print('jax' in sys.modules)") % (
        ROOT, os.path.join(common.HERE, "run.py"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60, check=True)
    assert out.stdout.strip() == "False"


def test_run_py_off_the_chip_exits_nonzero_with_no_result_line(tmp_path):
    """The real command on a box without a TPU: the node is pinned to
    JAX_PLATFORMS=tpu, cannot initialise it, and no number comes out."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(common.HERE, "run.py"), "--workload",
         "phi3_mini_d4_train_512", "--seed", "0", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert out.returncode != 0
    for line in out.stdout.splitlines():
        assert not line.startswith("{"), line
    assert "FAILED" in out.stderr


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit):
        common.resolve_cell("no_such_cell")
    assert isinstance(common.load_module("kinds", "fed_train"),
                      types.ModuleType)
    with pytest.raises(FileNotFoundError):
        common.load_module("kinds", "no_such_kind")
