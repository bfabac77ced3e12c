"""Tier-1 guard of the main path: what every cell of ``BENCHMARK.json``
measures, run end to end on the CPU at the rehearsal sizes.

``benchmark/run.py --rehearse-cpu`` takes the cell's whole route
(``tos.run`` -> node -> ``ctx.get_data_feed`` -> ``dp.make_batch_iterator``
-> the step, the reference check, the result line) with tiny sizes on CPU
devices.  A change that breaks that route for any reason but the chip's own
fails here in under a minute, before a chip-minute is spent.  Nothing is
read off the numbers: the run says itself that it is not a chip run.

The cases between them load every module a cell's processes import:
DIRECT + ``IngestFeed`` + ``make_bn_train_step`` (ResNet-50), STREAMING +
``make_train_step`` + the dropless ``ep.py`` + the attention path (OLMoE),
the block-diffusion loss over a chip's share of the experts with
grouped-query heads (SDAR), learned sparse attention with its indexer's
loss under remat (Keye), latent attention over a leading dense layer and
bias-corrected sigmoid routing with a buffer the optimizer leaves alone
(Kanana-2), a mixer a layer: the Mamba-2 scan, attention without
rotation and relu² experts in a latent (Nemotron-3), and four residual
streams under hyper-connections with a query latent, YaRN and a
multi-token-prediction module in the loss (Xing4.0), and window and global
attention mixed layer by layer over a group of 7 heads, a router on the
layer's input and ReGLU experts under remat (SmallThinker), and a ``Block``
whose attention slot is Kimi Delta Attention's chunked gated delta rule in
three layers and latent attention without rotation in the fourth, under
remat (Kimi-Linear): a crash on that cell's first step shows here.

The ResNet-50 case also reads the rehearsal's own ``logs/run_report.json``
before the clean-up (ISSUE 35): the ``lifecycle`` block's stages in order
and the eight readers that split ``setup_s``, through the files a chip run
reads them through.  On the CPU the environment pins the node's device
summary (``tpu_info.env_device_summary``), so the node neither imports jax
nor claims a backend at its start: those two stages do not run, their two
readers find nothing, and ``tests/test_telemetry_lifecycle.py`` holds them on
the path that does.  No further run of ``run.py``.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import sys

import pytest

from benchmark import common

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SPLIT_READERS = ("start_spawn_s", "start_register_s", "start_import_jax_s",
                 "start_chip_claim_s", "xla_trace_lower_s", "xla_backend_s",
                 "xla_cache_load_s", "xla_cache_misses")


def _read_setup_split(cell: dict) -> dict:
    """The rehearsal's own run report, and what the eight readers make of it
    (a run that began at the epoch's start: any report is this run's)."""
    run = {"cell": cell, "facts": {"window_epoch_start": 0.0}}
    with open(os.path.join(common.WORK_DIR, "runs", cell["workload"], "logs",
                           "run_report.json")) as f:
        report = json.load(f)
    return {"lifecycle": report["lifecycle"], "wall_secs": report["wall_secs"],
            "values": {name: common.load_module("layer_metrics", name).read(run)
                       for name in SPLIT_READERS}}


def _missing(path: str) -> list[str]:
    """``path`` and those of its ancestors that do not exist, innermost first."""
    out = []
    while not os.path.exists(path):
        out.append(path)
        path = os.path.dirname(path)
    return out


@pytest.mark.parametrize("workload", ["resnet50_train_tfrecord",
                                      "olmoe_1b_7b_d1_train_4k",
                                      "sdar_30b_a3b_d4_ep8_train_bd4k",
                                      "keye_vl2_30b_a3b_d4_ep8_train_16k",
                                      "kanana2_30b_a3b_d5_ep8_train_8k",
                                      "nemotron3_super_d11_tp8_ep64_train_8k",
                                      "xing4_29b_a4b_d5_tp8_ep8_train_4k",
                                      "smallthinker_21b_a3b_d8_ep8_train_16k",
                                      "kimi_linear_48b_a3b_d5_ep32_train_16k"])
def test_cell_rehearses_on_cpu(workload):
    cell = common.resolve_cell(workload)
    # run.py's work directory is not configurable and a DIRECT cell keeps one
    # seed's shards per (mix, configuration) there: what this run creates
    # goes again, so a checkout after tier-1 looks like one before it
    made = [_missing(os.path.join(common.WORK_DIR, "runs", workload)),
            _missing(os.path.join(
                common.WORK_DIR, "records",
                f"{cell['traffic_name']}.{cell['config_name']}"))]
    try:
        # a session of its own: at the timeout the node goes with the driver
        proc = subprocess.Popen(
            [sys.executable, os.path.join("benchmark", "run.py"),
             "--workload", workload, "--seed", "1", "--seconds", "2",
             "--trace", "0", "--rehearse-cpu"],
            cwd=_REPO, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, start_new_session=True)
        try:
            out, err = proc.communicate(timeout=180)
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, signal.SIGKILL)
            out, err = proc.communicate()
            pytest.fail(f"no result within 180 s\n{out[-3000:]}\n{err[-3000:]}")
        split = (_read_setup_split(cell)
                 if workload == "resnet50_train_tfrecord"
                 and proc.returncode == 0 else None)
    finally:
        for chain in made:
            if chain:
                shutil.rmtree(chain[0], ignore_errors=True)
            for parent in chain[1:]:
                try:
                    os.rmdir(parent)     # only while nothing else moved in
                except OSError:
                    break
    tail = out[-3000:] + "\n--- stderr ---\n" + err[-3000:]
    assert proc.returncode == 0, tail
    lines = out.strip().splitlines()
    assert "NOT A CHIP RUN" in lines[-1], tail
    result = json.loads(lines[-2])
    assert result["correct"] is True, tail
    assert result["attempted"] > 0 and result["failed"] == 0, tail
    assert result["device"]["platform"] == "cpu", tail
    if split is None:
        return
    stages = {key: [st["stage"] for st in proc_block["stages"]]
              for key, proc_block in split["lifecycle"].items()}
    assert stages == {
        "driver": ["cluster.launch", "cluster.await_registrations",
                   "shutdown.eof", "shutdown.join", "shutdown.gather"],
        "node0": ["node.spawn", "node.register", "node.map_fun",
                  "node.drain"]}, stages
    node = split["lifecycle"]["node0"]
    assert sum(st["secs"] for st in node["stages"]) <= split["wall_secs"]
    assert [p["fun_name"] for p in node["programs"]
            if p["fun_name"] == "jit(step)"], node["programs"]
    values = split["values"]
    # pinned by the CPU environment: no import and no claim at the start
    assert values.pop("start_import_jax_s") is None
    assert values.pop("start_chip_claim_s") is None
    assert all(isinstance(v, (int, float)) for v in values.values()), values
    assert values["start_spawn_s"] > 0 and values["start_register_s"] > 0
    assert values["xla_backend_s"] > 0 and values["xla_trace_lower_s"] > 0
    assert 0 <= values["xla_cache_load_s"] <= values["xla_backend_s"]
    assert values["xla_cache_misses"] >= 0
    # what the listener saw is inside what the node timed around it
    node_seconds = json.loads(next(
        ln for ln in lines if ln.startswith("bench: facts: "))[
            len("bench: facts: "):])["node_seconds"]
    assert (values["xla_trace_lower_s"] + values["xla_backend_s"]
            <= sum(node_seconds.values())), (values, node_seconds)
