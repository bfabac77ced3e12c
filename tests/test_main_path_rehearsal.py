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
grouped-query heads (SDAR), and learned sparse attention with its indexer's
loss under remat (Keye): a crash on that cell's first step shows here.
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
                                      "keye_vl2_30b_a3b_d4_ep8_train_16k"])
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
