"""The per-layer metric ``dp4_feed_convert_thread_ms`` (ISSUE 24), held to
the convention of the nine stage readers of ISSUE 23
(``test_benchmark_feed_stages.py``): the reader on a hand-made ``run``, its
value worked out by hand; nothing to read (``None``) from a program without
counters or a window without a batch.  No cluster, no backend."""

from __future__ import annotations

import pytest

from benchmark import common

NAME = "dp4_feed_convert_thread_ms"
CELL = "resnet50_train_tfrecord_dp4"

# the untraced window of a traced run: 40 batches produced in it, each
# converted as four device shards of 75 thread-ms
COUNTERS = {
    "batch.put.calls": 40,
    "batch.convert.us": 3_200_000,           # 80 ms of wall a batch
    "batch.convert_slice.us": 12_000_000,    # 300 thread-ms a batch
    "batch.convert_slice.calls": 160,
}


def _run(counters: dict) -> dict:
    return {"counters": counters, "facts": {"steps": 40}, "trace": None,
            "spans": {"seconds": {}, "counts": {}}, "peaks": None}


def test_reader_divides_slice_thread_time_by_batches_produced():
    reader = common.load_module("layer_metrics", NAME)
    assert reader.read(_run(dict(COUNTERS))) == pytest.approx(300.0)
    # one device shard a batch: no slice stage ran, the delta lacks it: 0
    assert reader.read(_run({"batch.put.calls": 40})) == 0.0


@pytest.mark.parametrize("counters", [
    {},                                     # a program without the counters
    {**COUNTERS, "batch.put.calls": 0},     # a window that produced no batch
])
def test_reader_finds_nothing_to_read(counters):
    reader = common.load_module("layer_metrics", NAME)
    assert reader.read(_run(counters)) is None


def test_reader_matches_its_manifest_entry():
    manifest = common.load_manifest()
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    reader = common.load_module("layer_metrics", NAME)
    assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
        entry["layer"], entry["unit"], entry["moves"])
    assert entry["source"] == "program_counter"
    assert entry["workloads"] == [CELL]
    # the cell reports the end-to-end metric this one moves
    (moved,) = [m for m in manifest["end_to_end"] if m["name"] == entry["moves"]]
    assert CELL in moved["workloads"]
    # and it is the last entry: appended, nothing before it moved
    assert manifest["per_layer"][-1] is entry
