"""The nine per-layer metrics that read the program's ``telemetry.stage``
counters (ISSUE 23): each reader on a hand-made ``run``, its value worked
out by hand; nothing to read (``None``) where the program has no such stage,
as the parent commit has not.  No cluster, no backend."""

from __future__ import annotations

import pytest

from benchmark import common

# the untraced window of a traced run: 40 batches produced in it
COUNTERS = {
    "batch.put.calls": 40,
    "ingest.read.us": 1_200_000,        # 30 thread-ms a batch
    "ingest.decode.us": 6_400_000,      # 160 thread-ms a batch
    "feed.collect.us": 7_000_000,       # 175 ms
    "batch.convert.us": 1_600_000,      # 40 ms
    "batch.put.us": 200_000,            # 5 ms
    "batch.queue_full.us": 20_000,      # 0.5 ms
    "batch.h2d_bytes": 40 * (1024 * 150_528 + 1024 * 4),
    "feed.starved_polls": 0,
}

CASES = [
    ("dp4_feed_read_ms", 30.0),
    ("dp4_feed_decode_ms", 160.0),
    ("dp4_feed_collect_ms", 175.0),
    ("dp4_feed_convert_ms", 40.0),
    ("dp4_feed_put_ms", 5.0),
    ("dp4_feed_backpressure_ms", 0.5),
    ("dp4_feed_h2d_mb", 154.144768),
    ("feed_produce_ms", 175.0 + 40.0 + 5.0),
    ("feed_backpressure_ms", 0.5),
]


def _run(counters: dict) -> dict:
    return {"counters": counters, "facts": {"steps": 40}, "trace": None,
            "spans": {"seconds": {}, "counts": {}}, "peaks": None}


@pytest.mark.parametrize("name,expected", CASES)
def test_reader_divides_the_stage_counter_by_batches_produced(name, expected):
    reader = common.load_module("layer_metrics", name)
    assert reader.read(_run(dict(COUNTERS))) == pytest.approx(expected)
    # a stage that did not move in the window is absent from the delta: 0
    only_batches = {"batch.put.calls": 40}
    assert reader.read(_run(only_batches)) == 0.0
    # the parent commit has no such counter, and a window may produce no
    # batch: nothing to read, the metric is left out, nothing raises
    assert reader.read(_run({})) is None
    assert reader.read(_run({**COUNTERS, "batch.put.calls": 0})) is None


@pytest.mark.parametrize("name,_expected", CASES)
def test_reader_matches_its_manifest_entry(name, _expected):
    manifest = common.load_manifest()
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    reader = common.load_module("layer_metrics", name)
    assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
        entry["layer"], entry["unit"], entry["moves"])
    assert entry["source"] == "program_counter"
    cell = {"dp4": "resnet50_train_tfrecord_dp4"}.get(
        name.split("_")[0], "resnet50_train_tfrecord")
    assert entry["workloads"] == [cell]
    # the cell reports the end-to-end metric this one moves
    (moved,) = [m for m in manifest["end_to_end"] if m["name"] == entry["moves"]]
    assert cell in moved["workloads"]
