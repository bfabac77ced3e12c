"""``telemetry.stage`` — the layer-boundary primitive of the hot path — and
the feed path it splits (ISSUE 23).

Units: counters, nesting in the span ring, the ``TOS_METRICS=0`` no-op, the
``TraceAnnotation`` only once jax is loaded, the epoch anchor through the
export.  One integration test: TFRecord shards -> ``IngestFeed`` ->
``make_batch_iterator(prefetch=2)`` on a 4-device CPU mesh, every stage of
the table counted against chunks and batches (never records), and the four
stages of the prefetch thread closing on that thread's wall time.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time
import types

import pytest

from tensorflowonspark_tpu import telemetry
from tensorflowonspark_tpu.telemetry import trace as ttrace
from tensorflowonspark_tpu.telemetry import trace_export


@pytest.fixture()
def fresh():
    """A registry and a tracer of this test's own; both restored after."""
    telemetry.reset(enabled=True)
    tracer = ttrace.reset(enabled=True)
    yield tracer
    telemetry.reset()
    ttrace.reset()


def _counters() -> dict:
    return telemetry.snapshot()["counters"]


def test_stage_adds_microseconds_and_calls(fresh):
    for _ in range(3):
        with telemetry.stage("t.work"):
            time.sleep(0.01)
    counters = _counters()
    assert counters["t.work.calls"] == 3
    # three sleeps of 10 ms: busy time is recorded where the work happens
    assert 30_000 <= counters["t.work.us"] < 300_000


def test_a_blocked_stage_ticks_its_time_out_in_slices(fresh):
    """A wait that began before a reader's window must not land in the
    window whole: the part already ticked is in the counter BEFORE exit."""
    with telemetry.stage("t.blocked") as blocked:
        time.sleep(0.02)
        blocked.tick()
        mid = _counters()
        assert mid["t.blocked.us"] >= 20_000 and not mid["t.blocked.calls"]
        time.sleep(0.01)
    after = _counters()
    assert after["t.blocked.calls"] == 1
    assert 10_000 <= after["t.blocked.us"] - mid["t.blocked.us"] < 20_000
    # the ring's span is still the whole stage, once
    (span,) = fresh.collect_final()["spans"]
    assert span["d"] >= 0.03
    ttrace.NULL_SPAN.tick()     # the disabled stage takes the same call


def test_stage_records_an_exception_and_lets_it_through(fresh):
    with pytest.raises(KeyError):
        with telemetry.stage("t.raises"):
            raise KeyError("x")
    assert _counters()["t.raises.calls"] == 1


def test_stages_nest_in_the_ring_under_one_loop_trace(fresh):
    with telemetry.stage("t.outer"):
        with telemetry.stage("t.inner"):
            pass
        with telemetry.stage("t.inner"):
            pass
    with telemetry.stage("t.next"):
        pass
    spans = fresh.collect_final()["spans"]
    by_name: dict[str, list] = {}
    for s in spans:
        by_name.setdefault(s["n"], []).append(s)
    outer, nxt = by_name["t.outer"][0], by_name["t.next"][0]
    assert outer["p"] is None and nxt["p"] is None
    assert [s["p"] for s in by_name["t.inner"]] == [outer["s"]] * 2
    # unsampled, and one trace id per process: "the loop"
    assert len({s["t"] for s in spans}) == 1 and len(spans) == 4
    # a child lies inside its parent on the monotonic clock
    for child in by_name["t.inner"]:
        assert outer["t0"] <= child["t0"]
        assert child["t0"] + child["d"] <= outer["t0"] + outer["d"] + 1e-6


def test_ring_is_left_alone_without_tos_trace(fresh):
    ttrace.reset(enabled=False)
    with telemetry.stage("t.quiet"):
        pass
    assert _counters()["t.quiet.calls"] == 1      # the counters are always on
    delta = ttrace.get_tracer().collect_final()
    assert not (delta or {}).get("spans")


def test_metrics_off_makes_the_whole_stage_the_shared_noop(fresh):
    telemetry.reset(enabled=False)
    st = telemetry.stage("t.off")
    assert st is ttrace.NULL_SPAN
    with st:
        pass
    assert not (fresh.collect_final() or {}).get("spans")
    assert _counters() == {}


class _FakeAnnotation:
    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


def test_trace_annotation_only_once_jax_is_loaded(fresh, monkeypatch):
    monkeypatch.setattr(ttrace, "_annotation_cls", None)
    monkeypatch.delitem(sys.modules, "jax", raising=False)
    _FakeAnnotation.log = []
    with telemetry.stage("t.nojax"):
        pass
    assert _FakeAnnotation.log == [] and ttrace._annotation_cls is None
    fake_jax = types.ModuleType("jax")
    fake_jax.profiler = types.SimpleNamespace(TraceAnnotation=_FakeAnnotation)
    monkeypatch.setitem(sys.modules, "jax", fake_jax)
    with telemetry.stage("t.jax"):
        _FakeAnnotation.log.append(("body", "t.jax"))
    assert _FakeAnnotation.log == [("enter", "t.jax"), ("body", "t.jax"),
                                   ("exit", "t.jax")]
    assert _counters()["t.jax.calls"] == 1


def test_importing_telemetry_and_using_a_stage_imports_no_jax():
    code = ("import sys\n"
            "from tensorflowonspark_tpu import telemetry\n"
            "with telemetry.stage('t.x'):\n"
            "    pass\n"
            "assert telemetry.snapshot()['counters']['t.x.calls'] == 1\n"
            "assert 'jax' not in sys.modules, 'telemetry imported jax'\n")
    env = {**os.environ, "TOS_TRACE": "1"}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, env=env)
    assert out.returncode == 0, out.stderr


def test_stream_carries_the_epoch_anchor_and_export_is_absolute(fresh):
    before_ns = time.time_ns()
    with telemetry.stage("t.anchored"):
        time.sleep(0.002)
    after_ns = time.time_ns()
    delta = fresh.collect_final()
    mono, epoch_ns, host = delta["anchor"]
    assert host and abs(epoch_ns - time.time_ns()) < 600e9
    assert fresh.flight_snapshot()["anchor"] == [mono, epoch_ns, host]
    stream = trace_export.build_stream("node0", delta["spans"], [], None,
                                       anchor=delta["anchor"])
    doc = trace_export.merge_streams({"node0": stream})
    assert trace_export.validate_chrome_trace(doc) == len(doc["traceEvents"])
    (ev,) = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    # absolute microseconds since the Unix epoch: the stage started between
    # the two wall-clock readings around it (1 ms of slack for the distance
    # between the anchor's two clock reads and float rounding)
    assert before_ns / 1e3 - 1e3 <= ev["ts"] <= after_ns / 1e3 + 1e3
    assert ev["dur"] >= 2000


def test_offset_is_used_only_for_a_stream_from_another_host():
    span = {"n": "x", "t": 1, "s": 2, "p": None, "t0": 50.0, "d": 0.001,
            "th": 1}
    driver = trace_export.build_stream(
        "driver", [dict(span, s=1, t0=1000.0)], [], 0.0,
        anchor=[1000.0, 2_000_000_000_000, "hostA"])
    # same host: the node's own anchor places it, whatever the estimate says
    same = trace_export.build_stream(
        "node0", [dict(span)], [], 123.0,
        anchor=[40.0, 2_000_010_000_000, "hostA"])
    # another host with a wall clock an hour off: offset + the driver's anchor
    other = trace_export.build_stream(
        "node1", [dict(span, s=3)], [], 960.0,
        anchor=[40.0, 5_600_000_000_000, "hostB"])
    doc = trace_export.merge_streams(
        {"driver": driver, "node0": same, "node1": other})
    ts = {e["args"]["span_id"]: e["ts"] for e in doc["traceEvents"]
          if e["ph"] == "X"}
    assert ts["1"] == pytest.approx(2_000_000_000.0)
    assert ts["2"] == pytest.approx(2_000_010_000.0 + 10e6)
    assert ts["3"] == pytest.approx(2_000_000_000.0 + 10e6)


# -- integration: the feed path, split ---------------------------------------

SHARDS, RECS_PER_SHARD, CHUNK, BATCH, PIXELS = 8, 32, 8, 16, 48
FEED_STAGES = ("ingest.read", "ingest.decode", "ingest.put_wait",
               "feed.collect", "feed.wait", "batch.convert", "batch.put",
               "batch.queue_full", "batch.queue_empty")


def _write_example_shards(root) -> list[str]:
    import numpy as np

    from tensorflowonspark_tpu import dfutil, tfrecord

    rng = np.random.default_rng(23)
    paths = []
    for si in range(SHARDS):
        path = str(root / f"part-{si:05d}.tfrecord")
        tfrecord.write_records(path, (
            dfutil.to_example({
                "image": rng.integers(0, 256, PIXELS, dtype=np.uint8).tobytes(),
                "label": si * RECS_PER_SHARD + i})
            for i in range(RECS_PER_SHARD)))
        paths.append(path)
    return paths


def test_every_feed_stage_counts_chunks_and_batches_and_the_loop_closes(
        fresh, tmp_path):
    import jax
    import numpy as np

    from tensorflowonspark_tpu import dfutil
    from tensorflowonspark_tpu.feeding import FeedQueues
    from tensorflowonspark_tpu.ingest.feed import IngestFeed
    from tensorflowonspark_tpu.marker import EndOfFeed, EndPartition
    from tensorflowonspark_tpu.parallel import dp
    from tensorflowonspark_tpu.parallel.mesh import make_mesh

    paths = _write_example_shards(tmp_path)
    queues = FeedQueues(("input",))
    q = queues.get_queue("input")
    for p in paths:                 # ONE ledger partition: no partial batch
        q.put(p)
    q.put(EndPartition(key=(0, 0)))
    q.put(EndOfFeed())
    feed = IngestFeed(
        queues, readers=2, autotune=False, chunk_records=CHUNK, prefetch=4,
        decode=lambda rec: dfutil.from_example(rec,
                                               binary_features={"image"}))

    def to_arrays(rows):
        return {"image": np.stack([np.frombuffer(r["image"][0], np.uint8)
                                   for r in rows]),
                "label": np.asarray([r["label"][0] for r in rows], np.int32)}

    mesh = make_mesh(jax.devices()[:4], dp=-1)
    labels: list[int] = []
    put_bytes = 0
    batches = 0
    for batch, n in dp.make_batch_iterator(feed, BATCH, to_arrays, mesh=mesh,
                                           prefetch=2):
        assert n == BATCH
        put_bytes += sum(x.nbytes for x in jax.tree.leaves(batch))
        labels.extend(int(x) for x in np.asarray(batch["label"]))
        batches += 1
        time.sleep(0.004)           # a device slower than the feed
    records = SHARDS * RECS_PER_SHARD
    chunks = records // CHUNK
    assert sorted(labels) == list(range(records))
    assert batches == records // BATCH

    counters = _counters()
    for name in FEED_STAGES:
        assert counters.get(name + ".calls", 0) > 0, name
        assert name + ".us" in counters, name
    # per work item, per chunk, per batch -- never per record
    assert counters["ingest.read.calls"] == SHARDS
    assert counters["ingest.decode.calls"] == chunks
    # every chunk, every ShardDone token and the drain sentinel is one put
    assert counters["ingest.put_wait.calls"] == chunks + SHARDS + 1
    assert counters["batch.convert.calls"] == batches
    assert counters["batch.put.calls"] == batches
    assert counters["batch.queue_full.calls"] == batches
    # one more pull than batches: the one that finds the feed drained
    assert batches <= counters["feed.collect.calls"] <= batches + 2
    assert counters["feed.wait.calls"] <= chunks + SHARDS + 1 + 8
    assert max(counters[n + ".calls"] for n in FEED_STAGES) < records
    assert counters["batch.h2d_bytes"] == put_bytes \
        == batches * BATCH * (PIXELS + 4)
    assert counters["ingest.records_read"] == records

    # closure: on the prefetch thread collect + convert + put + queue_full
    # is the whole loop, so their sum is that thread's wall time
    spans = fresh.collect_final()["spans"]
    thread = {s["th"] for s in spans if s["n"] == "batch.convert"}
    assert len(thread) == 1
    mine = [s for s in spans if s["th"] in thread]
    top = [s for s in mine if s["p"] is None]
    assert {s["n"] for s in top} == {"feed.collect", "batch.convert",
                                     "batch.put", "batch.queue_full"}
    # feed.wait is the only stage nested on that thread, under feed.collect
    collects = {s["s"] for s in top if s["n"] == "feed.collect"}
    nested = [s for s in mine if s["p"] is not None]
    assert nested and all(s["n"] == "feed.wait" and s["p"] in collects
                          for s in nested)
    wall = (max(s["t0"] + s["d"] for s in top) - min(s["t0"] for s in top))
    busy = sum(s["d"] for s in top)
    assert busy <= wall * 1.0001
    assert busy >= 0.9 * wall, (busy, wall)
    # and the consumer's side of the queue ran on another thread
    assert {s["th"] for s in spans if s["n"] == "batch.queue_empty"} != thread


def test_datafeed_collect_and_wait_are_staged_too(fresh):
    from tensorflowonspark_tpu.feeding import DataFeed, FeedQueues
    from tensorflowonspark_tpu.marker import EndOfFeed

    queues = FeedQueues(("input",))
    q = queues.get_queue("input")
    feed = DataFeed(queues, poll_interval=0.05)
    for i in range(6):
        q.put(i)
    assert feed.next_batch(4) == [0, 1, 2, 3]     # buffered: no wait at all
    counters = _counters()
    assert counters["feed.collect.calls"] == 1
    assert "feed.wait.calls" not in counters
    # an empty queue: the blocked part of the call is feed.wait
    import threading

    def late():
        time.sleep(0.03)
        q.put(6)
        q.put(EndOfFeed())

    producer = threading.Thread(target=late)
    producer.start()
    assert feed.next_batch(4) == [4, 5, 6]
    producer.join(timeout=10)
    assert not producer.is_alive()
    counters = _counters()
    assert counters["feed.collect.calls"] == 2
    assert counters["feed.wait.calls"] >= 1
    assert 20_000 <= counters["feed.wait.us"] <= counters["feed.collect.us"]
