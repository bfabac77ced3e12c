"""Telemetry subsystem: registry semantics, the heartbeat delta transport,
and cluster-wide aggregation on a real in-process 2-node cluster (ISSUE 4).

Layers under test, bottom-up:

- registry units — lock-free counter exactness under thread contention,
  gauge/histogram/span semantics, the compact wire delta
  (``collect_changed``), and the ``TOS_METRICS=0`` no-op mode;
- transport units — an in-process ``CoordinatorServer`` merging heartbeat
  deltas (absolute values, replacement merge, fenced zombies dropped) and
  serving the ``metrics`` control-plane op;
- end-to-end — ``cluster.metrics()`` on a real 2-node STREAMING cluster
  returns data-plane byte/chunk counters from every node plus the user's
  ``ctx.metrics`` entries, ``debug_dump()`` renders, and shutdown writes the
  JSON run report next to the logs;
- chaos — a ``TOS_FAULTINJECT=kill`` supervised restart increments
  ``elastic.restarts_total`` in the aggregate (the acceptance criterion).
"""

from __future__ import annotations

import json
import os
import threading

import pytest

from tensorflowonspark_tpu import cluster as tcluster
from tensorflowonspark_tpu import telemetry
from tensorflowonspark_tpu.coordinator import CoordinatorClient, CoordinatorServer
from tensorflowonspark_tpu.telemetry.registry import MetricsRegistry

import mapfuns


# -- registry units -----------------------------------------------------------


def test_counter_is_exact_under_thread_contention():
    reg = MetricsRegistry(enabled=True)
    c = reg.counter("t.bytes")

    def worker():
        for _ in range(20_000):
            c.inc(3)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # a shared `value += n` would lose updates here; per-thread cells don't
    assert c.value() == 8 * 20_000 * 3


def test_counter_interning_and_gauge_last_write_wins():
    reg = MetricsRegistry(enabled=True)
    assert reg.counter("a") is reg.counter("a")
    g = reg.gauge("g")
    g.set(1)
    g.set(2.5)
    assert g.value() == 2.5


def test_histogram_digest_and_percentiles():
    reg = MetricsRegistry(enabled=True)
    h = reg.histogram("h")
    for i in range(100):
        h.observe(i)
    d = h.digest()
    assert d["count"] == 100 and d["min"] == 0 and d["max"] == 99
    assert abs(h.percentile(50) - 49.5) < 5  # reservoir holds all 100 here
    with reg.timed("span"):
        pass
    assert reg.histogram("span").count == 1


def test_snapshot_is_json_safe_and_delta_is_compact():
    reg = MetricsRegistry(enabled=True)
    reg.counter("c").inc(5)
    reg.gauge("g").set(1.5)
    reg.histogram("h").observe(0.25)
    json.dumps(reg.snapshot(include_samples=True))
    payload, state = reg.collect_changed(None)
    json.dumps(payload)
    assert payload["counters"] == {"c": 5}
    assert payload["gauges"] == {"g": 1.5}
    assert payload["histograms"]["h"]["count"] == 1
    assert payload["histograms"]["h"]["recent"] == [0.25]
    # nothing changed -> empty delta (heartbeats stay light)
    payload2, state = reg.collect_changed(state)
    assert payload2 == {}
    # one increment -> only that counter travels, absolute-valued
    reg.counter("c").inc()
    payload3, _ = reg.collect_changed(state)
    assert payload3 == {"counters": {"c": 6}}


def test_failed_delta_samples_can_be_restored():
    """collect_changed drains histogram outboxes destructively; when the
    carrying heartbeat fails, restore_recent must give the samples back so
    the cluster percentile pool doesn't silently lose them."""
    reg = MetricsRegistry(enabled=True)
    reg.histogram("h").observe(0.1)
    reg.histogram("h").observe(0.2)
    payload, _ = reg.collect_changed(None)
    assert payload["histograms"]["h"]["recent"] == [0.1, 0.2]
    # send failed -> restore; the next delta re-ships the same samples
    reg.restore_recent(payload)
    payload2, _ = reg.collect_changed(None)
    assert payload2["histograms"]["h"]["recent"] == [0.1, 0.2]


def test_reservoir_sampling_is_deterministic_across_processes():
    # the seed must not depend on per-process str-hash randomization
    import subprocess
    import sys

    code = ("from tensorflowonspark_tpu.telemetry.registry import Histogram;"
            "h = Histogram('x', reservoir_size=4);"
            "[h.observe(i) for i in range(100)];"
            "print(h.reservoir())")
    outs = {subprocess.run([sys.executable, "-c", code], check=True,
                           capture_output=True, text=True).stdout
            for _ in range(2)}
    assert len(outs) == 1, outs


def test_disabled_registry_is_noop():
    reg = MetricsRegistry(enabled=False)
    reg.counter("c").inc(10)
    reg.gauge("g").set(1)
    reg.histogram("h").observe(2)
    with reg.timed("t"):
        pass
    assert reg.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}
    assert reg.collect_changed(None)[0] == {}


def test_aggregate_snapshots_merges_counters_and_pools_percentiles():
    nodes = {
        "0": {"counters": {"x": 10}, "gauges": {},
              "histograms": {"s": {"count": 2, "sum": 0.3, "min": 0.1,
                                   "max": 0.2, "recent": [0.1, 0.2]}}},
        "1": {"counters": {"x": 5, "y": 1}, "gauges": {"g": 2.0},
              "histograms": {"s": {"count": 1, "sum": 0.9, "min": 0.9,
                                   "max": 0.9, "recent": [0.9]}}},
    }
    agg = telemetry.aggregate_snapshots(nodes)
    assert agg["counters"] == {"x": 15, "y": 1}
    s = agg["histograms"]["s"]
    assert s["count"] == 3 and s["min"] == 0.1 and s["max"] == 0.9
    assert s["p50"] == 0.2 and abs(s["mean"] - 0.4) < 1e-9
    # per-node detail preserved, raw samples stripped
    assert agg["nodes"]["1"]["gauges"] == {"g": 2.0}
    assert "recent" not in agg["nodes"]["0"]["histograms"]["s"]
    # the whole aggregate is a JSON document (control-plane servable)
    json.dumps(agg)
    dump = telemetry.debug_dump(agg)
    assert "x" in dump and "node 1" in dump


def test_run_report_derives_headlines():
    agg = telemetry.aggregate_snapshots(
        {"0": {"counters": {"dataplane.rx_bytes": 2_000_000,
                            "elastic.restarts_total": 2},
               "gauges": {}, "histograms": {}}})
    rep = telemetry.build_run_report(agg, wall_secs=2.0,
                                     extras={"num_executors": 1})
    assert rep["schema"] == "tos-run-report-v1"
    assert rep["throughput_mb_per_s"] == 1.0
    assert rep["restarts_total"] == 2
    assert rep["num_executors"] == 1
    json.dumps(rep)


# -- transport units (in-process coordinator) ---------------------------------


def _pair():
    srv = CoordinatorServer(2)
    addr = srv.start()
    clients = []
    for host in ("h0", "h1"):
        c = CoordinatorClient(addr)
        ident = c.register({"host": host})
        c.set_identity(ident["executor_id"], ident["incarnation"])
        clients.append((c, ident))
    return srv, clients


def test_heartbeat_delta_merge_and_metrics_op():
    # cluster_metrics() folds THIS process's registry in under "driver";
    # earlier in-process dataplane tests leave counters there — reset so
    # the aggregate assertions below see only what this test reports
    telemetry.reset()
    srv, clients = _pair()
    try:
        (c0, id0), (c1, id1) = clients
        c0.heartbeat(0, metrics={"counters": {"dataplane.rx_bytes": 100}})
        c1.heartbeat(1, metrics={
            "counters": {"dataplane.rx_bytes": 40},
            "histograms": {"span": {"count": 2, "sum": 0.4, "min": 0.1,
                                    "max": 0.3, "recent": [0.1, 0.3]}}})
        # absolute values: a later report REPLACES, never re-adds
        c1.heartbeat(1, metrics={"counters": {"dataplane.rx_bytes": 70}})
        snap = c1.metrics()  # the `metrics` control-plane op
        assert snap["counters"]["dataplane.rx_bytes"] == 170
        assert snap["nodes"]["0"]["counters"]["dataplane.rx_bytes"] == 100
        assert snap["nodes"]["1"]["counters"]["dataplane.rx_bytes"] == 70
        assert snap["histograms"]["span"]["count"] == 2
        # final snapshot rides deregister
        c0.deregister(0, metrics={"counters": {"final.rows": 9,
                                               "dataplane.rx_bytes": 120}})
        assert srv.cluster_metrics()["nodes"]["0"]["counters"]["final.rows"] == 9
        # a LATE in-flight heartbeat (the node's heartbeat thread racing its
        # own teardown) must not regress the final deregister snapshot
        c0.heartbeat(0, metrics={"counters": {"dataplane.rx_bytes": 100}})
        assert (srv.cluster_metrics()["nodes"]["0"]["counters"]
                ["dataplane.rx_bytes"] == 120)
        for c, _ in clients:
            c.close()
    finally:
        srv.stop()


def test_fenced_zombie_metrics_are_dropped():
    srv, clients = _pair()
    try:
        (c0, id0), (c1, id1) = clients
        srv.mark_dead([id1["executor_id"]], record_error=False)
        # the zombie's heartbeat is answered stop=True and its metrics must
        # NOT pollute the slot's store (a replacement owns it now)
        assert c1.heartbeat(1, metrics={"counters": {"zombie.rows": 666}}) is True
        assert "zombie.rows" not in (srv.cluster_metrics()["nodes"]
                                     .get("1", {}).get("counters", {}))
        for c, _ in clients:
            c.close()
    finally:
        srv.stop()


# -- end-to-end: 2-node cluster aggregation + run report ----------------------


def _poll_metrics(cluster, want_nodes, want_rows=None, timeout=30.0):
    """Wait until every wanted node key reported data-plane rows — and,
    when ``want_rows`` is given, until the aggregate row count reaches it,
    ARRIVED and CONSUMED: a node's counters ride the NEXT heartbeat after
    they move, so a snapshot taken the moment a node first shows up can
    still be a stale mid-train value (nonzero but not final), and one taken
    when the last rows arrived can precede a node's first batch."""
    import time

    deadline = time.monotonic() + timeout
    snap = {}
    while time.monotonic() < deadline:
        snap = cluster.metrics()
        nodes = snap.get("nodes", {})
        if all(nodes.get(k, {}).get("counters", {}).get("dataplane.rows_in")
               for k in want_nodes):
            if want_rows is None or all(
                    snap["counters"].get(name) == want_rows
                    for name in ("dataplane.rows_in", "feed.rows_consumed")):
                return snap
        time.sleep(0.25)
    return snap


def test_cluster_metrics_aggregates_every_node_and_writes_run_report(tmp_path):
    """The acceptance scenario: an in-process 2-node STREAMING cluster's
    ``cluster.metrics()`` returns an aggregated snapshot holding data-plane
    byte/chunk counters from EVERY node, plus the map_fun's own
    ``ctx.metrics`` entries; shutdown writes the JSON run report."""
    telemetry.reset()  # isolate the driver-side registry from earlier tests
    items = list(range(80))
    parts = [items[i * 20:(i + 1) * 20] for i in range(4)]
    cluster = tcluster.run(
        mapfuns.metered_sum_batches,
        {"batch_size": 5, "out_dir": str(tmp_path)},
        num_executors=2,
        input_mode=tcluster.InputMode.STREAMING,
        heartbeat_interval=0.5,
        log_dir=str(tmp_path / "logs"),
        reservation_timeout=120.0,
    )
    cluster.train(parts, num_epochs=1)
    snap = _poll_metrics(cluster, ("0", "1"), want_rows=len(items))
    for eid in ("0", "1"):
        counters = snap["nodes"][eid]["counters"]
        assert counters.get("dataplane.rx_bytes", 0) > 0, snap["nodes"]
        assert counters.get("dataplane.chunks_in", 0) > 0
        assert counters.get("feed.rows_consumed", 0) > 0
        assert counters.get("train.user_batches", 0) > 0  # ctx.metrics
    # driver side: its own registry (feed pump) is in the same view
    assert snap["nodes"]["driver"]["counters"]["dataplane.tx_bytes"] > 0
    assert snap["nodes"]["driver"]["histograms"][
        "driver.feed_partition_secs"]["count"] == 4
    # aggregate sums across nodes
    agg_rows = snap["counters"]["dataplane.rows_in"]
    assert agg_rows == sum(snap["nodes"][e]["counters"]["dataplane.rows_in"]
                           for e in ("0", "1"))
    assert agg_rows == len(items)
    dump = cluster.debug_dump()
    assert "dataplane.rx_bytes" in dump and "node 1" in dump
    cluster.shutdown(timeout=120.0)
    # the run report landed next to the logs, final node snapshots included
    report_path = tmp_path / "logs" / "run_report.json"
    assert report_path.exists()
    report = json.loads(report_path.read_text())
    assert report["schema"] == "tos-run-report-v1"
    assert report["rows_fed"] == len(items)
    assert report["restarts_total"] == 0
    # the gauge set AFTER the last heartbeat arrived via deregister
    totals = [report["nodes"][e]["gauges"].get("train.total_sum")
              for e in ("0", "1")]
    assert sum(t for t in totals if t is not None) == sum(items)
    # the map_fun span made it into the merged histograms
    assert report["histograms"]["node.map_fun_secs"]["count"] == 2


def test_metrics_disabled_cluster_still_trains(tmp_path, monkeypatch):
    """TOS_METRICS=0 must be a pure kill switch: the cluster runs, metrics
    come back empty, and no run report is written."""
    monkeypatch.setenv("TOS_METRICS", "0")
    telemetry.reset()
    try:
        parts = [[1, 2, 3], [4, 5, 6]]
        cluster = tcluster.run(
            mapfuns.sum_batches,
            {"batch_size": 2, "out_dir": str(tmp_path)},
            num_executors=2,
            input_mode=tcluster.InputMode.STREAMING,
            log_dir=str(tmp_path / "logs"),
            reservation_timeout=120.0,
        )
        cluster.train(parts, num_epochs=1)
        snap = cluster.metrics()
        assert snap["counters"] == {}
        assert "driver" not in snap["nodes"]
        cluster.shutdown(timeout=120.0)
        assert not (tmp_path / "logs" / "run_report.json").exists()
        # TOS_TRACE defaults off: a default-config run leaves ZERO trace
        # artifacts (the ISSUE-8 acceptance criterion)
        leftovers = [p.name for p in (tmp_path / "logs").glob("trace*.json")]
        assert leftovers == [], leftovers
    finally:
        monkeypatch.setenv("TOS_METRICS", "1")
        telemetry.reset()


# -- chaos: restart counters under an injected kill (acceptance) --------------


@pytest.mark.chaos
def test_restart_counter_increments_under_injected_kill(tmp_path, monkeypatch):
    """``TOS_FAULTINJECT=kill`` + elastic=True: the supervised restart must
    show up as ``elastic.restarts_total`` >= 1 in the aggregated snapshot
    and in the run report (the ISSUE 4 acceptance criterion)."""
    monkeypatch.setenv("TOS_DEAD_NODE_TIMEOUT", "4")
    monkeypatch.setenv("TOS_RESTART_BACKOFF_BASE", "0.2")
    telemetry.reset()  # isolate this test's driver-side counters
    items = list(range(120))
    parts = [items[i * 20:(i + 1) * 20] for i in range(6)]
    per_node_env = [{}, {"TOS_FAULTINJECT": "kill:after_batches=3,incarnation=0"}]
    cluster = tcluster.run(
        mapfuns.elastic_sum_batches,
        {"batch_size": 2, "out_dir": str(tmp_path)},
        num_executors=2,
        input_mode=tcluster.InputMode.STREAMING,
        queue_capacity=4,
        heartbeat_interval=0.5,
        per_node_env=per_node_env,
        log_dir=str(tmp_path / "logs"),
        reservation_timeout=120.0,
        elastic=True,
    )
    cluster.train(parts, num_epochs=1)
    snap = cluster.metrics()
    assert snap["counters"].get("elastic.restarts_total", 0) >= 1, snap["counters"]
    assert snap["counters"].get("coordinator.deaths_total", 0) >= 1
    cluster.shutdown(timeout=120.0)
    report = json.loads((tmp_path / "logs" / "run_report.json").read_text())
    assert report["restarts_total"] >= 1
    assert report["restarts_by_executor"]  # names the restarted slot
