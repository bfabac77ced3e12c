"""Online serving subsystem (ISSUE 5): micro-batching, admission control,
replica routing, hot reload, and the satellites that ride along.

Layers under test, bottom-up:

- satellites — ``load_bundle_cached`` single-flight under thread contention
  (+ the ``invalidate_bundle`` hot-reload hook) and ``rows_to_features``
  integer-dtype preservation (LM token-id regression);
- batcher units — coalescing/flush timing, static-shape padding, requests
  spanning batches, queue-full fast-fail, deadline expiry — against a fake
  router, so the semantics are exercised with no cluster and no clock
  slack beyond the configured delays;
- end-to-end — a real 2-node STREAMING cluster running ``serving_loop``
  over a linear bundle: single round-trip, the TCP wire endpoint
  (``GatewayClient``), concurrent clients coalescing into ONE dispatched
  batch (one apply served N waiters), and the version-watch hot reload;
- chaos — ``TOS_FAULTINJECT=kill`` SIGKILLs a serving replica mid-flight:
  the in-flight batch must retry on the survivor and every accepted
  request be answered exactly once (the acceptance criterion), with the
  slot recovering via the elastic supervisor.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np
import pytest

from tensorflowonspark_tpu import checkpoint as tckpt
from tensorflowonspark_tpu import cluster as tcluster
from tensorflowonspark_tpu import serving, telemetry
from tensorflowonspark_tpu.checkpoint import export_bundle
from tensorflowonspark_tpu.inference import rows_to_features
from tensorflowonspark_tpu.models import linear as linmod
from tensorflowonspark_tpu.serving import (
    GatewayClient,
    MicroBatcher,
    ServeClosed,
    ServeQueueFull,
    ServeTimeout,
)

LINEAR = {"model": "linear", "in_dim": 4, "out_dim": 4}


def _drive_until_fault_fires(gw, one, timeout=90.0):
    """Chaos-test driver: fire SEQUENTIAL single predicts (each its own
    batch — no coalescing to starve the victim of its op/batch threshold)
    until the injected fault demonstrably fired; the LRU routing tiebreak
    alternates replicas, so the victim's counter advances every other
    request.  Returns the next unused request index."""
    i = 0
    deadline = time.monotonic() + timeout
    while (telemetry.counter("serve.replica_failures").value() == 0
           and time.monotonic() < deadline):
        one(i)
        i += 1
    assert telemetry.counter("serve.replica_failures").value() >= 1, \
        f"fault never fired after {i} sequential requests"
    return i


# -- satellite: bundle cache single-flight ------------------------------------


def test_load_bundle_cached_single_flight_under_contention(tmp_path, monkeypatch):
    """Concurrent serving threads hitting a cold cache must trigger exactly
    ONE load (the old unlocked dict loaded once per racer), and
    invalidate_bundle must force exactly one fresh load afterwards."""
    calls = []
    lock = threading.Lock()

    def slow_load(export_dir):
        with lock:
            calls.append(export_dir)
        time.sleep(0.2)  # wide race window: every thread arrives mid-load
        return {"w": np.ones(2)}, {"model": "fake"}

    monkeypatch.setattr(tckpt, "load_bundle", slow_load)
    built = []

    def build_apply(config):
        built.append(config)
        return lambda v, x: x

    export = str(tmp_path / "bundle")
    os.makedirs(export)
    out: list = [None] * 8

    def worker(i):
        out[i] = tckpt.load_bundle_cached(export, build_apply)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(calls) == 1, f"{len(calls)} loads for one export_dir"
    assert len(built) == 1
    assert all(o is out[0] for o in out)  # everyone shares the one entry

    # the hot-reload hook: next load is fresh, but still exactly one
    tckpt.invalidate_bundle(export)
    again = tckpt.load_bundle_cached(export, build_apply)
    assert len(calls) == 2
    assert again is not out[0]
    tckpt.invalidate_bundle(export)


def test_invalidate_during_inflight_load_is_not_undone(tmp_path, monkeypatch):
    """invalidate_bundle racing a load that already STARTED (reading the
    old export) must fence that load's result out of the cache, or the hot
    reload would be silently undone by the stale re-cache."""
    started = threading.Event()
    release = threading.Event()
    versions = iter(["old", "new"])

    def gated_load(export_dir):
        v = next(versions)
        started.set()
        assert release.wait(10.0)
        return {"w": np.ones(1)}, {"model": v}

    monkeypatch.setattr(tckpt, "load_bundle", gated_load)
    export = str(tmp_path / "bundle3")
    os.makedirs(export)
    got: list = []
    t = threading.Thread(target=lambda: got.append(
        tckpt.load_bundle_cached(export, lambda c: (lambda v, x: x))))
    t.start()
    assert started.wait(10.0)
    tckpt.invalidate_bundle(export)  # the hot reload, mid-old-load
    release.set()
    t.join(10.0)
    assert got and got[0][1] == {"model": "old"}  # its caller gets its load
    # ...but the cache must NOT hold it: the next load reads the new export
    release.set()
    _, config, _ = tckpt.load_bundle_cached(export,
                                            lambda c: (lambda v, x: x))
    assert config == {"model": "new"}
    tckpt.invalidate_bundle(export)


def test_load_bundle_cached_failed_load_is_not_cached(tmp_path, monkeypatch):
    boom = [True]

    def flaky_load(export_dir):
        if boom[0]:
            raise OSError("transient fs error")
        return {"w": np.ones(2)}, {"model": "fake"}

    monkeypatch.setattr(tckpt, "load_bundle", flaky_load)
    export = str(tmp_path / "bundle2")
    os.makedirs(export)
    with pytest.raises(OSError):
        tckpt.load_bundle_cached(export, lambda c: (lambda v, x: x))
    boom[0] = False  # the error must not have poisoned the cache
    params, config, _ = tckpt.load_bundle_cached(export,
                                                 lambda c: (lambda v, x: x))
    assert config == {"model": "fake"}
    tckpt.invalidate_bundle(export)


# -- satellite: integer dtypes survive rows_to_features -----------------------


def test_rows_to_features_preserves_token_id_dtypes():
    """LM-style bundles feed int token ids into embedding lookups; the old
    force-cast to float32 silently corrupted ids above 2**24."""
    big = 2**24 + 1  # not representable in float32 (rounds to 2**24)
    tokens = [np.array([1, 5, big], dtype=np.int32) for _ in range(3)]
    x = rows_to_features(tokens, None)
    assert x.dtype == np.int32
    assert int(x[0, 2]) == big

    # dict rows through input_mapping keep the dtype too
    rows = [{"tokens": np.array([7, big], np.int64)} for _ in range(2)]
    x2 = rows_to_features(rows, {"tokens": "x"})
    assert x2.dtype == np.int64 and int(x2[1, 1]) == big

    # inexact inputs still normalize to float32 (the jitted-apply contract)
    floats = [np.array([0.5, 1.5], np.float64) for _ in range(2)]
    assert rows_to_features(floats, None).dtype == np.float32
    f32 = [np.array([0.5], np.float32)]
    assert rows_to_features(f32, None).dtype == np.float32

    # a MIXED multi-column mapping is a dense float feature matrix: int
    # columns cast to float32 there (numpy promotion would yield float64,
    # which no jitted apply compiled for)
    mixed = [{"ids": np.array([3, 4], np.int64),
              "dense": np.array([0.5, 0.25], np.float32)} for _ in range(2)]
    xm = rows_to_features(mixed, {"ids": "a", "dense": "b"})
    assert xm.dtype == np.float32 and xm.shape == (2, 4)

    # NARROW ints keep the historical float32 cast (lossless below 2**24;
    # uint8 image pipelines feed float32-compiled convs)
    imgs = [{"image": np.zeros((4, 4, 1), np.uint8)} for _ in range(2)]
    assert rows_to_features(imgs, {"image": "x"}).dtype == np.float32

    # a column mixing int and float ROWS (JSON-decoded data) must land on
    # float32 — per-row dtype decisions would stack-promote to float64,
    # which no jitted apply compiled for (and TPUs don't support)
    assert rows_to_features([[1, 2], [1.5, 2.5]], None).dtype == np.float32


# -- batcher units (fake router) ----------------------------------------------


class _FakeRouter:
    """Records batches; completes them with f(row) when told to."""

    def __init__(self, batcher_ref: list, fn=lambda r: r, auto: bool = True):
        self.batches: list = []
        self.fn = fn
        self.auto = auto
        self._batcher_ref = batcher_ref

    def submit(self, batch):
        self.batches.append(batch)
        if self.auto:
            self.complete(batch)

    def complete(self, batch):
        self._batcher_ref[0].complete_batch(
            batch, [self.fn(r) for r in batch.rows])


def _make(batcher_ref, *, max_batch=8, delay=0.05, queue=16, pause=None,
          fn=lambda r: r, auto=True, capacity=None):
    router = _FakeRouter(batcher_ref, fn=fn, auto=auto)
    b = MicroBatcher(router.submit, max_batch=max_batch, max_delay_secs=delay,
                     queue_limit=queue, pause_fn=pause, capacity_fn=capacity)
    batcher_ref[0] = b
    return b, router


def test_batcher_coalesces_concurrent_requests_into_one_padded_batch():
    ref: list = [None]
    b, router = _make(ref, max_batch=8, delay=0.25, fn=lambda r: r * 2)
    try:
        results: dict = {}

        def one(i):
            req = b.submit([float(i)], time.monotonic() + 30.0)
            results[i] = b.await_request(req)[0]

        threads = [threading.Thread(target=one, args=(i,)) for i in range(5)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # all five rode ONE batch, padded to the static shape
        assert len(router.batches) == 1
        batch = router.batches[0]
        assert batch.n == 5 and len(batch.rows) == 8
        assert results == {i: float(i) * 2 for i in range(5)}
    finally:
        b.close()


def test_batcher_flushes_full_batch_before_delay():
    ref: list = [None]
    b, router = _make(ref, max_batch=4, delay=10.0)  # delay can never trip
    try:
        t0 = time.monotonic()
        req = b.submit([1.0, 2.0, 3.0, 4.0], time.monotonic() + 30.0)
        assert b.await_request(req) == [1.0, 2.0, 3.0, 4.0]
        assert time.monotonic() - t0 < 5.0  # size-triggered, not delay
        assert router.batches[0].n == 4
    finally:
        b.close()


def test_batcher_request_spanning_batches_keeps_row_order():
    ref: list = [None]
    b, router = _make(ref, max_batch=4, delay=0.02, fn=lambda r: r + 100)
    try:
        rows = [float(i) for i in range(10)]
        req = b.submit(rows, time.monotonic() + 30.0)
        assert b.await_request(req) == [r + 100 for r in rows]
        assert len(router.batches) == 3  # 4 + 4 + 2(padded)
        assert [batch.n for batch in router.batches] == [4, 4, 2]
        assert all(len(batch.rows) == 4 for batch in router.batches)
    finally:
        b.close()


def test_batcher_failed_spanning_request_tail_never_dispatches():
    """When a spanning request's first batch fails, its queued tail rows
    must be pulled out — not scored on a replica and not held against the
    admission bound (review finding on the fail_batch path)."""
    ref: list = [None]
    # capacity gate: one batch may dispatch per allowance — holds the
    # spanning request's tail in the QUEUE while its first batch fails
    allowance = [1]
    b, router = _make(ref, max_batch=4, delay=0.02, auto=False,
                      capacity=lambda: len(router.batches) < allowance[0])
    try:
        req = b.submit([float(i) for i in range(10)], time.monotonic() + 30.0)
        deadline = time.monotonic() + 5.0
        while not router.batches and time.monotonic() < deadline:
            time.sleep(0.01)
        assert router.batches, "first slice never dispatched"
        b.fail_batch(router.batches[0], RuntimeError("replica down"))
        with pytest.raises(RuntimeError, match="replica down"):
            b.await_request(req)
        # the tail (rows 4..9) must not become further batches
        n_after_fail = len(router.batches)
        allowance[0] = 2  # gate reopens: only NEW work may flush now
        clean = b.submit([42.0], time.monotonic() + 30.0)
        deadline = time.monotonic() + 5.0
        while len(router.batches) == n_after_fail \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        last = router.batches[-1]
        assert last.n == 1 and last.rows[0] == 42.0, (
            "dead request's tail rows leaked into a later batch")
        router.complete(last)
        assert b.await_request(clean) == [42.0]
    finally:
        b.close()


def test_batcher_queue_full_fast_fails_and_close_resolves_pending():
    ref: list = [None]
    b, _ = _make(ref, max_batch=8, delay=10.0, queue=2,
                 pause=lambda: True)  # paused: nothing ever dispatches
    reqs = [b.submit([1.0], time.monotonic() + 60.0) for _ in range(2)]
    with pytest.raises(ServeQueueFull):
        b.submit([2.0], time.monotonic() + 60.0)
    b.close()
    for req in reqs:  # queued work resolves (with an error), never hangs
        with pytest.raises(ServeClosed):
            b.await_request(req)
    with pytest.raises(ServeClosed):
        b.submit([3.0], time.monotonic() + 60.0)


def test_batcher_deadline_expires_queued_request():
    ref: list = [None]
    b, _ = _make(ref, max_batch=8, delay=10.0, pause=lambda: True)
    try:
        t0 = time.monotonic()
        req = b.submit([1.0], time.monotonic() + 0.15)
        with pytest.raises(ServeTimeout):
            b.await_request(req)
        assert 0.1 < time.monotonic() - t0 < 5.0
    finally:
        b.close()


# -- end-to-end: 2-node serving cluster ---------------------------------------


def _serve_cluster(tmp_path, *, scale=2.0, elastic=False, per_node_env=None,
                   max_batch=4):
    export = str(tmp_path / "bundle")
    export_bundle(export, linmod.init_params(LINEAR, scale=scale), LINEAR)
    cluster = tcluster.run(
        serving.serving_loop,
        {"export_dir": export, "max_batch": max_batch},
        num_executors=2,
        input_mode=tcluster.InputMode.STREAMING,
        heartbeat_interval=0.5,
        per_node_env=per_node_env,
        reservation_timeout=120.0,
        elastic=elastic,
    )
    return cluster, export


def test_gateway_round_trip_and_tcp_endpoint_and_coalescing(tmp_path):
    telemetry.reset()
    cluster, export = _serve_cluster(tmp_path, scale=2.0, max_batch=4)
    try:
        gw = cluster.serve(export, max_batch=4, max_delay_ms=5.0,
                           reload_poll_secs=0)
        rows = [np.arange(4, dtype=np.float32) + i for i in range(3)]

        # single request round-trip: one result per row, in order
        out = gw.predict(rows, timeout=60.0)
        assert len(out) == 3
        for i in range(3):
            np.testing.assert_allclose(out[i], rows[i] * 2.0)

        # the TCP wire endpoint speaks the same protocol (authkey + v2
        # frames) and surfaces the same results
        host, port = gw.endpoint
        client = GatewayClient("127.0.0.1", port, cluster.authkey)
        try:
            assert client.ping()
            out2 = client.predict(rows, timeout=60.0)
            np.testing.assert_allclose(out2[1], rows[1] * 2.0)
        finally:
            client.close()

        # batch coalescing: N concurrent 1-row requests inside one delay
        # window ride ONE dispatched batch — one apply served N waiters
        before = telemetry.counter("serve.batches_total").value()
        gw2 = cluster.serve(export, max_batch=8, max_delay_ms=300.0,
                            listen=False, reload_poll_secs=0)
        results: dict = {}

        def one(i):
            results[i] = gw2.predict([rows[0] + i], timeout=60.0)[0]

        threads = [threading.Thread(target=one, args=(i,)) for i in range(5)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert telemetry.counter("serve.batches_total").value() - before == 1
        for i in range(5):
            np.testing.assert_allclose(results[i], (rows[0] + i) * 2.0)
    finally:
        cluster.shutdown(timeout=120.0)
    # latency histograms made it into the telemetry pool for the run report
    reg = telemetry.get_registry()
    assert reg.histogram("serve.request_secs").count >= 2
    assert reg.histogram("serve.batch_secs").count >= 2
    assert reg.histogram("serve.queue_wait_secs").count >= 2


def test_gateway_hot_reload_swaps_bundle(tmp_path):
    """Re-exporting into the same export_dir must swap predictions on every
    replica without restarting anything (version watch -> drain -> reload
    control round)."""
    telemetry.reset()
    cluster, export = _serve_cluster(tmp_path, scale=2.0, max_batch=4)
    try:
        gw = cluster.serve(export, max_batch=4, max_delay_ms=2.0,
                           listen=False, reload_poll_secs=0.2)
        row = np.arange(4, dtype=np.float32) + 1.0
        np.testing.assert_allclose(gw.predict([row], timeout=60.0)[0],
                                   row * 2.0)
        export_bundle(export, linmod.init_params(LINEAR, scale=3.0), LINEAR)
        deadline = time.monotonic() + 60.0
        swapped = False
        while time.monotonic() < deadline and not swapped:
            out = gw.predict([row], timeout=30.0)[0]
            swapped = np.allclose(out, row * 3.0)
            if not swapped:
                np.testing.assert_allclose(out, row * 2.0)  # old, never junk
                time.sleep(0.2)
        assert swapped, "hot reload never swapped the bundle in"
        assert telemetry.counter("serve.reloads_total").value() >= 1
    finally:
        cluster.shutdown(timeout=120.0)


@pytest.mark.chaos
def test_severed_live_replica_is_resynced_and_readmitted(tmp_path):
    """``TOS_FAULTINJECT=sever`` drops a serving replica's data connection
    with the NODE STILL ALIVE (no restart, no incarnation bump): the failed
    batch retries on the peer, and the router must re-admit the live
    process after the order-fenced resync — not quarantine it forever
    waiting for a restart that will never come."""
    telemetry.reset()
    cluster, export = _serve_cluster(
        tmp_path, scale=2.0, max_batch=4,
        per_node_env=[{}, {"TOS_FAULTINJECT": "sever:after_data_ops=3"}])
    try:
        gw = cluster.serve(export, max_batch=4, max_delay_ms=2.0,
                           listen=False, reload_poll_secs=0)
        base = np.arange(4, dtype=np.float32)
        answers: dict = {}
        errors: list = []
        lock = threading.Lock()

        def one(i):
            try:
                out = gw.predict([base + i], timeout=60.0)[0]
                with lock:
                    answers[i] = out
            except Exception as e:  # noqa: BLE001 - asserted empty below
                with lock:
                    errors.append((i, repr(e)))

        # phase 1: sequential probes until the sever demonstrably fired
        # (the severed round itself retries on the peer and still answers)
        start = _drive_until_fault_fires(gw, one)
        # phase 2: concurrent burst for exactly-once correctness
        threads = []
        n = 16
        for wave in range(n // 4):
            ws = [threading.Thread(target=one, args=(start + wave * 4 + j,))
                  for j in range(4)]
            threads += ws
            for t in ws:
                t.start()
            time.sleep(0.05)
        for t in threads:
            t.join()
        assert not errors, errors[:3]
        assert sorted(answers) == list(range(start + n))
        for i, out in answers.items():
            np.testing.assert_allclose(out, (base + i) * 2.0)
        # the LIVE severed replica must rejoin without any restart
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline and len(gw.healthy_replicas()) < 2:
            time.sleep(0.5)
        assert gw.healthy_replicas() == [0, 1]
        np.testing.assert_allclose(gw.predict([base], timeout=60.0)[0],
                                   base * 2.0)
        assert telemetry.counter("elastic.restarts_total").value() == 0
    finally:
        cluster.shutdown(timeout=120.0)


@pytest.mark.chaos
def test_serving_survives_replica_kill_with_exactly_one_answer_each(
        tmp_path, monkeypatch):
    """SIGKILL a serving replica mid-flight (TOS_FAULTINJECT=kill on its
    3rd consumed batch): the in-flight batch retries once on the survivor,
    every accepted request is answered exactly once with the right result,
    and the elastic supervisor brings the slot back."""
    monkeypatch.setenv("TOS_DEAD_NODE_TIMEOUT", "4")
    monkeypatch.setenv("TOS_RESTART_BACKOFF_BASE", "0.2")
    telemetry.reset()
    cluster, export = _serve_cluster(
        tmp_path, scale=2.0, max_batch=4, elastic=True,
        per_node_env=[{}, {"TOS_FAULTINJECT":
                           "kill:after_batches=3,incarnation=0"}])
    try:
        gw = cluster.serve(export, max_batch=4, max_delay_ms=2.0,
                           listen=False, reload_poll_secs=0)
        base = np.arange(4, dtype=np.float32)
        answers: dict = {}
        errors: list = []
        lock = threading.Lock()

        def one(i):
            try:
                out = gw.predict([base + i], timeout=90.0)[0]
                with lock:
                    answers[i] = out
            except Exception as e:  # noqa: BLE001 - asserted empty below
                with lock:
                    errors.append((i, repr(e)))

        # phase 1: sequential probes until the kill demonstrably fired —
        # the batch whose consumption triggers the SIGKILL is in flight on
        # the victim, so its failure IS the retry-on-survivor path
        start = _drive_until_fault_fires(gw, one)
        # phase 2: concurrent burst (replica 0 only until recovery)
        threads = []
        n = 16
        for wave in range(n // 4):
            ws = [threading.Thread(target=one, args=(start + wave * 4 + j,))
                  for j in range(4)]
            threads += ws
            for t in ws:
                t.start()
            time.sleep(0.05)
        for t in threads:
            t.join()
        # exactly once each: every accepted request answered, correctly
        assert not errors, errors[:3]
        assert sorted(answers) == list(range(start + n))
        for i, out in answers.items():
            np.testing.assert_allclose(out, (base + i) * 2.0)
        # the in-flight batch on the killed replica really was retried
        assert telemetry.counter("serve.retries_total").value() >= 1
        # the supervised restart re-admits the slot into routing
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline and len(gw.healthy_replicas()) < 2:
            time.sleep(0.5)
        assert gw.healthy_replicas() == [0, 1]
        np.testing.assert_allclose(gw.predict([base], timeout=60.0)[0],
                                   base * 2.0)
    finally:
        cluster.shutdown(timeout=120.0)
    assert telemetry.counter("elastic.restarts_total").value() >= 1
