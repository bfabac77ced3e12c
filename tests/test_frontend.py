"""Reactor gateway frontend (ISSUE 7): pipelined multiplexed connections,
zero-copy out-of-order responses, adversarial clients.

Layers under test, bottom-up:

- decoder/batcher units — incremental v1/v2 frame parse (byte-dribbled
  input, oversized/corrupt frames), done-callback + cancel semantics of
  the batcher (the reactor's completion path);
- end-to-end — a real 2-node serving cluster behind the reactor endpoint:
  a pipelined ``GatewayClient`` with many requests outstanding on one
  socket, the ``GatewayClientPool``, and WIRE COMPATIBILITY — the
  pre-reactor one-request-per-round-trip caller (id-less predict frames,
  v2 AND legacy v1 framing) must keep round-tripping (ISSUE 7 acceptance);
- adversarial connections — a slow-loris peer parked mid-frame must not
  stall other clients, a malformed frame must end in a clean disconnect
  with the reactor (and every other connection) alive, a handshake that
  stalls must be reaped within ``TOS_SERVE_HANDSHAKE_TIMEOUT``, and a
  client that disconnects with requests in flight must have its batcher
  admission slots released;
- chaos — SIGKILL a replica mid-pipelined-burst: every request accepted
  on the pipelined connection is still answered exactly once.
"""

from __future__ import annotations

import struct
import threading
import time

import numpy as np
import pytest

from tensorflowonspark_tpu import cluster as tcluster
from tensorflowonspark_tpu import serving, telemetry
from tensorflowonspark_tpu.checkpoint import export_bundle
from tensorflowonspark_tpu.dataserver import _recv, _send
from tensorflowonspark_tpu.models import linear as linmod
from tensorflowonspark_tpu.serving import (
    GatewayClient,
    GatewayClientPool,
    LegacyGatewayClient,
    MicroBatcher,
    ServeClosed,
)
from tensorflowonspark_tpu.serving.frontend import (
    _INCOMPLETE,
    FrameDecoder,
    ProtocolError,
)
from tensorflowonspark_tpu.utils.net import (
    connect_with_backoff,
    hmac_handshake_client,
)

LINEAR = {"model": "linear", "in_dim": 4, "out_dim": 4}


# -- decoder units ------------------------------------------------------------


def test_frame_decoder_incremental_both_formats():
    """Frames dribbled in one byte at a time decode exactly once each, for
    legacy v1 and zero-copy v2 framing interleaved on one stream."""
    from tensorflowonspark_tpu.dataserver import frame_parts

    msgs = [("predict", [np.arange(4, dtype=np.float32)], None, 7),
            ("ping",),
            ("predict", [b"x" * 8192], 1.5, 8)]
    wire = b"".join(
        bytes(memoryview(p).cast("B"))
        for i, m in enumerate(msgs)
        for p in frame_parts(m, wire=2 if i % 2 == 0 else 1))
    dec = FrameDecoder()
    out = []
    for i in range(len(wire)):
        dec.feed(wire[i:i + 1])
        while True:
            obj = dec.next_frame()
            if obj is _INCOMPLETE:
                break
            out.append(obj)
    assert len(out) == 3
    assert out[1] == ("ping",)
    assert out[0][0] == "predict" and out[0][3] == 7
    np.testing.assert_array_equal(out[0][1][0], np.arange(4, dtype=np.float32))
    assert out[2][1][0] == b"x" * 8192
    assert not dec.buf  # fully consumed


def test_frame_decoder_rejects_oversized_and_corrupt_frames():
    from tensorflowonspark_tpu.serving import frontend

    dec = FrameDecoder()
    dec.feed(struct.pack(">Q", frontend.MAX_REQUEST_FRAME + 1))
    with pytest.raises(ProtocolError, match="oversized"):
        dec.next_frame()
    # a plausible length word followed by junk bytes is a protocol error,
    # not a reactor-killing exception of whatever type pickle feels like
    dec2 = FrameDecoder()
    dec2.feed(struct.pack(">Q", 16) + b"not-a-pickle-ever")
    with pytest.raises(ProtocolError, match="undecodable"):
        dec2.next_frame()


# -- batcher completion-path units --------------------------------------------


def test_batcher_done_callbacks_fire_off_lock_and_cancel_releases_slot():
    dispatched: list = []
    ref: list = [None]
    b = MicroBatcher(dispatched.append, max_batch=4, max_delay_secs=10.0,
                     queue_limit=2, pause_fn=lambda: True)  # nothing flushes
    ref[0] = b
    try:
        fired: list = []
        req1 = b.submit([1.0], time.monotonic() + 30.0)
        b.add_done_callback(req1, lambda r: fired.append(("cb1", r.error)))
        req2 = b.submit([2.0], time.monotonic() + 30.0)
        # queue_limit=2 reached: admission is full until a slot frees
        with pytest.raises(serving.ServeQueueFull):
            b.submit([3.0], time.monotonic() + 30.0)
        # cancel releases the queued slot without any replica work...
        b.cancel(req1)
        assert fired and fired[0][0] == "cb1"
        assert isinstance(fired[0][1], ServeClosed)
        assert telemetry.counter("serve.cancelled_total").value() >= 1
        # ...so admission admits again
        req3 = b.submit([3.0], time.monotonic() + 30.0)
        # a callback added to an ALREADY-resolved request runs immediately
        late: list = []
        b.add_done_callback(req1, lambda r: late.append(r.error))
        assert len(late) == 1
        # close resolves the rest and fires their callbacks too
        done: list = []
        for r in (req2, req3):
            b.add_done_callback(r, lambda rr: done.append(rr.error))
        b.close()
        assert len(done) == 2
        assert all(isinstance(e, ServeClosed) for e in done)
        assert not dispatched  # paused throughout: nothing ever dispatched
    finally:
        b.close()


def test_batcher_expire_is_idempotent_and_fires_callback_once():
    ref: list = [None]
    b = MicroBatcher(lambda batch: None, max_batch=4, max_delay_secs=10.0,
                     queue_limit=8, pause_fn=lambda: True)
    ref[0] = b
    try:
        req = b.submit([1.0], time.monotonic() + 0.05)
        fired: list = []
        b.add_done_callback(req, lambda r: fired.append(r.error))
        b.expire(req)
        b.expire(req)  # second call is a no-op
        b.cancel(req)  # and cancel after resolve is a no-op too
        assert len(fired) == 1
        assert isinstance(fired[0], serving.ServeTimeout)
    finally:
        b.close()


# -- end-to-end over the reactor endpoint -------------------------------------


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


def _handshaked_raw_conn(endpoint, authkey):
    sock = connect_with_backoff((endpoint[0], endpoint[1]), timeout=10.0)
    sock.settimeout(30.0)
    assert hmac_handshake_client(sock, authkey)
    return sock


def test_pipelined_clients_pool_and_wire_compat(tmp_path):
    telemetry.reset()
    cluster, export = _serve_cluster(tmp_path, scale=2.0, max_batch=4)
    try:
        gw = cluster.serve(export, max_batch=4, max_delay_ms=5.0,
                           listen_host="127.0.0.1", reload_poll_secs=0)
        host, port = gw.endpoint
        base = np.arange(4, dtype=np.float32)

        # pipelined: MANY requests outstanding on ONE socket, resolved by
        # id as their batches complete (spans several batches: 24 rows at
        # max_batch=4)
        client = GatewayClient(host, port, cluster.authkey)
        try:
            futs = [client.predict_async([base + i], timeout=60.0)
                    for i in range(24)]
            for i, fut in enumerate(futs):
                np.testing.assert_allclose(fut.result()[0], (base + i) * 2.0)
            assert client.outstanding() == 0
            # closed-loop predict still works on the same socket
            np.testing.assert_allclose(
                client.predict([base], timeout=60.0)[0], base * 2.0)
            assert client.ping()
        finally:
            client.close()

        # an IDLE pipelined client must survive past call_timeout: the
        # resident receiver's socket timeout is quiet time, not an error
        # (a poisoned idle pool was the review regression)
        idler = GatewayClient(host, port, cluster.authkey, call_timeout=1.0)
        try:
            np.testing.assert_allclose(
                idler.predict([base], timeout=60.0)[0], base * 2.0)
            time.sleep(2.2)  # > call_timeout with nothing outstanding
            np.testing.assert_allclose(
                idler.predict([base], timeout=60.0)[0], base * 2.0)
        finally:
            idler.close()

        # pooled client: caller threads share pooled pipelined connections
        pool = GatewayClientPool(host, port, cluster.authkey, size=2)
        try:
            results: dict = {}
            errors: list = []

            def one(i):
                try:
                    results[i] = pool.predict([base + i], timeout=60.0)[0]
                except Exception as e:  # noqa: BLE001 - asserted empty below
                    errors.append(repr(e))

            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(12)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert not errors, errors[:3]
            for i in range(12):
                np.testing.assert_allclose(results[i], (base + i) * 2.0)
            assert pool.ping()
        finally:
            pool.close()

        # WIRE COMPATIBILITY (acceptance): the pre-reactor one-request-per-
        # round-trip client — id-less predict frames — still round-trips
        legacy = LegacyGatewayClient(host, port, cluster.authkey)
        try:
            assert legacy.ping()
            out = legacy.predict([base, base + 1], timeout=60.0)
            np.testing.assert_allclose(out[1], (base + 1) * 2.0)
        finally:
            legacy.close()

        # ...including over legacy v1 (plain-pickle) framing
        sock = _handshaked_raw_conn(gw.endpoint, cluster.authkey)
        try:
            _send(sock, ("predict", [base + 5], None), wire=1)
            reply = _recv(sock)
            assert reply[0] == "ok"
            np.testing.assert_allclose(reply[1][0], (base + 5) * 2.0)
        finally:
            sock.close()

        # frontend telemetry reached the registry
        reg = telemetry.get_registry()
        assert telemetry.counter("serve.frontend.frames_in").value() >= 40
        # out-frames are FEWER than requests: one scatter's replies to a
        # pipelined peer coalesce into a single multi-reply (okm) frame
        assert telemetry.counter("serve.frontend.frames_out").value() >= 10
        assert reg.histogram("serve.frontend.loop_lag_secs").count >= 1
        # the reactor notices client EOFs asynchronously
        deadline = time.monotonic() + 10.0
        while (telemetry.gauge("serve.frontend.connections").value() != 0
               and time.monotonic() < deadline):
            time.sleep(0.05)
        assert telemetry.gauge("serve.frontend.connections").value() == 0
    finally:
        cluster.shutdown(timeout=120.0)


def test_adversarial_connections_do_not_stall_the_reactor(tmp_path):
    """Slow-loris partial frames, malformed frames, handshake stalls, and
    disconnects with requests in flight: one reactor survives all four with
    a healthy client round-tripping throughout."""
    telemetry.reset()
    cluster, export = _serve_cluster(tmp_path, scale=2.0, max_batch=4)
    try:
        gw = cluster.serve(export, max_batch=4, max_delay_ms=5.0,
                           listen_host="127.0.0.1", reload_poll_secs=0,
                           handshake_timeout=1.0)
        base = np.arange(4, dtype=np.float32)
        healthy = GatewayClient(*gw.endpoint, cluster.authkey)
        try:
            np.testing.assert_allclose(
                healthy.predict([base], timeout=60.0)[0], base * 2.0)

            # 1) slow loris: a frame header promising 4096 bytes, 10 sent,
            # connection parked — other clients must keep round-tripping
            loris = _handshaked_raw_conn(gw.endpoint, cluster.authkey)
            loris.sendall(struct.pack(">Q", 4096) + b"\x80" * 10)
            for i in range(5):
                np.testing.assert_allclose(
                    healthy.predict([base + i], timeout=60.0)[0],
                    (base + i) * 2.0)

            # 2) malformed frame: junk pickle bytes -> clean disconnect of
            # THAT connection, reactor alive
            bad = _handshaked_raw_conn(gw.endpoint, cluster.authkey)
            bad.sendall(struct.pack(">Q", 16) + b"junk" * 4)
            deadline = time.monotonic() + 10.0
            got = b"pending"
            while got and time.monotonic() < deadline:
                got = bad.recv(4096)  # drains to EOF once the server closes
            assert got == b"", "malformed-frame connection was not closed"
            bad.close()
            assert telemetry.counter(
                "serve.frontend.protocol_errors").value() >= 1
            np.testing.assert_allclose(
                healthy.predict([base], timeout=60.0)[0], base * 2.0)

            # 3) handshake stall: connect, never answer the challenge ->
            # reaped within the (1s) handshake timeout
            staller = connect_with_backoff(gw.endpoint, timeout=10.0)
            staller.settimeout(30.0)
            t0 = time.monotonic()
            chunks = [staller.recv(4096)]  # server nonce
            while chunks[-1]:  # then EOF when the reactor reaps us
                chunks.append(staller.recv(4096))
            assert time.monotonic() - t0 < 15.0
            staller.close()
            assert telemetry.counter(
                "serve.frontend.handshake_timeouts").value() >= 1

            # 4) disconnect with requests in flight releases batcher slots:
            # a second gateway whose batcher coalesces for 2s holds the
            # requests queued, so the cancel path is deterministic
            gw2 = cluster.serve(export, max_batch=64, max_delay_ms=2000.0,
                                listen_host="127.0.0.1", reload_poll_secs=0)
            goner = _handshaked_raw_conn(gw2.endpoint, cluster.authkey)
            before = telemetry.counter("serve.cancelled_total").value()
            for i in range(3):
                _send(goner, ("predict", [base + i], 60.0, i + 1), wire=2)
            time.sleep(0.2)  # let the reactor admit all three
            goner.close()
            loris.close()
            deadline = time.monotonic() + 10.0
            while (telemetry.counter("serve.cancelled_total").value()
                   < before + 3 and time.monotonic() < deadline):
                time.sleep(0.05)
            assert (telemetry.counter("serve.cancelled_total").value()
                    >= before + 3), "disconnect did not cancel queued requests"
            # the frontends end with zero outstanding wire requests and the
            # healthy client is still served
            deadline = time.monotonic() + 10.0
            while (telemetry.gauge("serve.frontend.outstanding").value() != 0
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            assert telemetry.gauge("serve.frontend.outstanding").value() == 0
            np.testing.assert_allclose(
                healthy.predict([base], timeout=60.0)[0], base * 2.0)
        finally:
            healthy.close()
    finally:
        cluster.shutdown(timeout=120.0)


def test_per_connection_outstanding_cap_fast_fails(tmp_path):
    """The per-connection pipelining cap answers 'unavailable' (503)
    synchronously on the reactor — no thread handoff, connection intact."""
    telemetry.reset()
    cluster, export = _serve_cluster(tmp_path, scale=2.0, max_batch=4)
    try:
        gw = cluster.serve(export, max_batch=4, max_delay_ms=2000.0,
                           listen_host="127.0.0.1", reload_poll_secs=0,
                           max_conn_outstanding=2, queue_limit=64)
        base = np.arange(4, dtype=np.float32)
        client = GatewayClient(*gw.endpoint, cluster.authkey)
        try:
            # max_delay=2s + max_batch=4 means 1-row requests sit queued:
            # the 3rd outstanding request on this connection must fast-fail
            futs = [client.predict_async([base], timeout=30.0)
                    for _ in range(6)]
            outcomes = []
            for fut in futs:
                try:
                    fut.result()
                    outcomes.append("ok")
                except serving.ServeQueueFull:
                    outcomes.append("throttled")
            assert outcomes.count("throttled") >= 1
            assert telemetry.counter(
                "serve.frontend.throttled_total").value() >= 1
            # the connection survives throttling
            np.testing.assert_allclose(
                client.predict([base], timeout=60.0)[0], base * 2.0)
        finally:
            client.close()
    finally:
        cluster.shutdown(timeout=120.0)


@pytest.mark.chaos
def test_chaos_replica_kill_mid_pipelined_burst_answers_every_request(
        tmp_path, monkeypatch):
    """SIGKILL a serving replica while a pipelined TCP burst is in flight:
    every request accepted on the multiplexed connection is answered
    exactly once with the right result (retry-on-survivor underneath), and
    the slot recovers."""
    monkeypatch.setenv("TOS_DEAD_NODE_TIMEOUT", "4")
    monkeypatch.setenv("TOS_RESTART_BACKOFF_BASE", "0.2")
    telemetry.reset()
    cluster, export = _serve_cluster(
        tmp_path, scale=2.0, max_batch=4, elastic=True,
        per_node_env=[{}, {"TOS_FAULTINJECT":
                           "kill:after_batches=3,incarnation=0"}])
    try:
        gw = cluster.serve(export, max_batch=4, max_delay_ms=2.0,
                           listen_host="127.0.0.1", reload_poll_secs=0)
        base = np.arange(4, dtype=np.float32)
        client = GatewayClient(*gw.endpoint, cluster.authkey)
        try:
            # phase 1: sequential probes until the kill demonstrably fired
            # (the victim's batch is in flight -> retry-on-survivor path)
            i = 0
            deadline = time.monotonic() + 90.0
            while (telemetry.counter("serve.replica_failures").value() == 0
                   and time.monotonic() < deadline):
                np.testing.assert_allclose(
                    client.predict([base + i], timeout=90.0)[0],
                    (base + i) * 2.0)
                i += 1
            assert telemetry.counter("serve.replica_failures").value() >= 1, \
                f"fault never fired after {i} sequential requests"
            # phase 2: pipelined burst while the survivor carries the load
            futs = [(j, client.predict_async([base + j], timeout=90.0))
                    for j in range(i, i + 32)]
            for j, fut in futs:
                np.testing.assert_allclose(fut.result()[0], (base + j) * 2.0)
            assert client.outstanding() == 0
            # the in-flight batch on the killed replica really was retried
            assert telemetry.counter("serve.retries_total").value() >= 1
            # the supervised restart re-admits the slot into routing
            deadline = time.monotonic() + 60.0
            while (time.monotonic() < deadline
                   and len(gw.healthy_replicas()) < 2):
                time.sleep(0.5)
            assert gw.healthy_replicas() == [0, 1]
        finally:
            client.close()
    finally:
        cluster.shutdown(timeout=120.0)
    assert telemetry.counter("elastic.restarts_total").value() >= 1
