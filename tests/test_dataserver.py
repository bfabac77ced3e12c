"""Data-plane server/client tests (replaces the reference's manager-queue
feeding paths, SURVEY.md §3.2/§3.3)."""

import threading
import time

import numpy as np
import pytest

from tensorflowonspark_tpu.dataserver import DataClient, DataServer
from tensorflowonspark_tpu.feeding import DataFeed, FeedQueues

AUTH = b"secret"


def start_pair(feed_timeout=5.0, capacity=1024, **client_opts):
    queues = FeedQueues(capacity=capacity)
    server = DataServer(queues, AUTH, feed_timeout=feed_timeout)
    port = server.start()
    client = DataClient("127.0.0.1", port, AUTH,
                        **{"chunk_size": 8, "stall_timeout": feed_timeout,
                           **client_opts})
    return queues, server, client


def serve_model(queues, fn):
    """A map_fun's inference loop on a thread: results are ``fn`` of each
    row, in order."""
    def model():
        feed = DataFeed(queues, train_mode=False)
        while not feed.should_stop():
            batch = feed.next_batch(4)
            if batch:
                feed.batch_results([fn(x) for x in batch])

    t = threading.Thread(target=model, daemon=True)
    t.start()
    return t


# 200 KiB rows: one chunk of four is far past a socket buffer, so a frame
# is sent and received in many pieces, in both directions
BIG = b"B" * (200 * 1024)
BIG_ROWS = [BIG, BIG, b"small", BIG, BIG]
PARTITIONS = [
    pytest.param(list(range(20)), {}, id="ints"),
    pytest.param(BIG_ROWS, {"chunk_size": 4, "send_window": 1},
                 id="200KiB-rows-window1"),
    pytest.param(BIG_ROWS, {"chunk_size": 4, "send_window": 4},
                 id="200KiB-rows-window4"),
]


@pytest.mark.parametrize("rows,client_opts", PARTITIONS)
def test_feed_partition_and_markers(rows, client_opts):
    queues, server, client = start_pair(**client_opts)
    feed = DataFeed(queues)
    state = client.feed_partition(rows)
    assert state == "running"
    client.send_eof()
    assert feed.next_batch(100) == rows
    assert feed.next_batch(1) == []
    assert feed.should_stop()
    client.close()
    server.stop()


def test_auth_rejected():
    queues = FeedQueues()
    server = DataServer(queues, AUTH)
    port = server.start()
    with pytest.raises(RuntimeError, match="auth"):
        DataClient("127.0.0.1", port, b"wrong")
    server.stop()


@pytest.mark.parametrize("rows,client_opts", [
    pytest.param(list(range(30)), {}, id="ints"),
    *PARTITIONS[1:],          # replies of 600 KiB a row
])
def test_infer_exactly_count_ordered(rows, client_opts):
    queues, server, client = start_pair(**client_opts)
    t = serve_model(queues, lambda x: x * 3)
    results = client.infer_partition(rows)
    assert results == [x * 3 for x in rows]
    client.send_eof()
    t.join(5)
    client.close()
    server.stop()


def test_infer_empty_partition():
    queues, server, client = start_pair()
    assert client.infer_partition([]) == []
    client.close()
    server.stop()


def test_terminating_fast_drain():
    queues, server, client = start_pair()
    feed = DataFeed(queues)
    feed.terminate()
    state = client.feed_partition(range(10_000))
    assert state == "terminating"
    client.close()
    server.stop()


def test_feed_timeout_when_consumer_stalls():
    queues, server, client = start_pair(feed_timeout=0.3, capacity=4)
    with pytest.raises(RuntimeError, match="feed timeout"):
        client.feed_partition(range(100))
    client.close()
    server.stop()


def test_infer_timeout_when_model_absent():
    queues, server, client = start_pair(feed_timeout=0.3)
    with pytest.raises(RuntimeError, match="inference produced"):
        client.infer_partition([1, 2, 3])
    client.close()
    server.stop()


def test_tcp_path_works_with_the_client_defaults():
    """No option given: the default chunk (512 rows) and send window carry a
    partition of several chunks and a short tail."""
    queues = FeedQueues(capacity=4096)
    server = DataServer(queues, AUTH, feed_timeout=5.0)
    client = DataClient("127.0.0.1", server.start(), AUTH)
    feed = DataFeed(queues)
    assert client.feed_partition(range(1300)) == "running"
    client.send_eof()
    assert feed.next_batch(2000) == list(range(1300))
    client.close()
    server.stop()


# -- zero-copy wire format (ISSUE 3 tentpole) ---------------------------------


def test_wire_negotiates_v2_and_packs_chunks():
    """Current client x current server negotiate the vectorized wire and
    round-trip packed bytes/ndarray/tuple/dict chunks bit-identically."""
    queues, server, client = start_pair()
    assert client._wire >= 2  # vectorized wire (v3 = v2 frames + trace ops)
    feed = DataFeed(queues)
    byte_rows = [bytes([i]) * 4096 for i in range(20)]
    assert client.feed_partition(byte_rows) == "running"
    assert feed.next_batch(100) == byte_rows
    arr_rows = [np.full((4, 3), i, np.float32) for i in range(10)]
    assert client.feed_partition(arr_rows) == "running"
    got = feed.next_batch(100)
    assert all(np.array_equal(a, b) and a.dtype == b.dtype
               for a, b in zip(arr_rows, got))
    tup_rows = [(np.arange(6, dtype=np.int64) + i, i) for i in range(10)]
    assert client.feed_partition(tup_rows) == "running"
    got = feed.next_batch(100)
    assert all(np.array_equal(a[0], b[0]) and a[1] == b[1]
               for a, b in zip(tup_rows, got))
    dict_rows = [{"x": np.ones(3, np.float32) * i, "label": i}
                 for i in range(10)]
    assert client.feed_partition(dict_rows) == "running"
    got = feed.next_batch(100)
    assert all(np.array_equal(a["x"], b["x"]) and a["label"] == b["label"]
               for a, b in zip(dict_rows, got))
    client.close()
    server.stop()


def test_wire_v2_roundtrip_values_exact():
    queues, server, client = start_pair()
    feed = DataFeed(queues)
    rows = [bytes([i]) * 1000 for i in range(16)]
    client.feed_partition(rows)
    assert feed.next_batch(100) == rows
    arrs = [np.full((5, 2), i, np.int64) for i in range(8)]
    client.feed_partition(arrs)
    got = feed.next_batch(100)
    assert all(np.array_equal(a, b) and a.dtype == b.dtype
               for a, b in zip(arrs, got))
    dicts = [{"x": np.full(4, i, np.float32), "y": float(i)} for i in range(6)]
    client.feed_partition(dicts)
    got = feed.next_batch(100)
    assert all(np.array_equal(a["x"], b["x"]) and a["y"] == b["y"]
               for a, b in zip(dicts, got))
    client.close()
    server.stop()


def test_old_server_negotiates_down_to_v1():
    """A server that predates the hello op answers unknown-op; the client
    must stay on the v1 wire and still feed correctly (auto-negotiation)."""
    from tensorflowonspark_tpu import dataserver as ds

    queues = FeedQueues(capacity=1024)
    server = DataServer(queues, AUTH, feed_timeout=5.0)
    orig_handle = ds.DataServer._handle

    def legacy_handle(self, msg):
        if msg[0] == "hello":  # old servers have no hello branch
            return ("err", f"unknown op {msg[0]!r}")
        return orig_handle(self, msg)

    server._handle = legacy_handle.__get__(server)
    port = server.start()
    client = DataClient("127.0.0.1", port, AUTH, chunk_size=8)
    assert client._wire == 1
    feed = DataFeed(queues)
    rows = [bytes([i]) * 256 for i in range(20)]
    assert client.feed_partition(rows) == "running"
    assert feed.next_batch(100) == rows
    client.close()
    server.stop()


def test_v1_client_against_current_server():
    """A legacy client (plain length-framed pickle, no hello) must keep
    working against the new server: v1 frames get v1 replies."""
    import pickle
    import socket
    import struct

    from tensorflowonspark_tpu.utils.net import (
        hmac_handshake_client, recv_exact)

    queues = FeedQueues(capacity=1024)
    server = DataServer(queues, AUTH, feed_timeout=5.0)
    port = server.start()
    sock = socket.create_connection(("127.0.0.1", port), timeout=10)
    assert hmac_handshake_client(sock, AUTH)
    LEN = struct.Struct(">Q")

    def v1_call(msg):
        data = pickle.dumps(msg, protocol=4)
        sock.sendall(LEN.pack(len(data)) + data)
        (n,) = LEN.unpack(recv_exact(sock, 8))
        assert n < (1 << 62), "reply must be a v1 frame for a v1 peer"
        return pickle.loads(recv_exact(sock, n))

    assert v1_call(("feed", "input", [1, 2, 3])) == ("ok", "running")
    reply = v1_call(("end_partition", "input", None))
    assert reply[0] == "ok"
    feed = DataFeed(queues)
    assert feed.next_batch(10) == [1, 2, 3]
    v1_call(("close",))
    sock.close()
    server.stop()


def test_pipelined_window_preserves_order_and_terminating():
    """send_window > 1 pipelines chunk frames; ordering is preserved and a
    mid-stream 'terminating' still stops the feed fast."""
    queues, server, client = start_pair()
    client.send_window = 8
    feed = DataFeed(queues)
    items = list(range(200))
    assert client.feed_partition(items) == "running"
    got = feed.next_batch(500)
    assert got == items  # in-order delivery across the pipelined window
    feed.terminate()
    assert client.feed_partition(range(10_000)) == "terminating"
    client.close()
    server.stop()


def test_pipelined_window_one_is_strict_ping_pong():
    queues, server, client = start_pair()
    client.send_window = 1
    feed = DataFeed(queues)
    assert client.feed_partition(range(50)) == "running"
    assert feed.next_batch(100) == list(range(50))
    client.close()
    server.stop()


def test_feed_timeout_error_surfaces_through_pipeline():
    """An err reply (server-side feed timeout) mid-burst must surface as the
    same RuntimeError the unpipelined path raised."""
    queues, server, client = start_pair(feed_timeout=0.3, capacity=4)
    client.send_window = 4
    with pytest.raises(RuntimeError, match="feed timeout"):
        client.feed_partition(range(100))
    client.close()
    server.stop()


def _packed_rows():
    return [np.full((64, 64), i, np.float32) for i in range(6)]  # >= 4KB: packed


def _mixed_rows():
    # mixed shapes >= 4KB: pack_chunk refuses, so numpy's OWN protocol-5
    # reduce puts these out-of-band — the plain-row receive path
    # reconstructs them from views of the receive blob
    return [np.full((64, 64), 1.0, np.float32),
            np.full((32, 64), 2.0, np.float32)]


def _structured_rows():
    # structured dtypes are excluded from columnar packing (dtype.str would
    # collapse them to raw void) and travel via numpy's own reduce
    dt = np.dtype([("a", "<f4"), ("b", "<i4")])
    rows = [np.zeros(2048, dtype=dt) for _ in range(3)]  # >= 4KB each
    for i, r in enumerate(rows):
        r["a"] += i
        r["b"] += 10 * i
    return rows


@pytest.mark.parametrize("make_rows", [_packed_rows, _mixed_rows])
def test_received_ndarrays_are_writable(make_rows):
    """Pickled ndarrays were always writable; the zero-copy receive path
    must not hand user code read-only arrays, packed or not."""
    batch = make_rows()
    queues, server, client = start_pair()
    feed = DataFeed(queues)
    client.feed_partition(batch)
    got = feed.next_batch(10)
    for a, b in zip(batch, got):
        assert np.array_equal(a, b)
        assert b.flags.writeable
        b += 1.0  # in-place mutation (the map_fun normalize idiom)
    client.close()
    server.stop()


def _same_row(a, b) -> bool:
    if isinstance(a, bytes):
        return a == b
    return a.dtype == b.dtype and a.shape == b.shape and a.tobytes() == b.tobytes()


@pytest.mark.parametrize("op", ["feed_partition", "infer_partition"])
@pytest.mark.parametrize("make_rows,packs", [
    pytest.param(lambda: [bytes([i]) * 4096 for i in range(10)], True,
                 id="bytes"),
    pytest.param(_packed_rows, True, id="packed-ndarrays"),
    pytest.param(_mixed_rows, False, id="mixed-ndarrays"),
    pytest.param(_structured_rows, False, id="structured-dtype"),
])
def test_payload_kinds_round_trip(make_rows, packs, op):
    """Every kind of row the wire frames differently (columnar-packed or
    not, out-of-band buffers or in-band) comes back value- and dtype-exact,
    towards the node (``feed_partition``) and, as results, from it
    (``infer_partition`` through a model that echoes its rows)."""
    from tensorflowonspark_tpu.data import pack_chunk

    rows = make_rows()
    assert (pack_chunk(rows) is not None) == packs
    queues, server, client = start_pair()
    if op == "feed_partition":
        client.feed_partition(rows)
        got = DataFeed(queues).next_batch(100)
    else:
        t = serve_model(queues, lambda x: x)
        got = client.infer_partition(rows)
        client.send_eof()
        t.join(5)
    assert len(got) == len(rows)
    assert all(_same_row(a, b) for a, b in zip(rows, got))
    client.close()
    server.stop()


# -- bounded waits on the socket ----------------------------------------------


def _wedge(server, op: str) -> threading.Event:
    """Make ``server`` sit on every ``op`` request until the returned event
    is set: a node that is alive and holds the connection, but never
    answers."""
    release = threading.Event()
    handle = server._handle

    def wedged(msg):
        if msg[0] == op:
            release.wait(30.0)
        return handle(msg)

    server._handle = wedged
    return release


def test_send_eof_to_a_wedged_node_fails_within_its_own_timeout():
    """EOF is a teardown message: a node that holds the connection open and
    never answers costs ``send_eof`` its own short timeout, not the
    connection's ``call_timeout``; the socket is then poisoned, so a late
    reply can never be read as the answer to a later call."""
    queues, server, client = start_pair(call_timeout=600.0)
    client.send_eof("input")  # healthy path works
    release = _wedge(server, "eof")
    t0 = time.monotonic()
    with pytest.raises((TimeoutError, OSError)):
        client.send_eof("input", timeout=0.5)
    assert time.monotonic() - t0 < 5.0
    release.set()
    with pytest.raises(OSError):
        client.poll_consumed("input", timeout=0.5)
    client.close()
    server.stop()


def test_close_is_bounded_against_a_wedged_node():
    """``cluster.resize`` and ``gateway.reload`` reach ``close()`` under
    their own locks: it waits ``min(10 s, call_timeout)`` for the close ack
    and then drops the socket, whatever the node does."""
    queues, server, client = start_pair(call_timeout=0.5)
    release = _wedge(server, "close")
    t0 = time.monotonic()
    client.close()
    assert time.monotonic() - t0 < 5.0
    release.set()
    server.stop()


def test_abort_wakes_a_call_blocked_on_the_socket():
    """The monitor's death path: ``abort()`` takes no lock, so it cuts a call
    that would otherwise ride out its whole ``call_timeout`` under it."""
    queues, server, client = start_pair(call_timeout=600.0)
    release = _wedge(server, "consumed")
    errors: list[BaseException] = []

    def call():
        try:
            client.poll_consumed("input", timeout=600.0)
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    t = threading.Thread(target=call, daemon=True)
    t.start()
    time.sleep(0.3)  # let the request land and the reply wait begin
    t0 = time.monotonic()
    client.abort()
    t.join(5.0)
    assert not t.is_alive() and time.monotonic() - t0 < 5.0
    assert errors and isinstance(errors[0], (OSError, EOFError)), errors
    release.set()
    server.stop()


def test_stop_returns_at_once_with_a_client_connected():
    """``stop()`` closes the listener and waits for nothing; a connection
    that is already up is served until its peer closes it or the node
    process exits (so a late EOF still lands)."""
    queues, server, client = start_pair()
    client.feed_partition(range(5))
    t0 = time.monotonic()
    server.stop()
    assert time.monotonic() - t0 < 1.0
    client.send_eof("input", timeout=5.0)
    feed = DataFeed(queues)
    assert feed.next_batch(10) == list(range(5))
    assert feed.next_batch(1) == [] and feed.should_stop()
    client.close()
