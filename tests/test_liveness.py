"""Failure-detection liveness tests (SURVEY.md §5.3; VERDICT r2 item 3).

The round-2 liveness code paths under test:
- coordinator loss mid-feed → heartbeat failures force EndOfFeed and the
  node process exits on its own (``node.py`` heartbeat loop +
  ``feeding.DataFeed`` stop_event polling);
- node SIGKILL mid-call → ``DataClient`` surfaces the lost connection at
  once, far inside ``call_timeout``, and later calls fail promptly.
"""

from __future__ import annotations

import os
import secrets
import signal
import subprocess
import sys
import threading
import time

import pytest

import tensorflowonspark_tpu as tos
from tensorflowonspark_tpu.cluster import InputMode
from tensorflowonspark_tpu.dataserver import DataClient

import mapfuns


def test_coordinator_death_unblocks_node(tmp_path):
    """Driver dies mid-feed (no EOF ever sent): the node must ride out the
    self-fence grace (parking, then giving up at 4x
    TOS_COORDINATOR_GRACE_SECS — tuned tight here) and exit on its own
    instead of wedging on the empty feed (reference feed_timeout semantics,
    ``TFSparkNode.py:~460-490``; the park-then-give-up ladder is ISSUE 13's
    zombie self-fence)."""
    cluster = tos.run(
        mapfuns.sum_batches,
        {"out_dir": str(tmp_path), "batch_size": 4},
        num_executors=1,
        input_mode=InputMode.STREAMING,
        reservation_timeout=60,
        heartbeat_interval=0.3,
        # park at 1s of silence, give up (forced end-of-feed) at 4s
        env={"TOS_COORDINATOR_GRACE_SECS": "1"},
    )
    client = cluster._client(0)
    client.feed_partition(range(10))  # node consumed a partition, now blocked
    t0 = time.monotonic()
    cluster.coordinator.stop()  # the "driver crash": no EOF, no stop signal
    # 3 failed heartbeats at 0.3s spacing plus connect/teardown slack; some
    # headroom over the ~1s design point because concurrent XLA compiles can
    # starve this process on a 1-core CI box, but tight enough that a
    # teardown regression into tens of seconds still fails the gate
    assert cluster.launcher.join(timeout=30.0), (
        "node did not exit after coordinator loss"
    )
    elapsed = time.monotonic() - t0
    assert [p.exitcode for p in cluster.launcher.processes] == [0]
    # the forced EndOfFeed let map_fun finish cleanly: its output exists
    assert (tmp_path / "node_0.txt").read_text().split()[1] == "10"
    assert elapsed < 30.0
    for c in cluster._clients.values():
        c.close()


def _spawn_dataserver_child(authkey: bytes) -> tuple[subprocess.Popen, int]:
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    child = subprocess.Popen(
        [sys.executable, os.path.join(os.path.dirname(__file__), "dataserver_child.py"),
         authkey.hex()],
        stdout=subprocess.PIPE, text=True, env=env)
    port = int(child.stdout.readline())
    return child, port


@pytest.mark.parametrize("call", [
    # no consumer drains the output queue: the collect round trips never
    # bring a result, and the child is killed while one of them waits
    pytest.param(lambda c: c.infer_partition([1, 2, 3]), id="infer_partition"),
    # no consumer drains the input queue either: past its 1,024 slots the
    # server sits on a chunk's ack (backpressure) when the child is killed
    pytest.param(lambda c: c.feed_partition(range(5000)), id="feed_partition"),
])
def test_node_sigkill_mid_call_raises_promptly(call):
    """SIGKILL the node process while a request is in flight: the kernel
    closes the dead process's socket, so the client sees the loss at once
    (far inside ``call_timeout``), and a later call on the same client fails
    promptly instead of hanging."""
    authkey = secrets.token_bytes(16)
    child, port = _spawn_dataserver_child(authkey)
    try:
        client = DataClient("127.0.0.1", port, authkey, call_timeout=120.0)
        errors: list[BaseException] = []

        def _call():
            try:
                call(client)
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        t = threading.Thread(target=_call)
        t.start()
        time.sleep(0.5)  # let the request land on the server
        t0 = time.monotonic()
        os.kill(child.pid, signal.SIGKILL)
        t.join(timeout=15.0)
        assert not t.is_alive(), "the call outlived the node by 15 s"
        assert time.monotonic() - t0 < 10.0
        assert errors and isinstance(
            errors[0], (RuntimeError, ConnectionError, OSError, EOFError)), errors
        with pytest.raises((RuntimeError, ConnectionError, OSError)):
            client.send_eof("input")
    finally:
        if child.poll() is None:
            child.kill()
        child.wait(10)
