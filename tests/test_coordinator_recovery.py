"""Coordinator crash recovery (ISSUE 13): journal round-trip/replay units,
epoch fencing, supervised in-process failover, the node self-fence, and the
chaos ``kill_coordinator`` end-to-end suite.

The chaos tests are tier-1 by design, like the elastic and collective
suites: the control plane crashes on a deterministic op count
(``TOS_FAULTINJECT=kill_coordinator:after_ops=N`` armed in the DRIVER
process), the CoordinatorSupervisor replays the write-ahead journal, and
every client class — node heartbeats, ledger feed workers, collective
groups, serving routers — must resume without human intervention.  The
randomized network-degradation soak (``flap`` + ``delay_net``) is ``slow``.
"""

from __future__ import annotations

import contextlib
import json
import os
import time

import pytest

from tensorflowonspark_tpu import cluster as tcluster
from tensorflowonspark_tpu import faultinject
from tensorflowonspark_tpu.coordinator import (
    CoordinatorClient,
    CoordinatorRestarted,
    CoordinatorServer,
)
from tensorflowonspark_tpu.journal import Journal, replay
from tensorflowonspark_tpu.supervisor import CoordinatorSupervisor, RestartPolicy

import mapfuns


# -- journal units ------------------------------------------------------------


def test_journal_append_replay_round_trip(tmp_path):
    path = str(tmp_path / "j")
    j = Journal(path)
    j.append("a", {"x": 1})
    j.append("b", {"y": [1, 2]})
    j.close()
    snap, records = replay(path)
    assert snap is None
    assert [(r["k"], r["d"]) for r in records] == [("a", {"x": 1}),
                                                  ("b", {"y": [1, 2]})]
    # deterministic: a second replay is identical
    assert replay(path) == (snap, records)


def test_journal_torn_tail_is_dropped(tmp_path):
    path = str(tmp_path / "j")
    j = Journal(path)
    j.append("a", {"x": 1})
    j.append("b", {"x": 2})
    j.close()
    # simulate a crash mid-append: truncate the final record mid-line
    raw = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(raw[:-7])
    snap, records = replay(path)
    assert [r["k"] for r in records] == ["a"]
    # corruption that is NOT the tail fails loudly
    with open(path, "wb") as f:
        f.write(b'{"n": 1, "k": "a", "d"\n{"n":2,"k":"b","d":{}}\n')
    with pytest.raises(ValueError, match="corrupt journal record"):
        replay(path)


def test_journal_snapshot_truncates_and_seq_filters(tmp_path):
    path = str(tmp_path / "j")
    j = Journal(path)
    for i in range(3):
        j.append("pre", {"i": i})
    j.snapshot({"folded": 3})
    j.append("post", {"i": 99})
    j.close()
    snap, records = replay(path)
    assert snap == {"folded": 3}
    assert [(r["k"], r["d"]["i"]) for r in records] == [("post", 99)]
    # the journal file itself was truncated at snapshot time
    assert open(path, "rb").read().count(b"\n") == 1


def test_journal_fresh_run_truncates_stale_state(tmp_path):
    path = str(tmp_path / "j")
    j = Journal(path)
    j.append("old", {})
    j.snapshot({"stale": True})
    j.append("older", {})
    j.close()
    # a NEW server run opens with truncate=True: nothing of the previous
    # run's control plane may leak into this run's recovery
    Journal(path, truncate=True).close()
    assert replay(path) == (None, [])


# -- fault grammar: the network-degradation actions ---------------------------


def test_fault_plan_kill_coordinator_counts_ops():
    plan = faultinject.FaultPlan.parse("kill_coordinator:after_ops=3")
    assert not plan._tick("kill_coordinator")
    assert not plan._tick("kill_coordinator")
    assert plan._tick("kill_coordinator")
    assert not plan._tick("kill_coordinator")  # one-shot


def test_fault_plan_delay_net_and_flap_grammar():
    plan = faultinject.FaultPlan.parse("delay_net:ms=7;flap:period=1")
    assert plan.delay_ms() == 7
    # flap phase is wall-clock since arming: shift the anchor to force a
    # DOWN (odd) window, then an UP one
    plan._t0 = time.monotonic() - 1.5  # window index 1 -> down
    assert plan.flap_down()
    assert plan.flap_sever()
    assert not plan.flap_sever()  # one sever per down window
    plan._t0 = time.monotonic() - 0.5  # window index 0 -> up
    assert not plan.flap_down()
    assert not plan.flap_sever()
    with pytest.raises(ValueError, match="unknown keys"):
        faultinject.FaultPlan.parse("delay_net:bogus=1")


def test_fault_plan_delay_net_respects_executor_filter():
    plan = faultinject.FaultPlan.parse("delay_net:ms=9,executor=3")
    plan.set_identity(executor_id=1)
    assert plan.delay_ms() == 0
    plan.set_identity(executor_id=3)
    assert plan.delay_ms() == 9


# -- in-process crash/restore units ------------------------------------------


def _recovery_pair(tmp_path, expected=2, hosts=("h0", "h1")):
    srv = CoordinatorServer(expected,
                            journal_path=str(tmp_path / "coordinator.journal"))
    addr = srv.start()
    clients = []
    for host in hosts:
        c = CoordinatorClient(addr)
        ident = c.register({"host": host})
        c.set_identity(ident["executor_id"], ident["incarnation"])
        clients.append(c)
    return srv, addr, clients


def test_crash_restore_replays_state_and_bumps_epoch(tmp_path):
    srv, addr, (c0, c1) = _recovery_pair(tmp_path)
    try:
        srv.set_manifest({"kind": "x", "num_epochs": 2})
        srv.mark_dead([1], record_error=False)
        srv.note_serving_replicas("router1", [0])
        srv.crash()
        assert srv.crashed()
        assert srv.dead_nodes(0.0) == []  # mid-failover: nobody is "dead"
        epoch = srv.restore()
        assert epoch == 1 and srv.epoch == 1
        # replayed: slot table, manifest, incarnation fence, registry
        assert [m["host"] for m in srv.cluster_info()] == ["h0", "h1"]
        assert srv.manifest_state()["kind"] == "x"
        assert srv.registered_incarnation(1) == (1, False)  # dead stays dead
        assert srv.registered_incarnation(0) == (0, True)   # live re-seeded
        assert srv.serving_replicas() == {"router1": [0]}
        assert srv.address == addr  # same port: NodeConfig addresses hold
        # a second failover keeps compounding the epoch
        srv.crash()
        assert srv.restore() == 2
        for c in (c0, c1):
            c.close()
    finally:
        srv.stop()


def test_restore_keeps_deregistered_slot_untracked(tmp_path):
    """A node that EXITED CLEANLY before the crash must stay untracked after
    recovery — re-seeding its liveness clock would get the finished node
    re-declared dead later and fail a healthy run."""
    srv, addr, (c0, c1) = _recovery_pair(tmp_path)
    try:
        c1.deregister(1)
        srv.crash()
        srv.restore()
        assert srv.registered_incarnation(1) == (0, False)
        assert srv.registered_incarnation(0) == (0, True)
        c0.close()
        c1.close()
    finally:
        srv.stop()


def test_client_transparent_retry_rides_failover(tmp_path):
    """Idempotent client ops (manifest/heartbeat/metrics...) reconnect with
    backoff and retry through a supervised coordinator restart — callers
    never see the failover."""
    srv, addr, (c0, c1) = _recovery_pair(tmp_path)
    sup = CoordinatorSupervisor(srv, RestartPolicy(max_restarts=3,
                                                   backoff_base=0.1,
                                                   backoff_max=0.2))
    try:
        srv.set_manifest({"kind": "x"})
        assert c0.epoch == 0
        srv.crash()
        assert c0.manifest()["kind"] == "x"  # rode the failover
        assert c0.epoch == 1                 # and detected it
        assert sup.restart_count() == 1
        assert c1.heartbeat(1) is False      # peer re-asserts liveness
        assert srv.registered_incarnation(1) == (0, True)
        c0.close()
        c1.close()
    finally:
        sup.stop()
        srv.stop()


def test_stale_epoch_rendezvous_is_fenced_then_fresh_retry_succeeds(tmp_path):
    srv, addr, (c0, c1) = _recovery_pair(tmp_path)
    try:
        srv.crash()
        srv.restore()
        # re-establish the connection first (idempotent op rides the
        # reconnect) so the fence below is tested on a LIVE socket
        c0._check(c0._call({"op": "query"}, retry=True))
        assert c0.epoch == 1
        # a reduce stamped with the PRE-crash epoch is fenced (its
        # generation died with the crash), exactly like a zombie
        # incarnation would be — the explicit stamp wins over _stamp's
        # setdefault, standing in for a request composed before the crash
        with pytest.raises(CoordinatorRestarted, match="epoch 0 fenced"):
            c0._check(c0._call({"op": "reduce", "name": "r", "value": 1,
                                "kind": "sum", "count": 1,
                                "coordinator_epoch": 0}))
        # the fencing reply taught the client the new epoch: retry passes
        assert c0.epoch == 1
        assert c0.reduce("r", 5, kind="sum", count=1) == 5
        c0.close()
        c1.close()
    finally:
        srv.stop()


def test_crash_aborts_inflight_rendezvous_promptly(tmp_path):
    import threading

    srv, addr, (c0, c1) = _recovery_pair(tmp_path)
    sup = CoordinatorSupervisor(srv, RestartPolicy(max_restarts=3,
                                                   backoff_base=0.1,
                                                   backoff_max=0.2))
    result: list = []

    def _waiter():
        try:
            c0.reduce("pair", 1, kind="sum", count=2, timeout=30.0)
        except (RuntimeError, ConnectionError) as e:
            result.append(e)

    try:
        t = threading.Thread(target=_waiter, daemon=True)
        t.start()
        time.sleep(0.3)  # let the waiter join the generation
        t0 = time.monotonic()
        srv.crash()
        t.join(10.0)
        # unblocked in seconds (severed connection / aborted generation),
        # never the 30s rendezvous timeout
        assert result and time.monotonic() - t0 < 10.0
        # post-recovery the same name forms a FRESH generation.  Both
        # clients follow the documented caller contract: a reduce is never
        # replayed by the transport — on CoordinatorRestarted (reconnect,
        # or the epoch fence teaching the client the new epoch) the CALLER
        # re-enters, exactly like collective/group.py's form loop.
        deadline = time.monotonic() + 10.0
        while srv.crashed() and time.monotonic() < deadline:
            time.sleep(0.05)

        def _resilient_reduce(c, value, out):
            end = time.monotonic() + 20.0
            while True:
                try:
                    out.append(c.reduce("pair", value, kind="sum", count=2,
                                        timeout=30.0))
                    return
                except (CoordinatorRestarted, ConnectionError):
                    if time.monotonic() > end:
                        raise
                    time.sleep(0.1)

        got0: list = []
        got1: list = []
        peer = threading.Thread(target=_resilient_reduce, args=(c1, 2, got1),
                                daemon=True)
        peer.start()
        _resilient_reduce(c0, 1, got0)
        peer.join(10.0)
        assert got0 == [3] and got1 == [3]
        c0.close()
        c1.close()
    finally:
        sup.stop()
        srv.stop()


def test_coordinator_supervisor_budget_exhaustion_is_permanent(tmp_path):
    srv, addr, clients = _recovery_pair(tmp_path)
    sup = CoordinatorSupervisor(srv, RestartPolicy(max_restarts=0,
                                                   backoff_base=0.01))
    try:
        srv.crash()
        deadline = time.monotonic() + 10.0
        while sup.permanently_failed() is None \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        assert sup.permanently_failed() is not None
        # surfaced through the node-error channel (executor -1 = the
        # control plane) so shutdown() raises it
        errs = srv.errors()
        assert errs and errs[-1]["executor_id"] == -1
        assert "restart budget" in errs[-1]["traceback"]
        for c in clients:
            c.close()
    finally:
        sup.stop()
        srv.stop()


# -- chaos end-to-end (deterministic, tier-1) ---------------------------------


@pytest.fixture
def arm_driver_faults(monkeypatch):
    """Arm TOS_FAULTINJECT in the DRIVER process (kill_coordinator lives
    there) and guarantee disarm afterwards — the parsed plan is module
    state that would otherwise leak into every later test."""
    def arm(spec: str) -> None:
        monkeypatch.setenv("TOS_FAULTINJECT", spec)
        faultinject.init_from_env(force=True)

    yield arm
    monkeypatch.delenv("TOS_FAULTINJECT", raising=False)
    faultinject.init_from_env(force=True)


def _coverage(tmp_path):
    seen: list[int] = []
    for f in tmp_path.glob("node_*.txt"):
        seen.extend(int(x) for x in f.read_text().split(",") if x.strip())
    return seen


def _flight_kinds(log_dir) -> list[str]:
    report = json.loads((log_dir / "run_report.json").read_text())
    return [e["kind"] for e in report["flight"]["events"]]


@contextlib.contextmanager
def _ensure_shutdown(cluster):
    """Tear the cluster down even when an assertion fails mid-test: a
    leaked cluster's coordinator keeps dispatching heartbeats in this
    process and would consume the NEXT chaos test's fault ticks —
    one genuine failure must never cascade through the suite.  shutdown()
    is idempotent, so the success path's own (assertion-bearing) shutdown
    call is unaffected."""
    try:
        yield
    except BaseException:
        with contextlib.suppress(Exception):
            cluster.shutdown(timeout=60.0)
        raise


def _await_epoch(cluster, timeout: float = 30.0) -> int:
    """Wait for the op-counted kill to fire + recover: the threshold op may
    land on a heartbeat shortly AFTER the train call returns (boot speed
    and box load move the op clock)."""
    deadline = time.monotonic() + timeout
    while cluster.coordinator.epoch < 1 and time.monotonic() < deadline:
        time.sleep(0.1)
    return cluster.coordinator.epoch


def _assert_failover_sequence(kinds: list[str]) -> None:
    """The acceptance ordering: crash -> replay -> up, visible as an
    ordered sequence on the flight-recorder timeline."""
    assert "coordinator_crash" in kinds, kinds
    i = kinds.index("coordinator_crash")
    assert "coordinator_replay" in kinds[i:], kinds
    j = i + kinds[i:].index("coordinator_replay")
    assert "coordinator_up" in kinds[j:], kinds


@pytest.mark.chaos
def test_kill_coordinator_mid_streaming_train_recovers(tmp_path, monkeypatch,
                                                       arm_driver_faults):
    """Acceptance: the control plane crashes mid-STREAMING-train; the
    supervisor replays the journal, nodes re-assert over reconnecting
    heartbeats, the ledger feed never loses a partition (at-least-once
    accounting exact), and the failover lands as an ordered
    crash -> replay -> up sequence in the flight recorder."""
    from tensorflowonspark_tpu.telemetry import trace as ttrace

    ttrace.collect_final()  # earlier tests' driver events must not pollute
    monkeypatch.setenv("TOS_RESTART_BACKOFF_BASE", "0.2")
    arm_driver_faults("kill_coordinator:after_ops=15")
    items = list(range(120))
    parts = [items[i * 20:(i + 1) * 20] for i in range(6)]
    cluster = tcluster.run(
        mapfuns.record_items,
        {"batch_size": 4, "out_dir": str(tmp_path), "sleep_per_batch": 0.1},
        num_executors=2,
        input_mode=tcluster.InputMode.STREAMING,
        heartbeat_interval=0.2,
        queue_capacity=8,
        # nodes must NOT inherit the driver's kill spec
        env={"TOS_FAULTINJECT": ""},
        log_dir=str(tmp_path / "logs"),
        reservation_timeout=120.0,
    )
    with _ensure_shutdown(cluster):
        cluster.train(parts, num_epochs=1)
        assert _await_epoch(cluster) >= 1, \
            "the chaos kill never fired (op threshold too high?)"
        assert cluster.coordinator_supervisor.restart_count() >= 1
        cluster.shutdown(timeout=120.0)
    assert cluster.coordinator.errors() == []
    seen = _coverage(tmp_path)
    assert set(seen) == set(items)      # every partition delivered & consumed
    assert len(seen) >= len(items)      # at-least-once: duplicates allowed
    _assert_failover_sequence(_flight_kinds(tmp_path / "logs"))


@pytest.mark.chaos
def test_kill_coordinator_mid_direct_train_recovers(tmp_path, monkeypatch,
                                                    arm_driver_faults):
    """DIRECT mode: shard paths travel through the same ledger; the crash
    also wipes the published job manifest, which the journal must bring
    back (nodes read it via ctx.job_manifest after the failover)."""
    from tensorflowonspark_tpu import tfrecord
    from tensorflowonspark_tpu.telemetry import trace as ttrace

    ttrace.collect_final()
    monkeypatch.setenv("TOS_RESTART_BACKOFF_BASE", "0.2")
    arm_driver_faults("kill_coordinator:after_ops=15")
    shard_dir = tmp_path / "shards"
    shard_dir.mkdir()
    expect_ids = set()
    for s in range(6):
        records = [f"s{s}-r{i}".encode() for i in range(40)]
        tfrecord.write_records(str(shard_dir / f"part-{s:05d}"), records)
        expect_ids.update(r.decode() for r in records)
    cluster = tcluster.run(
        mapfuns.direct_record_counter,
        {"batch_size": 8, "out_dir": str(tmp_path), "sleep_per_batch": 0.1},
        num_executors=2,
        input_mode=tcluster.InputMode.DIRECT,
        heartbeat_interval=0.2,
        # tiny path-feed queue: the ledger feed stays in flight while the
        # nodes consume, so the op-counted crash lands mid-train
        queue_capacity=2,
        env={"TOS_FAULTINJECT": ""},
        log_dir=str(tmp_path / "logs"),
        reservation_timeout=120.0,
    )
    with _ensure_shutdown(cluster):
        cluster.train(str(shard_dir), num_epochs=1)
        # nodes are still consuming (and reading the manifest) after
        # train() acks — wait for the failover before judging recovery
        assert _await_epoch(cluster) >= 1, \
            "the chaos kill never fired (op threshold too high?)"
        cluster.shutdown(timeout=120.0)
    assert cluster.coordinator.errors() == []
    seen: list[str] = []
    for f in tmp_path.glob("seen_*.txt"):
        seen.extend(x for x in f.read_text().split("\n") if x)
    assert set(seen) == expect_ids      # exact coverage, duplicates allowed
    # the journal brought the manifest back: nodes read it post-failover
    metas = {m["executor_id"]: m for m in cluster.coordinator.cluster_info()}
    for m in metas.values():
        assert m["manifest"]["kind"] == "tfrecord_shards"
        assert m["manifest"]["num_shards"] == 6
    _assert_failover_sequence(_flight_kinds(tmp_path / "logs"))


@pytest.mark.chaos
def test_kill_coordinator_mid_serve_zero_failed_requests(tmp_path, monkeypatch,
                                                         arm_driver_faults):
    """Serving acceptance: the data plane (gateway -> router -> replicas)
    never touches the control plane per request, so a coordinator failover
    must cost ZERO non-503 failures — here every request succeeds outright
    — and the journal restores the serving replica registry."""
    import numpy as np

    from tensorflowonspark_tpu import serving
    from tensorflowonspark_tpu.checkpoint import export_bundle
    from tensorflowonspark_tpu.models import linear as linmod
    from tensorflowonspark_tpu.telemetry import trace as ttrace

    ttrace.collect_final()
    monkeypatch.setenv("TOS_RESTART_BACKOFF_BASE", "0.2")
    arm_driver_faults("kill_coordinator:after_ops=40")
    config = {"model": "linear", "in_dim": 4, "out_dim": 4}
    export = str(tmp_path / "bundle")
    export_bundle(export, linmod.init_params(config, scale=2.0), config)
    cluster = tcluster.run(
        serving.serving_loop,
        {"export_dir": export, "max_batch": 4},
        num_executors=2,
        input_mode=tcluster.InputMode.STREAMING,
        heartbeat_interval=0.25,
        env={"TOS_FAULTINJECT": ""},
        log_dir=str(tmp_path / "logs"),
        reservation_timeout=120.0,
    )
    try:
        gw = cluster.serve(export, max_batch=4, max_delay_ms=2.0,
                           listen=False, reload_poll_secs=0)
        row = np.arange(4, dtype=np.float32)
        answered = 0
        deadline = time.monotonic() + 60.0
        while (cluster.coordinator.epoch < 1
               and time.monotonic() < deadline) or answered < 50:
            out = gw.predict([row + answered], timeout=30.0)
            np.testing.assert_allclose(out[0], (row + answered) * 2.0)
            answered += 1
            if answered > 5000:  # safety valve, never expected
                break
            time.sleep(0.01)
        assert cluster.coordinator.epoch >= 1, \
            "the chaos kill never fired during the serving burst"
        assert answered >= 50
        # no replica ever looked unhealthy: the failover was invisible to
        # the data plane
        assert gw.healthy_replicas() == [0, 1]
        # the journal restored the registry across the failover
        reg = cluster.coordinator.serving_replicas()
        assert any(v == [0, 1] for v in reg.values()), reg
    finally:
        cluster.shutdown(timeout=120.0)
    assert cluster.coordinator.errors() == []
    _assert_failover_sequence(_flight_kinds(tmp_path / "logs"))


@pytest.mark.chaos
def test_kill_coordinator_mid_sync_train_reforms_exact(tmp_path, monkeypatch,
                                                       arm_driver_faults):
    """Sync-train acceptance: the crash poisons the in-flight control-plane
    barrier; both members re-form at the next generation barrier against
    the journal-recovered coordinator and finish at EXACTLY ``steps`` with
    params identical to the fault-free run."""
    import numpy as np

    from tensorflowonspark_tpu.launcher import SubprocessLauncher
    from tensorflowonspark_tpu.telemetry import trace as ttrace

    ttrace.collect_final()
    monkeypatch.setenv("TOS_RESTART_BACKOFF_BASE", "0.2")
    total_steps = 12
    cluster = tcluster.run(
        mapfuns.sync_coordinator_chaos,
        {"steps": total_steps, "step_delay": 0.3},
        num_executors=2,
        input_mode=tcluster.InputMode.STREAMING,
        launcher=SubprocessLauncher(),
        heartbeat_interval=0.25,
        env={"TOS_FAULTINJECT": ""},
        log_dir=str(tmp_path / "logs"),
        reservation_timeout=120.0,
    )
    # Arm the kill against the ROUNDS, not against the boot: ops are counted
    # from arming, and how many heartbeats two nodes send while they import
    # jax depends on the box (armed from the start, op 30 fell ~2 s before
    # the group formed: no barrier in flight, nothing to re-form).  Once the
    # group stands, rank 0 waits in a barrier while rank 1 sleeps its
    # step_delay, so the 6th op from here (two rounds in) crashes the
    # coordinator with a round in flight whatever kind of op it is.
    deadline = time.monotonic() + 120.0
    while sum(bool(m.get("coord_chaos_formed"))
              for m in cluster.coordinator.cluster_info()) < 2:
        assert time.monotonic() < deadline, "the group never formed"
        time.sleep(0.02)
    arm_driver_faults("kill_coordinator:after_ops=6")
    # no train() feed blocks this map_fun: wait for both nodes to publish
    # (generous: the slow-convergence path stacks several bounded
    # collective backstops before the generation barrier aligns)
    deadline = time.monotonic() + 360.0
    metas: dict = {}
    while time.monotonic() < deadline:
        metas = {m["executor_id"]: m.get("coord_chaos")
                 for m in cluster.coordinator.cluster_info()}
        # BOTH nodes: between the crash and the journal's replay the
        # coordinator knows no node, and all() of nothing is true — a poll
        # that fell in that gap went on to shutdown(), which stops the
        # coordinator's supervisor, and the nodes could never finish
        if len(metas) == 2 and all(v is not None for v in metas.values()):
            break
        time.sleep(0.5)
    epoch = cluster.coordinator.epoch
    cluster.shutdown(timeout=180.0)
    assert len(metas) == 2 and all(
        v is not None for v in metas.values()), metas
    assert epoch >= 1, "the chaos kill never fired mid-run"
    for v in metas.values():
        assert v["steps"] == total_steps  # exact step accounting
    # the poisoned round re-formed at a bumped generation barrier
    assert any(v["reforms"] >= 1 for v in metas.values()), metas
    assert all(v["generation"] >= 2 for v in metas.values()), metas
    # identical params equal to the fault-free reference (numpy
    # recomputation of the same deterministic schedule)
    assert metas[0]["final_w"] == metas[1]["final_w"]
    w = np.full((3, 1), 0.25, np.float32)
    for s in range(total_steps):
        grads = []
        for rank in range(2):
            b = mapfuns.chaos_batch(rank, s)
            err = (b["x"] @ w)[:, 0] - b["y"]
            grads.append((2.0 / len(err)) * (b["x"].T @ err)[:, None])
        w = w - np.float32(0.125) * ((grads[0] + grads[1]) / 2.0)
    np.testing.assert_allclose(np.asarray(metas[0]["final_w"]),
                               w.ravel(), rtol=1e-4)
    _assert_failover_sequence(_flight_kinds(tmp_path / "logs"))


@pytest.mark.chaos
def test_self_fence_parks_node_until_readmitted(tmp_path, monkeypatch,
                                                arm_driver_faults):
    """Heartbeat-loss asymmetry satellite: with recovery DELAYED past
    TOS_COORDINATOR_GRACE_SECS, the node must SELF-FENCE (park, no new
    ledger work — it can no longer prove it still owns its slot), then
    resume when the recovered coordinator re-admits it; the train still
    completes with exact coverage and the park is flight-recorded."""
    from tensorflowonspark_tpu.telemetry import trace as ttrace

    ttrace.collect_final()
    # coordinator restore waits ~3-5s (jittered); nodes park at 2s of
    # silence and would give up at 8s — recovery lands inside the window
    monkeypatch.setenv("TOS_RESTART_BACKOFF_BASE", "4.0")
    arm_driver_faults("kill_coordinator:after_ops=15")
    items = list(range(160))
    parts = [items[i * 20:(i + 1) * 20] for i in range(8)]
    cluster = tcluster.run(
        mapfuns.record_items,
        {"batch_size": 4, "out_dir": str(tmp_path), "sleep_per_batch": 0.2},
        num_executors=2,
        input_mode=tcluster.InputMode.STREAMING,
        heartbeat_interval=0.2,
        queue_capacity=8,
        env={"TOS_FAULTINJECT": "",
             "TOS_COORDINATOR_GRACE_SECS": "2"},
        log_dir=str(tmp_path / "logs"),
        reservation_timeout=120.0,
    )
    with _ensure_shutdown(cluster):
        cluster.train(parts, num_epochs=1)
        assert _await_epoch(cluster) >= 1, \
            "the chaos kill never fired (op threshold too high?)"
        cluster.shutdown(timeout=120.0)
    assert cluster.coordinator.errors() == []
    assert set(_coverage(tmp_path)) == set(items)
    kinds = _flight_kinds(tmp_path / "logs")
    _assert_failover_sequence(kinds)
    # at least one node parked during the outage and was re-admitted after
    assert "self_fence" in kinds, kinds
    assert "readmit" in kinds[kinds.index("self_fence"):], kinds


@pytest.mark.slow
@pytest.mark.chaos
def test_flap_and_delay_soak_completes_exact(tmp_path, monkeypatch,
                                             arm_driver_faults):
    """Network-degradation soak: one node lives behind a flapping, delayed
    link (1s flap windows severing its data plane + swallowing its
    heartbeats, 3ms injected latency per send) for a whole train — the
    ledger re-feed, reconnecting heartbeats, and (if the flap outlasts the
    death window) incarnation fencing must still deliver every record."""
    monkeypatch.setenv("TOS_DEAD_NODE_TIMEOUT", "6")
    # ~8s of paced consumption: the degraded node lives through SEVERAL
    # 1s flap windows (multiple severs + heartbeat-swallowing phases), not
    # a lucky single healthy window
    items = list(range(600))
    parts = [items[i * 20:(i + 1) * 20] for i in range(30)]
    per_node_env = [{"TOS_FAULTINJECT": ""},
                    {"TOS_FAULTINJECT": "flap:period=1;delay_net:ms=3"}]
    cluster = tcluster.run(
        mapfuns.record_items,
        {"batch_size": 4, "out_dir": str(tmp_path), "sleep_per_batch": 0.1},
        num_executors=2,
        input_mode=tcluster.InputMode.STREAMING,
        heartbeat_interval=0.5,
        # backpressure: the feed must stay IN FLIGHT across flap windows so
        # the severs hit live feed_partition calls (a capacity-1024 queue
        # would buffer everything before the first down window)
        queue_capacity=8,
        per_node_env=per_node_env,
        log_dir=str(tmp_path / "logs"),
        reservation_timeout=120.0,
    )
    cluster.train(parts, num_epochs=1)
    counters = cluster.metrics().get("counters") or {}
    cluster.shutdown(timeout=180.0)
    seen = _coverage(tmp_path)
    assert set(seen) == set(items)
    assert len(seen) >= len(items)
    # the degradation demonstrably fired: several down windows were metered
    # (the counter rides the final deregister snapshot even when flap
    # swallowed the last heartbeats)
    assert counters.get("faultinject.injected.flap", 0) >= 2, counters
