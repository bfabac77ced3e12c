"""Elastic recovery: incarnation fencing, restart policy/supervisor units,
the fault-injection grammar, and deterministic chaos end-to-end tests.

The chaos tests are tier-1 by design (ISSUE 1): every recovery path —
supervised restart with checkpoint resume, partition re-feed after a severed
socket, exactly-once inference retry against a restarted node — runs on a
deterministic fault schedule (``TOS_FAULTINJECT``) instead of waiting for a
soak run to hit a flake.  The randomized soak variant lives in
``test_soak_dataplane.py`` (``slow`` + ``chaos``).
"""

from __future__ import annotations

import socket
import threading
import time

import pytest

from tensorflowonspark_tpu import cluster as tcluster
from tensorflowonspark_tpu import faultinject
from tensorflowonspark_tpu.coordinator import CoordinatorClient, CoordinatorServer
from tensorflowonspark_tpu.node import NodeConfig
from tensorflowonspark_tpu.supervisor import RestartPolicy, Supervisor
from tensorflowonspark_tpu.utils.net import connect_with_backoff

import mapfuns


# -- fault-injection grammar -------------------------------------------------

def test_fault_plan_grammar():
    plan = faultinject.FaultPlan.parse(
        "kill:after_batches=3,incarnation=0;sever:after_data_ops=2;"
        "drop_heartbeats:count=5,executor=1")
    plan.set_identity(executor_id=1, incarnation=0)
    # kill counts batches deterministically: fires exactly on the 3rd
    assert not plan._tick("kill")
    assert not plan._tick("kill")
    assert plan._tick("kill")
    assert not plan._tick("kill")  # one-shot
    # sever fires on the 2nd data op
    assert not plan._tick("sever")
    assert plan._tick("sever")
    # drop_heartbeats scoped to executor 1 (matches)
    assert plan._tick("drop_heartbeats")


def test_fault_plan_incarnation_disarms_after_restart():
    plan = faultinject.FaultPlan.parse("kill:after_batches=1,incarnation=0")
    plan.set_identity(executor_id=0, incarnation=1)  # restarted process
    for _ in range(5):
        assert not plan._tick("kill")


def test_fault_plan_executor_filter():
    plan = faultinject.FaultPlan.parse("sever:after_data_ops=1,executor=3")
    plan.set_identity(executor_id=2)
    assert not plan._tick("sever")
    plan.set_identity(executor_id=3)
    assert plan._tick("sever")


def test_fault_plan_rejects_junk():
    with pytest.raises(ValueError, match="unknown fault action"):
        faultinject.FaultPlan.parse("explode:after=1")
    with pytest.raises(ValueError, match="unknown keys"):
        faultinject.FaultPlan.parse("kill:after_batches=1,bogus=2")


# -- restart policy / backoff ------------------------------------------------

def test_restart_policy_delay_bounds():
    policy = RestartPolicy(max_restarts=3, backoff_base=0.5,
                           backoff_factor=2.0, backoff_max=4.0, jitter=0.25)
    for attempt, base in [(0, 0.5), (1, 1.0), (2, 2.0), (3, 4.0), (10, 4.0)]:
        for _ in range(20):
            d = policy.delay(attempt)
            assert base * 0.75 <= d <= base * 1.25, (attempt, d)


def test_connect_with_backoff_rides_out_dark_port():
    # reserve a port, go dark, come back 0.6s later — the restart window
    probe = socket.socket()
    probe.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()

    server = socket.socket()
    server.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)

    def _listen_late():
        time.sleep(0.6)
        server.bind(("127.0.0.1", port))
        server.listen(1)

    t = threading.Thread(target=_listen_late, daemon=True)
    t.start()
    try:
        sock = connect_with_backoff(("127.0.0.1", port), timeout=5.0,
                                    attempts=8, base=0.2, factor=1.5)
        sock.close()
    finally:
        t.join()
        server.close()


def test_connect_with_backoff_surfaces_failure():
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()  # nothing listens here
    t0 = time.monotonic()
    with pytest.raises(ConnectionError, match="after 2 attempt"):
        connect_with_backoff(("127.0.0.1", port), timeout=1.0,
                             attempts=2, base=0.05)
    assert time.monotonic() - t0 < 5.0


# -- consumption watermark bookkeeping ---------------------------------------

def test_consumption_watermark_lags_returned_batch():
    """The partition-consumed count must not advance until the batch that
    CLOSED the partition has been returned to the map_fun — otherwise a death
    between EndPartition-pop and the map_fun processing that final batch
    silently loses it (the ledger would believe the partition consumed)."""
    from tensorflowonspark_tpu.feeding import DataFeed, FeedQueues
    from tensorflowonspark_tpu.marker import EndOfFeed, EndPartition

    queues = FeedQueues(("input",))
    q = queues.get_queue("input")
    for item in (1, 2, EndPartition(), 3, 4, EndPartition(), EndOfFeed()):
        q.put(item)
    feed = DataFeed(queues, qname_in="input")
    assert feed.next_batch(3) == [1, 2]
    # the closing batch was only just handed back: not yet consumed
    assert queues.partitions_consumed("input") == 0
    assert feed.next_batch(3) == [3, 4]
    # coming back for more proves batch 1 was processed
    assert queues.partitions_consumed("input") == 1
    assert feed.next_batch(3) == []
    assert feed.should_stop()
    assert queues.partitions_consumed("input") == 2


def test_watermark_dedupes_refed_partition():
    """An at-least-once re-feed can put TWO EndPartition markers for one
    logical partition in the queue (reply lost after the server queued the
    first); keyed markers must count once, or the watermark over-advances
    past still-buffered work that a later death would fail to re-deliver."""
    from tensorflowonspark_tpu.feeding import DataFeed, FeedQueues
    from tensorflowonspark_tpu.marker import EndOfFeed, EndPartition

    queues = FeedQueues(("input",))
    q = queues.get_queue("input")
    for item in (1, 2, EndPartition(key=(0, 0)), 1, 2, EndPartition(key=(0, 0)),
                 3, EndPartition(key=(0, 1)), EndOfFeed()):
        q.put(item)
    feed = DataFeed(queues, qname_in="input")
    while not feed.should_stop():
        feed.next_batch(8)
    # the EndOfFeed pop flushed every deferred report on its way in
    assert queues.partitions_consumed("input") == 2  # (0,0) counted once


def test_ledger_tail_drain_accounting():
    """needs_drain reflects acked-but-unconsumed work; update_watermark (the
    tail-drain poll path) empties it; requeue_unconsumed puts it back in play
    and resets the watermark anchor for the replacement process."""
    from tensorflowonspark_tpu.cluster import _PartitionLedger

    ledger = _PartitionLedger(num_partitions=2, num_epochs=1, num_slots=1)
    for consumed_at_ack in (0, 1):
        assert ledger.next_task(0) is not None
        ledger.ack(0, consumed=consumed_at_ack)
    # first ack anchored at 0, second advanced by 1: one of the two acked
    # partitions is still only buffered
    assert ledger.needs_drain(0)
    ledger.update_watermark(0, 2)
    assert not ledger.needs_drain(0)
    assert ledger.next_task(0) is None  # all resolved, nothing to drain

    ledger2 = _PartitionLedger(num_partitions=2, num_epochs=1, num_slots=1)
    for consumed_at_ack in (0, 1):
        assert ledger2.next_task(0) is not None
        ledger2.ack(0, consumed=consumed_at_ack)
    assert ledger2.requeue_unconsumed(0) == 1  # the buffered one, not both
    assert not ledger2.needs_drain(0)
    assert ledger2.next_task(0) is not None  # back in play


def test_abandon_slot_returns_orphans_forfeits_own():
    """A terminating consumer forfeits its OWN share, but an in-flight task
    it picked up from the orphan pool is a dead peer's work and must go back
    in play instead of being silently dropped."""
    from tensorflowonspark_tpu.cluster import _PartitionLedger

    ledger = _PartitionLedger(num_partitions=2, num_epochs=1, num_slots=2)
    t1 = ledger.next_task(1)
    ledger.requeue(1)                    # slot 1 died: its task is orphaned
    assert ledger.next_task(0) == (0, 0)
    ledger.ack(0)
    assert ledger.next_task(0) == t1     # slot 0 adopts the orphan...
    ledger.abandon_slot(0)               # ...then its consumer terminates
    assert ledger.next_task(1) == t1     # the orphan survives the forfeit


# -- incarnation fencing (in-process coordinator) ----------------------------

def _fenced_pair():
    srv = CoordinatorServer(2)
    addr = srv.start()
    clients = []
    for host in ("h0", "h1"):
        c = CoordinatorClient(addr)
        ident = c.register({"host": host})
        c.set_identity(ident["executor_id"], ident["incarnation"])
        clients.append((c, ident))
    return srv, clients


def test_incarnation_fencing_rejects_stale_node():
    srv, clients = _fenced_pair()
    try:
        (c0, id0), (c1, id1) = clients
        assert id0["incarnation"] == id1["incarnation"] == 0
        # declare node 1 dead: fenced, idempotent, no double-declare
        assert srv.mark_dead([id1["executor_id"]], record_error=False) == [id1["executor_id"]]
        assert srv.mark_dead([id1["executor_id"]], record_error=False) == []
        assert srv.registered_incarnation(id1["executor_id"]) == (1, False)
        # the zombie's heartbeat is answered with stop=True (wind down)
        assert c1.heartbeat(id1["executor_id"]) is True
        # its barriers/reduces fail loudly instead of joining live generations
        with pytest.raises(RuntimeError, match="stale incarnation"):
            c1.reduce("zombie-reduce", 1, kind="sum", count=1)
        # its meta updates are swallowed
        c1.update_meta(id1["executor_id"], {"zombie_patch": True})
        assert "zombie_patch" not in srv.cluster_info()[id1["executor_id"]]
        # a replacement re-registers into the slot and adopts incarnation 1
        c2 = CoordinatorClient(srv.address)
        ident2 = c2.register({"host": "h1-replacement"},
                             replace=id1["executor_id"])
        assert ident2["executor_id"] == id1["executor_id"]
        assert ident2["incarnation"] == 1
        c2.set_identity(ident2["executor_id"], ident2["incarnation"])
        assert c2.reduce("live-reduce", 2, kind="sum", count=1) == 2
        # slot meta was replaced wholesale
        assert srv.cluster_info()[id1["executor_id"]]["host"] == "h1-replacement"
        # the pre-restart zombie stays fenced even after the replacement is up
        with pytest.raises(RuntimeError, match="stale incarnation"):
            c1.reduce("zombie-reduce-2", 1, kind="sum", count=1)
        # a live (still-tracked) slot refuses replacement
        c3 = CoordinatorClient(srv.address)
        with pytest.raises(RuntimeError, match="still .*tracked"):
            c3.register({"host": "usurper"}, replace=id0["executor_id"])
        for c in (c0, c1, c2, c3):
            c.close()
    finally:
        srv.stop()


def test_mark_dead_aborts_inflight_rendezvous():
    srv, clients = _fenced_pair()
    try:
        (c0, id0), (c1, id1) = clients
        result: list = []

        def _waiter():
            try:
                c0.reduce("pair", 1, kind="sum", count=2, timeout=30.0)
            except RuntimeError as e:
                result.append(e)

        t = threading.Thread(target=_waiter, daemon=True)
        t.start()
        time.sleep(0.3)  # let the waiter join the generation
        srv.mark_dead([id1["executor_id"]], record_error=False)
        t.join(5.0)
        # the survivor unblocked in seconds, not after the 30s timeout
        assert result and "aborted" in str(result[0])
        c0.close()
        c1.close()
    finally:
        srv.stop()


# -- supervisor units --------------------------------------------------------

class _StubCoordinator:
    def __init__(self, info=None, errors=None, tracked_after_respawn=True):
        self.failures: list = []
        self.stopped = False
        # liveness mirrors the real protocol: the dead slot is untracked
        # until a respawned replacement re-registers (or never, for the
        # boot-death scenario)
        self.tracked = False
        self.tracked_after_respawn = tracked_after_respawn
        self._errors = errors or []
        self._info = info or []

    def record_failure(self, executor_id, reason):
        self.failures.append((executor_id, reason))

    def signal_stop(self):
        self.stopped = True

    def errors(self):
        return self._errors

    def cluster_info(self):
        return self._info

    def node_meta(self, executor_id):
        return next((m for m in self._info
                     if m["executor_id"] == executor_id), None)

    def registered_incarnation(self, executor_id):
        return (1, self.tracked)


class _StubLauncher:
    def __init__(self, n=2, coord=None):
        self.processes = [object()] * n
        self.configs = [
            NodeConfig(coordinator_addr=("127.0.0.1", 1), authkey=b"k",
                       map_fun=mapfuns.noop, launch_index=i)
            for i in range(n)
        ]
        self.respawned: list = []
        self.coord = coord

    def respawn(self, index, config):
        self.respawned.append((index, config))
        if self.coord is not None:
            self.coord.tracked = self.coord.tracked_after_respawn


def _drain(sup, executor_id):
    """Wait for the in-flight restart to resolve BEFORE stopping (stop()
    cancels pending backoff waits, which is correct in production but would
    make these assertions race the restart thread)."""
    deadline = time.monotonic() + 10.0
    while sup.restarting(executor_id) and time.monotonic() < deadline:
        time.sleep(0.01)
    sup.stop(timeout=10.0)


def test_supervisor_respawns_into_slot_with_replacement_config():
    coord = _StubCoordinator(info=[{"executor_id": 1, "launch_index": 1}])
    launcher = _StubLauncher(coord=coord)
    sup = Supervisor(coord, launcher,
                     RestartPolicy(max_restarts=2, backoff_base=0.01,
                                   backoff_max=0.02))
    sup.handle_death(1)
    _drain(sup, 1)
    assert launcher.respawned, "supervisor never respawned the slot"
    index, config = launcher.respawned[0]
    assert index == 1
    assert config.replace_executor_id == 1
    assert sup.restart_count(1) == 1
    assert not coord.stopped and not coord.failures


def test_supervisor_exhausted_budget_is_permanent():
    coord = _StubCoordinator(info=[{"executor_id": 1, "launch_index": 1}])
    sup = Supervisor(coord, _StubLauncher(),
                     RestartPolicy(max_restarts=0, backoff_base=0.01))
    sup.handle_death(1)
    _drain(sup, 1)
    assert sup.permanently_failed(1) is not None
    assert coord.stopped
    assert coord.failures and "restart budget" in coord.failures[0][1]


def test_supervisor_map_fun_error_is_not_restartable():
    coord = _StubCoordinator(
        info=[{"executor_id": 1, "launch_index": 1}],
        errors=[{"executor_id": 1, "traceback": "ValueError: app bug"}])
    launcher = _StubLauncher()
    sup = Supervisor(coord, launcher,
                     RestartPolicy(max_restarts=2, backoff_base=0.01))
    sup.handle_death(1)
    _drain(sup, 1)
    assert not launcher.respawned
    assert sup.permanently_failed(1) is not None
    assert coord.stopped


def test_supervisor_boot_death_consumes_budget():
    """A replacement that dies before re-registering never enters liveness
    tracking — the supervisor itself must notice (re-register window) and
    spend the remaining budget, rather than leaving the slot dark forever."""
    coord = _StubCoordinator(info=[{"executor_id": 1, "launch_index": 1}],
                             tracked_after_respawn=False)
    launcher = _StubLauncher(coord=coord)
    sup = Supervisor(coord, launcher,
                     RestartPolicy(max_restarts=2, backoff_base=0.01,
                                   backoff_max=0.02))
    sup._reregister_timeout = 0.1
    sup.handle_death(1)
    _drain(sup, 1)
    assert len(launcher.respawned) == 2       # both budgeted attempts spent
    assert sup.permanently_failed(1) is not None
    assert coord.stopped
    assert coord.failures and "restart budget" in coord.failures[0][1]


def test_supervisor_spares_late_registering_replacement():
    """A replacement that boots slower than the re-register window (cold
    jax/TPU init) but registers during the NEXT backoff must not be reaped —
    killing it would burn budget on a slot that already recovered."""
    coord = _StubCoordinator(info=[{"executor_id": 1, "launch_index": 1}],
                             tracked_after_respawn=False)
    launcher = _StubLauncher(coord=coord)
    sup = Supervisor(coord, launcher,
                     RestartPolicy(max_restarts=5, backoff_base=0.2,
                                   backoff_factor=1.5, backoff_max=0.3))
    sup._reregister_timeout = 0.05
    sup.handle_death(1)
    time.sleep(0.3)       # respawn #1 happened; its boot outlived the window
    coord.tracked = True  # ...but it registers during the next backoff
    _drain(sup, 1)
    assert len(launcher.respawned) == 1
    assert sup.permanently_failed(1) is None
    assert not coord.stopped


def test_elastic_refuses_jax_distributed():
    with pytest.raises(ValueError, match="jax_distributed"):
        tcluster.run(mapfuns.noop, None, num_executors=1,
                     jax_distributed=True, elastic=True)


def test_elastic_refuses_pod_launcher():
    from tensorflowonspark_tpu.launcher import TPUPodLauncher

    with pytest.raises(ValueError, match="TPUPodLauncher"):
        tcluster.run(mapfuns.noop, None, num_executors=1,
                     launcher=TPUPodLauncher(hosts=["h0"]), elastic=True)


# -- chaos end-to-end (deterministic, tier-1) --------------------------------

def _coverage(tmp_path):
    seen: list[int] = []
    for f in tmp_path.glob("seen_*.txt"):
        seen.extend(int(x) for x in f.read_text().split())
    return seen


@pytest.mark.chaos
def test_elastic_restart_resumes_from_checkpoint_and_completes(tmp_path, monkeypatch):
    """The acceptance scenario: 2-worker STREAMING train, SIGKILL one worker
    mid-epoch (after its 3rd batch), elastic=True.  train() must complete
    without raising, every item must be delivered (at-least-once), and the
    restarted worker must have resumed from the latest committed checkpoint
    under a bumped incarnation."""
    monkeypatch.setenv("TOS_DEAD_NODE_TIMEOUT", "4")
    monkeypatch.setenv("TOS_RESTART_BACKOFF_BASE", "0.2")
    items = list(range(120))
    parts = [items[i * 20:(i + 1) * 20] for i in range(6)]
    # per_node_env targets ONE launch slot; `incarnation=0` keeps the fault
    # disarmed in the replacement process (it re-parses the same env)
    per_node_env = [{}, {"TOS_FAULTINJECT": "kill:after_batches=3,incarnation=0"}]
    cluster = tcluster.run(
        mapfuns.elastic_sum_batches,
        {"batch_size": 2, "out_dir": str(tmp_path),
         "model_dir": str(tmp_path / "ckpt")},
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
    metas = {m["executor_id"]: m for m in cluster.coordinator.cluster_info()}
    victims = [eid for eid, m in metas.items() if m.get("incarnation") == 1]
    assert len(victims) == 1, metas
    victim = victims[0]
    assert cluster.supervisor.restart_count(victim) == 1
    # the replacement loaded the latest checkpoint its predecessor committed
    # (killed during batch 3 => steps 1 and 2 were saved)
    assert metas[victim]["resumed_step_inc1"] == 2
    # fencing: the predecessor's incarnation is burned, the slot is live
    assert cluster.coordinator.registered_incarnation(victim) == (1, True)
    cluster.shutdown(timeout=120.0)
    # the recovered death never became a fatal node error
    assert cluster.coordinator.errors() == []
    seen = _coverage(tmp_path)
    assert set(seen) == set(items)      # every partition delivered & consumed
    assert len(seen) >= len(items)      # at-least-once: duplicates allowed


@pytest.mark.chaos
def test_severed_data_socket_is_refed_without_restart(tmp_path):
    """`sever` drops the data connection mid-stream with the node healthy:
    the driver must requeue the unacknowledged partition and re-feed it over
    a fresh connection — no supervisor involved, no item lost, and (because
    the sever fires before any of that partition's items were queued) none
    duplicated."""
    items = list(range(80))
    parts = [items[i * 20:(i + 1) * 20] for i in range(4)]
    per_node_env = [{}, {"TOS_FAULTINJECT": "sever:after_data_ops=2"}]
    cluster = tcluster.run(
        mapfuns.elastic_sum_batches,
        {"batch_size": 4, "out_dir": str(tmp_path)},
        num_executors=2,
        input_mode=tcluster.InputMode.STREAMING,
        per_node_env=per_node_env,
        log_dir=str(tmp_path / "logs"),
        reservation_timeout=120.0,
    )
    cluster.train(parts, num_epochs=1)
    cluster.shutdown(timeout=60.0)
    assert sorted(_coverage(tmp_path)) == items


@pytest.mark.chaos
def test_elastic_inference_retries_exactly_once_on_restarted_node(tmp_path, monkeypatch):
    """Killing a scoring node mid-partition must not lose or duplicate
    results: the in-flight partition is retried ONLY against the restarted
    node (fresh queues), and the partition-index dedupe keeps the output
    ordered exactly-count."""
    monkeypatch.setenv("TOS_DEAD_NODE_TIMEOUT", "4")
    monkeypatch.setenv("TOS_RESTART_BACKOFF_BASE", "0.2")
    import tensorflowonspark_tpu as tos

    vals = list(range(60))
    per_node_env = [{}, {"TOS_FAULTINJECT": "kill:after_batches=2,incarnation=0"}]
    cluster = tcluster.run(
        mapfuns.echo_inference, {},
        num_executors=2,
        input_mode=tcluster.InputMode.STREAMING,
        heartbeat_interval=0.5,
        per_node_env=per_node_env,
        log_dir=str(tmp_path),
        reservation_timeout=120.0,
        elastic=True,
    )
    preds = cluster.inference(tos.PartitionedDataset.from_iterable(vals, 6))
    cluster.shutdown(timeout=120.0)
    assert preds == [v * 2 for v in vals]


@pytest.mark.chaos
def test_feed_failure_names_executor_and_partition(tmp_path, monkeypatch):
    """Satellite: a feed failure that exhausts its retry budget surfaces a
    RuntimeError naming the executor AND partition (the old code collected
    bare exceptions with no identity)."""
    monkeypatch.setenv("TOS_MAX_PARTITION_ATTEMPTS", "1")  # fail on first sever
    items = list(range(80))
    parts = [items[i * 20:(i + 1) * 20] for i in range(4)]
    per_node_env = [{}, {"TOS_FAULTINJECT": "sever:after_data_ops=2"}]
    cluster = tcluster.run(
        mapfuns.elastic_sum_batches,
        {"batch_size": 4, "out_dir": str(tmp_path)},
        num_executors=2,
        input_mode=tcluster.InputMode.STREAMING,
        per_node_env=per_node_env,
        log_dir=str(tmp_path / "logs"),
        reservation_timeout=120.0,
    )
    with pytest.raises(RuntimeError,
                       match=r"feeding executor \d+ failed on partition \d+ "
                             r"\(epoch 0, attempt 1/1\)"):
        cluster.train(parts, num_epochs=1)
    cluster.shutdown(timeout=60.0)


# -- node death x pipelined consensus vote (ISSUE 3 satellite, weak #7) -------


def test_mark_dead_aborts_pipelined_vote_and_cons_pending_resets():
    """Deterministic interleaving of the dead-node monitor's abort with an
    in-flight PIPELINED consensus vote: result() must raise the abort
    promptly (never ride out the vote timeout), and — because the raise
    skips the _cons_pending clear — the NEXT all_done_begin must recover by
    resetting the dedicated consensus connection instead of deadlocking on
    its held lock."""
    from tensorflowonspark_tpu.feeding import FeedQueues
    from tensorflowonspark_tpu.node import NodeContext

    srv, clients = _fenced_pair()
    try:
        (c0, id0), (c1, id1) = clients
        info = [{"executor_id": 0, "job_name": "chief"},
                {"executor_id": 1, "job_name": "worker"}]
        ctx0 = NodeContext(
            executor_id=0, job_name="chief", task_index=0, num_executors=2,
            cluster_info=info, queues=FeedQueues(),
            config=NodeConfig(coordinator_addr=srv.address, authkey=None,
                              map_fun=mapfuns.noop),
            client=c0)
        result = ctx0.all_done_begin(False, timeout=60.0)
        assert ctx0._cons_pending
        time.sleep(0.3)  # let the vote join the generation
        srv.mark_dead([id1["executor_id"]], record_error=False)
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="aborted"):
            result()
        assert time.monotonic() - t0 < 10.0  # abort, not the 60s vote timeout
        # the raise skipped the _cons_pending clear: the abandoned vote's
        # reply is unread and its connection lock still held
        assert ctx0._cons_pending
        old_cons = ctx0._cons_client
        result2 = ctx0.all_done_begin(True, timeout=30.0)
        assert ctx0._cons_client is not old_cons  # fresh connection, no deadlock
        # a replacement registers into the dead slot and completes the round
        c2 = CoordinatorClient(srv.address)
        ident2 = c2.register({"host": "h1-replacement"},
                             replace=id1["executor_id"])
        c2.set_identity(ident2["executor_id"], ident2["incarnation"])
        name = f"all_done:{c0._gen}"  # the generation ctx0's second vote used
        peer = threading.Thread(
            target=lambda: c2.reduce(name, True, kind="all", count=2,
                                     timeout=30.0), daemon=True)
        peer.start()
        assert result2() is True
        assert not ctx0._cons_pending
        peer.join(10.0)
        ctx0._reset_consensus_client()
        c2.close()
    finally:
        srv.stop()


@pytest.mark.chaos
def test_node_death_mid_pipelined_vote_unblocks_survivor(tmp_path, monkeypatch):
    """e2e: SIGKILL one node after its 2nd batch while its peer's pipelined
    consensus vote is in flight.  The survivor must see the monitor's abort
    within seconds (not the 120s vote timeout), survive the abandoned-vote
    reset, and exit; the driver must surface the death instead of hanging."""
    monkeypatch.setenv("TOS_DEAD_NODE_TIMEOUT", "4")
    items = list(range(120))
    parts = [items[i * 20:(i + 1) * 20] for i in range(6)]
    per_node_env = [{}, {"TOS_FAULTINJECT": "kill:after_batches=2"}]
    cluster = tcluster.run(
        mapfuns.pipelined_consensus_consumer,
        {"batch_size": 4, "out_dir": str(tmp_path), "step_delay": 0.05},
        num_executors=2,
        input_mode=tcluster.InputMode.STREAMING,
        heartbeat_interval=0.5,
        per_node_env=per_node_env,
        log_dir=str(tmp_path / "logs"),
        reservation_timeout=120.0,
    )
    t0 = time.monotonic()
    raised = []
    try:
        cluster.train(parts, num_epochs=1)
    except RuntimeError as e:
        raised.append(e)
    try:
        cluster.shutdown(timeout=120.0)
    except RuntimeError as e:
        raised.append(e)
    assert raised, "the node death was never surfaced to the driver"
    assert time.monotonic() - t0 < 120.0  # never rode out the vote timeout
    survivor = (tmp_path / "cons_0.txt").read_text() \
        if (tmp_path / "cons_0.txt").exists() else \
        (tmp_path / "cons_1.txt").read_text()
    assert survivor.startswith("aborted:"), survivor
    assert "reset-ok" in survivor, survivor
