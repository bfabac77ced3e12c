"""Multi-host (multi-process) jax.distributed integration tests.

The CPU analogue of the reference's ``local-cluster[2,1,1024]`` in-process
cluster tests (SURVEY.md §4): two real node processes, each seeing its own
virtual CPU "chips", bootstrap one ``jax.distributed`` job through the
coordinator's port-reduce (``node.py``), and run a cross-process collective.
"""

from __future__ import annotations

import pytest

from tensorflowonspark_tpu import cluster as tcluster
from tensorflowonspark_tpu import tpu_info
from tensorflowonspark_tpu.launcher import SubprocessLauncher


def _dist_map_fun(args, ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import multihost_utils
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    info = {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": jax.local_device_count(),
        "global_devices": jax.device_count(),
    }
    # Cross-process data-parallel reduction: each process contributes its own
    # host-local shard; the jitted sum is an all-reduce over gloo (the DCN
    # stand-in for XLA's ICI collectives on real pods).
    mesh = Mesh(np.array(jax.devices()), ("dp",))
    x = jnp.ones((info["local_devices"],), jnp.float32) * (jax.process_index() + 1)
    arr = multihost_utils.host_local_array_to_global_array(x, mesh, P("dp"))
    total = jax.jit(lambda a: jnp.sum(a), out_shardings=NamedSharding(mesh, P()))(arr)
    info["global_sum"] = float(total)
    ctx.update_meta({"dist_check": info})
    ctx.barrier("dist-done", timeout=120.0)


@pytest.mark.slow
def test_two_process_jax_distributed_psum(tmp_path):
    env = tpu_info.chip_visibility_env((), platform="cpu", simulate_chips=2)
    cluster = tcluster.run(
        _dist_map_fun,
        None,
        num_executors=2,
        input_mode=tcluster.InputMode.DIRECT,
        launcher=SubprocessLauncher(),
        env=env,
        jax_distributed=True,
        log_dir=str(tmp_path),
        reservation_timeout=180.0,
    )
    cluster.shutdown(timeout=300.0)
    infos = [m.get("dist_check") for m in cluster.coordinator.cluster_info()]
    assert all(i is not None for i in infos), f"missing dist_check: {infos}"
    for info in infos:
        assert info["process_count"] == 2
        assert info["local_devices"] == 2
        # global view = union of both processes' devices
        assert info["global_devices"] == 4
        # host0 contributes [1,1], host1 [2,2] -> 6
        assert info["global_sum"] == 6.0
    # the post-initialize device report replaced the placeholder
    for m in cluster.coordinator.cluster_info():
        assert m["device"]["platform"] == "cpu"
        assert m["device"]["num_devices"] == 2


@pytest.mark.slow
def test_two_process_1f1b_pipeline_over_dcn(tmp_path):
    """Pipeline parallelism ACROSS hosts: pp=4 spans two processes (2
    virtual chips each), every 1F1B tick ppermutes activations/grad wires
    over the process boundary, and loss + addressable grad shards match
    sequential autodiff on both hosts."""
    from tests import mapfuns

    env = tpu_info.chip_visibility_env((), platform="cpu", simulate_chips=2)
    cluster = tcluster.run(
        mapfuns.train_1f1b_pipeline_dist,
        None,
        num_executors=2,
        input_mode=tcluster.InputMode.DIRECT,
        launcher=SubprocessLauncher(),
        env=env,
        jax_distributed=True,
        log_dir=str(tmp_path),
        reservation_timeout=180.0,
    )
    cluster.shutdown(timeout=300.0)
    infos = [m.get("pp_dist") for m in cluster.coordinator.cluster_info()]
    assert all(i is not None for i in infos), f"missing pp_dist: {infos}"
    for info in infos:
        assert info["process_count"] == 2
        assert info["pp"] == 4
        # exactly 2 of pp=4 stages' grad shards live on each 2-chip process;
        # more would mean the P('pp') grads silently became replicated
        assert info["n_local_shards"] == 2
        assert info["shards_ok"], info
        assert abs(info["loss"] - info["loss_ref"]) < 1e-5, info


def _dist_map_fun_check_env(args, ctx):
    """_dist_map_fun plus: assert env values with spaces survived the ssh
    shell-quoting (launcher.py ssh branch joins argv into one remote shell
    line — the exact bug class only an executed transport catches)."""
    import os

    expected = args["expect_env"]
    for key, want in expected.items():
        got = os.environ.get(key)
        assert got == want, f"env {key!r}: {got!r} != {want!r}"
    _dist_map_fun(args, ctx)


@pytest.mark.slow
def test_pod_launcher_ssh_transport_two_hosts(tmp_path, monkeypatch):
    """Drive the REAL ssh branch end-to-end with a fake `ssh` on PATH that
    execs the remote shell line locally (`bash -c "$*"`), exactly as sshd's
    remote shell would.  Covers: argv quoting (env values with spaces),
    stdin payload delivery, per-host env composition, log routing, and the
    2-process global mesh."""
    import os
    import stat

    shim_dir = tmp_path / "bin"
    shim_dir.mkdir()
    shim = shim_dir / "ssh"
    # argv: ssh -o BatchMode=yes <host> <tok> <tok> ...  → record, then run
    # the joined remote line through a shell (what sshd does remotely)
    shim.write_text(
        "#!/bin/bash\n"
        f'echo "$@" >> {tmp_path}/ssh_calls.log\n'
        'if [ "$1" = "-o" ]; then shift 2; fi\n'
        "host=$1; shift\n"
        'exec bash -c "$*"\n'
    )
    shim.chmod(shim.stat().st_mode | stat.S_IEXEC)
    monkeypatch.setenv("PATH", f"{shim_dir}{os.pathsep}{os.environ['PATH']}")

    from tensorflowonspark_tpu.launcher import TPUPodLauncher

    spaced = "--fake_a=1 --fake_b='two words'"
    # Real ssh does NOT inherit the driver's sys.path (remote hosts have
    # their own installs); the shim execs locally, so ship the import path
    # explicitly as pod env — which also covers quoting of ':'-joined values.
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pod = TPUPodLauncher(hosts=["pod-host-0", "pod-host-1"], transport="ssh",
                         platform="cpu", simulate_chips=2,
                         env={"TOS_TEST_SPACES": spaced,
                              "PYTHONPATH": f"{repo}{os.pathsep}{os.path.join(repo, 'tests')}"})
    cluster = tcluster.run(
        _dist_map_fun_check_env,
        {"expect_env": {"TOS_TEST_SPACES": spaced}},
        num_executors=2,
        input_mode=tcluster.InputMode.DIRECT,
        launcher=pod,
        log_dir=str(tmp_path / "logs"),
        reservation_timeout=180,
    )
    # Multi-host fidelity guard (VERDICT r4 weak #1): nothing a remote host
    # consumes may point at loopback — the advertised coordinator address and
    # every registered host must be routable, or a REAL pod (where the shim
    # is actual sshd) could never form.  (Skipped only when the box itself
    # has no routable interface, local_ip()'s documented fallback.)
    from tensorflowonspark_tpu.utils.net import local_ip

    if local_ip() != "127.0.0.1":
        assert cluster.coordinator.address[0] != "127.0.0.1"
        for m in cluster.coordinator.cluster_info():
            assert m["host"] != "127.0.0.1"
    cluster.shutdown(timeout=300.0)
    infos = [m.get("dist_check") for m in cluster.coordinator.cluster_info()]
    assert all(i is not None for i in infos), f"missing dist_check: {infos}"
    for info in infos:
        assert info["process_count"] == 2
        assert info["global_devices"] == 4
        assert info["global_sum"] == 6.0
    # the shim really was the transport: one call per host, BatchMode set
    calls = (tmp_path / "ssh_calls.log").read_text().strip().splitlines()
    assert len(calls) == 2
    hosts = {c.split()[2] for c in calls}
    assert hosts == {"pod-host-0", "pod-host-1"}
    assert all(c.startswith("-o BatchMode=yes") for c in calls)
    # log routing: one node log per host with node output in it
    for i in (0, 1):
        assert (tmp_path / "logs" / f"node_{i}.log").exists()


@pytest.mark.slow
def test_node_death_unblocks_stalled_train_and_barrier(tmp_path):
    """The stalled-train() variant (VERDICT r4 item 4): a peer dies while
    the survivor waits in a control-plane barrier and the driver's train()
    is stalled feeding the survivor's full queue.  The dead-node monitor
    must mark the death, abort the barrier via the stop signal, unblock
    train(), and surface a RuntimeError — all within a few heartbeat
    windows, with no 300s barrier / 600s feed timeout in the path."""
    import threading
    import time

    from tests import mapfuns

    parts = [[float(i) for i in range(1000)], [float(i) for i in range(1000)]]
    cluster = tcluster.run(
        mapfuns.batch_then_barrier,
        {"n": 8, "hang_id": 1},
        num_executors=2,
        input_mode=tcluster.InputMode.STREAMING,
        queue_capacity=64,
        log_dir=str(tmp_path),
        reservation_timeout=120.0,
    )
    # kill the HANGING node (executor 1): executor ids are assigned in
    # registration order, so map through launch_index instead of assuming
    # processes[1] is executor 1
    id_to_proc = {m["executor_id"]: cluster.launcher.processes[m["launch_index"]]
                  for m in cluster.cluster_info}
    victim = id_to_proc[1]
    threading.Timer(2.0, victim.terminate).start()
    t0 = time.monotonic()
    with pytest.raises(RuntimeError):
        cluster.train(parts, num_epochs=1)
    # a few heartbeat windows; looser than the <30s bound of the
    # jax.distributed variant to tolerate loaded 1-core CI boxes
    assert time.monotonic() - t0 < 60.0
    errs = cluster.coordinator.errors()
    assert any("stopped heartbeating" in e["traceback"] for e in errs), errs
    with pytest.raises(RuntimeError):
        cluster.shutdown(timeout=60.0)


@pytest.mark.slow
def test_evaluator_death_is_non_fatal(tmp_path, monkeypatch):
    """The evaluator is an optional sidecar (no feed, no collectives): its
    death mid-train must NOT abort training — the monitor logs it, forgets
    it, and the data nodes finish their feed with every sample delivered.
    (Shutdown still reports the killed process's abnormal exit, as it
    always did.)"""
    import threading
    import time

    from tests import mapfuns

    monkeypatch.setenv("TOS_DEAD_NODE_TIMEOUT", "3")
    items = list(range(200))
    cluster = tcluster.run(
        mapfuns.paced_sum_eval_waits,
        {"batch_size": 4, "delay": 0.2, "out_dir": str(tmp_path)},
        num_executors=3,
        eval_node=True,
        input_mode=tcluster.InputMode.STREAMING,
        log_dir=str(tmp_path / "logs"),
        reservation_timeout=120.0,
    )
    eval_id = next(m["executor_id"] for m in cluster.cluster_info
                   if m["job_name"] == "evaluator")
    victim = cluster.launcher.processes[
        next(m["launch_index"] for m in cluster.cluster_info
             if m["executor_id"] == eval_id)]
    threading.Timer(1.0, victim.terminate).start()
    # train() returns once the feed is buffered; the data nodes then drain
    # it PACED (2 nodes x 100 items x 0.2s/4 items ≈ 5s), so the 3s
    # dead-node window elapses while they are still consuming — a monitor
    # that treated the evaluator like a data node would signal stop and
    # force-end their feeds mid-drain, shorting the sums below.
    cluster.train([items[:100], items[100:]], num_epochs=1)
    with pytest.raises(RuntimeError):  # killed process's exit code, as ever
        cluster.shutdown(timeout=60.0)
    assert not any("stopped heartbeating" in e["traceback"]
                   for e in cluster.coordinator.errors())
    sums = [float((tmp_path / f"node_{i}.txt").read_text().split()[0])
            for i in cluster._feed_ids]
    assert sum(sums) == sum(items)  # every sample delivered despite the death


def _linreg_partitions(num_partitions: int, rows_per_partition: int):
    """Deterministic (x, y) rows; partition p is reproducible from its index."""
    import numpy as np

    parts = []
    for p in range(num_partitions):
        rng = np.random.RandomState(100 + p)
        parts.append([
            (rng.randn(4).astype(np.float32), float(rng.randn()))
            for _ in range(rows_per_partition)
        ])
    return parts


def _numpy_sgd_reference(global_batches, lr=0.1):
    """Host-side replica of mapfuns.train_streaming_dist's model/optimizer."""
    import numpy as np

    w = np.full((4, 1), 0.5, np.float32)
    b = np.zeros((1,), np.float32)
    losses = []
    for xs, ys in global_batches:
        e = (xs @ w)[:, 0] + b[0] - ys
        losses.append(float(np.mean(e * e)))
        n = len(ys)
        w = w - lr * (2.0 / n) * (xs.T @ e)[:, None]
        b = b - lr * (2.0 / n) * np.sum(e)
    return losses, w


@pytest.mark.slow
def test_two_process_streaming_training(tmp_path):
    """The reference's defining combination (SURVEY §3.2/§5.8-3): driver
    streams DISJOINT partitions to each of 2 jax.distributed processes; every
    step is ONE global SPMD program over the concatenated global batch.
    Losses must be identical across hosts and match a single-process numpy
    replica of the same global batch sequence."""
    import numpy as np

    from tests import mapfuns

    bs = 4
    parts = _linreg_partitions(num_partitions=4, rows_per_partition=bs)
    env = tpu_info.chip_visibility_env((), platform="cpu", simulate_chips=2)
    cluster = tcluster.run(
        mapfuns.train_streaming_dist,
        {"batch_size": bs},
        num_executors=2,
        input_mode=tcluster.InputMode.STREAMING,
        launcher=SubprocessLauncher(),
        env=env,
        jax_distributed=True,
        log_dir=str(tmp_path),
        reservation_timeout=180.0,
    )
    cluster.train(parts, num_epochs=1)
    cluster.shutdown(timeout=300.0)
    infos = {m["executor_id"]: m.get("stream_dist")
             for m in cluster.coordinator.cluster_info()}
    assert all(i is not None for i in infos.values()), f"missing: {infos}"
    for info in infos.values():
        assert info["process_count"] == 2
        assert info["global_devices"] == 4
    # both hosts observed the SAME global losses (replicated scalar out of
    # one shared SPMD program) and trained on every one of their batches
    assert infos[0]["losses"] == infos[1]["losses"]
    assert infos[0]["ns"] == [bs, bs] and infos[1]["ns"] == [bs, bs]
    # global batch k = node0's k-th partition ++ node1's k-th partition
    # (round-robin placement: node0 gets partitions 0,2; node1 gets 1,3;
    # process order in the global array follows process_index)
    global_batches = []
    for k in range(2):
        rows = parts[2 * k] + parts[2 * k + 1]
        xs = np.stack([r[0] for r in rows])
        ys = np.asarray([r[1] for r in rows], np.float32)
        global_batches.append((xs, ys))
    ref_losses, ref_w = _numpy_sgd_reference(global_batches)
    np.testing.assert_allclose(infos[0]["losses"], ref_losses, rtol=1e-4)
    np.testing.assert_allclose(infos[0]["final_w"], ref_w.ravel(), rtol=1e-4)
    np.testing.assert_allclose(infos[1]["final_w"], ref_w.ravel(), rtol=1e-4)


@pytest.mark.slow
def test_two_process_streaming_uneven_partitions(tmp_path):
    """End-of-data lockstep: node0 gets 3 partitions, node1 gets 2.  Node1
    must keep joining the global step with filler batches (n=0) until the
    all_done consensus fires — same number of global steps on both hosts, no
    hang (the MWMS no-early-exit constraint, SURVEY §5.8-3)."""
    from tests import mapfuns

    bs = 4
    parts = _linreg_partitions(num_partitions=5, rows_per_partition=bs)
    env = tpu_info.chip_visibility_env((), platform="cpu", simulate_chips=2)
    cluster = tcluster.run(
        mapfuns.train_streaming_dist,
        {"batch_size": bs},
        num_executors=2,
        input_mode=tcluster.InputMode.STREAMING,
        launcher=SubprocessLauncher(),
        env=env,
        jax_distributed=True,
        log_dir=str(tmp_path),
        reservation_timeout=180.0,
    )
    cluster.train(parts, num_epochs=1)
    cluster.shutdown(timeout=300.0)
    infos = {m["executor_id"]: m.get("stream_dist")
             for m in cluster.coordinator.cluster_info()}
    assert all(i is not None for i in infos.values()), f"missing: {infos}"
    # node0: partitions 0,2,4 -> 3 real batches; node1: 1,3 -> 2 real + 1 filler
    assert infos[0]["ns"] == [bs, bs, bs]
    assert infos[1]["ns"] == [bs, bs, 0]
    assert len(infos[0]["losses"]) == len(infos[1]["losses"]) == 3
    assert infos[0]["losses"] == infos[1]["losses"]
    assert all(l == l and l < float("inf") for l in infos[0]["losses"])


@pytest.mark.slow
def test_two_process_streaming_checkpoint_and_resume(tmp_path):
    """Checkpointing DURING multi-host streaming training: the collective
    chief_save writes the GLOBAL state (every process serializes its
    addressable shards), the driver can read it back, and a restarted
    2-process cluster resumes from it (step counter continues)."""
    import numpy as np

    from tensorflowonspark_tpu.checkpoint import restore_checkpoint, latest_step_dir
    from tests import mapfuns

    bs = 4
    parts = _linreg_partitions(num_partitions=4, rows_per_partition=bs)
    env = tpu_info.chip_visibility_env((), platform="cpu", simulate_chips=2)

    def run_once(logdir):
        cluster = tcluster.run(
            mapfuns.train_streaming_dist_ckpt,
            {"batch_size": bs, "model_dir": str(tmp_path / "model"),
             "checkpoint_every": 1},
            num_executors=2,
            input_mode=tcluster.InputMode.STREAMING,
            launcher=SubprocessLauncher(),
            env=env,
            jax_distributed=True,
            log_dir=str(tmp_path / logdir),
            reservation_timeout=180.0,
        )
        cluster.train(parts, num_epochs=1)
        cluster.shutdown(timeout=300.0)
        return {m["executor_id"]: m["ckpt_dist"]
                for m in cluster.coordinator.cluster_info()}

    infos = run_once("logs1")
    assert infos[0]["final_step"] == infos[1]["final_step"] == 2
    # mid-loop collective saves landed too (lockstep makes them safe):
    # steps 1 and 2 both committed
    import os as _os

    assert sorted(_os.listdir(tmp_path / "model")) == ["step_1", "step_2"]
    # the committed checkpoint is readable driver-side and matches the
    # state both hosts reported
    path = latest_step_dir(str(tmp_path / "model"))
    assert path is not None and path.endswith("step_2")
    tree = restore_checkpoint(path)
    np.testing.assert_allclose(np.asarray(tree["params"]["w"]).ravel(),
                               infos[0]["final_w"], rtol=1e-6)
    # restart over the same model_dir: training RESUMES (step continues,
    # first loss differs from the fresh run's first loss)
    infos2 = run_once("logs2")
    assert infos2[0]["final_step"] == 4
    assert infos2[0]["losses"][0] != infos[0]["losses"][0]


@pytest.mark.slow
def test_distributed_node_death_surfaces_bounded_error(tmp_path):
    """Failure detection in the defining mode (SURVEY §5.3): killing one
    process of a 2-process jax.distributed STREAMING job must surface as a
    driver-side RuntimeError within a bounded time — never a silent hang.
    The surviving peer may be wedged inside a gloo collective; the
    escalating shutdown (stop signal -> SIGTERM -> kill) must still reclaim
    it and report the abnormal exits."""
    import threading
    import time

    from tests import mapfuns

    bs = 4
    parts = _linreg_partitions(num_partitions=40, rows_per_partition=bs)
    env = tpu_info.chip_visibility_env((), platform="cpu", simulate_chips=2)
    cluster = tcluster.run(
        mapfuns.train_streaming_dist,
        {"batch_size": bs},
        num_executors=2,
        input_mode=tcluster.InputMode.STREAMING,
        launcher=SubprocessLauncher(),
        env=env,
        jax_distributed=True,
        log_dir=str(tmp_path),
        reservation_timeout=180.0,
    )
    victim = cluster.launcher.processes[1]
    threading.Timer(3.0, victim.terminate).start()
    t0 = time.monotonic()
    with pytest.raises(RuntimeError):
        cluster.train(parts, num_epochs=1)
        cluster.shutdown(timeout=30.0)
    # The driver's dead-node monitor (not a feed/collective timeout) must
    # surface the death: a few heartbeat windows, not feed_timeout (600s)
    # or jax's own ~100s missed-heartbeat detection.
    assert time.monotonic() - t0 < 30.0
    errs = cluster.coordinator.errors()
    assert any("stopped heartbeating" in e["traceback"] for e in errs), errs
    # reclaim whatever is left; errors already surfaced above
    try:
        cluster.shutdown(timeout=15.0)
    except RuntimeError:
        pass
    assert not cluster.launcher.alive()


@pytest.mark.slow
def test_two_process_sharded_streaming_inference(tmp_path):
    """Model-parallel streaming inference: params fsdp-sharded over a
    2-process global mesh, driver-streamed partitions scored by ONE SPMD
    forward per round, each host emitting only its own rows — ordered
    exactly-count results identical to local scoring.  Uneven partitions
    (5 over 2 workers) force filler rounds on the drier host."""
    import jax
    import numpy as np

    from tensorflowonspark_tpu import inference as tinfer
    from tensorflowonspark_tpu.checkpoint import export_bundle
    from tensorflowonspark_tpu.data import PartitionedDataset
    from tensorflowonspark_tpu.models import wide_deep
    from tensorflowonspark_tpu.models.registry import build_apply

    config = {"model": "wide_deep", "vocab_size": 101, "embed_dim": 4,
              "hidden": (8,), "bf16": False}
    model = wide_deep.build_wide_deep(config)
    params = wide_deep.init_params(model, jax.random.PRNGKey(0))
    export_bundle(str(tmp_path / "b"), jax.device_get(params), config)

    rows = wide_deep.synthetic_criteo(24, seed=5)
    feats = tinfer.rows_to_features(rows, None)
    expected = np.asarray(build_apply(config)(jax.device_get(params), feats))

    env = tpu_info.chip_visibility_env((), platform="cpu", simulate_chips=2)
    cluster = tcluster.run(
        tinfer.sharded_bundle_inference_loop,
        {"export_dir": str(tmp_path / "b"), "batch_size": 4,
         "mesh_axes": {"fsdp": -1}},
        num_executors=2,
        input_mode=tcluster.InputMode.STREAMING,
        launcher=SubprocessLauncher(),
        env=env,
        jax_distributed=True,
        log_dir=str(tmp_path / "logs"),
        reservation_timeout=180.0,
    )
    # window=1 would CIRCULAR-WAIT here without the sharded-mode clamp
    # (a window-gated node stops feeding its SPMD rounds while peers wait
    # for it in a collective); eof_when_done must force free dispatch
    parts_out = dict(cluster.inference_stream(
        PartitionedDataset.from_iterable(rows, 5), window=1,
        eof_when_done=True))
    cluster.shutdown(timeout=300.0)
    results = [x for p in sorted(parts_out) for x in parts_out[p]]
    assert len(results) == 24
    np.testing.assert_allclose(np.stack(results), expected,
                               rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_distributed_with_evaluator_collective_checkpoint(tmp_path):
    """jax_distributed + evaluator + collective checkpoint must compose: the
    evaluator stays OUT of the jax process group (orbax's internal
    sync_global_processes would otherwise wait on it forever), data nodes
    form a 2-process group and save collectively."""
    from tests import mapfuns

    bs = 4
    parts = _linreg_partitions(num_partitions=4, rows_per_partition=bs)
    env = tpu_info.chip_visibility_env((), platform="cpu", simulate_chips=2)
    cluster = tcluster.run(
        mapfuns.train_streaming_dist_ckpt,
        {"batch_size": bs, "model_dir": str(tmp_path / "model")},
        num_executors=3,
        eval_node=True,
        input_mode=tcluster.InputMode.STREAMING,
        launcher=SubprocessLauncher(),
        env=env,
        jax_distributed=True,
        log_dir=str(tmp_path / "logs"),
        reservation_timeout=180.0,
    )
    cluster.train(parts, num_epochs=1)
    cluster.shutdown(timeout=300.0)
    metas = {m["executor_id"]: m for m in cluster.coordinator.cluster_info()}
    # data nodes: one 2-process global job, checkpoint committed
    assert metas[0]["ckpt_dist"]["final_step"] == 2
    assert metas[1]["ckpt_dist"]["final_step"] == 2
    # evaluator: its own single-process jax, outside the group
    assert metas[2]["job_name"] == "evaluator"
    assert metas[2]["eval_process_count"] == 1


@pytest.mark.slow
def test_pod_launcher_local_transport_two_hosts(tmp_path):
    """A '2-host pod' on localhost through TPUPodLauncher(transport='local'):
    the launcher must compose per-host env, ship configs over stdin, force
    jax_distributed, and the two node processes must form one global mesh —
    the pod path end-to-end minus ssh (reference: Spark executor placement,
    ``TFCluster.py:~340-360``)."""
    from tensorflowonspark_tpu.launcher import TPUPodLauncher

    pod = TPUPodLauncher(hosts=["localhost", "localhost"], transport="local",
                         platform="cpu", simulate_chips=2)
    cluster = tcluster.run(
        _dist_map_fun,
        None,
        num_executors=2,
        input_mode=tcluster.InputMode.DIRECT,
        launcher=pod,
        log_dir=str(tmp_path),
        reservation_timeout=180,
    )
    cluster.shutdown(timeout=300.0)
    infos = [m.get("dist_check") for m in cluster.coordinator.cluster_info()]
    assert all(i is not None for i in infos), f"missing dist_check: {infos}"
    for info in infos:
        assert info["process_count"] == 2
        assert info["global_devices"] == 4
        assert info["global_sum"] == 6.0
