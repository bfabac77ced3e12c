"""Module-level map_funs used by cluster end-to-end tests.

Kept importable (not closures) so they ship cleanly to spawned node
processes, the way the reference's examples define ``main_fun`` at module
scope for Spark closure serialization.
"""

from __future__ import annotations

import os
import time


def noop(args, ctx):
    """Register, do nothing, exit."""
    return None


def sum_batches(args, ctx):
    """Drain the feed summing numbers; write the total to args['out_dir']."""
    feed = ctx.get_data_feed(train_mode=True)
    total = 0.0
    count = 0
    while not feed.should_stop():
        batch = feed.next_batch(args["batch_size"])
        total += sum(batch)
        count += len(batch)
    out = os.path.join(args["out_dir"], f"node_{ctx.executor_id}.txt")
    with open(out, "w") as f:
        f.write(f"{total} {count}")


def metered_sum_batches(args, ctx):
    """sum_batches plus explicit ``ctx.metrics`` usage — the user-facing
    telemetry surface: everything recorded here must ride the heartbeat
    piggyback into ``cluster.metrics()`` and the run report."""
    feed = ctx.get_data_feed(train_mode=True)
    total = 0.0
    count = 0
    with ctx.metrics.timed("train.drain_secs"):
        while not feed.should_stop():
            batch = feed.next_batch(args["batch_size"])
            total += sum(batch)
            count += len(batch)
            if batch:
                ctx.metrics.counter("train.user_batches").inc()
    ctx.metrics.gauge("train.total_sum").set(total)
    out = os.path.join(args["out_dir"], f"node_{ctx.executor_id}.txt")
    with open(out, "w") as f:
        f.write(f"{total} {count}")


def record_items(args, ctx):
    """Slow consumer that records every item it consumed — the autoscale
    coverage probe: the union of all nodes' files must cover the fed
    records exactly (duplicates allowed, loss not), whatever resizes
    happened mid-feed.  ``sleep_per_batch`` throttles consumption so a
    resize demonstrably lands while partitions are still queued/buffered.

    Each batch is appended and flushed as soon as it is consumed: the chaos
    test SIGKILLs this process mid-drain, and a write-at-exit log would
    silently lose every batch the victim consumed (the ledger only re-feeds
    what the victim never reported consumed)."""
    feed = ctx.get_data_feed(train_mode=True)
    out = os.path.join(args["out_dir"], f"node_{ctx.executor_id}.txt")
    with open(out, "a") as f:
        while not feed.should_stop():
            batch = feed.next_batch(args["batch_size"])
            if batch:
                f.write("".join(f"{int(x)}," for x in batch))
                f.flush()
                if args.get("sleep_per_batch"):
                    time.sleep(args["sleep_per_batch"])


def echo_inference(args, ctx):
    """Classic inference loop: read batches, emit one result per input item."""
    feed = ctx.get_data_feed(train_mode=False)
    while not feed.should_stop():
        batch = feed.next_batch(4)
        if batch:
            feed.batch_results([x * 2 for x in batch])


def early_terminator(args, ctx):
    """Consume a few items then terminate — exercises the fast-drain path."""
    feed = ctx.get_data_feed(train_mode=True)
    feed.next_batch(args["consume"])
    feed.terminate()


def failing(args, ctx):
    raise ValueError("intentional failure for error propagation test")


def barrier_user(args, ctx):
    """Exercise ctx.barrier and the all_done consensus."""
    ctx.barrier("start")
    # Node i claims done after i+1 rounds; all_done must only turn True when
    # every node is done (sync SPMD end-of-data consensus, SURVEY.md §7.3-1).
    rounds = 0
    me_done = False
    while True:
        rounds += 1
        me_done = rounds > ctx.executor_id
        if ctx.all_done(me_done):
            break
        time.sleep(0.01)
    out = os.path.join(args["out_dir"], f"rounds_{ctx.executor_id}.txt")
    with open(out, "w") as f:
        f.write(str(rounds))


def consensus_with_eval(args, ctx):
    """Evaluator never touches the feed/consensus; data nodes still converge."""
    if ctx.job_name == "evaluator":
        return
    rounds = 0
    while True:
        rounds += 1
        if ctx.all_done(rounds > ctx.executor_id):
            break
    out = os.path.join(args["out_dir"], f"rounds_{ctx.executor_id}.txt")
    with open(out, "w") as f:
        f.write(str(rounds))


def read_referenced_shards(args, ctx):
    """Consume file REFERENCES from the feed and read the shards locally
    (the Spark data-locality analogue, data.from_file_references): sums the
    'label' column of every row in every referenced TFRecord shard."""
    from tensorflowonspark_tpu import dfutil

    feed = ctx.get_data_feed(train_mode=True)
    total, rows = 0, 0
    while not feed.should_stop():
        for path in feed.next_batch(4):
            for row in dfutil.read_shard(path, dfutil.read_schema(os.path.dirname(path))):
                total += int(row["label"])
                rows += 1
    out = os.path.join(args["out_dir"], f"node_{ctx.executor_id}.txt")
    with open(out, "w") as f:
        f.write(f"{total} {rows}")


def sum_lens(args, ctx):
    """Drain the feed summing item LENGTHS (bytes rows) — the fan-out
    throughput bench's consumer."""
    feed = ctx.get_data_feed(train_mode=True)
    total = 0
    count = 0
    while not feed.should_stop():
        batch = feed.next_batch(args["batch_size"])
        total += sum(len(x) for x in batch)
        count += len(batch)
    out = os.path.join(args["out_dir"], f"node_{ctx.executor_id}.txt")
    with open(out, "w") as f:
        f.write(f"{total} {count}")


def paced_sum_eval_waits(args, ctx):
    """Data nodes drain the feed slowly (paced per batch); the evaluator
    sidecar just waits for stop — the evaluator-death-is-non-fatal test
    kills it mid-train and training must still complete."""
    if ctx.job_name == "evaluator":
        ctx.stop_requested.wait(600)
        return
    feed = ctx.get_data_feed(train_mode=True)
    total, count = 0.0, 0
    while not feed.should_stop():
        batch = feed.next_batch(args["batch_size"])
        total += sum(batch)
        count += len(batch)
        time.sleep(args.get("delay", 0.05))
    with open(os.path.join(args["out_dir"], f"node_{ctx.executor_id}.txt"), "w") as f:
        f.write(f"{total} {count}")


def batch_then_barrier(args, ctx):
    """Consume one batch, then wait at a barrier before draining the rest.
    The node named by ``hang_id`` wedges BEFORE the barrier (simulating
    death mid-compute once the test kills it), so the barrier never
    completes naturally; only the driver's dead-node-monitor stop signal
    breaks the survivor out."""
    feed = ctx.get_data_feed(train_mode=True)
    feed.next_batch(args["n"])
    if ctx.executor_id == args.get("hang_id", -1):
        time.sleep(600)  # killed mid-"compute" by the test
    ctx.barrier("sync", timeout=300.0)
    while not feed.should_stop():
        feed.next_batch(args["n"])


def writes_role(args, ctx):
    out = os.path.join(args["out_dir"], f"role_{ctx.executor_id}.txt")
    with open(out, "w") as f:
        f.write(f"{ctx.job_name}:{ctx.task_index}:{ctx.num_executors}")


def custom_queue_consumer(args, ctx):
    """Consume from a non-default input queue name until EOF."""
    feed = ctx.get_data_feed(qname_in="train_q")
    seen = []
    while not feed.should_stop():
        seen.extend(feed.next_batch(3))
    with open(os.path.join(args["out_dir"], f"node_{ctx.executor_id}_custom.txt"), "w") as f:
        f.write(str(seen))


def train_wide_deep(args, ctx):
    """Pipeline-style train_fn: stream rows, SPMD train, chief exports bundle.

    ``args`` is a pipeline.Namespace carrying export_dir/batch_size/epochs
    plus test knobs (vocab_size).
    """
    import optax

    from tensorflowonspark_tpu.checkpoint import export_bundle
    from tensorflowonspark_tpu.models import wide_deep
    from tensorflowonspark_tpu.parallel import dp as dplib
    from tensorflowonspark_tpu.parallel import mesh as meshlib
    import jax

    # model_config (pipeline HasModelConfig param) wins; vocab_size rides
    # as a bare test knob otherwise.  Never fall back to the module default
    # vocab — that is the ~530 MB monolithic-table footgun.
    config = dict(args.get("model_config") or
                  {"model": "wide_deep",
                   "vocab_size": args.get("vocab_size", 1009),
                   "embed_dim": 4, "hidden": (16, 8), "bf16": False})
    model = wide_deep.build_wide_deep(config)
    params = wide_deep.init_params(model, jax.random.PRNGKey(0))
    optimizer = optax.adam(1e-2)
    mesh = meshlib.make_mesh(dp=-1)
    state = dplib.TrainState.create(dplib.replicate(params, mesh), optimizer)
    step_fn = dplib.make_train_step(wide_deep.make_loss_fn(model), optimizer)

    feed = ctx.get_data_feed(train_mode=True)
    batches = dplib.make_batch_iterator(
        feed, int(args.get("batch_size", 16)), wide_deep.batch_to_arrays,
        mesh=mesh, ctx=ctx, max_steps=args.get("steps"))
    loss = None
    n_steps = 0
    for batch, _n in batches:
        state, metrics = step_fn(state, batch)
        loss = float(metrics["loss"])
        n_steps += 1
    ctx.update_meta({"train_steps": n_steps})
    if ctx.executor_id == 0:
        export_bundle(args.export_dir, jax.device_get(state.params), config)
    ctx.barrier("export")  # everyone waits for the bundle before exiting
    if loss is not None:
        with open(os.path.join(args.log_dir, f"loss_{ctx.executor_id}.txt"), "w") as f:
            f.write(str(loss))


def train_streaming_dist(args, ctx):
    """Multi-host STREAMING training: each node consumes its OWN streamed
    partitions, the global SPMD step trains over their concatenation.

    This is the reference's defining combination (Spark-streamed partitions
    feeding a multi-worker synchronized cluster, ``TFSparkNode.py:~430-510``
    + MWMS wiring): per-host ``DataFeed`` -> process-local batch ->
    ``mesh.shard_batch`` global assembly -> one jitted train step across all
    processes.  Records per-step losses and real-sample counts for the
    driver-side equivalence check.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflowonspark_tpu.parallel import dp as dplib

    mesh = ctx.make_mesh(dp=-1)
    params = {"w": np.full((4, 1), 0.5, np.float32), "b": np.zeros((1,), np.float32)}
    optimizer = optax.sgd(0.1)
    # Create state from HOST arrays, then place: optimizer.init must not run
    # eagerly on non-fully-addressable global arrays.
    state = dplib.replicate(dplib.TrainState.create(params, optimizer), mesh)

    def loss_fn(p, batch):
        pred = batch["x"] @ p["w"] + p["b"]
        err = pred[:, 0] - batch["y"]
        return jnp.mean(err * err), {}

    step_fn = dplib.make_train_step(loss_fn, optimizer)

    def to_arrays(items):
        xs = np.stack([np.asarray(i[0], np.float32) for i in items])
        ys = np.asarray([i[1] for i in items], np.float32)
        return {"x": xs, "y": ys}

    feed = ctx.get_data_feed(train_mode=True)
    losses, ns = [], []
    for batch, n in dplib.make_batch_iterator(
            feed, int(args["batch_size"]), to_arrays, mesh=mesh, ctx=ctx):
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
        ns.append(n)
    ctx.update_meta({"stream_dist": {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "global_devices": jax.device_count(),
        "losses": losses,
        "ns": ns,
        "final_w": np.asarray(jax.device_get(state.params["w"])).ravel().tolist(),
    }})
    ctx.barrier("stream-dist-done", timeout=120.0)


def train_streaming_dist_ckpt(args, ctx):
    """train_streaming_dist plus the full checkpoint lifecycle on a
    multi-process global mesh: restore-or-init at start (raw host restore ->
    process-aware placement), collective chief_save of the GLOBAL state at
    the end (every data node participates — orbax writes each process's
    addressable shards)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflowonspark_tpu.checkpoint import CheckpointManager, chief_save
    from tensorflowonspark_tpu.parallel import dp as dplib

    if ctx.job_name == "evaluator":
        # sidecar: OUTSIDE the jax.distributed process group (so orbax's
        # collective save barriers never wait on it); records that fact
        ctx.update_meta({"eval_process_count": jax.process_count()})
        return

    mesh = ctx.make_mesh(dp=-1)
    optimizer = optax.sgd(0.1)
    manager = CheckpointManager(args["model_dir"])
    host_state = dplib.TrainState.create(
        {"w": np.full((4, 1), 0.5, np.float32)}, optimizer)
    restored = manager.restore_latest(host_state._asdict())
    if restored is not None:
        host_state = dplib.TrainState(**restored[0])
    state = dplib.replicate(host_state, mesh)

    def loss_fn(p, batch):
        pred = batch["x"] @ p["w"]
        return jnp.mean((pred[:, 0] - batch["y"]) ** 2), {}

    step = dplib.make_train_step(loss_fn, optimizer)

    def to_arrays(items):
        return {"x": np.stack([np.asarray(i[0], np.float32) for i in items]),
                "y": np.asarray([i[1] for i in items], np.float32)}

    feed = ctx.get_data_feed(train_mode=True)
    ckpt_every = int(args.get("checkpoint_every", 0) or 0)
    losses = []
    for batch, _n in dplib.make_batch_iterator(
            feed, int(args["batch_size"]), to_arrays, mesh=mesh, ctx=ctx):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
        step_no = int(jax.device_get(state.step))
        # Mid-loop COLLECTIVE saves are safe under multi-process streaming:
        # the batch iterator keeps every host's global-step count in
        # lockstep, so all data nodes reach this save at the same step.
        if ckpt_every and step_no % ckpt_every == 0:
            chief_save(ctx, manager, step_no, state._asdict())
    chief_save(ctx, manager, int(jax.device_get(state.step)), state._asdict())
    ctx.update_meta({"ckpt_dist": {
        "losses": losses,
        "final_step": int(jax.device_get(state.step)),
        "final_w": np.asarray(jax.device_get(state.params["w"])).ravel().tolist(),
    }})


def train_1f1b_pipeline_dist(args, ctx):
    """Cross-process pipeline parallelism: the pp axis spans the global
    2-process mesh, so 1F1B's activation and gradient wires (ppermute)
    cross the process boundary every tick — pipeline parallelism over DCN
    (gloo stands in for XLA's cross-host collective-permute).  Loss and the
    locally-addressable gradient shards are parity-checked against
    sequential autodiff computed host-side."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorflowonspark_tpu.parallel import mesh as meshlib
    from tensorflowonspark_tpu.parallel import pp as pplib

    mesh = ctx.make_mesh(pp=-1)
    s = mesh.shape["pp"]
    d, batch, m = 4, 8, 2
    rng = np.random.RandomState(5)
    host_stacked = {"w": (rng.randn(s, d, d) * 0.4).astype(np.float32)}
    x_h = rng.randn(batch, d).astype(np.float32)
    y_h = rng.randn(batch, d).astype(np.float32)

    stacked = meshlib.shard_tree(mesh, host_stacked,
                                 pplib.stage_shardings(mesh, host_stacked))
    repl = {"x": meshlib.replicated(mesh), "y": meshlib.replicated(mesh)}
    data = meshlib.shard_tree(mesh, {"x": x_h, "y": y_h}, repl)

    def stage(p, h):
        return jnp.tanh(h @ p["w"])

    def mse(o, t):
        return jnp.mean((o - t) ** 2)

    loss, grads = pplib.pipeline_1f1b(stage, stacked, data["x"], mse,
                                      mesh=mesh, n_microbatches=m,
                                      targets=data["y"])
    loss = float(jax.device_get(loss))

    # sequential reference on this host's local default device
    def seq(p):
        h = jnp.asarray(x_h)
        for i in range(s):
            h = stage(jax.tree.map(lambda a: a[i], p), h)
        return jnp.mean((h - jnp.asarray(y_h)) ** 2)

    l_ref = float(seq(host_stacked))
    g_ref = np.asarray(jax.grad(seq)(host_stacked)["w"])
    shards_ok = all(
        np.allclose(np.asarray(sh.data), g_ref[sh.index], atol=1e-5)
        for sh in grads["w"].addressable_shards)
    ctx.update_meta({"pp_dist": {
        "process_count": jax.process_count(),
        "pp": int(s),
        "loss": loss,
        "loss_ref": l_ref,
        "shards_ok": bool(shards_ok),
        "n_local_shards": len(grads["w"].addressable_shards),
    }})
    ctx.barrier("pp-dist-done", timeout=120.0)


def hangs_forever(args, ctx):
    """Ignores EOF and stop signals (zombie teardown probe)."""
    while True:
        time.sleep(0.5)


def elastic_sum_batches(args, ctx):
    """Restartable feed consumer for the elastic-recovery tests.

    Appends every consumed item to a per-(executor, incarnation) coverage
    file (so the test can assert at-least-once delivery across a death), and
    — when ``args['model_dir']`` is set — checkpoints a step counter after
    every batch and resumes it via ``checkpoint.restore_for_restart`` on a
    supervised restart, reporting the resumed step through ``update_meta``.
    """
    manager = None
    step = 0
    if args.get("model_dir"):
        import numpy as np

        from tensorflowonspark_tpu import checkpoint as tckpt

        model_dir = os.path.join(args["model_dir"], f"node_{ctx.executor_id}")
        manager = tckpt.CheckpointManager(model_dir, max_to_keep=2,
                                          async_save=False)
        restored = tckpt.restore_for_restart(ctx, manager)
        if restored is not None:
            step = int(restored[1])
    ctx.update_meta({"incarnation": ctx.incarnation,
                     f"resumed_step_inc{ctx.incarnation}": step})
    cover = os.path.join(
        args["out_dir"], f"seen_{ctx.executor_id}_inc{ctx.incarnation}.txt")
    feed = ctx.get_data_feed(train_mode=True)
    with open(cover, "a") as f:
        while not feed.should_stop():
            batch = feed.next_batch(args["batch_size"])
            if not batch:
                continue
            f.write("".join(f"{int(x)}\n" for x in batch))
            f.flush()
            step += 1
            if manager is not None:
                manager.save(step, {"step": np.asarray(step)})


def direct_record_counter(args, ctx):
    """DIRECT-mode consumer: ``ctx.get_data_feed`` returns the ingest feed
    (shard paths in, record payload bytes out).  Appends every record's
    utf-8 payload to a per-(executor, incarnation) coverage file — the
    at-least-once / exact-coverage probe for the direct-ingestion tests —
    and publishes the job manifest + per-incarnation record count via
    ``update_meta`` once the feed ends."""
    feed = ctx.get_data_feed(train_mode=True)
    cover = os.path.join(
        args["out_dir"], f"seen_{ctx.executor_id}_inc{ctx.incarnation}.txt")
    ctx.update_meta({"incarnation": ctx.incarnation})
    n = 0
    with open(cover, "a") as f:
        while not feed.should_stop():
            batch = feed.next_batch(args.get("batch_size", 16))
            if not batch:
                continue
            # zero-copy contract: records are memoryviews (plain shards)
            # or bytes (gzip); str() handles both without retaining views
            f.write("".join(str(rec, "utf-8") + "\n" for rec in batch))
            f.flush()
            n += len(batch)
            if args.get("sleep_per_batch"):
                # chaos pacing: keep the feed in flight long enough for a
                # mid-train fault to land deterministically
                time.sleep(args["sleep_per_batch"])
    ctx.update_meta({f"records_inc{ctx.incarnation}": n,
                     "manifest": ctx.job_manifest()})


def direct_fit_counter(args, ctx):
    """DIRECT-mode pipeline train_fn: drain the ledger-driven ingest feed
    and write this node's record count — the probe for the
    ``TPUEstimator.fit`` DIRECT-onto-the-ledger satellite (``args`` is the
    merged pipeline Namespace, so params arrive attribute-style)."""
    feed = ctx.get_data_feed(train_mode=True)
    n = 0
    while not feed.should_stop():
        n += len(feed.next_batch(args.get("batch_size", 16)))
    out = os.path.join(args.log_dir, f"fit_count_{ctx.executor_id}.txt")
    with open(out, "w") as f:
        f.write(str(n))


def pipelined_consensus_consumer(args, ctx):
    """Feed consumer driving the PIPELINED end-of-data consensus by hand
    (vote -> "train step" -> resolve), for the death-mid-vote chaos tests.

    Writes its final consensus status to ``cons_<id>.txt``: "consensus" when
    the vote resolved normally, or "aborted:<err>" when a peer's death
    aborted the in-flight rendezvous — in which case it ALSO exercises the
    abandoned-vote recovery path (``_cons_pending`` reset: a fresh
    ``all_done_begin`` after an aborted pending vote must not deadlock on
    the dedicated connection's held lock).
    """
    feed = ctx.get_data_feed(train_mode=True)
    out = os.path.join(args["out_dir"], f"cons_{ctx.executor_id}.txt")
    status = "incomplete"
    while True:
        batch = feed.next_batch(args["batch_size"])  # victim's kill fires here
        dry = feed.should_stop() and not batch
        result = ctx.all_done_begin(dry, timeout=120.0)
        time.sleep(args.get("step_delay", 0.05))  # the overlapped "step"
        try:
            if result():
                status = "consensus"
                break
        except RuntimeError as e:
            status = f"aborted:{e}"
            try:
                # must return immediately on a fresh connection, not
                # self-deadlock on the abandoned vote's held client lock
                ctx.all_done_begin(True, timeout=5.0)
                status += ";reset-ok"
            except RuntimeError as e2:
                status += f";reset-raised:{e2}"
            break
    with open(out, "w") as f:
        f.write(status)


# -- cross-host collectives (ISSUE 12) ----------------------------------------


def collective_ops_probe(args, ctx):
    """Form a collective group and run every primitive once with exact
    integer-valued payloads; publish the results for driver-side equality
    checks (ring and naive must both produce the exact sums)."""
    import numpy as np

    group = ctx.collective_group(name="probe")
    group.form()
    r, w = group.rank, group.world
    base = np.arange(6, dtype=np.float32).reshape(2, 3) + float(r + 1)
    ring = group.all_reduce(base, algo="ring")
    naive = group.all_reduce(base, algo="naive")
    mean = group.all_reduce(base, average=True, algo="ring")
    bc = group.broadcast(np.full(5, 8.0, np.float32) if r == 1 else None,
                         root=1)
    gathered = group.all_gather(np.full(2 + r, float(r), np.float32))
    seg_idx, seg = group.reduce_scatter(
        np.arange(8, dtype=np.float32) * (r + 1))
    group.barrier()
    ctx.update_meta({"probe": {
        "rank": r, "world": w, "generation": group.generation,
        "ring": ring.tolist(), "naive": naive.tolist(),
        "mean": mean.tolist(), "bcast": bc.tolist(),
        "gathered": [g.tolist() for g in gathered],
        "seg_idx": int(seg_idx), "seg": seg.tolist(),
    }})
    group.close()


def train_sync_collective(args, ctx):
    """Feed-driven cross-host synchronous training (``mode="sync"``): each
    node drains its own streamed partitions in lockstep and the gradient
    tree mean-reduces across hosts each step via the group's bucketed ring
    all-reduce — the MultiWorkerMirrored replacement the equivalence test
    pins against a single-process run on the same data order."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflowonspark_tpu.parallel import dp as dplib

    group = ctx.collective_group()
    group.form()
    optimizer = optax.sgd(0.1)
    state = dplib.TrainState.create(
        {"w": np.full((3, 1), 0.5, np.float32),
         "b": np.zeros((1,), np.float32)}, optimizer)

    def loss_fn(p, batch):
        pred = batch["x"] @ p["w"] + p["b"]
        err = pred[:, 0] - batch["y"]
        return jnp.mean(err * err), {}

    train = dplib.make_train_step(loss_fn, optimizer,
                                  cross_host_grad_fn=group.grad_fn())

    def to_arrays(items):
        return {"x": np.stack([np.asarray(i[0], np.float32) for i in items]),
                "y": np.asarray([i[1] for i in items], np.float32)}

    feed = ctx.get_data_feed(train_mode=True)
    losses = []
    for batch, _n in dplib.make_batch_iterator(
            feed, int(args["batch_size"]), to_arrays, ctx=ctx,
            lockstep=True):
        state, metrics = train(state, batch)
        losses.append(float(metrics["loss"]))
    group.barrier()
    ctx.update_meta({"sync_train": {
        "rank": group.rank, "world": group.world, "losses": losses,
        "final_w": np.asarray(
            jax.device_get(state.params["w"])).ravel().tolist(),
        "final_b": float(np.asarray(jax.device_get(state.params["b"]))[0]),
        "steps": int(jax.device_get(state.step)),
        "manifest_mode": ctx.job_manifest().get("mode"),
        "manifest_sync": ctx.job_manifest().get("sync"),
    }})
    group.close()


def chaos_batch(rank, step, batch_size=8):
    """Deterministic per-(rank, step) linear-regression batch with small
    integer-valued floats, so the chaos test's fault-free reference can be
    recomputed exactly in the driver."""
    import numpy as np

    base = np.arange(batch_size * 3, dtype=np.float32).reshape(batch_size, 3)
    x = (base * (1.0 + rank) + step) % 5.0
    y = (np.arange(batch_size, dtype=np.float32) + rank) % 3.0
    return {"x": x.astype(np.float32), "y": y.astype(np.float32)}


def sync_coordinator_chaos(args, ctx):
    """Fixed-step synchronous training with a per-step CONTROL-PLANE
    barrier, surviving a coordinator crash (ISSUE 13): the barrier (or the
    all-reduce a poisoned generation aborts) raises, everyone re-forms at
    the next generation barrier against the journal-recovered coordinator
    (CoordinatorClient reconnects with backoff; the form loop rides
    ``CoordinatorRestarted``/epoch fencing), ``sync_state`` levels any
    member that got one step ahead, and every node finishes at EXACTLY
    ``args['steps']`` with params equal to the fault-free run.

    The barrier runs BEFORE the train step so a member that failed it has
    an unchanged state; a member whose barrier succeeded but whose
    all-reduce then aborted is also unchanged (the apply half never runs on
    an aborted exchange) — reform + sync_state therefore always agree.

    ``step_delay`` is slept by rank r as ``r * step_delay`` after each step,
    so rank 0 waits in the next round's barrier while the others sleep: a
    round is in flight nearly all of the time, and a crash on ANY control
    op (a heartbeat as well as a barrier) poisons it.  The nodes publish
    ``coord_chaos_formed`` once the group stands, so the driver can arm the
    kill against the rounds rather than against the boot's heartbeats."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflowonspark_tpu.collective import CollectiveAborted
    from tensorflowonspark_tpu.parallel import dp as dplib

    total = int(args["steps"])
    # bounded collective timeout: a member whose peer is mid-reform must
    # abort its own round and re-enter the barrier in seconds, not ride
    # out the production 120s budget — this also scales the comm-flight
    # (2t+30) and reform-drain (t+30) backstops, which bound how long one
    # wedged broadcast/all-reduce cycle can cost during convergence
    group = ctx.collective_group(name="coordchaos", timeout=10.0)
    step = group.form(resume_step=0)
    optimizer = optax.sgd(0.125)
    state = dplib.TrainState.create(
        {"w": np.full((3, 1), 0.25, np.float32)}, optimizer)
    state, step = group.sync_state(state, step)

    def loss_fn(p, batch):
        pred = batch["x"] @ p["w"]
        err = pred[:, 0] - batch["y"]
        return jnp.mean(err * err), {}

    train = dplib.make_train_step(loss_fn, optimizer,
                                  cross_host_grad_fn=group.grad_fn())
    reforms = 0
    epochs_seen = set()
    ctx.update_meta({"coord_chaos_formed": True})

    def recover(cur_state, cur_step):
        # re-form until it sticks: a reform attempted WHILE the coordinator
        # is still mid-restore (or while a loaded box stretches the form
        # budget) aborts and must simply be re-entered — the run only
        # fails once the overall budget is truly gone.  Generous on
        # purpose: worst-case convergence stacks a wedged peer flight
        # (2t+30) on a drain backstop (t+30) before the barrier aligns.
        deadline = time.monotonic() + 240.0
        while True:
            try:
                group.reform(resume_step=cur_step)
                return group.sync_state(cur_state, cur_step)
            except (CollectiveAborted, RuntimeError, ConnectionError):
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.5)

    while step < total:
        batch = chaos_batch(group.rank, step)
        try:
            # per-step control-plane sync point: the op the coordinator
            # crash poisons.  Short timeout: a peer already re-forming
            # never joins this generation, so ride it out fast.
            group.barrier(timeout=8.0)
            state, _metrics = train(state, batch)
        except (CollectiveAborted, RuntimeError, ConnectionError):
            state, step = recover(state, step)
            reforms += 1
            continue
        step += 1
        if group._client.epoch is not None:
            epochs_seen.add(group._client.epoch)
        if args.get("step_delay"):
            time.sleep(args["step_delay"] * group.rank)
    while True:
        try:
            group.barrier(timeout=8.0)
            break
        except (CollectiveAborted, RuntimeError, ConnectionError):
            # a crash landing on the FINAL barrier: re-form so the peer
            # (which may be re-forming) can meet us, then re-enter
            state, step = recover(state, step)
            reforms += 1
    ctx.update_meta({"coord_chaos": {
        "rank": group.rank, "steps": step, "reforms": reforms,
        "generation": group.generation,
        "epochs_seen": sorted(epochs_seen),
        "final_w": np.asarray(
            jax.device_get(state.params["w"])).ravel().tolist(),
    }})
    group.close()


def sync_gray_chaos(args, ctx):
    """Fixed-step synchronous training under a GRAY failure (ISSUE 15):
    one member stalls mid-all-reduce (``stall_collective`` — alive and
    heartbeating, just silent on the peer plane).  Survivors must detect
    the straggler, evict it at quorum, and continue at the DEGRADED world;
    with ``grow_checks`` on they also poll for the evicted member's
    readmission and re-form larger at a later generation barrier.

    Results are written to ``gray_<eid>.txt`` FILES (json), not
    ``update_meta``: an evicted-and-never-readmitted victim's control
    plane is fenced, and its record must still reach the test."""
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflowonspark_tpu.collective import CollectiveAborted
    from tensorflowonspark_tpu.parallel import dp as dplib

    total = int(args["steps"])
    group = ctx.collective_group(name=args.get("group", "gray"),
                                 timeout=float(args.get("timeout", 30.0)))
    step = group.form(resume_step=0)
    optimizer = optax.sgd(0.125)
    state = dplib.TrainState.create(
        {"w": np.full((3, 1), 0.25, np.float32)}, optimizer)
    state, step = group.sync_state(state, step)

    def loss_fn(p, batch):
        pred = batch["x"] @ p["w"]
        err = pred[:, 0] - batch["y"]
        return jnp.mean(err * err), {}

    train = dplib.make_train_step(loss_fn, optimizer,
                                  cross_host_grad_fn=group.grad_fn())
    reforms = 0
    evicted_out = False
    detect_secs = None      # stall onset -> CollectiveAborted (detection)
    resume_secs = None      # stall onset -> first completed degraded step
    t_stall_start = None
    deadline = time.monotonic() + float(args.get("run_budget", 180.0))
    while step < total and time.monotonic() < deadline:
        if args.get("grow_checks") and group.check_grow(min_interval=0.5):
            # a readmitted member stands ready: grow back at the next
            # generation barrier and level it onto our step
            group.reform(resume_step=step)
            state, step = group.sync_state(state, step)
            reforms += 1
            continue
        batch = chaos_batch(group.rank, step)
        t_step = time.monotonic()
        try:
            state, _metrics = train(state, batch)  # victim stalls inside
        except CollectiveAborted:
            if t_stall_start is None:
                t_stall_start = t_step
                detect_secs = time.monotonic() - t_step
            try:
                group.reform(resume_step=step,
                             timeout=float(args.get("reform_budget", 60.0)))
            except CollectiveAborted:
                # this node could not stand at any barrier within the
                # budget: it is the evicted one (fenced through probation)
                evicted_out = True
                break
            state, step = group.sync_state(state, step)
            reforms += 1
            continue
        if resume_secs is None and t_stall_start is not None:
            resume_secs = time.monotonic() - t_stall_start
        step += 1
        if args.get("step_delay"):
            time.sleep(args["step_delay"])
    record = {
        "rank": group.rank, "steps": step, "reforms": reforms,
        "generation": group.generation,
        "effective_world": group.effective_world,
        "evicted_out": evicted_out,
        "detect_secs": detect_secs, "resume_secs": resume_secs,
        "final_w": np.asarray(
            jax.device_get(state.params["w"])).ravel().tolist(),
    }
    out = os.path.join(args["out_dir"], f"gray_{ctx.executor_id}.txt")
    with open(out, "w") as f:
        json.dump(record, f)
    group.close()


def sync_collective_chaos(args, ctx):
    """Fixed-step synchronous training on self-generated deterministic
    data, surviving a SIGKILL mid-all-reduce: survivors abort the poisoned
    round at the generation barrier, the supervised restart rejoins via
    ``reform`` + ``sync_state`` (state broadcast from the highest-step
    survivor), and every node finishes at EXACTLY ``args['steps']`` with
    identical params equal to the fault-free run."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflowonspark_tpu.collective import CollectiveAborted
    from tensorflowonspark_tpu.parallel import dp as dplib

    total = int(args["steps"])
    group = ctx.collective_group(name="chaos")
    step = group.form(resume_step=0)
    optimizer = optax.sgd(0.125)
    state = dplib.TrainState.create(
        {"w": np.full((3, 1), 0.25, np.float32)}, optimizer)
    state, step = group.sync_state(state, step)

    def loss_fn(p, batch):
        pred = batch["x"] @ p["w"]
        err = pred[:, 0] - batch["y"]
        return jnp.mean(err * err), {}

    train = dplib.make_train_step(loss_fn, optimizer,
                                  cross_host_grad_fn=group.grad_fn())
    reforms = 0
    while step < total:
        batch = chaos_batch(group.rank, step)
        try:
            state, _metrics = train(state, batch)  # victim's kill fires inside
        except CollectiveAborted:
            group.reform(resume_step=step)
            state, step = group.sync_state(state, step)
            reforms += 1
            continue
        step += 1
    group.barrier()
    ctx.update_meta({"chaos_sync": {
        "rank": group.rank, "steps": step, "reforms": reforms,
        "generation": group.generation, "incarnation": ctx.incarnation,
        "final_w": np.asarray(
            jax.device_get(state.params["w"])).ravel().tolist(),
    }})
    group.close()


# -- sharded embeddings (ISSUE 19) --------------------------------------------


def tree_digest(tree) -> str:
    """Order-pinned sha256 of a params pytree (flattened, keys sorted) —
    the bit-for-bit comparison handle the sharded-vs-unsharded parity
    tests exchange through update_meta instead of whole tables."""
    import hashlib

    import numpy as np

    from tensorflowonspark_tpu.checkpoint import _flatten_tree

    h = hashlib.sha256()
    flat = _flatten_tree(tree)
    for key in sorted(flat):
        h.update(key.encode())
        arr = np.ascontiguousarray(np.asarray(flat[key]))
        h.update(str(arr.dtype).encode() + str(arr.shape).encode())
        h.update(arr.tobytes())
    return h.hexdigest()


def criteo_batch(rank, step, batch_size=8):
    """Deterministic per-(rank, step) synthetic-Criteo batch, so sharded
    parity/chaos references can replay the exact per-node schedule."""
    from tensorflowonspark_tpu.models import wide_deep

    rows = wide_deep.synthetic_criteo(batch_size, seed=rank * 10007 + step)
    return wide_deep.batch_to_arrays(rows)


def embedding_probe(args, ctx):
    """Sparse-collective probe: exact-sum with duplicate ids within AND
    across nodes, the empty-partition edge (one owner receives nothing),
    a sparse all-to-all echo, and dense/sparse parity on a small table.
    Publishes everything for driver-side equality checks."""
    import numpy as np

    from tensorflowonspark_tpu.embedding import ShardPlan

    group = ctx.collective_group(name="embprobe")
    group.form()
    r, w = group.rank, group.world
    plan = ShardPlan.even("probe", 40, 3, w)

    # all-to-all echo: rank r sends [r*100 + d] to each d
    parts = [(np.array([r * 100 + d], np.int64), None) for d in range(w)]
    echo = group.sparse_all_to_all(parts)
    echo_ids = [g[0].tolist() for g in echo]

    # exact-sum: duplicate id 1 within each node and across all nodes,
    # plus a per-rank id — integer-valued floats, so sums are exact
    ids = np.array([1, 1, 30 + r, 7], np.int64)
    rows = np.full((4, 3), float(r + 1), np.float32)
    got_ids, got_rows = group.sparse_reduce_scatter(ids, rows, plan.bounds)

    # dense parity: the same contribution as a dense [total, dim] gradient
    # all-reduced — the sparse result must match the dense sum row for row
    dense = np.zeros((40, 3), np.float32)
    np.add.at(dense, ids, rows)
    dense_sum = group.all_reduce(dense)
    lo, hi = plan.range_of(r)
    mine = dense_sum[lo:hi]
    sparse_full = np.zeros_like(mine)
    if got_ids.size:
        sparse_full[got_ids - lo] = got_rows
    dense_match = bool(np.array_equal(sparse_full, mine))

    # empty-partition edge: every id lands in rank 0's range, so all other
    # owners must see a zero-row result (and nobody deadlocks on the empty
    # frames)
    ids0 = np.array([0, 2, 0], np.int64)
    rows0 = np.full((3, 3), float(10 * (r + 1)), np.float32)
    e_ids, e_rows = group.sparse_reduce_scatter(ids0, rows0, plan.bounds)
    group.barrier()
    ctx.update_meta({"embed_probe": {
        "rank": r, "world": w,
        "echo_ids": echo_ids,
        "got_ids": got_ids.tolist(), "got_rows": got_rows.tolist(),
        "dense_match": dense_match,
        "empty_ids": e_ids.tolist(),
        "empty_shape": list(e_rows.shape),
    }})
    group.close()


def train_wide_deep_sharded(args, ctx):
    """Sharded wide-and-deep sync training on deterministic synthetic-
    Criteo batches: dense half replicated (ring-averaged grads), fused
    embedding table range-sharded via the sparse collectives.  Publishes
    bit-comparison digests; with ``args.export_dir`` set, exports a
    sharded bundle (dense bundle + per-node shard ranges) for the serving
    tier."""
    import jax
    import numpy as np
    import optax

    from tensorflowonspark_tpu.checkpoint import export_bundle
    from tensorflowonspark_tpu.embedding import (
        EmbeddingShard,
        ShardedTable,
        ShardPlan,
    )
    from tensorflowonspark_tpu.embedding.serve import (
        export_sharded_shard,
        sharded_config_block,
    )
    from tensorflowonspark_tpu.models import wide_deep

    config = dict(args.get("model_config") or
                  {"model": "wide_deep_dense", "vocab_size": 97,
                   "embed_dim": 4, "hidden": (8,), "bf16": False})
    lr = float(args.get("lr", 0.125))  # power of two: exact at any world
    total = int(args.get("steps", 4))
    bsz = int(args.get("batch_size", 8))
    seed = int(args.get("table_seed", 11))

    group = ctx.collective_group(name="embed")
    group.form()
    block = (ctx.job_manifest().get("sync") or {}).get("embedding")
    plan = (ShardPlan.from_manifest(block) if block else
            ShardPlan.even("wide_deep", wide_deep.table_total_rows(config),
                           int(config["embed_dim"]) + 1, group.world))
    # fused table: [embed_dim | wide weight]; wide column zero-init like
    # the monolithic model's wide_weights
    shard = EmbeddingShard.create(plan, group.rank, seed=seed,
                                  zero_cols=(plan.dim - 1,))
    table = ShardedTable(shard, group)

    model = wide_deep.build_wide_deep_dense(config)
    params = wide_deep.init_dense_params(model, jax.random.PRNGKey(0))
    grad_fn = wide_deep.make_sharded_grad_fn(model)
    optimizer = optax.sgd(lr)
    opt_state = optimizer.init(params)
    dense_reduce = group.grad_fn()  # ring mean — exact at world 2
    vocab = int(config["vocab_size"])

    losses = []
    for step in range(total):
        batch = criteo_batch(group.rank, step, bsz)
        ids = wide_deep.flat_categorical_ids(batch["features"], vocab)
        rows = table.lookup(ids)
        (loss, _aux), (dg, rg) = grad_fn(params, rows, batch)
        dg = dense_reduce(dg)
        updates, opt_state = optimizer.update(dg, opt_state, params)
        params = optax.apply_updates(params, updates)
        table.apply_gradients(ids, np.asarray(jax.device_get(rg)), lr=lr,
                              scale=1.0 / group.world)
        losses.append(float(loss))
    group.barrier()
    if args.get("export_dir"):
        export_sharded_shard(args["export_dir"], plan, group.rank,
                             shard.rows, total)
        group.barrier()  # all shards committed before the chief's bundle
        if group.rank == 0:
            export_bundle(
                args["export_dir"], jax.device_get(params),
                {**config, "sharded_embedding":
                 sharded_config_block(plan, total)})
        ctx.barrier("export")
    ctx.update_meta({"sharded_train": {
        "rank": group.rank, "world": group.world, "steps": total,
        "losses": losses,
        "dense_digest": tree_digest(jax.device_get(params)),
        "shard_digest": tree_digest({"rows": shard.rows}),
        "shard_range": [shard.lo, shard.hi],
        "stats": dict(table.stats),
        "manifest_embedding": block,
    }})
    group.close()


def sharded_embed_chaos(args, ctx):
    """Sharded-table sync training surviving a SIGKILL of a shard OWNER
    mid-step: nobody else holds the dead node's rows, so recovery is
    checkpoint-based — every completed step commits the shard range + the
    dense params, and after the generation reforms the members min-vote
    their newest complete checkpoint, ALL restore to it (survivors roll
    back), and the deterministic schedule replays.  Exact step accounting:
    every node finishes at ``args['steps']`` with digests equal to the
    fault-free reference."""
    import glob

    import jax
    import numpy as np
    import optax

    from tensorflowonspark_tpu.checkpoint import (
        _flatten_tree,
        _unflatten_tree,
    )
    from tensorflowonspark_tpu.collective import CollectiveAborted
    from tensorflowonspark_tpu.embedding import (
        EmbeddingShard,
        ShardedTable,
        ShardPlan,
    )
    from tensorflowonspark_tpu.models import wide_deep

    config = dict(args.get("model_config") or
                  {"model": "wide_deep_dense", "vocab_size": 53,
                   "embed_dim": 3, "hidden": (8,), "bf16": False})
    lr = 0.125
    total = int(args["steps"])
    bsz = int(args.get("batch_size", 8))
    model_dir = args["model_dir"]
    eid = ctx.executor_id

    group = ctx.collective_group(name="embchaos", timeout=15.0)
    group.form(resume_step=0)
    plan = ShardPlan.even("chaos", wide_deep.table_total_rows(config),
                          int(config["embed_dim"]) + 1, group.world)
    shard = EmbeddingShard.create(plan, group.rank, seed=5,
                                  zero_cols=(plan.dim - 1,))
    table = ShardedTable(shard, group)

    model = wide_deep.build_wide_deep_dense(config)
    params = wide_deep.init_dense_params(model, jax.random.PRNGKey(0))
    grad_fn = wide_deep.make_sharded_grad_fn(model)
    optimizer = optax.sgd(lr)
    opt_state = optimizer.init(params)
    dense_reduce = group.grad_fn()
    vocab = int(config["vocab_size"])

    def dense_path(s):
        return os.path.join(model_dir, f"dense_e{eid}_s{s}.npz")

    def save_all(s):
        shard.save(model_dir, s)
        flat = {k: np.asarray(v)
                for k, v in _flatten_tree(jax.device_get(params)).items()}
        tmp = dense_path(s) + ".tmp"
        with open(tmp, "wb") as f:
            np.savez(f, **flat)
        os.replace(tmp, dense_path(s))

    def restore_all(s):
        nonlocal params, opt_state
        shard.restore(model_dir, s)
        with np.load(dense_path(s)) as z:
            params = _unflatten_tree({k: z[k] for k in z.files})
        opt_state = optimizer.init(params)  # sgd: stateless, exact

    def latest_saved():
        best = -1
        for path in glob.glob(dense_path("*")):
            try:
                s = int(path.rsplit("_s", 1)[1][:-len(".npz")])
            except ValueError:
                continue
            shard_file = os.path.join(
                model_dir, f"embed_{plan.name}", f"step_{s}",
                f"shard_{shard.lo}_{shard.hi}.npz")
            if os.path.exists(shard_file):
                best = max(best, s)
        return best

    def rendezvous(reform):
        """(Re)align the group, min-vote the newest complete checkpoint,
        restore everyone to it.  Returns the agreed step."""
        deadline = time.monotonic() + 240.0
        while True:
            try:
                mine = latest_saved()
                if reform:
                    group.reform(resume_step=max(mine, 0))
                votes = group.all_gather(
                    np.array([mine], np.int64))
                agreed = int(min(int(v[0]) for v in votes))
                if agreed < 0:
                    raise RuntimeError(
                        "no complete checkpoint on some member")
                restore_all(agreed)
                return agreed
            except (CollectiveAborted, RuntimeError, ConnectionError):
                reform = True
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.5)

    if ctx.is_restart:
        # the restarted victim: its in-memory table is fresh init — level
        # everyone from checkpoints (survivors roll back to the min vote)
        step = rendezvous(reform=False)
    else:
        save_all(0)
        step = 0
    reforms = 0
    while step < total:
        batch = criteo_batch(group.rank, step, bsz)
        try:
            ids = wide_deep.flat_categorical_ids(batch["features"], vocab)
            rows = table.lookup(ids)  # victim's kill fires in here
            (_loss, _aux), (dg, rg) = grad_fn(params, rows, batch)
            dg = dense_reduce(dg)
            updates, opt_state = optimizer.update(dg, opt_state, params)
            params = optax.apply_updates(params, updates)
            table.apply_gradients(ids, np.asarray(jax.device_get(rg)),
                                  lr=lr, scale=1.0 / group.world)
        except CollectiveAborted:
            step = rendezvous(reform=True)
            reforms += 1
            continue
        step += 1
        save_all(step)
    while True:
        try:
            group.barrier(timeout=10.0)
            break
        except (CollectiveAborted, RuntimeError, ConnectionError):
            step = rendezvous(reform=True)
            reforms += 1
    ctx.update_meta({"embed_chaos": {
        "rank": group.rank, "steps": step, "reforms": reforms,
        "generation": group.generation, "incarnation": ctx.incarnation,
        "dense_digest": tree_digest(jax.device_get(params)),
        "shard_digest": tree_digest({"rows": shard.rows}),
    }})
    group.close()


def estimator_wide_deep_sharded(args, ctx):
    """Feed-driven sharded train_fn for the TFEstimator path: synthetic-
    Criteo rows stream through the ordinary ingest/feed tier in lockstep,
    the fused table rides the sparse collectives, and the chief exports a
    sharded bundle to ``args.export_dir``."""
    import jax
    import numpy as np
    import optax

    from tensorflowonspark_tpu.checkpoint import export_bundle
    from tensorflowonspark_tpu.embedding import (
        EmbeddingShard,
        ShardedTable,
        ShardPlan,
    )
    from tensorflowonspark_tpu.embedding.serve import (
        export_sharded_shard,
        sharded_config_block,
    )
    from tensorflowonspark_tpu.models import wide_deep
    from tensorflowonspark_tpu.parallel import dp as dplib

    config = dict(args.get("model_config") or {})
    if not config:
        raise ValueError("estimator_wide_deep_sharded needs model_config")
    lr = float(args.get("lr", 0.125))
    vocab = int(config["vocab_size"])

    group = ctx.collective_group(name="embed")
    group.form()
    block = (ctx.job_manifest().get("sync") or {}).get("embedding")
    plan = (ShardPlan.from_manifest(block) if block else
            ShardPlan.even("wide_deep", wide_deep.table_total_rows(config),
                           int(config["embed_dim"]) + 1, group.world))
    shard = EmbeddingShard.create(plan, group.rank, seed=11,
                                  zero_cols=(plan.dim - 1,))
    table = ShardedTable(shard, group)

    model = wide_deep.build_wide_deep_dense(config)
    params = wide_deep.init_dense_params(model, jax.random.PRNGKey(0))
    grad_fn = wide_deep.make_sharded_grad_fn(model)
    optimizer = optax.sgd(lr)
    opt_state = optimizer.init(params)
    dense_reduce = group.grad_fn()

    feed = ctx.get_data_feed(train_mode=True)
    n_steps = 0
    loss = None
    for batch, _n in dplib.make_batch_iterator(
            feed, int(args.get("batch_size", 8)),
            wide_deep.batch_to_arrays, ctx=ctx, lockstep=True,
            max_steps=args.get("steps")):
        ids = wide_deep.flat_categorical_ids(
            np.asarray(batch["features"]), vocab)
        rows = table.lookup(ids)
        (loss_v, _aux), (dg, rg) = grad_fn(params, rows, batch)
        dg = dense_reduce(dg)
        updates, opt_state = optimizer.update(dg, opt_state, params)
        params = optax.apply_updates(params, updates)
        table.apply_gradients(ids, np.asarray(jax.device_get(rg)), lr=lr,
                              scale=1.0 / group.world)
        table.maybe_checkpoint(args.get("model_dir") or args.get("export_dir"),
                               n_steps)
        loss = float(loss_v)
        n_steps += 1
    group.barrier()
    export_sharded_shard(args.get("export_dir"), plan, group.rank, shard.rows,
                         n_steps)
    group.barrier()
    if group.rank == 0:
        export_bundle(args.get("export_dir"), jax.device_get(params),
                      {**config, "sharded_embedding":
                       sharded_config_block(plan, n_steps)})
    ctx.barrier("export")
    ctx.update_meta({"sharded_train": {
        "rank": group.rank, "world": group.world, "steps": n_steps,
        "loss": loss, "stats": dict(table.stats),
        "manifest_embedding": block,
    }})
    group.close()
