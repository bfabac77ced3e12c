"""Sync SPMD data-parallel training — the ParameterServer/MWMS replacement.

Reference (SURVEY.md §2.3): data parallelism via async parameter servers
(``tf.train.replica_device_setter``) or ``MultiWorkerMirroredStrategy``
(NCCL all-reduce), both configured through the ``TF_CONFIG`` env var TFoS
wrote.  TPU-native replacement (BASELINE.json:5): one jitted SPMD program
over a named mesh; the gradient all-reduce is emitted by XLA over ICI from
sharding annotations — there are no server objects, no strategy classes, and
no NCCL.

Usage::

    mesh = make_mesh(dp=-1)
    state = replicate(TrainState.create(params, optax.adam(1e-3)), mesh)
    step = make_train_step(loss_fn, optimizer)
    for batch in feed:
        state, metrics = step(state, shard_batch(mesh, batch))

``loss_fn(params, batch) -> (loss, aux_metrics)`` is the user contract
(``loss_fn(params, batch, buffers)`` where ``TrainState.buffers`` is set).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax

from tensorflowonspark_tpu import telemetry
from tensorflowonspark_tpu.parallel.mesh import batch_sharding, replicated


class TrainState(NamedTuple):
    """Minimal functional train state (params + optimizer state + step).

    ``buffers`` is what the model reads beside its parameters and nobody
    trains (a flax collection of that name: a router's selection bias,
    ``parallel/ep.MoEMLP``): the step hands it to the loss, takes no
    gradient by it, shows it to no optimizer and passes it on as it is.
    ``None``: the model has none."""

    params: Any
    opt_state: Any
    step: jax.Array
    buffers: Any = None

    @classmethod
    def create(cls, params: Any, optimizer: optax.GradientTransformation,
               buffers: Any = None) -> "TrainState":
        return cls(params=params, opt_state=optimizer.init(params),
                   step=jnp.zeros((), jnp.int32), buffers=buffers)


def replicate(tree: Any, mesh) -> Any:
    """Place a pytree fully-replicated on the mesh (pure data parallelism).

    Copies through host memory on purpose: ``jax.device_put`` may alias the
    source buffer as one replica, and the train step *donates* its state —
    donation through an alias would silently delete the caller's original
    arrays.  Host-staging guarantees fresh device buffers and also accepts
    sources committed to any device subset (e.g. an orbax restore on device
    0).  This runs once at job start; the copy cost is irrelevant.

    Works on multi-process meshes too (every host holds the same full value;
    assembly is delegated to ``mesh.shard_tree``).
    """
    from tensorflowonspark_tpu.parallel.mesh import shard_tree

    sharding = replicated(mesh)
    return shard_tree(mesh, tree, jax.tree.map(lambda _: sharding, tree))


def make_train_step(
    loss_fn: Callable[[Any, Any], tuple[jax.Array, dict]],
    optimizer: optax.GradientTransformation,
    donate: bool = True,
    accum_steps: int = 1,
    cross_host_grad_fn: Callable[[Any], Any] | None = None,
) -> Callable[[TrainState, Any], tuple[TrainState, dict]]:
    """Build the jitted SPMD train step.

    The batch arrives sharded over the ``(dp, fsdp)`` axes and params arrive
    replicated (or fsdp-sharded); XLA partitions the forward/backward and
    inserts the gradient all-reduce over ICI automatically.  Metrics come
    back replicated scalars (already globally reduced, since the loss is a
    mean over the global batch).

    ``accum_steps > 1`` enables gradient accumulation: the global batch is
    split into that many microbatches along axis 0 and run through a
    ``lax.scan`` (one compiled microstep body, not an unrolled loop);
    gradients/metrics are averaged and the optimizer applies ONE update.
    The per-call batch size must be divisible by ``accum_steps``.

    Equivalence caveat: the accumulated step averages each microbatch's
    ALREADY-NORMALIZED loss gradient.  For losses that are plain means over
    examples this equals the full-batch step exactly; for losses with
    data-dependent normalization (e.g. ``loss_mask`` token averaging, where
    each microbatch divides by its own mask count) the weighting differs —
    microbatches with few unmasked tokens count more per token.  For masked
    LM training either keep mask density uniform across microbatches or use
    ``accum_steps=1``.

    ``cross_host_grad_fn`` composes the step with CROSS-HOST data
    parallelism over the cluster wire (``cluster.train(mode="sync")``): a
    host callable (e.g. ``CollectiveGroup.grad_fn()``) applied to the
    gradient pytree between backward and update — typically a bucketed
    ring all-reduce averaging gradients across nodes.  The step then
    compiles as TWO jitted halves (grads+metrics, then update) sharing the
    same optimizer code, with the exchange on host in between; each half
    compiles once, and the hook's bucket pipeline overlaps communication
    with the device->host tail of backprop.  ``None`` keeps the
    single-program step byte-for-byte as before.
    """

    def grads_and_metrics(params: Any, batch: Any,
                          buffers: Any = None) -> tuple[Any, dict]:
        fn = loss_fn if buffers is None else (
            lambda params, batch: loss_fn(params, batch, buffers))
        if accum_steps == 1:
            with jax.named_scope("loss_and_grad"):
                (loss, aux), grads = jax.value_and_grad(
                    fn, has_aux=True)(params, batch)
            return grads, {"loss": loss, **aux}
        micro = jax.tree.map(
            lambda x: x.reshape(accum_steps, x.shape[0] // accum_steps,
                                *x.shape[1:]), batch)

        def body(carry, mb):
            grads_acc, metrics_acc = carry
            with jax.named_scope("loss_and_grad"):
                (l, aux), g = jax.value_and_grad(fn, has_aux=True)(
                    params, mb)
            m = {"loss": l, **aux}
            return (jax.tree.map(jnp.add, grads_acc, g),
                    jax.tree.map(jnp.add, metrics_acc, m)), None

        # Carry structure from an abstract eval — loss_fn is traced once
        # (inside the scan body), not twice.
        loss_sd, aux_sd = jax.eval_shape(
            fn, params, jax.tree.map(lambda x: x[0], micro))
        zeros = lambda sd: jnp.zeros(sd.shape, sd.dtype)  # noqa: E731
        init = (jax.tree.map(jnp.zeros_like, params),
                jax.tree.map(zeros, {"loss": loss_sd, **aux_sd}))
        (grads, msum), _ = jax.lax.scan(body, init, micro)
        grads = jax.tree.map(lambda g: g / accum_steps, grads)
        metrics = jax.tree.map(lambda m: m / accum_steps, msum)
        return grads, metrics

    def apply_update(state: TrainState, grads: Any) -> TrainState:
        with jax.named_scope("optimizer_update"):
            updates, opt_state = optimizer.update(grads, state.opt_state,
                                                  state.params)
            params = optax.apply_updates(state.params, updates)
        return TrainState(params, opt_state, state.step + 1, state.buffers)

    if cross_host_grad_fn is None:
        def step(state: TrainState, batch: Any) -> tuple[TrainState, dict]:
            grads, metrics = grads_and_metrics(state.params, batch,
                                               state.buffers)
            return apply_update(state, grads), metrics

        # Shardings are inferred from operand placement (replicated params +
        # dp-sharded batch ⇒ XLA partitions the step and all-reduces grads).
        return jax.jit(step, donate_argnums=(0,) if donate else ())

    grad_step = jax.jit(grads_and_metrics)
    apply_step = jax.jit(apply_update, donate_argnums=(0,) if donate else ())

    def hooked_step(state: TrainState, batch: Any) -> tuple[TrainState, dict]:
        grads, metrics = grad_step(state.params, batch, state.buffers)
        grads = cross_host_grad_fn(grads)
        return apply_step(state, grads), metrics

    return hooked_step


class BNTrainState(NamedTuple):
    """Train state for models with mutable normalization stats (ResNet/BN)."""

    params: Any
    batch_stats: Any
    opt_state: Any
    step: jax.Array

    @classmethod
    def create(cls, params: Any, batch_stats: Any,
               optimizer: optax.GradientTransformation) -> "BNTrainState":
        return cls(params=params, batch_stats=batch_stats,
                   opt_state=optimizer.init(params), step=jnp.zeros((), jnp.int32))


def make_bn_train_step(
    loss_fn: Callable[[Any, Any, Any], tuple[jax.Array, tuple[Any, dict]]],
    optimizer: optax.GradientTransformation,
    donate: bool = True,
) -> Callable[[BNTrainState, Any], tuple[BNTrainState, dict]]:
    """Jitted SPMD train step for BN models.

    ``loss_fn(params, batch_stats, batch) -> (loss, (new_batch_stats, aux))``.
    Under GSPMD the BN batch reductions over the dp-sharded axis compile to
    global cross-replica reductions — sync BatchNorm for free.
    """

    def step(state: BNTrainState, batch: Any) -> tuple[BNTrainState, dict]:
        with jax.named_scope("loss_and_grad"):
            grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
            (loss, (batch_stats, aux)), grads = grad_fn(
                state.params, state.batch_stats, batch)
        with jax.named_scope("optimizer_update"):
            updates, opt_state = optimizer.update(grads, state.opt_state,
                                                  state.params)
            params = optax.apply_updates(state.params, updates)
        metrics = {"loss": loss, **aux}
        return BNTrainState(params, batch_stats, opt_state, state.step + 1), metrics

    return jax.jit(step, donate_argnums=(0,) if donate else ())


def make_eval_step(
    apply_fn: Callable[[Any, Any], jax.Array],
) -> Callable[[Any, Any], jax.Array]:
    """Jitted inference step: params + sharded inputs -> outputs."""
    return jax.jit(apply_fn)


def cross_entropy_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean softmax cross-entropy over the (global) batch."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def accuracy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    return jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))


def make_batch_iterator(
    feed,
    batch_size: int,
    to_arrays: Callable[[list], Any],
    mesh=None,
    ctx=None,
    pad_to_batch: bool = True,
    prefetch: int = 2,
    max_steps: int | None = -1,
    lockstep: bool | None = None,
):
    """Drain a DataFeed into device-ready, mesh-sharded batches.

    Handles the sync-SPMD end-of-data problem (SURVEY.md §7.3-1): partial
    final batches are padded (repeating the last sample) and, when ``ctx`` is
    given, a control-plane ``all_done`` consensus decides when *all* hosts
    stop — no host may exit the step loop early.

    ``prefetch`` > 0 double-buffers the host side (SURVEY.md §7.3-6): a
    background thread drains the feed, converts (``to_arrays``) and starts
    the host→device transfer (``shard_batch``) for batch N+1 while the
    caller's jitted step N is still executing — the conversion/transfer cost
    disappears behind the device step instead of serializing with it.  Set
    ``prefetch=0`` for strictly synchronous delivery.

    The unit of conversion and placement is ONE DEVICE'S SHARD of the batch,
    not the batch.  Where this process's devices hold more than one row
    range of it (``mesh.batch_shards``: four on ``dp=4``, one per ``tp``
    group on ``dp × tp``), ``to_arrays`` is called once per range, all at
    once on threads the iterator owns (``batch-convert_N``); each thread
    ``device_put``s its arrays to the device(s) of its range as soon as they
    exist, and the global arrays are assembled from those under the sharding
    ``shard_batch`` gives.  No global host array is made.  With one range
    (one device, or ``mesh=None``) it is ``to_arrays(items)`` then
    ``shard_batch``, inline.

    That asks of ``to_arrays`` what ``shard_batch`` half asks already: every
    leaf leads with the batch dimension, and row *i* of the output depends on
    item *i* alone.  A converter that breaks it visibly (one that pads to the
    longest row of ITS batch, say) is seen: if the ranges' outputs disagree
    in tree structure, dtype or trailing shape, or a leaf's leading
    dimension is not the range's row count, that batch and every later one
    of this iterator is converted whole, as before, and counted in
    ``batch.convert_whole``.  One that breaks it invisibly (values
    normalised over the batch) must not be passed with a multi-device mesh.

    Every boundary of the producing loop is a ``telemetry.stage``:
    ``feed.collect`` (inside ``feed.next_batch``), ``batch.convert`` (wall
    time from handing the rows out until every range is converted and on its
    way to its device; with one range, the ``to_arrays`` call), ``batch.put``
    (the assembly; with one range, ``shard_batch``; the counter
    ``batch.h2d_bytes`` beside it) and ``batch.queue_full`` (the prefetch
    queue has no room: the device is the bottleneck) — together the whole
    loop of the prefetch thread, so per batch they add up to the feed's
    period.  ``batch.convert_slice`` is each worker's ``to_arrays`` call
    (thread-microseconds; its calls per batch are the ranges).  The
    consumer's side of the queue is ``batch.queue_empty`` (the step loop
    waits for the feed).

    Weighting caveat (applies to the final batches of any uneven run): PAD
    rows (partial final batch) and FILLER rows (a dry host's lockstep
    batches, ``n=0``) participate in the global loss mean like real rows —
    duplicated last-sample data carries gradient mass for those few steps.
    This mirrors the reference's padded-batch semantics; for strictly
    unbiased tails either shard data evenly across hosts, or use the
    returned ``n`` to weight/skip the update (``n`` is per-HOST; a filler
    round has ``n=0``).

    ``max_steps`` >= 0 caps the number of yielded batches (the pipeline
    layer's ``steps`` Param; reference ``args.steps`` semantics —
    ``None`` and ``-1`` both mean uncapped, so ``args.get("steps")`` can be
    passed straight through).  On
    reaching the cap the host behaves exactly as if its feed ran dry: the
    feed is ``terminate()``d (upstream streaming stops fast), the host keeps
    voting in the ``all_done`` consensus, and on a multi-process mesh it
    keeps joining the remaining global steps with filler batches — so a
    capped host never deadlocks uncapped peers.

    ``lockstep`` forces the multi-process yield discipline (identical batch
    counts on every host, filler batches after a host's feed runs dry)
    WITHOUT a multi-process mesh — the shape cross-host collective training
    (``cluster.train(mode="sync")`` + ``cross_host_grad_fn``) needs: every
    global step carries a cluster-wide gradient all-reduce, so a host that
    stopped yielding early would wedge its peers mid-collective exactly
    like a missing ``jax.distributed`` participant would.  Default
    ``None`` keeps the old rule (lockstep iff the mesh spans processes).
    """
    inner = _batch_iterator(feed, batch_size, to_arrays, mesh, ctx,
                            pad_to_batch,
                            -1 if max_steps is None else int(max_steps),
                            lockstep)
    if prefetch <= 0:
        yield from inner
        return
    yield from _prefetch_iterator(inner, prefetch)


def _prefetch_iterator(inner, depth: int):
    """Run ``inner`` on a background thread through a bounded queue.

    An abandoned consumer (early ``break`` → ``GeneratorExit``) must not
    leave the producer blocked on a full queue holding the feed: ``close()``
    sets a stop flag and drains, and the producer re-checks it around every
    put.  Producer exceptions re-raise at the consumer's next pull — the same
    point they would have surfaced unprefetched.
    """
    import queue as _queue
    import threading

    q: _queue.Queue = _queue.Queue(maxsize=depth)
    stop = threading.Event()
    END = object()
    failure: list[BaseException] = []

    def _produce() -> None:
        try:
            for item in inner:
                # blocked here = the device is the bottleneck, not the feed
                with telemetry.stage("batch.queue_full") as blocked:
                    while not stop.is_set():
                        try:
                            q.put(item, timeout=0.1)
                            break
                        except _queue.Full:
                            blocked.tick()
                if stop.is_set():
                    return
        except BaseException as e:  # noqa: BLE001 - re-raised consumer-side
            failure.append(e)
        finally:
            inner.close()
            while not stop.is_set():
                try:
                    q.put(END, timeout=0.1)
                    break
                except _queue.Full:
                    continue

    thread = threading.Thread(target=_produce, name="batch-prefetch", daemon=True)
    thread.start()
    try:
        while True:
            # blocked here = the step loop waits for the feed
            with telemetry.stage("batch.queue_empty"):
                item = q.get()
            if item is END:
                break
            yield item
        if failure:
            raise failure[0]
        thread.join()
    finally:
        stop.set()
        thread.join(timeout=30.0)


class _Shard(NamedTuple):
    """One device shard of a batch, converted and on its way to its devices."""

    treedef: Any
    leaves: list    # per leaf: (trailing shape, dtype)
    nbytes: int
    placed: list    # per leaf: one single-device array per device of the shard


class _ShardedConvert:
    """``to_arrays`` and ``device_put`` per device shard of a batch, each
    shard on a thread of its own; see ``make_batch_iterator``."""

    def __init__(self, mesh, to_arrays: Callable[[list], Any],
                 total: int, owners: list[list]):
        from concurrent.futures import ThreadPoolExecutor

        self.mesh, self.to_arrays = mesh, to_arrays
        self.total, self.owners = total, owners
        self.pool = ThreadPoolExecutor(len(owners),
                                       thread_name_prefix="batch-convert")

    def _shard(self, items: list, devices: list) -> _Shard | None:
        with telemetry.stage("batch.convert_slice"):
            leaves, treedef = jax.tree.flatten(self.to_arrays(items))
        if any(getattr(x, "shape", ())[:1] != (len(items),) for x in leaves):
            return None
        return _Shard(treedef, [(x.shape[1:], x.dtype) for x in leaves],
                      sum(x.nbytes for x in leaves),
                      [[jax.device_put(x, d) for d in devices] for x in leaves])

    def convert(self, items: list) -> list[_Shard] | None:
        """Every shard this process holds, in row order; None where
        ``to_arrays`` shows itself not to be row-wise."""
        rows = len(items) // len(self.owners)
        shards = [f.result() for f in [
            self.pool.submit(self._shard, items[j * rows:(j + 1) * rows], devices)
            for j, devices in enumerate(self.owners)]]
        first = shards[0]
        if any(s is None or (s.treedef, s.leaves) != (first.treedef, first.leaves)
               for s in shards):
            return None
        return shards

    def assemble(self, shards: list[_Shard], rows: int):
        """The global arrays of a batch of ``rows`` process-local rows."""
        first = shards[0]
        rows = rows // len(self.owners) * self.total
        return jax.tree.unflatten(first.treedef, [
            jax.make_array_from_single_device_arrays(
                (rows, *trail), batch_sharding(self.mesh, len(trail)),
                [a for s in shards for a in s.placed[i]])
            for i, (trail, _dtype) in enumerate(first.leaves)])

    def close(self) -> None:
        self.pool.shutdown(wait=True, cancel_futures=True)


def _batch_iterator(
    feed,
    batch_size: int,
    to_arrays: Callable[[list], Any],
    mesh=None,
    ctx=None,
    pad_to_batch: bool = True,
    max_steps: int = -1,
    lockstep: bool | None = None,
):
    from tensorflowonspark_tpu.parallel.mesh import (
        batch_shards, is_multiprocess, shard_batch)

    if getattr(feed, "input_mapping", None):
        raise ValueError(
            "make_batch_iterator needs row-shaped batches; construct the "
            "DataFeed without input_mapping and map columns in to_arrays"
        )
    # Multi-host SPMD (jax.distributed + a mesh spanning processes): every
    # process runs ONE jitted global step per consensus round, so the number
    # of yielded batches must be identical on every host.  A host whose feed
    # runs dry before the others keeps yielding FILLER batches (its last real
    # sample repeated, reported as n=0) until the all_done consensus turns
    # true — if it just skipped rounds, the still-active hosts would enter
    # the next collective without it and the job would hang (SURVEY.md
    # §5.8-3; the reference's MWMS had the same no-early-exit constraint).
    multiproc = (bool(lockstep) if lockstep is not None
                 else mesh is not None and is_multiprocess(mesh))
    if multiproc and ctx is None:
        raise ValueError(
            "lockstep (multi-process mesh / cross-host sync) streaming "
            "requires ctx: the all_done consensus is what keeps per-host "
            "global-step counts in lockstep"
        )
    if multiproc and not pad_to_batch:
        raise ValueError(
            "lockstep streaming requires pad_to_batch=True: every "
            "host must contribute the same local batch shape or the global "
            "step (batch assembly / gradient collective) diverges"
        )
    # the unit of convert + put is one device's shard; None with one shard
    sharded = None
    if mesh is not None:
        total, owners = batch_shards(mesh)
        if len(owners) > 1:
            sharded = _ShardedConvert(mesh, to_arrays, total, owners)
    whole = False      # to_arrays showed itself not to be row-wise
    last_item = None   # filler source for multi-process end-of-data rounds
    exhausted = False  # feed hit end-of-feed: NEVER call next_batch again
    dry = False        # exhausted and nothing left to yield
    yielded = 0
    pending = None     # pipelined consensus vote from the previous round
    try:
        while True:
            if max_steps >= 0 and yielded >= max_steps and not dry:
                # steps cap: behave exactly like end-of-data from here on —
                # terminate the feed (upstream streaming stops fast, reference
                # args.steps semantics) and vote dry in the consensus.
                terminate = getattr(feed, "terminate", None)
                if terminate is not None and not exhausted:
                    terminate()
                exhausted = dry = True
            items: list = []
            if not dry:
                if not exhausted:
                    items = feed.next_batch(batch_size)
                    # EndOfFeed can arrive mid-batch: a non-empty partial batch
                    # with should_stop() set must still be trained on, but one
                    # more next_batch() call would block forever.
                    exhausted = feed.should_stop()
                dry = exhausted and not items
            if ctx is not None:
                # One consensus round per step: active hosts vote False once
                # per batch; dry hosts keep voting True (without touching the
                # feed) until everyone is dry, so no host exits the SPMD loop
                # early.  The vote is PIPELINED for active hosts (VERDICT r4
                # weak #2): they send their vote, run the training step while
                # the rendezvous resolves, and read the result here at the
                # top of the next round — the control-plane RTT hides behind
                # step compute instead of adding to it.  A dry host resolves
                # synchronously (blocking is free when there is nothing to
                # train), so exit timing and yield counts are IDENTICAL to
                # the fully-synchronous protocol: an all-dry consensus is
                # only ever observed by dry hosts, which return before
                # yielding any extra filler.
                if pending is not None:
                    prev, pending = pending(), None
                    if prev:
                        # impossible by construction: this host voted
                        # "active" in that generation and the reduce is
                        # kind="all"
                        raise RuntimeError(
                            "end-of-data consensus turned true in a round "
                            "this host voted active (protocol bug)")
                if dry:
                    if ctx.all_done(dry):
                        return
                else:
                    pending = ctx.all_done_begin(False)
            elif dry:
                return
            if not items and not multiproc:
                continue
            n = len(items)
            if not items:
                # multiproc: this host is dry (or drew an empty batch) but
                # other hosts still have data — join their global step with a
                # filler.
                if last_item is None:
                    raise RuntimeError(
                        "multi-process streaming: this host reached end-of-feed "
                        "before receiving any data; every data node needs at "
                        "least one sample to participate in the global SPMD step"
                    )
                items = [last_item] * batch_size
            else:
                last_item = items[-1]
            if pad_to_batch and len(items) < batch_size:
                items = list(items) + [items[-1]] * (batch_size - len(items))
            shards = None
            with telemetry.stage("batch.convert"):   # rows -> host arrays
                if (sharded is not None and not whole
                        and len(items) % len(sharded.owners) == 0):
                    shards = sharded.convert(items)
                    whole = shards is None
                if shards is None:
                    batch = to_arrays(items)
            if whole:
                telemetry.counter("batch.convert_whole").inc()
            if shards is not None:
                telemetry.counter("batch.h2d_bytes").inc(
                    sum(s.nbytes for s in shards))
                with telemetry.stage("batch.put"):   # the shards, assembled
                    batch = sharded.assemble(shards, len(items))
            elif mesh is not None:
                telemetry.counter("batch.h2d_bytes").inc(
                    sum(getattr(x, "nbytes", 0) for x in jax.tree.leaves(batch)))
                with telemetry.stage("batch.put"):   # host -> device
                    batch = shard_batch(mesh, batch)
            yield batch, n
            yielded += 1
    finally:
        if sharded is not None:
            sharded.close()
        if pending is not None and ctx is not None:
            # The caller abandoned the iterator (break / exception in its
            # train step) with a vote in flight; the unread reply would
            # desync any future consensus on this connection — drop it.
            ctx._reset_consensus_client()
