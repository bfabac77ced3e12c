"""SPMD data-parallel training tests on the virtual 8-device CPU mesh
(the reference's local-cluster analogue for mesh logic, SURVEY.md §4)."""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from tensorflowonspark_tpu import telemetry
from tensorflowonspark_tpu.feeding import DataFeed, FeedQueues, IteratorFeed
from tensorflowonspark_tpu.marker import EndOfFeed, EndPartition
from tensorflowonspark_tpu.parallel.dp import (
    TrainState,
    cross_entropy_loss,
    make_batch_iterator,
    make_train_step,
    replicate,
)
from tensorflowonspark_tpu.parallel.mesh import make_mesh, shard_batch


def cpu_mesh(**axes):
    return make_mesh(jax.devices("cpu"), **axes)


def linreg_setup():
    params = {"w": jnp.zeros((4,)), "b": jnp.zeros(())}

    def loss_fn(params, batch):
        pred = batch["x"] @ params["w"] + params["b"]
        loss = jnp.mean((pred - batch["y"]) ** 2)
        return loss, {}

    return params, loss_fn


def test_train_step_learns_and_stays_sharded():
    mesh = cpu_mesh(dp=8)
    params, loss_fn = linreg_setup()
    optimizer = optax.sgd(0.1)
    state = replicate(TrainState.create(params, optimizer), mesh)
    step = make_train_step(loss_fn, optimizer)

    rng = np.random.RandomState(0)
    w_true = np.array([1.0, -2.0, 3.0, 0.5], np.float32)
    losses = []
    for _ in range(30):
        x = rng.randn(32, 4).astype(np.float32)
        y = x @ w_true + 0.75
        batch = shard_batch(mesh, {"x": x, "y": y})
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] * 0.05, losses[:3] + losses[-3:]
    assert int(state.step) == 30
    np.testing.assert_allclose(np.asarray(state.params["w"]), w_true, atol=0.15)
    # params remain replicated across all 8 devices
    assert state.params["w"].sharding.is_fully_replicated


def test_batch_is_actually_sharded_over_dp():
    mesh = cpu_mesh(dp=8)
    batch = shard_batch(mesh, {"x": np.zeros((16, 3), np.float32)})
    shard_shapes = {s.data.shape for s in batch["x"].addressable_shards}
    assert shard_shapes == {(2, 3)}  # 16 rows / 8 devices


def test_gradient_matches_single_device():
    """The SPMD step must produce the same math as an unsharded step."""
    mesh = cpu_mesh(dp=8)
    params, loss_fn = linreg_setup()
    optimizer = optax.sgd(0.1)
    x = np.random.RandomState(1).randn(16, 4).astype(np.float32)
    y = np.ones((16,), np.float32)

    state_m = replicate(TrainState.create(params, optimizer), mesh)
    step_m = make_train_step(loss_fn, optimizer)
    state_m, metrics_m = step_m(state_m, shard_batch(mesh, {"x": x, "y": y}))

    state_1 = TrainState.create(params, optimizer)
    step_1 = make_train_step(loss_fn, optimizer, donate=False)
    state_1, metrics_1 = step_1(state_1, {"x": jnp.asarray(x), "y": jnp.asarray(y)})

    # sharded reductions reassociate float adds; tolerate that noise only
    np.testing.assert_allclose(np.asarray(state_m.params["w"]), np.asarray(state_1.params["w"]),
                               rtol=1e-4, atol=1e-6)
    assert float(metrics_m["loss"]) == pytest.approx(float(metrics_1["loss"]), rel=1e-4)


def test_cross_entropy_sane():
    logits = jnp.array([[10.0, -10.0], [-10.0, 10.0]])
    labels = jnp.array([0, 1])
    assert float(cross_entropy_loss(logits, labels)) < 1e-3
    assert float(cross_entropy_loss(logits, 1 - labels)) > 5.0


def feed_with(items, batch_markers=True):
    queues = FeedQueues()
    q = queues.get_queue("input")
    for it in items:
        q.put(it)
    if batch_markers:
        q.put(EndPartition())
    q.put(EndOfFeed())
    return DataFeed(queues)


def test_batch_iterator_pads_final_batch():
    feed = feed_with(list(range(10)))
    batches = list(make_batch_iterator(feed, 4, to_arrays=lambda xs: np.asarray(xs)))
    sizes = [(b.shape[0], n) for b, n in batches]
    assert sizes == [(4, 4), (4, 4), (4, 2)]
    assert batches[-1][0].tolist() == [8, 9, 9, 9]  # padded with last sample


def test_batch_iterator_max_steps_caps_and_terminates_feed():
    """The pipeline `steps` Param: the iterator stops after max_steps batches
    and terminates the feed (so upstream streaming stops fast) even with
    data left."""
    feed = feed_with(list(range(100)))
    batches = list(make_batch_iterator(feed, 4, to_arrays=np.asarray,
                                       max_steps=3))
    assert len(batches) == 3
    assert all(n == 4 for _, n in batches)
    assert feed.should_stop()
    assert feed.queues.get("state") == "terminating"  # drained upstream
    # IteratorFeed (DIRECT mode) has no terminate(); the cap still applies
    from tensorflowonspark_tpu.feeding import IteratorFeed

    got = list(make_batch_iterator(IteratorFeed(iter(range(50))), 5,
                                   to_arrays=np.asarray, max_steps=2))
    assert len(got) == 2
    # and max_steps larger than the data is a no-op
    got = list(make_batch_iterator(IteratorFeed(iter(range(6))), 4,
                                   to_arrays=np.asarray, max_steps=99))
    assert [n for _, n in got] == [4, 2]


def test_batch_iterator_prefetch_matches_sync():
    """The double-buffered path must deliver byte-identical batches in the
    same order as strictly-synchronous delivery (SURVEY.md §7.3-6)."""
    sync = list(make_batch_iterator(feed_with(list(range(23))), 4,
                                    to_arrays=np.asarray, prefetch=0))
    pre = list(make_batch_iterator(feed_with(list(range(23))), 4,
                                   to_arrays=np.asarray, prefetch=3))
    assert [n for _, n in sync] == [n for _, n in pre]
    for (a, _), (b, _) in zip(sync, pre):
        np.testing.assert_array_equal(a, b)


def test_batch_iterator_prefetch_propagates_errors():
    def bad_to_arrays(xs):
        raise ValueError("conversion exploded")

    it = make_batch_iterator(feed_with([1, 2, 3]), 2, to_arrays=bad_to_arrays)
    with pytest.raises(ValueError, match="conversion exploded"):
        list(it)


def test_batch_iterator_prefetch_abandoned_consumer_unblocks():
    """An early break must stop the producer thread promptly instead of
    leaving it blocked on the bounded queue holding the feed."""

    before = threading.active_count()
    it = make_batch_iterator(feed_with(list(range(100))), 2,
                             to_arrays=np.asarray, prefetch=1)
    next(it)
    it.close()  # GeneratorExit -> stop flag -> producer exits
    deadline = time.monotonic() + 10.0
    while threading.active_count() > before and time.monotonic() < deadline:
        time.sleep(0.05)
    assert threading.active_count() <= before, "prefetch thread leaked"


# -- conversion and placement per device shard (ISSUE 24) ---------------------

SHARD_MESHES = {
    "dp4": (4, {"dp": 4}, 4),
    "dp8": (8, {"dp": 8}, 8),
    "dp2_tp2": (4, {"dp": 2, "tp": 2}, 2),
    "one_device": (1, {"dp": 1}, 1),
}


def shard_mesh(name):
    devices, axes, slices = SHARD_MESHES[name]
    return make_mesh(jax.devices("cpu")[:devices], **axes), slices


def rows_to_tree(rows):
    """Row-wise, every leaf leading with the batch dimension: what
    ``make_batch_iterator`` asks of a converter."""
    return {"x": np.stack([np.full((3, 2), r, np.float32) for r in rows]),
            "y": np.asarray(rows, np.int32)}


def expected_items(k, batch, total):
    items = list(range(batch * k, min(total, batch * (k + 1))))
    return items + [items[-1]] * (batch - len(items))


def assert_same_placement(got, want):
    """Same tree, shapes, dtypes, sharding, and every addressable shard's
    device, index and bytes."""
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (a.shape, a.dtype, a.sharding) == (b.shape, b.dtype, b.sharding)
        assert a.committed == b.committed
        assert len(a.addressable_shards) == len(b.addressable_shards)
        for sa, sb in zip(a.addressable_shards, b.addressable_shards):
            assert (sa.device, sa.index) == (sb.device, sb.index)
            np.testing.assert_array_equal(np.asarray(sa.data),
                                          np.asarray(sb.data))


@pytest.fixture
def counters():
    """This test's own registry; ``counters()`` reads it."""
    telemetry.reset(enabled=True)
    yield lambda: telemetry.snapshot()["counters"]
    telemetry.reset()


@pytest.mark.parametrize("prefetch", [0, 2])
@pytest.mark.parametrize("mesh_name", ["dp4", "dp8", "dp2_tp2"])
def test_sharded_convert_equals_shard_batch_of_the_whole(mesh_name, prefetch):
    """41 rows in batches of 16: two full batches and a padded final one,
    each indistinguishable from ``shard_batch(mesh, to_arrays(items))``."""
    mesh, _ = shard_mesh(mesh_name)
    got = list(make_batch_iterator(IteratorFeed(iter(range(41))), 16,
                                   rows_to_tree, mesh=mesh, prefetch=prefetch))
    assert [n for _, n in got] == [16, 16, 9]
    for k, (batch, _n) in enumerate(got):
        want = shard_batch(mesh, rows_to_tree(expected_items(k, 16, 41)))
        assert_same_placement(batch, want)


@pytest.mark.parametrize("mesh_name", [*SHARD_MESHES, None])
def test_to_arrays_is_called_once_per_device_shard(mesh_name):
    """Contiguous row ranges, one call each; ONE call with the whole batch
    on a one-device mesh and without a mesh."""
    calls, lock = [], threading.Lock()

    def spy(rows):
        with lock:
            calls.append((list(rows), threading.current_thread().name))
        return rows_to_tree(rows)

    mesh, slices = shard_mesh(mesh_name) if mesh_name else (None, 1)
    got = list(make_batch_iterator(IteratorFeed(iter(range(48))), 16, spy,
                                   mesh=mesh, prefetch=0))
    assert len(got) == 3 and len(calls) == 3 * slices
    per = 16 // slices
    want = [list(range(lo, lo + per)) for lo in range(0, 48, per)]
    assert sorted(rows for rows, _ in calls) == want
    names = {name for _, name in calls}
    if slices == 1:
        assert names == {threading.current_thread().name}
    else:
        # the pool starts a thread only when none of its own is idle
        assert 1 <= len(names) <= slices
        assert all(name.startswith("batch-convert") for name in names)


def pads_to_its_longest_row(rows):
    """Row r is r % 5 + 1 long; the batch is padded to its longest row, so
    two slices of one batch can differ in trailing shape."""
    width = max(r % 5 + 1 for r in rows)
    return {"ids": np.stack([np.pad(np.full(r % 5 + 1, r, np.int32),
                                    (0, width - (r % 5 + 1))) for r in rows])}


def leaf_without_batch_dimension(rows):
    return {"x": np.asarray(rows, np.float32),
            "table": np.arange(8, dtype=np.float32)}


@pytest.mark.parametrize("converter,items", [
    # rows 0..3 are 1..4 long, row 4 is 5 long: the slices of the first
    # batch [0, 1 | 2, 3] disagree (2 against 4 columns)
    (pads_to_its_longest_row, list(range(12))),
    (leaf_without_batch_dimension, list(range(24))),
])
def test_converter_that_is_not_rowwise_falls_back_whole(converter, items,
                                                        counters):
    mesh, _ = shard_mesh("dp4")
    batch_size = 4 if converter is pads_to_its_longest_row else 8
    calls = []

    def spy(rows):
        calls.append(len(rows))
        return converter(rows)

    got = list(make_batch_iterator(IteratorFeed(iter(items)), batch_size, spy,
                                   mesh=mesh, prefetch=0))
    steps = len(items) // batch_size
    assert len(got) == steps
    for k, (batch, _n) in enumerate(got):
        rows = items[k * batch_size:(k + 1) * batch_size]
        assert_same_placement(batch, shard_batch(mesh, converter(rows)))
    # the first batch was tried by slices, then it and every later one whole
    assert sorted(calls[:5]) == [batch_size // 4] * 4 + [batch_size]
    assert calls[5:] == [batch_size] * (steps - 1)
    c = counters()
    assert c["batch.convert_whole"] == steps
    assert c["batch.convert_slice.calls"] == 4
    assert c["batch.convert.calls"] == c["batch.put.calls"] == steps


@pytest.mark.parametrize("mesh_name", list(SHARD_MESHES))
def test_sharded_convert_counters_keep_their_meaning(mesh_name, counters):
    """One ``batch.convert`` and one ``batch.put`` a batch whatever the
    slices; ``batch.convert_slice`` once a slice; the bytes the arrays'."""
    mesh, slices = shard_mesh(mesh_name)
    steps, nbytes = 0, 0
    for batch, _n in make_batch_iterator(IteratorFeed(iter(range(80))), 16,
                                         rows_to_tree, mesh=mesh, prefetch=2):
        steps += 1
        nbytes += sum(x.nbytes for x in jax.tree.leaves(batch))
    c = counters()
    assert steps == 5
    assert c["batch.convert.calls"] == c["batch.put.calls"] == steps
    assert c.get("batch.convert_slice.calls", 0) == (
        slices * steps if slices > 1 else 0)
    assert c["batch.h2d_bytes"] == nbytes == steps * 16 * (3 * 2 * 4 + 4)
    assert c.get("batch.convert_whole", 0) == 0


def convert_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("batch-convert")]


@pytest.mark.parametrize("prefetch", [0, 2])
def test_one_slices_exception_reaches_the_consumer(prefetch):
    def explodes_on_row_21(rows):
        if 21 in rows:
            raise ValueError("conversion exploded")
        return rows_to_tree(rows)

    mesh, _ = shard_mesh("dp4")
    it = make_batch_iterator(IteratorFeed(iter(range(64))), 16,
                             explodes_on_row_21, mesh=mesh, prefetch=prefetch)
    batch, n = next(it)
    assert n == 16 and batch["y"].shape == (16,)
    with pytest.raises(ValueError, match="conversion exploded"):
        next(it)
    assert convert_threads() == []


@pytest.mark.parametrize("prefetch", [0, 2])
def test_abandoned_iterator_leaves_no_convert_thread(prefetch):
    mesh, _ = shard_mesh("dp4")
    it = make_batch_iterator(IteratorFeed(iter(range(1000))), 16,
                             rows_to_tree, mesh=mesh, prefetch=prefetch)
    next(it)
    alive = len(convert_threads())
    it.close()
    assert 1 <= alive <= 4
    assert convert_threads() == []

