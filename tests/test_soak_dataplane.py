"""Bounded soak of the data plane: many small partitions through feed and
inference round-trips — shakes the TCP framing, EndPartition bookkeeping,
and the ordered exactly-count invariant at a partition count well above what
the e2e tests use (reference regime: hundreds of Spark partitions)."""

import os

import pytest
import tensorflowonspark_tpu as tos
from tensorflowonspark_tpu.cluster import InputMode

import mapfuns


@pytest.mark.slow
def test_many_partition_train_and_inference(tmp_path):
    # 60 uneven partitions (sizes 0..~12) x 2 epochs through 2 nodes
    items = list(range(300))
    parts, i = [], 0
    size = 0
    while i < len(items):
        parts.append(items[i : i + size])
        i += size
        size = (size + 1) % 13
    parts.append(items[i:])
    data = tos.PartitionedDataset.from_partitions(parts)
    assert data.num_partitions >= 40

    cluster = tos.run(mapfuns.sum_batches, {"out_dir": str(tmp_path), "batch_size": 7},
                      num_executors=2, input_mode=InputMode.STREAMING,
                      reservation_timeout=60)
    cluster.train(data, num_epochs=2, shuffle_seed=5)
    cluster.shutdown()
    totals = counts = 0
    for i in range(2):
        t, c = (tmp_path / f"node_{i}.txt").read_text().split()
        totals += float(t)
        counts += int(c)
    assert counts == 600
    assert totals == 2 * sum(items)

    # inference: 47 uneven partitions, ordered exactly-count
    c2 = tos.run(mapfuns.echo_inference, {}, num_executors=2,
                 input_mode=InputMode.STREAMING, reservation_timeout=60)
    vals = list(range(211))
    preds = c2.inference(tos.PartitionedDataset.from_iterable(vals, 47))
    c2.shutdown()
    assert preds == [v * 2 for v in vals]


@pytest.mark.slow
@pytest.mark.chaos
def test_randomized_chaos_soak(tmp_path, monkeypatch):
    """Randomized fault schedule over an elastic many-partition train: one
    node's data socket severs at a random op, the other is SIGKILLed after a
    random number of batches and supervised-restarted — the job must still
    deliver every item.  The seed is printed on failure; pin it with
    ``TOS_CHAOS_SEED`` to reproduce (the deterministic single-fault variants
    live in ``test_elastic.py`` and stay tier-1)."""
    import random

    seed = int(os.environ.get("TOS_CHAOS_SEED", random.randrange(100000)))
    rng = random.Random(seed)
    # bound kill_after so the victim is always killed MID-partition (its
    # queue backlog never spans a partition boundary): consumed + capacity
    # + in-flight put < items-per-partition
    kill_after = rng.randint(2, 6)        # 3*6 + 4 + 1 < 25
    sever_after = rng.randint(1, 6)       # each node feeds 6 partitions
    monkeypatch.setenv("TOS_DEAD_NODE_TIMEOUT", "4")
    monkeypatch.setenv("TOS_RESTART_BACKOFF_BASE", "0.2")
    items = list(range(300))
    parts = [items[i * 25:(i + 1) * 25] for i in range(12)]
    per_node_env = [
        {"TOS_FAULTINJECT": f"sever:after_data_ops={sever_after}"},
        {"TOS_FAULTINJECT": f"kill:after_batches={kill_after},incarnation=0"},
    ]
    cluster = tos.run(
        mapfuns.elastic_sum_batches,
        {"batch_size": 3, "out_dir": str(tmp_path)},
        num_executors=2, input_mode=InputMode.STREAMING,
        queue_capacity=4, heartbeat_interval=0.5,
        per_node_env=per_node_env, log_dir=str(tmp_path / "logs"),
        reservation_timeout=120.0, elastic=True)
    try:
        cluster.train(parts, num_epochs=1)
        cluster.shutdown(timeout=120.0)
        seen = set()
        count = 0
        for f in tmp_path.glob("seen_*.txt"):
            vals = [int(x) for x in f.read_text().split()]
            seen.update(vals)
            count += len(vals)
        assert seen == set(items), f"lost items with TOS_CHAOS_SEED={seed}"
        assert count >= len(items)
    except BaseException:
        print(f"chaos soak failed; reproduce with TOS_CHAOS_SEED={seed}")
        raise
