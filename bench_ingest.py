"""Node-scaling ingest bench: DIRECT node-side reads vs the STREAMING pump.

The number this bench exists to produce (ISSUE 6 / PERF_NOTES round 9):
aggregate feed bandwidth as a function of node count, for the two input
modes over the SAME TFRecord shard set —

- ``direct``: the ``InputMode.DIRECT`` data path — the driver sends only
  shard *paths* (tens of bytes each) through real ``DataClient``s into each
  node's ``FeedQueues``; every node's ``IngestFeed`` (claimer + parallel
  reader pipeline) reads, CRC-verifies, and chunks the bytes itself.
  Storage bandwidth is per-node, so the aggregate scales with N.
- ``streaming``: the ``InputMode.STREAMING`` data path — the same record
  payloads pre-materialized in driver memory (generous to streaming: shard
  read+decode cost excluded) and pumped over the zero-copy v2 wire to
  draining ``DataFeed`` consumers.  One driver core is the pump; the
  aggregate is flat in N (BENCH_r06 measured the ceiling at ~650-800 MB/s
  on this box).

Every node consumes a DISTINCT shard subset (total work scales with N), and
both legs assert exact record counts end to end — a lost or duplicated
record fails the run, it never just skews the MB/s.

Round 12 adds three compares on top of the fan-out table
(``--scenario round12``, BENCH_r12):

- ``zerocopy``: memoryview record views vs the bytes-copy decode path,
  same shard set, single node, interleaved cells;
- ``columnar``: schema'd columnar Example decode in the reader pool vs
  per-record ``from_example`` row decode;
- ``bigshard``: ONE large plain shard, fixed total work, 1 vs 2 nodes —
  sub-shard ``ShardSpan`` items let both nodes read disjoint ranges of
  the same file (the whole-shard cell pins to one node and is the
  pre-split x1.0 baseline).

Usage::

    python bench_ingest.py                  # full table, markdown + JSON
    python bench_ingest.py --quick          # tiny sizes (CI smoke)
    python bench_ingest.py --json BENCH_r08.json
    python bench_ingest.py --scenario round12 --json BENCH_r12.json
"""

from __future__ import annotations

import argparse
import json
import multiprocessing as mp
import os
import tempfile
import threading
import time


def prepare_shards(out_dir: str, num_shards: int, records_per_shard: int,
                   record_bytes: int) -> tuple[list[str], int]:
    """Write ``num_shards`` TFRecord shards of DISTINCT payloads; returns
    (paths, total payload bytes).  Distinct rows matter: pickle memoizes
    repeated objects, which would fake the streaming numbers."""
    from tensorflowonspark_tpu import tfrecord

    os.makedirs(out_dir, exist_ok=True)
    paths = []
    total = 0
    for s in range(num_shards):
        buf = os.urandom(record_bytes + records_per_shard)
        records = [bytes(memoryview(buf)[i:i + record_bytes])
                   for i in range(records_per_shard)]
        path = os.path.join(out_dir, f"part-{s:05d}")
        tfrecord.write_records(path, records)
        paths.append(path)
        total += record_bytes * records_per_shard
    return paths, total


def _pin_node(index: int) -> None:
    """Pin this node process to ONE cpu (round-robin).  On a shared bench
    box a node's pipeline threads otherwise spill onto its neighbors'
    cores, inflating the N=1 baseline — the scale-out axis must measure
    node count, not thread spill.  Real deployments give each node its own
    host; the pin emulates that.  Best-effort (containers may forbid it)."""
    try:
        cpus = sorted(os.sched_getaffinity(0))
        os.sched_setaffinity(0, {cpus[index % len(cpus)]})
    except (AttributeError, OSError):
        pass


def _report(conn, server, totals) -> None:
    """A child's last act: hand the totals to the driver, then stay up until
    the driver lets go.  The driver's ``send_eof`` is answered by a daemon
    thread of THIS process; exiting as soon as the consumer saw EndOfFeed
    killed that thread before its reply on a busy box (``ConnectionError:
    socket closed mid-read`` in the feeder)."""
    conn.send(totals)
    conn.recv()
    server.stop()


def _direct_consumer_main(conn, authkey: bytes, capacity: int,
                          node_index: int, readers: int | None = 0) -> None:
    """Child process: one DIRECT-mode node — DataServer (receiving shard
    paths) + IngestFeed draining the reader pipeline.

    ``readers=0`` (the scale-out rows) reads synchronously in the consumer
    thread: on a box where every node is pinned to ONE core, cross-thread
    queue/GIL traffic only costs, so the sync pipeline is the per-core-
    honest configuration.  ``readers=None`` (the ``direct_threaded`` row)
    takes the default autotuned pool — the shape for real hosts, where
    read/decode overlap with map_fun compute is the point."""
    from tensorflowonspark_tpu.dataserver import DataServer
    from tensorflowonspark_tpu.feeding import FeedQueues
    from tensorflowonspark_tpu.ingest import IngestFeed

    _pin_node(node_index)
    queues = FeedQueues(capacity=capacity)
    server = DataServer(queues, authkey, feed_timeout=120.0)
    conn.send(server.start())
    feed = IngestFeed(queues, readers=readers)
    rows = 0
    nbytes = 0
    while not feed.should_stop():
        batch = feed.next_batch(1024)
        rows += len(batch)
        # C-speed drain: the clock measures the pipeline, not the consumer
        nbytes += sum(map(len, batch))
    _report(conn, server, (rows, nbytes))


def _streaming_consumer_main(conn, authkey: bytes, capacity: int,
                             node_index: int) -> None:
    """Child process: one STREAMING-mode node — DataServer + draining
    DataFeed (the bench_dataplane consumer)."""
    from tensorflowonspark_tpu.dataserver import DataServer
    from tensorflowonspark_tpu.feeding import DataFeed, FeedQueues

    _pin_node(node_index)
    queues = FeedQueues(capacity=capacity)
    server = DataServer(queues, authkey, feed_timeout=120.0)
    conn.send(server.start())
    feed = DataFeed(queues)
    rows = 0
    nbytes = 0
    while not feed.should_stop():
        batch = feed.next_batch(1024)
        rows += len(batch)
        nbytes += sum(map(len, batch))
    _report(conn, server, (rows, nbytes))


def _run_mode(mode: str, num_nodes: int, shard_paths: list[str],
              records_per_shard: int, capacity: int = 1024) -> dict:
    """One measured run; nodes consume disjoint round-robin shard shares."""
    from tensorflowonspark_tpu import tfrecord
    from tensorflowonspark_tpu.dataserver import DataClient

    authkey = b"bench"
    ctx = mp.get_context("fork")
    procs, conns, ports = [], [], []
    for i in range(num_nodes):
        parent, child = ctx.Pipe()
        if mode == "streaming":
            args = (child, authkey, capacity, i)
            target = _streaming_consumer_main
        else:
            args = (child, authkey, capacity, i,
                    None if mode == "direct_threaded" else 0)
            target = _direct_consumer_main
        p = ctx.Process(target=target, args=args, daemon=True)
        p.start()
        procs.append(p)
        conns.append(parent)
        ports.append(parent.recv())

    # Pre-touch every shard OUTSIDE the clock: the bench measures ingest
    # pipeline throughput, not cold-storage latency — and on a shared box a
    # neighboring run (e.g. streaming's payload materialization) may have
    # evicted the page cache between cells, which would charge one cell for
    # another's memory pressure.
    for p in shard_paths:
        with open(p, "rb") as f:  # toslint: disable=shard-io-discipline
            while f.read(1 << 22):
                pass

    shares = [shard_paths[i::num_nodes] for i in range(num_nodes)]
    if mode == "streaming":
        # generous to streaming: shard read+decode is done OUTSIDE the clock,
        # so the measured leg is the pure driver pump (its best case)
        payload = [[list(tfrecord.read_records(p)) for p in share]
                   for share in shares]

    clients = [DataClient("127.0.0.1", port, authkey, chunk_size=64)
               for port in ports]

    errors: list[BaseException] = []

    def _feed(i: int) -> None:
        try:
            if mode != "streaming":
                # one partition per node (the train(num_partitions=W)
                # grouping): the whole share is a single ~tens-of-bytes
                # path chunk, so the driver goes quiet for the entire
                # measured window — the DIRECT design point
                clients[i].feed_partition(shares[i], task_key=(0, i))
            else:
                for pi, records in enumerate(payload[i]):
                    clients[i].feed_partition(records, task_key=(0, i, pi))
            clients[i].send_eof()
        except BaseException as e:  # noqa: BLE001 - surfaced below
            errors.append(e)

    threads = [threading.Thread(target=_feed, args=(i,)) for i in range(num_nodes)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    totals = [conn.recv() for conn in conns]
    for conn in conns:
        conn.send(None)      # lets the child go: see _report
    elapsed = time.perf_counter() - t0
    for c in clients:
        c.close()
    for p in procs:
        p.join(timeout=30)
        if p.is_alive():
            p.terminate()
    if errors:
        raise errors[0]
    total_rows = sum(t[0] for t in totals)
    total_bytes = sum(t[1] for t in totals)
    expect = sum(len(s) for s in shares) * records_per_shard
    if total_rows != expect:
        raise RuntimeError(
            f"{mode} N={num_nodes}: record count {total_rows} != exact {expect}")
    return {
        "mode": mode,
        "num_nodes": num_nodes,
        "num_shards": len(shard_paths),
        "seconds": round(elapsed, 4),
        "mb_per_s": round(total_bytes / elapsed / 1e6, 1),
        "rows_per_s": round(total_rows / elapsed, 1),
    }


def _cell_main(conn, fn_name: str, kwargs: dict):
    """Run one cell in a FRESH interpreter (spawn): the streaming cells
    materialize tens of MB in their driver, and a shared long-lived driver
    would carry that heap (and its fork/COW cost) into every later cell."""
    try:
        conn.send(globals()[fn_name](**kwargs))
    except BaseException as e:  # noqa: BLE001 - surfaced driver-side
        conn.send(e)


def _run_cell_fn(fn_name: str, **kwargs) -> dict:
    ctx = mp.get_context("spawn")
    parent, child = ctx.Pipe()
    p = ctx.Process(target=_cell_main, args=(child, fn_name, kwargs))
    p.start()
    out = parent.recv()
    p.join(timeout=120)
    if isinstance(out, BaseException):
        raise out
    return out


def _run_cell(mode: str, num_nodes: int, shard_paths, records_per_shard) -> dict:
    return _run_cell_fn("_run_mode", mode=mode, num_nodes=num_nodes,
                        shard_paths=shard_paths,
                        records_per_shard=records_per_shard)


def bench(quick: bool = False, fanout=(1, 2), repeats: int = 3,
          data_dir: str | None = None) -> dict:
    """The scaling table; each cell is the BEST of ``repeats`` runs (on a
    shared box the slower runs measure the neighbors, not the code)."""
    # 4 KB records x 8 MB shards: the regime where ingest cost is
    # per-record CPU (framing, CRC, slicing, chunking) rather than pure
    # DRAM bandwidth — per-record work is what node count parallelizes.
    # (BASELINE config 2's mnist Examples are this class of record.)
    record_bytes = 4_000
    records_per_shard = 64 if quick else 2_048
    shards_per_node = 2 if quick else 8
    repeats = 1 if quick else max(1, repeats)
    max_nodes = max(fanout)
    tmp = None
    if data_dir is None:
        tmp = tempfile.TemporaryDirectory(prefix="bench_ingest_")
        data_dir = tmp.name
    try:
        paths, _ = prepare_shards(data_dir, max_nodes * shards_per_node,
                                  records_per_shard, record_bytes)
        results: dict = {"record_bytes": record_bytes,
                         "records_per_shard": records_per_shard,
                         "direct": [], "direct_threaded": [], "streaming": []}
        # INTERLEAVED rounds (the bench_dataplane --metrics-compare trick):
        # box-load drift over the minutes a full pass takes would otherwise
        # land entirely on whichever cell ran during the bad stretch; with
        # round-robin rounds every cell samples every stretch, and best-of
        # picks each cell's clean run.
        cells = [(mode, n) for mode in ("direct", "direct_threaded", "streaming")
                 for n in fanout]
        best: dict = {}
        for _ in range(repeats):
            for mode, n in cells:
                # every node always consumes shards_per_node shards: total
                # work scales with N, which is what "aggregate bandwidth
                # scales with node count" means
                share = paths[: n * shards_per_node]
                run = _run_cell(mode, n, share, records_per_shard)
                prev = best.get((mode, n))
                if prev is None or run["mb_per_s"] > prev["mb_per_s"]:
                    best[(mode, n)] = run
        for mode, n in cells:
            results[mode].append(best[(mode, n)])
        for mode in ("direct", "direct_threaded", "streaming"):
            base = results[mode][0]["mb_per_s"]
            results[f"{mode}_scaling"] = [
                round(r["mb_per_s"] / base, 2) if base else None
                for r in results[mode]]
        return results
    finally:
        if tmp is not None:
            tmp.cleanup()


# -- round-12 scenarios: zero-copy / columnar / single-large-shard ------------


def prepare_example_shards(out_dir: str, num_shards: int,
                           records_per_shard: int, floats_per_record: int
                           ) -> tuple[list[str], object, int]:
    """Schema'd Example shards (x: float[k], y: int64 scalar); returns
    (paths, schema, total payload bytes).  Distinct values per record so
    pickle memoization can't fake any leg."""
    import numpy as np

    from tensorflowonspark_tpu import dfutil
    from tensorflowonspark_tpu.data import PartitionedDataset

    rng = np.random.default_rng(7)
    parts = []
    idx = 0
    for _ in range(num_shards):
        rows = []
        for _ in range(records_per_shard):
            rows.append({"x": rng.random(floats_per_record,
                                         np.float32).tolist(),
                         "y": idx})
            idx += 1
        parts.append(rows)
    schema = dfutil.save_as_tfrecords(
        PartitionedDataset.from_partitions(parts), out_dir)
    paths = dfutil.shard_files(out_dir)
    total = sum(os.path.getsize(p) for p in paths)
    return paths, schema, total


def _direct_feed_consumer_main(conn, authkey: bytes, capacity: int,
                               node_index: int, opts: dict) -> None:
    """Child process: one DIRECT-mode node with a configurable IngestFeed
    (zerocopy / columnar-schema / per-record row decode) draining at C
    speed; reports its row count."""
    from tensorflowonspark_tpu import dfutil
    from tensorflowonspark_tpu.dataserver import DataServer
    from tensorflowonspark_tpu.feeding import FeedQueues
    from tensorflowonspark_tpu.ingest import IngestFeed

    _pin_node(node_index)
    queues = FeedQueues(capacity=capacity)
    server = DataServer(queues, authkey, feed_timeout=120.0)
    conn.send(server.start())
    schema = opts.get("schema")
    decode = None
    if opts.get("rowdecode"):
        rd_schema = opts["rowdecode"]
        decode = lambda rec: dfutil.from_example(bytes(rec), rd_schema)  # noqa: E731
        schema = None
    feed = IngestFeed(queues, readers=opts.get("readers", 0),
                      zerocopy=opts.get("zerocopy"), schema=schema,
                      decode=decode)
    rows = 0
    while not feed.should_stop():
        batch = feed.next_batch(1024)
        if isinstance(batch, dict):
            rows += len(batch["y"])  # columnar: the scalar column's length
        else:
            rows += len(batch)
    _report(conn, server, (rows, 0))


def _run_direct_items(work_items: list, num_nodes: int, expect_rows: int,
                      total_bytes: int, opts: dict,
                      capacity: int = 1024) -> dict:
    """One measured DIRECT run over arbitrary work items (shard paths
    and/or ShardSpan sub-shard ranges), exact-count asserted; MB/s from
    the known payload byte total (identical across compared legs)."""
    from tensorflowonspark_tpu.dataserver import DataClient

    authkey = b"bench"
    ctx = mp.get_context("fork")
    procs, conns, ports = [], [], []
    for i in range(num_nodes):
        parent, child = ctx.Pipe()
        p = ctx.Process(target=_direct_feed_consumer_main,
                        args=(child, authkey, capacity, i, opts), daemon=True)
        p.start()
        procs.append(p)
        conns.append(parent)
        ports.append(parent.recv())

    paths = sorted({it.path if hasattr(it, "path") else it
                    for it in work_items})
    for p in paths:  # page-cache pre-warm, outside the clock
        with open(p, "rb") as f:  # toslint: disable=shard-io-discipline
            while f.read(1 << 22):
                pass

    shares = [work_items[i::num_nodes] for i in range(num_nodes)]
    clients = [DataClient("127.0.0.1", port, authkey, chunk_size=64)
               for port in ports]

    errors: list[BaseException] = []

    def _feed(i: int) -> None:
        try:
            clients[i].feed_partition(shares[i], task_key=(0, i))
            clients[i].send_eof()
        except BaseException as e:  # noqa: BLE001 - surfaced below
            errors.append(e)

    threads = [threading.Thread(target=_feed, args=(i,))
               for i in range(num_nodes)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    totals = [conn.recv() for conn in conns]
    for conn in conns:
        conn.send(None)      # lets the child go: see _report
    elapsed = time.perf_counter() - t0
    for c in clients:
        c.close()
    for p in procs:
        p.join(timeout=30)
        if p.is_alive():
            p.terminate()
    if errors:
        raise errors[0]
    rows = sum(t[0] for t in totals)
    if rows != expect_rows:
        raise RuntimeError(f"record count {rows} != exact {expect_rows}")
    return {
        "num_nodes": num_nodes,
        "num_items": len(work_items),
        "seconds": round(elapsed, 4),
        "mb_per_s": round(total_bytes / elapsed / 1e6, 1),
        "rows_per_s": round(rows / elapsed, 1),
    }


def _interleaved_rounds(cells: list[tuple[str, str, dict]], repeats: int
                        ) -> list[dict]:
    """Round-robin the cells ``repeats`` times in fresh interpreters,
    returning per-ROUND result dicts.  Compares are then computed within
    one round (cells that ran back-to-back), never across rounds: on a
    shared KVM box, hypervisor steal varies minute to minute, and pairing
    cell A's quiet-window best with cell B's noisy-window best would
    measure the neighbors, not the code."""
    rounds: list[dict] = []
    for _ in range(repeats):
        rounds.append({name: _run_cell_fn(fn, **kwargs)
                       for name, fn, kwargs in cells})
    return rounds


def _cleanest_round(rounds: list[dict], names: list[str]) -> dict:
    """The round with the highest combined throughput — the one that ran
    in the cleanest box window."""
    return max(rounds, key=lambda r: sum(r[n]["mb_per_s"] for n in names))


def bench_zerocopy(quick: bool = False, repeats: int = 3,
                   data_dir: str | None = None) -> dict:
    """Acceptance compare: zero-copy memoryview record views vs the
    bytes-copy path, single node, same shard set, interleaved."""
    record_bytes = 4_000
    rps = 64 if quick else 2_048
    nsh = 2 if quick else 16  # ~128 MB: the window must dwarf cell setup
    repeats = 1 if quick else max(1, repeats)
    tmp = None
    if data_dir is None:
        tmp = tempfile.TemporaryDirectory(prefix="bench_ingest_zc_")
        data_dir = tmp.name
    try:
        paths, total = prepare_shards(data_dir, nsh, rps, record_bytes)
        expect = nsh * rps
        common = dict(work_items=paths, num_nodes=1, expect_rows=expect,
                      total_bytes=total)
        rounds = _interleaved_rounds(
            [("zerocopy", "_run_direct_items",
              {**common, "opts": {"zerocopy": "1"}}),
             ("bytescopy", "_run_direct_items",
              {**common, "opts": {"zerocopy": "0"}})], repeats)
        best = _cleanest_round(rounds, ["zerocopy", "bytescopy"])
        zc, bc = best["zerocopy"]["mb_per_s"], best["bytescopy"]["mb_per_s"]
        return {"record_bytes": record_bytes, "records": expect,
                "zerocopy": best["zerocopy"], "bytescopy": best["bytescopy"],
                "speedup_pct": round((zc / bc - 1) * 100, 1),
                "round_speedups_pct": [
                    round((r["zerocopy"]["mb_per_s"]
                           / r["bytescopy"]["mb_per_s"] - 1) * 100, 1)
                    for r in rounds]}
    finally:
        if tmp is not None:
            tmp.cleanup()


def bench_columnar(quick: bool = False, repeats: int = 3,
                   data_dir: str | None = None) -> dict:
    """Columnar Example decode in the reader pool vs per-record
    from_example row decode — same schema'd shard set, single node,
    interleaved."""
    k = 1_000  # 4 KB of float payload per record
    rps = 64 if quick else 1_024
    nsh = 2 if quick else 8
    repeats = 1 if quick else max(1, repeats)
    tmp = None
    if data_dir is None:
        tmp = tempfile.TemporaryDirectory(prefix="bench_ingest_col_")
        data_dir = tmp.name
    try:
        paths, schema, total = prepare_example_shards(data_dir, nsh, rps, k)
        expect = nsh * rps
        common = dict(work_items=paths, num_nodes=1, expect_rows=expect,
                      total_bytes=total)
        rounds = _interleaved_rounds(
            [("columnar", "_run_direct_items",
              {**common, "opts": {"schema": schema}}),
             ("rowdecode", "_run_direct_items",
              {**common, "opts": {"rowdecode": schema}})], repeats)
        best = _cleanest_round(rounds, ["columnar", "rowdecode"])
        col, row = best["columnar"]["mb_per_s"], best["rowdecode"]["mb_per_s"]
        return {"floats_per_record": k, "records": expect,
                "columnar": best["columnar"], "rowdecode": best["rowdecode"],
                "speedup_x": round(col / row, 2)}
    finally:
        if tmp is not None:
            tmp.cleanup()


def bench_bigshard(quick: bool = False, repeats: int = 3,
                   data_dir: str | None = None) -> dict:
    """The single-large-shard scenario: ONE plain shard, FIXED total work,
    1 vs 2 nodes.  Before sub-shard items the shard pinned to one node
    (scaling x1.0 by construction); with ``ShardSpan`` splitting the
    aggregate must scale.

    Record size is 512 B — the small-tabular-row class (Criteo-style
    Examples) where ingest cost is per-RECORD CPU (largely the CRC scan),
    which is exactly what node count parallelizes.  With the zero-copy
    mmap fast path, larger (4 KB+) records are memory-bandwidth-bound on
    a 2-core box: both span-split nodes together saturate DRAM and the
    ratio measures the memory bus, not the reader.  Scaling is
    best-of-cell across the interleaved rounds (the fan-out table's own
    methodology): KVM neighbor steal is strictly one-sided noise, so each
    cell's fastest round is its closest look at the machine; the
    per-round ratio list and the measured parallel-CPU ceiling are
    recorded alongside.
    """
    from tensorflowonspark_tpu.ingest import split_shards

    record_bytes = 512
    recs = 1_024 if quick else 524_288  # ~268 MB full
    ceiling = _parallel_cpu_ceiling(0.2 if quick else 1.5)
    repeats = 1 if quick else max(1, repeats)
    tmp = None
    if data_dir is None:
        tmp = tempfile.TemporaryDirectory(prefix="bench_ingest_big_")
        data_dir = tmp.name
    try:
        paths, total = prepare_shards(data_dir, 1, recs, record_bytes)
        span_bytes = max(1 << 14, os.path.getsize(paths[0]) // 16)
        items = split_shards(paths, span_bytes=span_bytes)
        common = dict(expect_rows=recs, total_bytes=total,
                      opts={"zerocopy": "1"})
        rounds = _interleaved_rounds(
            [("n1", "_run_direct_items",
              {**common, "work_items": items, "num_nodes": 1}),
             ("n2", "_run_direct_items",
              {**common, "work_items": items, "num_nodes": 2}),
             ("n2_whole", "_run_direct_items",  # the pre-split behavior
              {**common, "work_items": paths, "num_nodes": 2})], repeats)
        best = {name: max((r[name] for r in rounds),
                          key=lambda run: run["mb_per_s"])
                for name in ("n1", "n2", "n2_whole")}
        return {"record_bytes": record_bytes, "records": recs,
                "span_bytes": span_bytes, "num_items": len(items),
                "n1": best["n1"], "n2": best["n2"],
                "n2_whole_shard": best["n2_whole"],
                "scaling": round(best["n2"]["mb_per_s"]
                                 / best["n1"]["mb_per_s"], 2),
                "scaling_whole_shard": round(
                    best["n2_whole"]["mb_per_s"]
                    / best["n1"]["mb_per_s"], 2),
                "round_scalings": [
                    round(r["n2"]["mb_per_s"] / r["n1"]["mb_per_s"], 2)
                    for r in rounds],
                "best_round_scaling": max(
                    round(r["n2"]["mb_per_s"] / r["n1"]["mb_per_s"], 2)
                    for r in rounds),
                # what "x2.0" can even look like here: aggregate CPU two
                # busy cores actually receive on this (KVM, steal-prone)
                # box, relative to one — the scenario's hardware ceiling
                "parallel_cpu_ceiling": ceiling}
    finally:
        if tmp is not None:
            tmp.cleanup()


# -- round-15 scenario: disaggregated data-service tier vs node-local ---------


def _disagg_trainer_main(conn, authkey: bytes, capacity: int,
                         node_index: int, count_col: str) -> None:
    """Child process: one PURE-CONSUMER trainer (pinned to one core) — a
    DataServer receiving forwarded ``DecodedChunk``s + an IngestFeed
    draining them at C speed.  The measured quantity is trainer-side
    rows/s with the trainer's single core NOT paying for decode."""
    from tensorflowonspark_tpu.dataserver import DataServer
    from tensorflowonspark_tpu.feeding import FeedQueues
    from tensorflowonspark_tpu.ingest import IngestFeed

    _pin_node(node_index)
    queues = FeedQueues(capacity=capacity)
    server = DataServer(queues, authkey, feed_timeout=120.0)
    conn.send(server.start())
    feed = IngestFeed(queues, readers=0)
    rows = 0
    cpu0 = time.process_time()
    while not feed.should_stop():
        batch = feed.next_batch(1024)
        rows += len(batch[count_col]) if isinstance(batch, dict) else len(batch)
    # trainer-core accounting: process CPU seconds this trainer's single
    # core spent per row is the entitlement the tier exists to free
    _report(conn, server, (rows, time.process_time() - cpu0))


def _node_local_trainer_main(conn, authkey: bytes, capacity: int,
                             node_index: int, opts: dict,
                             count_col: str) -> None:
    """Child process: one NODE-LOCAL trainer (pinned to one core) that
    claims shard paths and runs the columnar decode ITSELF — the BENCH_r12
    configuration whose per-box decode CPU ceiling the tier removes."""
    from tensorflowonspark_tpu import dfutil
    from tensorflowonspark_tpu.dataserver import DataServer
    from tensorflowonspark_tpu.feeding import FeedQueues
    from tensorflowonspark_tpu.ingest import IngestFeed

    _pin_node(node_index)
    queues = FeedQueues(capacity=capacity)
    server = DataServer(queues, authkey, feed_timeout=120.0)
    conn.send(server.start())
    schema = opts.get("schema")
    if isinstance(schema, str):
        schema = dfutil.Schema.from_json(schema)
    feed = IngestFeed(queues, readers=0, schema=schema,
                      chunk_records=opts.get("chunk_records", 256))
    rows = 0
    cpu0 = time.process_time()
    while not feed.should_stop():
        batch = feed.next_batch(1024)
        rows += len(batch[count_col]) if isinstance(batch, dict) else len(batch)
    _report(conn, server, (rows, time.process_time() - cpu0))


def _ingest_worker_proc_main(conn, authkey: bytes, capacity: int,
                             node_index: int, trainer_ports: list,
                             opts: dict) -> None:
    """Child process: one data-service worker — DataServer (receiving the
    driver's shard-path feed) + IngestService decoding and forwarding to
    the trainer fleet."""
    from tensorflowonspark_tpu import dfutil
    from tensorflowonspark_tpu.dataserver import DataServer
    from tensorflowonspark_tpu.feeding import FeedQueues
    from tensorflowonspark_tpu.ingest import IngestService

    _pin_node(node_index)
    queues = FeedQueues(capacity=capacity)
    server = DataServer(queues, authkey, feed_timeout=120.0)
    conn.send(server.start())
    opts = dict(opts)
    schema = opts.get("schema")
    if isinstance(schema, str):
        opts["schema"] = dfutil.Schema.from_json(schema)
    svc = IngestService(queues,
                        [(i, "127.0.0.1", p)
                         for i, p in enumerate(trainer_ports)],
                        authkey, stop_event=None, readers=0,
                        rr_offset=node_index, **opts)
    stats = svc.run()
    _report(conn, server, (stats["rows"], 0))


def _run_tier(shard_paths: list, num_trainers: int, num_workers: int,
              expect_rows: int, total_bytes: int, schema_json: str,
              chunk_records: int = 256, count_col: str = "y",
              capacity: int = 64) -> dict:
    """One measured run of the disaggregated tier (``num_workers`` > 0) or
    the node-local baseline (== 0): exact-count asserted; the clock covers
    feed-start -> every trainer drained (decode + forward + consume)."""
    from tensorflowonspark_tpu.dataserver import DataClient

    authkey = b"bench"
    ctx = mp.get_context("fork")
    procs, tconns, tports = [], [], []
    for i in range(num_trainers):
        parent, child = ctx.Pipe()
        if num_workers:
            args = (child, authkey, capacity, i, count_col)
            target = _disagg_trainer_main
        else:
            args = (child, authkey, capacity, i,
                    {"schema": schema_json, "chunk_records": chunk_records},
                    count_col)
            target = _node_local_trainer_main
        p = ctx.Process(target=target, args=args, daemon=True)
        p.start()
        procs.append(p)
        tconns.append(parent)
        tports.append(parent.recv())
    wconns, wports = [], []
    for j in range(num_workers):
        parent, child = ctx.Pipe()
        p = ctx.Process(target=_ingest_worker_proc_main,
                        args=(child, authkey, capacity,
                              num_trainers + j, tports,
                              {"schema": schema_json,
                               "chunk_records": chunk_records}),
                        daemon=True)
        p.start()
        procs.append(p)
        wconns.append(parent)
        wports.append(parent.recv())

    for path in shard_paths:  # page-cache pre-warm, outside the clock
        with open(path, "rb") as f:  # toslint: disable=shard-io-discipline
            while f.read(1 << 22):
                pass

    feed_ports = wports if num_workers else tports
    shares = [shard_paths[i::len(feed_ports)]
              for i in range(len(feed_ports))]
    clients = [DataClient("127.0.0.1", port, authkey, chunk_size=64)
               for port in feed_ports]
    errors: list[BaseException] = []

    def _feed(i: int) -> None:
        try:
            clients[i].feed_partition(shares[i], task_key=(0, i))
            clients[i].send_eof()
        except BaseException as e:  # noqa: BLE001 - surfaced below
            errors.append(e)

    threads = [threading.Thread(target=_feed, args=(i,))
               for i in range(len(feed_ports))]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        # surface NOW: a failed feed skipped its send_eof, so the
        # recv()s below would block forever on children that never
        # finish — kill them and raise the real failure instead
        for p in procs:
            if p.is_alive():
                p.terminate()
        raise errors[0]
    if num_workers:
        # worker EOFs end their service loops; the trainers then get
        # theirs so EndOfFeed queues BEHIND every forwarded chunk
        for conn in wconns:
            conn.recv()
            conn.send(None)  # lets the child go: see _report
        eofs = [DataClient("127.0.0.1", port, authkey)
                for port in tports]
        for c in eofs:
            c.send_eof()
            c.close()
    totals = [conn.recv() for conn in tconns]
    for conn in tconns:
        conn.send(None)      # lets the child go: see _report
    elapsed = time.perf_counter() - t0
    for c in clients:
        c.close()
    for p in procs:
        p.join(timeout=30)
        if p.is_alive():
            p.terminate()
    if errors:
        raise errors[0]
    rows = sum(t[0] for t in totals)
    trainer_cpu = sum(t[1] for t in totals)
    if rows != expect_rows:
        raise RuntimeError(f"trainer-side rows {rows} != exact "
                           f"{expect_rows}")
    return {"num_trainers": num_trainers, "num_workers": num_workers,
            "seconds": round(elapsed, 4),
            "mb_per_s": round(total_bytes / elapsed / 1e6, 1),
            "rows_per_s": round(rows / elapsed, 1),
            # what the tier actually moves OFF the trainer: CPU seconds
            # the trainer cores spent per row (recv+unpickle+slice in
            # disaggregated mode vs read+CRC+columnar decode+slice
            # node-locally) — the per-core entitlement number that
            # holds on any box, spare cores or not
            "trainer_cpu_secs": round(trainer_cpu, 4),
            "rows_per_trainer_cpu_s": (round(rows / trainer_cpu, 1)
                                       if trainer_cpu > 0 else None)}


def _run_cache_epochs(shard_paths: list, schema_json: str, cache_bytes: int,
                      chunk_records: int = 256) -> dict:
    """Two sequential epochs over the same work items through ONE shared
    ChunkCache: epoch 1 is the cold decode, epoch 2 the warm (cache-served)
    one.  Returns per-epoch decode throughput — the repeated-epoch
    acceptance compare."""
    from tensorflowonspark_tpu import dfutil
    from tensorflowonspark_tpu.ingest import ChunkCache, ReaderPipeline

    _pin_node(0)
    schema = dfutil.Schema.from_json(schema_json)
    cache = ChunkCache(cache_bytes)
    epochs = []
    for _epoch in range(2):
        pipeline = ReaderPipeline(readers=0, schema=schema,
                                  chunk_records=chunk_records, cache=cache)
        for p in shard_paths:
            pipeline.submit(p)
        pipeline.close()
        rows = 0
        t0 = time.perf_counter()
        while True:
            item = pipeline.get(timeout=5.0)
            if item is None:
                break
            if hasattr(item, "path"):  # ShardDone
                continue
            rows += len(item)
        elapsed = time.perf_counter() - t0
        epochs.append({"rows": rows, "seconds": round(elapsed, 4),
                       "rows_per_s": round(rows / elapsed, 1)})
    return {"cold": epochs[0], "warm": epochs[1],
            "cache": cache.stats(),
            "warm_over_cold": round(epochs[1]["rows_per_s"]
                                    / epochs[0]["rows_per_s"], 2)}


def bench_disagg(quick: bool = False, repeats: int = 3,
                 data_dir: str | None = None) -> dict:
    """Round-15 acceptance compares (BENCH_r15):

    1. **disaggregated vs node-local decode** on the CPU-bound columnar
       workload, trainers pinned to 1 core each: 1 pinned trainer doing
       its own columnar decode (the BENCH_r12 shape) vs the same trainer
       as a pure consumer with 2 data-service workers decoding.
       Interleaved same-round pairing per the PERF_NOTES methodology; the
       measured ``parallel_cpu_ceiling`` is recorded alongside — on a box
       without spare cores for the workers the ratio reads against that
       entitlement, not against 2.0.
    2. **cross-epoch chunk cache**: cold vs repeated epoch decode
       throughput through one shared cache.
    """
    k = 1_000  # 4 KB float payload per record: decode-bound columnar rows
    rps = 64 if quick else 1_024
    nsh = 2 if quick else 8
    repeats = 1 if quick else max(1, repeats)
    ceiling = _parallel_cpu_ceiling(0.2 if quick else 1.5)
    tmp = None
    if data_dir is None:
        tmp = tempfile.TemporaryDirectory(prefix="bench_ingest_svc_")
        data_dir = tmp.name
    try:
        paths, schema, total = prepare_example_shards(data_dir, nsh, rps, k)
        expect = nsh * rps
        schema_json = schema.to_json()
        common = dict(shard_paths=paths, num_trainers=1, expect_rows=expect,
                      total_bytes=total, schema_json=schema_json)
        rounds = _interleaved_rounds(
            [("node_local", "_run_tier", {**common, "num_workers": 0}),
             ("disagg_w2", "_run_tier", {**common, "num_workers": 2})],
            repeats)
        best = _cleanest_round(rounds, ["node_local", "disagg_w2"])
        cache = _run_cell_fn("_run_cache_epochs", shard_paths=paths,
                             schema_json=schema_json,
                             cache_bytes=max(total * 4, 64 << 20))
        nl, dg = best["node_local"]["rows_per_s"], best["disagg_w2"]["rows_per_s"]
        nl_cpu = best["node_local"]["rows_per_trainer_cpu_s"]
        dg_cpu = best["disagg_w2"]["rows_per_trainer_cpu_s"]
        return {"floats_per_record": k, "records": expect,
                "node_local": best["node_local"],
                "disagg_w2": best["disagg_w2"],
                "disagg_over_node_local": round(dg / nl, 2),
                "trainer_core_relief": (round(dg_cpu / nl_cpu, 2)
                                        if nl_cpu and dg_cpu else None),
                "round_ratios": [
                    round(r["disagg_w2"]["rows_per_s"]
                          / r["node_local"]["rows_per_s"], 2)
                    for r in rounds],
                "round_core_reliefs": [
                    round(r["disagg_w2"]["rows_per_trainer_cpu_s"]
                          / r["node_local"]["rows_per_trainer_cpu_s"], 2)
                    for r in rounds
                    if r["node_local"]["rows_per_trainer_cpu_s"]
                    and r["disagg_w2"]["rows_per_trainer_cpu_s"]],
                "cache_epochs": cache,
                # what "x1.5" can even look like here: the aggregate CPU
                # two busy processes actually receive vs one on this box
                "parallel_cpu_ceiling": ceiling}
    finally:
        if tmp is not None:
            tmp.cleanup()


def markdown_r15(res: dict) -> str:
    nl, dg = res["node_local"], res["disagg_w2"]
    cache = res["cache_epochs"]
    return "\n".join([
        "### disaggregated ingest tier (round 15)",
        "| compare | A | B | result |",
        "|---|---|---|---|",
        f"| node-local vs 2-worker tier (trainer rows/s, 1-core trainer) "
        f"| {nl['rows_per_s']:,.0f} | {dg['rows_per_s']:,.0f} "
        f"| x{res['disagg_over_node_local']} "
        f"(cpu ceiling x{res['parallel_cpu_ceiling']}) |",
        f"| trainer-core relief (rows per trainer-CPU-second) "
        f"| {nl['rows_per_trainer_cpu_s']:,.0f} "
        f"| {dg['rows_per_trainer_cpu_s']:,.0f} "
        f"| x{res['trainer_core_relief']} |",
        f"| cold vs repeated epoch (decode rows/s, shared chunk cache) "
        f"| {cache['cold']['rows_per_s']:,.0f} "
        f"| {cache['warm']['rows_per_s']:,.0f} "
        f"| x{cache['warm_over_cold']} |",
    ])


def _parallel_cpu_ceiling(secs: float = 1.5) -> float:
    """Measured aggregate-CPU ratio of 2 busy cores vs 1 on this box (KVM
    steal makes it < 2.0) — the hardware ceiling any fixed-work 1->2 node
    scaling result should be read against."""

    def _burn(q, secs):
        t0 = time.process_time()
        t1 = time.perf_counter()
        x = 0
        while time.perf_counter() - t1 < secs:
            for i in range(10_000):
                x += i * i
        q.put(time.process_time() - t0)

    ctx = mp.get_context("fork")
    totals = []
    for n in (1, 2):
        q = ctx.Queue()
        procs = [ctx.Process(target=_burn, args=(q, secs)) for _ in range(n)]
        for p in procs:
            p.start()
        totals.append(sum(q.get() for _ in procs))
        for p in procs:
            p.join()
    return round(totals[1] / totals[0], 2) if totals[0] else 0.0


def markdown_round12(zc: dict, col: dict, big: dict) -> str:
    return "\n".join([
        "### zero-copy / columnar / single-large-shard (round 12)",
        "| compare | A | B | result |",
        "|---|---|---|---|",
        f"| zerocopy vs bytes-copy (MB/s, N=1) | {zc['zerocopy']['mb_per_s']:,.0f}"
        f" | {zc['bytescopy']['mb_per_s']:,.0f} | {zc['speedup_pct']:+.1f}% |",
        f"| columnar vs row decode (MB/s, N=1) | {col['columnar']['mb_per_s']:,.0f}"
        f" | {col['rowdecode']['mb_per_s']:,.0f} | x{col['speedup_x']} |",
        f"| one {big['records'] * big['record_bytes'] // 1_000_000} MB shard,"
        f" 1->2 nodes (MB/s) | {big['n1']['mb_per_s']:,.0f}"
        f" | {big['n2']['mb_per_s']:,.0f} | x{big['scaling']}"
        f" (whole-shard: x{big['scaling_whole_shard']}) |",
    ])


def markdown_table(results: dict) -> str:
    ns = [r["num_nodes"] for r in results["direct"]]
    lines = [f"### ingest fan-out ({results['record_bytes'] // 1000} KB records,"
             f" MB/s aggregate, per-node work constant)",
             "| mode | " + " | ".join(f"N={n}" for n in ns) + " | scaling |",
             "|---|" + "---|" * (len(ns) + 1)]
    for mode in ("direct", "direct_threaded", "streaming"):
        vals = " | ".join(f"{r['mb_per_s']:,.0f}" for r in results[mode])
        scale = "x".join(str(s) for s in results[f"{mode}_scaling"])
        lines.append(f"| {mode} | {vals} | {scale} |")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="tiny sizes (smoke test, noisy numbers)")
    ap.add_argument("--fanout", default="1,2",
                    help="comma-separated node counts (default 1,2)")
    ap.add_argument("--repeats", type=int, default=3,
                    help="runs per cell; the best is reported (default 3)")
    ap.add_argument("--data-dir", default="",
                    help="reuse an existing shard directory instead of a tempdir")
    ap.add_argument("--json", default="",
                    help="also write the raw results to this JSON file")
    ap.add_argument("--scenario", default="fanout",
                    choices=["fanout", "zerocopy", "columnar", "bigshard",
                             "round12", "r15", "all"],
                    help="fanout = the BENCH_r08 scaling table; zerocopy / "
                         "columnar / bigshard = the round-12 compares "
                         "(round12 runs all three; all adds fanout); r15 = "
                         "the disaggregated data-service tier vs node-local "
                         "decode + the cross-epoch cache compare")
    args = ap.parse_args(argv)
    data_dir = args.data_dir or None
    results: dict = {}
    if args.scenario in ("fanout", "all"):
        fanout = tuple(int(x) for x in args.fanout.split(",") if x)
        results["fanout"] = bench(quick=args.quick, fanout=fanout,
                                  repeats=args.repeats, data_dir=data_dir)
        print(markdown_table(results["fanout"]))
    if args.scenario in ("zerocopy", "round12", "all"):
        results["zerocopy"] = bench_zerocopy(quick=args.quick,
                                             repeats=args.repeats,
                                             data_dir=data_dir)
    if args.scenario in ("columnar", "round12", "all"):
        results["columnar"] = bench_columnar(quick=args.quick,
                                             repeats=args.repeats,
                                             data_dir=data_dir)
    if args.scenario in ("bigshard", "round12", "all"):
        results["bigshard"] = bench_bigshard(quick=args.quick,
                                             repeats=args.repeats,
                                             data_dir=data_dir)
    if args.scenario in ("r15", "all"):
        results["disagg"] = bench_disagg(quick=args.quick,
                                         repeats=args.repeats,
                                         data_dir=data_dir)
        print(markdown_r15(results["disagg"]))
    if {"zerocopy", "columnar", "bigshard"} <= set(results):
        print(markdown_round12(results["zerocopy"], results["columnar"],
                               results["bigshard"]))
    else:
        for key in ("zerocopy", "columnar", "bigshard"):
            if key in results:
                print(json.dumps({key: results[key]}, indent=2))
    if args.json:
        out = results["fanout"] if set(results) == {"fanout"} else results
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2)
        print(f"raw results -> {args.json}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
