"""CPU-side data-plane microbench: the STREAMING fan-out table, driver-only.

Regenerates the PERF_NOTES "STREAMING fan-out ceiling" numbers without a
chip: N consumer processes each run a real ``DataServer``
+ ``FeedQueues`` + a draining ``DataFeed`` consumer, and the driver feeds
them from one thread per node through real ``DataClient``s — the exact
send/serialize/ack path ``cluster.train`` drives, minus the map_fun.

Two wire configurations are compared:

- ``legacy``: wire v1 frames (whole-chunk pickle blob) with a send window
  of 1 — the request/reply ping-pong the framework shipped before the
  zero-copy data plane (ISSUE 3).
- ``zerocopy``: negotiated v2 frames (pickle protocol 5 out-of-band buffers,
  ``sendmsg`` scatter-gather, ``recv_into``) with the default pipelined
  send window.

Workloads mirror PERF_NOTES round 5: 150 KB byte rows (ImageNet idiom) and
1 KB byte rows (tabular idiom).  Rows are DISTINCT objects (pickle memoizes
repeated objects, which would fake the legacy numbers).

Usage::

    python bench_dataplane.py                 # full table, markdown + JSON
    python bench_dataplane.py --quick         # small sizes (CI smoke)
    python bench_dataplane.py --json out.json

Run on an otherwise idle box; the driver threads and the N consumers share
the host, exactly like the same-box PERF_NOTES measurement.
"""

from __future__ import annotations

import argparse
import json
import multiprocessing as mp
import os
import threading
import time


def _report(conn, server, totals) -> None:
    """A child's last act: hand the totals to the driver, then stay up until
    the driver lets go.  The driver's ``send_eof`` is answered by a daemon
    thread of THIS process; exiting as soon as the consumer saw EndOfFeed
    killed that thread before its reply on a busy box (``ConnectionError:
    socket closed mid-read`` in the feeder)."""
    conn.send(totals)
    conn.recv()
    server.stop()


def _consumer_main(conn, authkey: bytes, capacity: int, batch: int) -> None:
    """Child process: one node's data plane + a drain-everything consumer."""
    from tensorflowonspark_tpu.dataserver import DataServer
    from tensorflowonspark_tpu.feeding import DataFeed, FeedQueues

    queues = FeedQueues(capacity=capacity)
    server = DataServer(queues, authkey, feed_timeout=120.0)
    conn.send(server.start())
    feed = DataFeed(queues)
    rows = 0
    nbytes = 0
    while not feed.should_stop():
        for item in feed.next_batch(batch):
            rows += 1
            nbytes += len(item)
    _report(conn, server, (rows, nbytes))


def _make_partition(rows: int, row_bytes: int, seed: int) -> list[bytes]:
    """``rows`` DISTINCT bytes objects of ``row_bytes`` each (cheap: sliced
    windows of one random buffer, so generation never dominates)."""
    buf = os.urandom(row_bytes + rows)
    return [bytes(memoryview(buf)[i:i + row_bytes]) for i in range(rows)]


def run_fanout(num_nodes: int, *, row_bytes: int, rows_per_part: int,
               parts_per_node: int, wire: int, send_window: int | None,
               chunk_rows: int, capacity: int = 1024,
               metrics: bool | None = None) -> dict:
    """One fan-out run; returns {mb_per_s, rows_per_s, seconds, ...}.

    ``metrics`` pins ``TOS_METRICS`` for this run (None = leave the
    environment alone): the registry is reset BEFORE the consumer processes
    fork, so driver and consumers agree on the setting — the on-vs-off
    comparison that guards the hot path against instrumentation overhead
    (``--metrics-compare``, BENCH_r06.json).
    """
    from tensorflowonspark_tpu import telemetry

    if metrics is None:
        return _run_fanout(num_nodes, row_bytes=row_bytes,
                           rows_per_part=rows_per_part,
                           parts_per_node=parts_per_node, wire=wire,
                           send_window=send_window, chunk_rows=chunk_rows,
                           capacity=capacity)
    prev = os.environ.get("TOS_METRICS")
    os.environ["TOS_METRICS"] = "1" if metrics else "0"
    telemetry.reset()
    try:
        return _run_fanout(num_nodes, row_bytes=row_bytes,
                           rows_per_part=rows_per_part,
                           parts_per_node=parts_per_node, wire=wire,
                           send_window=send_window, chunk_rows=chunk_rows,
                           capacity=capacity)
    finally:
        if prev is None:
            os.environ.pop("TOS_METRICS", None)
        else:
            os.environ["TOS_METRICS"] = prev
        telemetry.reset()


def _run_fanout(num_nodes: int, *, row_bytes: int, rows_per_part: int,
                parts_per_node: int, wire: int, send_window: int | None,
                chunk_rows: int, capacity: int = 1024) -> dict:
    from tensorflowonspark_tpu.dataserver import DataClient

    authkey = b"bench"
    ctx = mp.get_context("fork")
    procs, conns, ports = [], [], []
    for _ in range(num_nodes):
        parent, child = ctx.Pipe()
        p = ctx.Process(target=_consumer_main,
                        args=(child, authkey, capacity, 256), daemon=True)
        p.start()
        procs.append(p)
        conns.append(parent)
        ports.append(parent.recv())

    # pre-generate every partition so the clock measures the data plane,
    # not os.urandom
    parts = [[_make_partition(rows_per_part, row_bytes, seed=n * 100 + i)
              for i in range(parts_per_node)] for n in range(num_nodes)]

    clients = [DataClient("127.0.0.1", port, authkey,
                          chunk_size=chunk_rows, send_window=send_window)
               for port in ports]
    if wire == 1:
        for c in clients:
            c._wire = 1  # force the legacy frame format

    errors: list[BaseException] = []

    def _feed(i: int) -> None:
        try:
            for part in parts[i]:
                clients[i].feed_partition(part)
            clients[i].send_eof()
        except BaseException as e:  # noqa: BLE001 - surfaced below
            errors.append(e)

    threads = [threading.Thread(target=_feed, args=(i,)) for i in range(num_nodes)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # the clock stops when every consumer has DRAINED its feed (end-to-end,
    # like the cluster.train measurement), not when the last send returned
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
    expect = num_nodes * parts_per_node * rows_per_part
    if total_rows != expect:
        raise RuntimeError(f"row loss: consumed {total_rows}, fed {expect}")
    return {
        "num_nodes": num_nodes,
        "row_bytes": row_bytes,
        "wire": wire,
        "send_window": send_window,
        "seconds": round(elapsed, 4),
        "mb_per_s": round(total_bytes / elapsed / 1e6, 1),
        "rows_per_s": round(total_rows / elapsed, 1),
    }


def bench(quick: bool = False, fanout=(1, 2, 4), repeats: int = 3) -> dict:
    """Full table; each cell is the BEST of ``repeats`` runs (throughput
    benches on shared boxes take the max — the slower runs measure the
    neighbors, not the code)."""
    image = dict(row_bytes=150_000,
                 rows_per_part=16 if quick else 64,
                 parts_per_node=2 if quick else 6,
                 chunk_rows=64)
    tabular = dict(row_bytes=1_000,
                   rows_per_part=512 if quick else 4096,
                   parts_per_node=2 if quick else 4,
                   chunk_rows=512)
    repeats = 1 if quick else max(1, repeats)
    results: dict = {"image_150KB": {}, "tabular_1KB": {}}
    for name, wl in (("image_150KB", image), ("tabular_1KB", tabular)):
        key = "mb_per_s" if name.startswith("image") else "rows_per_s"
        for label, wire, window in (("legacy_v1_pingpong", 1, 1),
                                    ("zerocopy_v2_pipelined", 2, None)):
            results[name][label] = [
                max((run_fanout(n, wire=wire, send_window=window, **wl)
                     for _ in range(repeats)), key=lambda r: r[key])
                for n in fanout
            ]
    return results


def metrics_compare(quick: bool = False, num_nodes: int = 2,
                    repeats: int = 3) -> dict:
    """Instrumentation-overhead guard: the 150 KB-row zero-copy config run
    with telemetry enabled vs disabled (best of ``repeats`` each).  The
    acceptance bar is enabled staying within 3% of disabled — the data
    plane meters every frame, so this is the config where overhead would
    show first."""
    # 4x the table's partition count: each leg must run long enough
    # (~seconds) that the on-vs-off delta is signal, not scheduler noise
    wl = dict(row_bytes=150_000,
              rows_per_part=16 if quick else 64,
              parts_per_node=2 if quick else 24,
              chunk_rows=64, wire=2, send_window=None)
    repeats = 1 if quick else max(1, repeats)
    # INTERLEAVED off/on pairs: on a shared box the load drifts over the
    # seconds a phase takes, and two back-to-back phases would measure the
    # drift, not the instrumentation; paired runs see the same conditions.
    runs: dict[str, list[dict]] = {"metrics_off": [], "metrics_on": []}
    for _ in range(repeats):
        runs["metrics_off"].append(run_fanout(num_nodes, metrics=False, **wl))
        runs["metrics_on"].append(run_fanout(num_nodes, metrics=True, **wl))
    out: dict = {label: max(rs, key=lambda r: r["mb_per_s"])
                 for label, rs in runs.items()}
    off, on = out["metrics_off"]["mb_per_s"], out["metrics_on"]["mb_per_s"]
    out["overhead_pct"] = round((off - on) / off * 100.0, 2) if off else None
    return out


def markdown_table(results: dict) -> str:
    lines = []
    for name, by_mode in results.items():
        metric = "MB/s" if name.startswith("image") else "rows/s"
        key = "mb_per_s" if name.startswith("image") else "rows_per_s"
        ns = [r["num_nodes"] for r in next(iter(by_mode.values()))]
        lines.append(f"### {name} ({metric}, aggregate)")
        lines.append("| wire | " + " | ".join(f"N={n}" for n in ns) + " |")
        lines.append("|---|" + "---|" * len(ns))
        for label, runs in by_mode.items():
            vals = " | ".join(f"{r[key]:,.0f}" for r in runs)
            lines.append(f"| {label} | {vals} |")
        lines.append("")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="small sizes (smoke test, noisy numbers)")
    ap.add_argument("--fanout", default="1,2,4",
                    help="comma-separated node counts (default 1,2,4)")
    ap.add_argument("--json", default="",
                    help="also write the raw results to this JSON file")
    ap.add_argument("--repeats", type=int, default=3,
                    help="runs per cell; the best is reported (default 3)")
    ap.add_argument("--metrics-compare", action="store_true",
                    help="run the 150KB zero-copy config with telemetry "
                         "enabled vs disabled (instrumentation-overhead "
                         "guard; see BENCH_r06.json)")
    args = ap.parse_args(argv)
    fanout = tuple(int(x) for x in args.fanout.split(",") if x)
    if args.metrics_compare:
        results = metrics_compare(quick=args.quick, repeats=args.repeats)
        on, off = results["metrics_on"], results["metrics_off"]
        print(f"metrics off: {off['mb_per_s']:,.1f} MB/s   "
              f"metrics on: {on['mb_per_s']:,.1f} MB/s   "
              f"overhead: {results['overhead_pct']}%")
        if args.json:
            with open(args.json, "w") as f:
                json.dump(results, f, indent=2)
            print(f"raw results -> {args.json}")
        return 0
    results = bench(quick=args.quick, fanout=fanout, repeats=args.repeats)
    print(markdown_table(results))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=2)
        print(f"raw results -> {args.json}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
