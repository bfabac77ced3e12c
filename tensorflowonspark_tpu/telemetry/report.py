"""Cluster-wide metric aggregation and human/machine-readable reports.

The coordinator keeps one raw snapshot per node (replaced key-by-key as
heartbeat deltas arrive); this module turns ``{node_key: snapshot}`` into

- an **aggregated snapshot** (``aggregate_snapshots``): counters summed
  across nodes, histogram digests merged, cluster-wide percentiles pooled
  from the nodes' shipped samples, per-node detail preserved under
  ``"nodes"`` — the ``cluster.metrics()`` payload;
- a **text report** (``debug_dump``) for eyeballs and bug reports;
- an **end-of-run JSON run report** (``build_run_report``), written next to
  the job's checkpoints/logs at shutdown — throughput, restarts, span
  percentiles, per-node detail (the tf.data-paper "built-in per-stage
  counters" idea applied run-level);
- the report's **lifecycle block** (``build_lifecycle``): per process the
  once-a-process stages in order with the gaps between them, and the
  programs XLA traced, lowered and compiled or loaded — where the time a
  chip is held and not stepping went.
"""

from __future__ import annotations

import json
import time
from typing import Any

from tensorflowonspark_tpu.telemetry.registry import percentile_of
from tensorflowonspark_tpu.telemetry.trace import event_origin

#: Percentiles rendered for every merged histogram.
PERCENTILES = (50.0, 90.0, 99.0)


def aggregate_snapshots(nodes: dict[str, dict]) -> dict:
    """Merge per-node snapshots into one cluster view.

    ``nodes`` maps a node key (stringified executor id, or ``"driver"``) to
    a registry snapshot (``{"counters": ..., "gauges": ...,
    "histograms": {name: digest [+ "recent" samples]}}``).  Counter values
    are cumulative per process, so the aggregate is their plain sum; gauges
    stay per-node (a cluster-summed gauge is rarely meaningful); histogram
    digests merge exactly (count/sum/min/max) and percentiles are estimated
    from the pooled per-node samples.
    """
    counters: dict[str, int] = {}
    hists: dict[str, dict] = {}
    samples: dict[str, list[float]] = {}
    for snap in nodes.values():
        for name, value in (snap.get("counters") or {}).items():
            counters[name] = counters.get(name, 0) + value
        for name, d in (snap.get("histograms") or {}).items():
            agg = hists.setdefault(name, {"count": 0, "sum": 0.0,
                                          "min": None, "max": None})
            agg["count"] += int(d.get("count") or 0)
            agg["sum"] += float(d.get("sum") or 0.0)
            for key, pick in (("min", min), ("max", max)):
                v = d.get(key)
                if v is not None:
                    agg[key] = v if agg[key] is None else pick(agg[key], v)
            samples.setdefault(name, []).extend(d.get("recent") or ())
    for name, agg in hists.items():
        pool = sorted(samples.get(name) or ())
        for q in PERCENTILES:
            agg[f"p{q:g}"] = percentile_of(pool, q)
        if agg["count"]:
            agg["mean"] = agg["sum"] / agg["count"]
    return {"nodes": _strip_samples(nodes), "counters": counters,
            "histograms": hists}


def _strip_samples(nodes: dict[str, dict]) -> dict[str, dict]:
    """Per-node detail without the raw sample lists (digest-only)."""
    out: dict[str, dict] = {}
    for key, snap in nodes.items():
        hists = {name: {k: v for k, v in d.items() if k != "recent"}
                 for name, d in (snap.get("histograms") or {}).items()}
        out[key] = {"counters": dict(snap.get("counters") or {}),
                    "gauges": dict(snap.get("gauges") or {}),
                    "histograms": hists}
    return out


#: Fields of a flight event that are the recorder's own, not the stage's tags.
_EVENT_BOOKKEEPING = frozenset(
    ("kind", "t0", "wall", "t", "node", "stage", "start", "secs"))
#: ``xla.*`` counters (``xla_events.py``) -> their names in the block.
_XLA_SECONDS = {"xla.trace.us": "trace_secs", "xla.lower.us": "lower_secs",
                "xla.backend.us": "backend_secs",
                "xla.cache_load.us": "cache_load_secs"}
_XLA_COUNTS = {"xla.programs": "programs", "xla.cache.hits": "cache_hits",
               "xla.cache.misses": "cache_misses"}


def build_lifecycle(events: list[dict], nodes: dict[str, dict]) -> dict:
    """Where each process's time went when no step ran, from the flight
    events ``lifecycle`` and ``xla_program`` (``trace.lifecycle``,
    ``xla_events.py``) and the ``xla.*`` counters.

    ``events`` is ``trace.merge_events``' list (each event with ``node`` and
    ``t``, driver-monotonic seconds: a lifecycle event is recorded as its
    stage ENDS, so ``t - secs`` is its beginning on one clock for all
    processes); ``nodes`` the per-node snapshots of the aggregate.  Per
    process (``driver``, ``node0``, ...):

    - ``stages``: in order of their beginning, each with ``stage``, ``start``
      (epoch seconds), ``secs``, its tags and ``gap_secs``, the time since
      the end of the stage before it that no stage names (None for the
      first; negative: it began inside that stage);
    - ``programs``: the programs over ``xla_events.PROGRAM_FLOOR_SECS``, in
      order, with ``trace_secs`` / ``lower_secs`` / ``backend_secs`` /
      ``cache_load_secs`` and ``cache`` (``hit`` or ``miss``);
    - ``xla``: the process's totals over ALL its programs, from the counters;
    - ``exit_secs`` (a node): from the end of its ``node.drain`` to the end
      of the driver's ``shutdown.join`` — the final snapshot, the deregister
      and the process's own exit (the PJRT client's teardown).  Exact for a
      job of one node process that was still alive when the driver began
      to join (``exit_secs_exact``); otherwise an upper bound.
    """
    out: dict[str, dict] = {}
    spans: dict[str, list] = {}     # process -> [(begin, end, its entry)]
    for ev in events:
        kind = ev.get("kind")
        if kind not in ("lifecycle", "xla_program"):
            continue
        key = event_origin(str(ev.get("node", "")))
        proc = out.setdefault(key, {"stages": [], "programs": []})
        secs = float(ev.get("secs") or 0.0)
        entry = {"start": ev.get("start"), "secs": round(secs, 6),
                 **{k: v for k, v in ev.items()
                    if k not in _EVENT_BOOKKEEPING}}
        if kind == "xla_program":
            proc["programs"].append(entry)
        else:
            end = float(ev.get("t", 0.0))
            spans.setdefault(key, []).append(
                (end - secs, end, {"stage": ev.get("stage"), **entry}))
    last: dict[tuple, tuple] = {}   # (process, stage) -> its last (end, secs)
    for key, proc in out.items():
        reached = None
        for begin, end, entry in sorted(spans.get(key, ()),
                                        key=lambda span: span[:2]):
            entry["gap_secs"] = (None if reached is None
                                 else round(begin - reached, 6))
            reached = end if reached is None else max(reached, end)
            proc["stages"].append(entry)
            last[key, entry["stage"]] = (end, entry["secs"])
        counters = (nodes.get(key[len("node"):] if key.startswith("node")
                              else key) or {}).get("counters") or {}
        if counters.get("xla.programs"):
            proc["xla"] = {
                **{name: counters.get(c, 0) for c, name in _XLA_COUNTS.items()},
                **{name: counters.get(c, 0) / 1e6
                   for c, name in _XLA_SECONDS.items()}}
    joined, join_secs = last.get(("driver", "shutdown.join"), (None, 0.0))
    drained = {key: end for (key, stage), (end, _secs) in last.items()
               if stage == "node.drain"}
    if joined is not None:
        for key, end in drained.items():
            out[key]["exit_secs"] = round(max(0.0, joined - end), 6)
            out[key]["exit_secs_exact"] = (len(drained) == 1
                                           and join_secs > 0.05)
    return out


def _lifecycle_lines(lifecycle: dict) -> list[str]:
    lines = ["-- lifecycle (once-a-process stages, XLA by program) --"]
    for key in sorted(lifecycle):
        proc = lifecycle[key]
        lines.append(f"  {key}")
        for st in proc.get("stages") or ():
            gap = st.get("gap_secs")
            lines.append(f"    {st['stage']:<28} {st['secs']:>10.3f}s"
                         + ("" if gap is None else f"  gap {gap:+.3f}s"))
        if "exit_secs" in proc:
            exact = "" if proc.get("exit_secs_exact") else " (at most)"
            lines.append(f"    {'(exit)':<28} {proc['exit_secs']:>10.3f}s"
                         f"{exact}")
        xla = proc.get("xla")
        if xla:
            lines.append("    xla: " + " ".join(
                f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
                for k, v in xla.items()))
        for prog in proc.get("programs") or ():
            lines.append(
                f"    program {prog.get('fun_name')}: {prog['secs']:.3f}s "
                f"(trace {prog.get('trace_secs', 0.0):.3f} lower "
                f"{prog.get('lower_secs', 0.0):.3f} backend "
                f"{prog.get('backend_secs', 0.0):.3f}) {prog.get('cache')}")
    return lines


def debug_dump(aggregated: dict, lifecycle: dict | None = None) -> str:
    """Render an ``aggregate_snapshots`` result as a text report, with the
    run report's ``lifecycle`` block (``build_lifecycle``) when given."""
    lines: list[str] = ["== cluster metrics =="]
    counters = aggregated.get("counters") or {}
    if counters:
        lines.append("-- counters (cluster total) --")
        width = max(len(n) for n in counters)
        for name in sorted(counters):
            lines.append(f"  {name:<{width}}  {counters[name]}")
    hists = aggregated.get("histograms") or {}
    if hists:
        lines.append("-- spans (cluster merged) --")
        for name in sorted(hists):
            d = hists[name]
            parts = [f"count={d.get('count')}"]
            if d.get("count"):
                parts.append(f"mean={d.get('mean'):.6g}")
                parts.append(f"min={d.get('min'):.6g}")
                parts.append(f"max={d.get('max'):.6g}")
                for q in PERCENTILES:
                    v = d.get(f"p{q:g}")
                    if v is not None:
                        parts.append(f"p{q:g}={v:.6g}")
            lines.append(f"  {name}  " + " ".join(parts))
    for key in sorted(aggregated.get("nodes") or {}):
        snap = aggregated["nodes"][key]
        lines.append(f"-- node {key} --")
        for kind in ("counters", "gauges"):
            for name in sorted(snap.get(kind) or {}):
                lines.append(f"  {name} = {snap[kind][name]}")
        for name in sorted(snap.get("histograms") or {}):
            d = snap["histograms"][name]
            lines.append(f"  {name} count={d.get('count')} sum={d.get('sum')}")
    if lifecycle:
        lines.extend(_lifecycle_lines(lifecycle))
    return "\n".join(lines)


def _gauge_max(aggregated: dict, name: str):
    """Largest per-node value of a gauge, or None when no node reports it
    (gauges stay per-node in the aggregate; for the serving frontend's
    connection/outstanding gauges the driver is the only reporter, so max
    IS the value)."""
    vals = [snap["gauges"][name]
            for snap in (aggregated.get("nodes") or {}).values()
            if name in (snap.get("gauges") or {})]
    return max(vals) if vals else None


def _hist_ms(aggregated: dict, name: str, q: str):
    """A merged histogram's percentile in milliseconds, or None."""
    v = ((aggregated.get("histograms") or {}).get(name) or {}).get(q)
    return round(v * 1e3, 3) if v is not None else None


def build_run_report(aggregated: dict, *, wall_secs: float | None = None,
                     extras: dict | None = None) -> dict:
    """End-of-run JSON document: the aggregate + derived headline numbers.

    Headlines are best-effort derivations from well-known counter names —
    absent instrumentation just omits them (``None``), it never fails the
    report.
    """
    counters = aggregated.get("counters") or {}
    rx_bytes = counters.get("dataplane.rx_bytes")
    ingest_bytes = counters.get("ingest.bytes_read")
    serve_requests = counters.get("serve.requests_total")
    serving = None
    if serve_requests:
        # serving headlines: gateway qps/latency plus the reactor
        # frontend's health next to them (connections, pipelining depth,
        # frame counts, loop lag) — the wire endpoint is a single thread,
        # so its loop-lag p99 is the first thing to check when TCP p99
        # diverges from in-process
        serving = {
            "requests_total": serve_requests,
            "qps": (round(serve_requests / wall_secs, 1)
                    if wall_secs else None),
            "request_p50_ms": _hist_ms(aggregated, "serve.request_secs", "p50"),
            "request_p99_ms": _hist_ms(aggregated, "serve.request_secs", "p99"),
            "frontend_frames_in": counters.get("serve.frontend.frames_in"),
            "frontend_frames_out": counters.get("serve.frontend.frames_out"),
            "frontend_connections_open": _gauge_max(
                aggregated, "serve.frontend.connections"),
            "frontend_outstanding_requests": _gauge_max(
                aggregated, "serve.frontend.outstanding"),
            "frontend_loop_lag_p99_ms": _hist_ms(
                aggregated, "serve.frontend.loop_lag_secs", "p99"),
        }
    ingest_tier = None
    fwd_rows = counters.get("ingest.rows_forwarded")
    cache_hits = counters.get("ingest.cache_hits", 0)
    cache_misses = counters.get("ingest.cache_misses", 0)
    if fwd_rows or cache_hits or cache_misses:
        # the disaggregated data-service tier ran (or the chunk cache was
        # live node-locally): the run's ingest postmortem block
        ingest_tier = {
            "chunks_forwarded": counters.get("ingest.chunks_forwarded"),
            "rows_forwarded": fwd_rows,
            "forwarded_mb": (
                round(counters["ingest.bytes_forwarded"] / 1e6, 3)
                if counters.get("ingest.bytes_forwarded") else None),
            "cache_hits": cache_hits,
            "cache_misses": cache_misses,
            "cache_hit_rate": (
                round(cache_hits / (cache_hits + cache_misses), 4)
                if (cache_hits + cache_misses) else None),
            "cache_evictions": counters.get("ingest.cache_evictions", 0),
            "forward_errors": counters.get("ingest.forward_errors", 0),
        }
    collective = None
    if counters.get("collective.rounds_total") \
            or counters.get("collective.formations_total") \
            or counters.get("collective.evictions_total"):
        # the sync-training postmortem block: how many rounds/formations
        # ran, how often the group aborted and re-formed, and the gray-
        # failure tallies (suspicion votes filed, quorum evictions,
        # probation readmissions) — the first place to look when a sync
        # run degraded to W-1 or thrashed
        collective = {
            "rounds_total": counters.get("collective.rounds_total", 0),
            "formations_total": counters.get(
                "collective.formations_total", 0),
            "reforms_total": counters.get("collective.reforms_total", 0),
            "aborts_total": counters.get("collective.aborts_total", 0),
            "suspects_total": counters.get("collective.suspects_total", 0),
            "evictions_total": counters.get(
                "collective.evictions_total", 0),
            "readmits_total": counters.get("collective.readmits_total", 0),
            "form_p50_ms": _hist_ms(aggregated, "collective.form_secs",
                                    "p50"),
            "all_reduce_p50_ms": _hist_ms(
                aggregated, "collective.all_reduce_secs", "p50"),
        }
    report: dict[str, Any] = {
        "schema": "tos-run-report-v1",
        "written_at": time.time(),
        "wall_secs": wall_secs,
        "throughput_mb_per_s": (
            round(rx_bytes / wall_secs / 1e6, 3)
            if rx_bytes and wall_secs else None),
        # DIRECT-mode twin of the driver-pump number: bytes the nodes read
        # straight from storage (cluster aggregate), which never transit
        # the data plane and so never land in dataplane.rx_bytes
        "ingest_mb_per_s": (
            round(ingest_bytes / wall_secs / 1e6, 3)
            if ingest_bytes and wall_secs else None),
        "records_ingested": counters.get("ingest.records_read"),
        "ingest_tier": ingest_tier,
        "rows_fed": counters.get("dataplane.rows_in"),
        "rows_consumed": counters.get("feed.rows_consumed"),
        "serving": serving,
        "collective": collective,
        "restarts_total": counters.get("elastic.restarts_total", 0),
        "faults_injected": counters.get("faultinject.injected_total", 0),
        "counters": counters,
        "histograms": aggregated.get("histograms") or {},
        "nodes": aggregated.get("nodes") or {},
    }
    if extras:
        report.update(extras)
    flight = (report.get("flight") or {}).get("events")
    if flight:
        lifecycle = build_lifecycle(flight, report["nodes"])
        if lifecycle:
            report["lifecycle"] = lifecycle
    return report


def write_run_report(path: str, report: dict) -> str:
    """Write the report JSON (pretty, stable key order) and return ``path``."""
    with open(path, "w", encoding="utf-8") as f:
        json.dump(report, f, indent=2, sort_keys=True)
        f.write("\n")
    return path
