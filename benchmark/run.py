#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process is the driver: it never imports jax (one process owns the
chip).  It starts the cell's job through ``tos.run`` with the node pinned
to ``JAX_PLATFORMS=tpu``; the cell's kind of traffic (``kinds/<kind>.py``,
named by the traffic file) supplies the node's map_fun and offers the load;
the configuration (``configs/<name>.py``) supplies the system under test,
its inputs and its plain reference; each per-layer metric is read by
``layer_metrics/<name>.py``.  All are found by the names in BENCHMARK.json:
nothing here names a cell, a configuration, a mix or a metric.

The last line of stdout is the result, one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced).  No TPU, fewer chips than the cell asks for, or any failure: a
non-zero exit and no result line.

``--rehearse-cpu`` is a debugging aid for a box without a chip: the
configuration's and the mix's ``rehearsal`` sizes on CPU devices.  It says on
its last line that it is not a chip run, so nothing can read a number off it.
"""

from __future__ import annotations

import time

T_START = time.time()          # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import common  # noqa: E402

NOT_A_CHIP_RUN = "[CPU REHEARSAL - NOT A CHIP RUN: no number above is a device metric]"


def say(msg: str) -> None:
    print(f"bench: {msg}", flush=True)


def fail(msg: str):
    raise SystemExit(f"benchmark/run.py: FAILED - {msg}")


def await_device(cluster, platform: str, timeout: float = 300.0) -> dict:
    """The chief's ``device`` block from ``cluster_info()``, once the node
    has claimed its accelerator; exits if it found anything but ``platform``
    (the rule of ``chip_smoke.py``, copied: later PRs may change that file)."""
    deadline = time.monotonic() + timeout
    while True:
        errors = cluster.coordinator.errors()
        if errors:
            last = " ".join(
                errors[0].get("traceback", "").strip().splitlines()[-1:])
            fail(f"the node failed before reporting its device "
                 f"(JAX_PLATFORMS={platform}): {last}")
        device = cluster.coordinator.cluster_info()[0].get("device") or {}
        if device.get("num_devices") is not None:
            break
        if not cluster.launcher.alive():
            fail("the node exited before reporting its device")
        if time.monotonic() > deadline:
            fail(f"the node reported no device within {timeout:.0f}s")
        time.sleep(0.05)
    if device["platform"] != platform:
        fail(f"needs platform {platform!r}; the node found "
             f"{device['platform']!r} ({device.get('device_kind')}, "
             f"{device['num_devices']} device(s))")
    return device


def apply_rehearsal(cell: dict) -> None:
    for part in ("config", "traffic"):
        overrides = cell[part].get("rehearsal", {})
        for key, value in overrides.items():
            if isinstance(value, dict) and isinstance(cell[part].get(key), dict):
                cell[part][key] = {**cell[part][key], **value}
            else:
                cell[part][key] = value


def node_entry(args: dict, ctx) -> None:
    """The map_fun of every cell: hands over to the cell's kind."""
    kind = common.load_module("kinds", args["cell"]["traffic"]["kind"],
                              args["cell"]["base"])
    kind.node(args, ctx)


def read_layer_metrics(cell: dict, run: dict) -> dict:
    """Each per-layer metric of the cell through its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for metric in cell["per_layer"]:
        reader = common.load_module("layer_metrics", metric["name"],
                                    cell["base"])
        for key, want in (("LAYER", metric["layer"]), ("UNIT", metric["unit"]),
                          ("MOVES", metric["moves"])):
            if getattr(reader, key) != want:
                fail(f"layer_metrics/{metric['name']}.py says {key}="
                     f"{getattr(reader, key)!r}, BENCHMARK.json {want!r}")
        value = reader.read(run)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def assemble_result(cell: dict, kind, node_result: dict, facts: dict,
                    traced: bool, reduced: dict | None,
                    peaks: dict | None) -> dict:
    """The result line's object: exactly ``correct``, ``attempted``,
    ``failed``, ``metrics``, ``device`` and, when a trace was reduced,
    ``breakdown``.  Untraced runs carry the cell's end-to-end metrics,
    traced runs its per-layer metrics."""
    dev = node_result["device"]
    device_out = {"platform": dev["platform"], "kind": dev["kind"],
                  "count": dev["count"],
                  "memory_peak_bytes": node_result["memory_peak_bytes"]}
    result = {
        "correct": bool(node_result["check"]["ok"]
                        and facts["compilations"] == 0
                        and node_result["failed"] == 0),
        "attempted": int(node_result["attempted"]),
        "failed": int(node_result["failed"]),
    }
    if not traced:
        values = {**kind.end_to_end(cell, facts), "setup_s": facts["setup_s"]}
        result["metrics"] = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in cell["end_to_end"]}
        result["device"] = device_out
        return result
    run = {"cell": cell, "facts": facts, "trace": reduced,
           "spans": {"seconds": node_result["measured"]["span_seconds"],
                     "counts": node_result["measured"]["span_counts"]},
           "counters": node_result["measured"]["counters"],
           "peaks": peaks, "node": node_result}
    result["metrics"] = read_layer_metrics(cell, run)
    result["device"] = device_out
    if reduced is not None:
        device_out["busy_s"] = reduced["busy_s"]
        device_out["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                               "idle_gaps": reduced["idle_gaps"][:10]}
        if len(reduced["per_device_busy_s"]) > 1:
            result["breakdown"]["per_device_busy_s"] = \
                reduced["per_device_busy_s"]
    return result


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse-cpu", action="store_true",
                        help="debugging aid: tiny sizes on CPU; NOT a chip run")
    a = parser.parse_args()

    manifest = common.load_manifest()
    cell = common.resolve_cell(a.workload)
    platform = "tpu"
    node_env = {"JAX_PLATFORMS": platform}
    if a.rehearse_cpu:
        platform = "cpu"
        apply_rehearsal(cell)
        node_env = {"JAX_PLATFORMS": "cpu", "XLA_FLAGS":
                    f"--xla_force_host_platform_device_count={cell['chips']}"}
    run_dir = os.path.join(common.WORK_DIR, "runs", a.workload)
    os.makedirs(run_dir, exist_ok=True)
    opts = {"seed": a.seed, "trace": bool(a.trace),
            "seconds": float(a.seconds if a.seconds is not None
                             else manifest["run_seconds"]),
            "work_dir": common.WORK_DIR, "run_dir": run_dir}

    import tensorflowonspark_tpu as tos
    from xla_cache_bootstrap import enable_persistent_cache

    # Exported before tos.run so the node inherits it.  Threshold 0: every
    # program is cached, so a later run of the cell compiles nothing.
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    cache_dir = enable_persistent_cache()
    entries_before = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    kind = common.load_module("kinds", cell["traffic"]["kind"], cell["base"])
    t_run = time.time()
    cluster = tos.run(node_entry, {"cell": cell, "opts": opts}, env=node_env,
                      log_dir=os.path.join(run_dir, "logs"),
                      **kind.cluster_options(cell))
    finished = False
    try:
        plan = kind.prepare(cell, opts)      # while the node claims the chip
        prepared_s = time.time() - t_run
        device = await_device(cluster, platform)
        claim_s = time.time() - t_run
        if device["num_devices"] < cell["chips"]:
            fail(f"{a.workload} needs {cell['chips']} chip(s); the node found "
                 f"{device['num_devices']}")
        say(f"device: {device['platform']} {device['device_kind']} x"
            f"{device['num_devices']}, claimed {claim_s:.1f}s after tos.run "
            f"(inputs ready after {prepared_s:.1f}s)")
        kind.drive(cluster, cell, plan, opts)
        finished = True
    finally:
        if not finished:
            cluster.launcher.terminate()
            cluster.coordinator.stop()
    # patience well beyond the default 120 s: a cold compile of the step can
    # still be running when a STREAMING train() has buffered its rows
    cluster.shutdown(timeout=900.0)
    t_down = time.time()
    node_result = cluster.coordinator.cluster_info()[0].get("bench")
    if not node_result:
        fail("the node published no result")
    if "jax" in sys.modules:
        fail("the driver process imported jax")

    facts = kind.facts(cell, node_result, opts)
    facts.update({"claim_s": claim_s,
                  "teardown_s": t_down - node_result["finished_epoch"],
                  "setup_s": facts["window_epoch_start"] - T_START,
                  "cache_entries_new":
                  len(os.listdir(cache_dir)) - entries_before,
                  "inputs_written": bool(plan.get("written"))})
    reduced = None
    if a.trace and node_result.get("reduced_trace"):
        reduced = common.read_json(node_result["reduced_trace"])
    if a.trace and reduced is None and not a.rehearse_cpu:
        fail("the traced run recorded no operation on the device")
    peaks = (None if a.rehearse_cpu
             else common.peaks_for(node_result["device"]["kind"], cell["base"]))
    result = assemble_result(cell, kind, node_result, facts, bool(a.trace),
                             reduced, peaks)
    # everything that is not the result goes on earlier lines
    say("facts: " + json.dumps({k: v for k, v in facts.items()
                                if k != "kernels"}))
    say("node: " + json.dumps({k: node_result[k] for k in (
        "check", "seconds", "program_bytes", "allocator_peak_bytes",
        "first_loss", "last_loss", "non_finite_losses")}))
    print(json.dumps(result), flush=True)
    if a.rehearse_cpu:
        print(NOT_A_CHIP_RUN, flush=True)


if __name__ == "__main__":
    main()
