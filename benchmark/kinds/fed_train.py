"""The general generator for fed training: ``cluster.train`` keeps a node's
feed full while its map_fun steps for the window.

A traffic mix of this kind is a data file (``traffic/<name>.json``):

    input_mode   "direct"  — seeded TFRecord shards, the node reads them
                 "streaming" — seeded rows, the driver streams them
    records, shards | partitions, epochs   how much is offered (more than
                 any window consumes; the node ends the job)
    warm_steps   steps after the first, before the window
    trace_seconds  how long the profiler is on in a ``--trace 1`` run
    trace_options  fields of ``jax.profiler.ProfileOptions`` for that trace
    + whatever the configuration reads (``seq_len``, ``rows_per_chip``)

What a row IS belongs to the configuration (``configs/<name>.py``:
``train_records``, ``feed_options``, ``rows_to_arrays``).  The path is the
one a user takes: ``tos.run`` -> node -> ``ctx.make_mesh`` ->
``ctx.get_data_feed`` -> ``dp.make_batch_iterator`` -> the jitted step.
"""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import time

from benchmark import common

SPAN_NAMES = ("feed_wait", "step_dispatch", "fetch")
WINDOW_SPAN = "traced_window"
IN_FLIGHT = 2    # steps dispatched ahead of the last loss fetched


# ---------------------------------------------------------------------------
# Driver side (never imports jax).
# ---------------------------------------------------------------------------

def cluster_options(cell: dict) -> dict:
    import tensorflowonspark_tpu as tos

    mode = {"direct": tos.InputMode.DIRECT,
            "streaming": tos.InputMode.STREAMING}[cell["traffic"]["input_mode"]]
    return {"num_executors": 1, "input_mode": mode}


def prepare(cell: dict, opts: dict) -> dict:
    """Make the inputs from the seed while the node claims its chip."""
    traffic = cell["traffic"]
    config_mod = common.load_module("configs", cell["config_name"],
                                    cell["base"])
    rng = common.seeded_rng(opts["seed"], "records")
    if traffic["input_mode"] == "streaming":
        rows = config_mod.train_records(cell["config"], traffic, rng,
                                        int(traffic["records"]))
        return {"rows": rows}
    # DIRECT: one directory per (mix, configuration), holding ONE seed's
    # shards; the same seed finds them again, another seed replaces them
    data_dir = os.path.join(opts["work_dir"], "records",
                            f"{cell['traffic_name']}.{cell['config_name']}")
    stamp = {"seed": opts["seed"], "records": traffic["records"],
             "shards": traffic["shards"], "config": cell["config"]}
    stamp_path = os.path.join(data_dir, "stamp.json")
    if os.path.exists(stamp_path) and common.read_json(stamp_path) == stamp:
        return {"path": data_dir, "written": False}
    from tensorflowonspark_tpu import tfrecord

    shutil.rmtree(data_dir, ignore_errors=True)
    os.makedirs(data_dir)
    n, shards = int(traffic["records"]), int(traffic["shards"])
    records = config_mod.train_records(cell["config"], traffic, rng, n)
    per = n // shards
    for si in range(shards):
        tfrecord.write_records(
            os.path.join(data_dir, f"part-{si:05d}.tfrecord"),
            (next(records) for _ in range(per)))
    with open(stamp_path, "w") as f:
        json.dump(stamp, f)
    return {"path": data_dir, "written": True}


def drive(cluster, cell: dict, plan: dict, opts: dict) -> None:
    """Offer the data.  STREAMING: returns when the node has ended the job;
    DIRECT: returns once the shard paths are queued (``shutdown`` waits)."""
    import tensorflowonspark_tpu as tos

    traffic = cell["traffic"]
    epochs = int(traffic["epochs"])
    if traffic["input_mode"] == "streaming":
        data = tos.PartitionedDataset.from_iterable(
            plan["rows"], int(traffic["partitions"]))
        cluster.train(data, num_epochs=epochs)
    else:
        cluster.train(plan["path"], num_epochs=epochs)


# ---------------------------------------------------------------------------
# Node side.
# ---------------------------------------------------------------------------

def node(args: dict, ctx) -> None:
    import jax

    from tensorflowonspark_tpu import telemetry
    from tensorflowonspark_tpu.parallel import dp as dplib

    cell, opts = args["cell"], args["opts"]
    cfg, traffic = cell["config"], cell["traffic"]
    config_mod = common.load_module("configs", cell["config_name"],
                                    cell["base"])
    compiles = common.CompileCounter()
    spans = common.Spans()
    clock = time.perf_counter
    seconds = {}

    @contextlib.contextmanager
    def timed(name):
        t0 = clock()
        yield
        seconds[name] = clock() - t0

    # the reference check first: its programs and arrays are gone before
    # the training state fills the chip
    with timed("check_s"):
        check = config_mod.check_train(cfg, traffic, opts["seed"])
    print(f"bench: reference check {json.dumps(check)}", flush=True)

    mesh = ctx.make_mesh(dp=-1)
    with timed("build_s"):
        built = config_mod.build_train(cfg, traffic, mesh, opts["seed"])
        state = jax.block_until_ready(built["state"])
    rows_per_step = built["rows_per_step"]
    samples_per_step = rows_per_step * built["samples_per_row"]

    feed = ctx.get_data_feed(**config_mod.feed_options(
        cfg, traffic["input_mode"]))
    batches = dplib.make_batch_iterator(
        feed, rows_per_step, config_mod.rows_to_arrays(cfg), mesh=mesh)
    losses: list[float] = []
    result: dict = {}
    # ambient mesh: the model's sharding constraints and the flash kernel's
    # per-shard partitioning (ops/attention.py) both read it
    with jax.set_mesh(mesh):
        with timed("first_batch_s"):
            batch, _n = next(batches)
        with timed("first_step_s"):      # compile-or-load, then one step
            compiled = built["step_fn"].lower(state, batch).compile()
            state, metrics = compiled(state, batch)
            losses.append(float(metrics["loss"]))
        with timed("warm_s"):
            for _ in range(int(traffic["warm_steps"])):
                batch, _n = next(batches)
                state, metrics = compiled(state, batch)
            losses.append(float(metrics["loss"]))

        def window(duration: float) -> dict:
            """Step for ``duration`` seconds.  At most IN_FLIGHT steps run
            ahead of the last loss fetched; the window ends when the last
            step dispatched has finished (``block_until_ready``)."""
            nonlocal state
            in_flight, steps, out_of_data = [], 0, False
            dispatched_s: list[float] = []
            spans.reset()
            before = telemetry.snapshot()
            compiled_before = compiles.count
            epoch0, t0 = time.time(), clock()
            while clock() - t0 < duration:
                with spans.span("feed_wait"):
                    item = next(batches, None)
                if item is None:
                    out_of_data = True
                    break
                with spans.span("step_dispatch"):
                    state, metrics = compiled(state, item[0])
                in_flight.append(metrics["loss"])
                steps += 1
                dispatched_s.append(clock() - t0)
                if len(in_flight) > IN_FLIGHT:
                    with spans.span("fetch"):
                        losses.append(float(in_flight.pop(0)))
            with spans.span("fetch"):
                jax.block_until_ready(state)
            t1 = clock()
            losses.extend(float(x) for x in in_flight)
            return {"epoch_start": epoch0, "window_s": t1 - t0, "steps": steps,
                    "out_of_data": out_of_data,
                    # when each step's dispatch returned: with steps in
                    # flight and a host that feeds, the intervals are the
                    # feed's; a reader can look for stalls in them
                    "dispatched_s": dispatched_s,
                    "compilations": compiles.count - compiled_before,
                    "span_seconds": dict(spans.totals),
                    "span_counts": dict(spans.counts),
                    "counters": common.counter_delta(before,
                                                     telemetry.snapshot())}

        if opts["trace"]:
            trace_dir = os.path.join(opts["run_dir"], "trace")
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            for key, value in traffic.get("trace_options", {}).items():
                setattr(options, key, value)
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                traced = window(float(traffic["trace_seconds"]))
            jax.profiler.stop_trace()
            result["traced"] = traced
            result["reduced_trace"] = reduce_trace(trace_dir, opts["run_dir"])
            measured = window(max(1.0, opts["seconds"]
                                  - float(traffic["trace_seconds"])))
        else:
            measured = window(float(opts["seconds"]))
    # end the job: the driver's train() returns, shutdown() finds us done
    feed.terminate()
    batches.close()

    import math

    bad = sum(1 for x in losses if not math.isfinite(x))
    failed = bad + (1 if measured["out_of_data"] else 0)
    program = common.program_bytes(compiled)
    allocator_peak = common.allocator_peak_bytes()
    result.update({
        "device": common.device_facts(),
        "check": check,
        "measured": measured,
        "seconds": seconds,
        "samples_per_step": samples_per_step,
        "rows_per_step": rows_per_step,
        "chips": mesh.size,
        "attempted": measured["steps"],
        "failed": failed,
        "non_finite_losses": bad,
        "first_loss": losses[0], "last_loss": losses[-1],
        "program_bytes": program,
        "allocator_peak_bytes": allocator_peak,
        # the allocator's peak leaves the program's temporaries out on this
        # runtime (PR 21: 0.48 GB beside 9.1 GB), so the two are added
        "memory_peak_bytes": (allocator_peak or 0)
        + (program["temporaries"] if program else 0),
        "finished_epoch": time.time(),
    })
    ctx.update_meta({"bench": result})


def reduce_trace(trace_dir: str, run_dir: str) -> str | None:
    """Reduce the trace here, in the process that took it; the summary goes
    to a file the driver reads (it is too long for the node's metadata)."""
    from benchmark import trace_reduce

    path = common.find_xplane(trace_dir)
    if path is None:
        return None
    summary = trace_reduce.summarize(trace_reduce.load(path), SPAN_NAMES,
                                     WINDOW_SPAN)
    if summary is None:
        return None
    out = os.path.join(run_dir, "reduced_trace.json")
    with open(out, "w") as f:
        json.dump(summary, f)
    return out


# ---------------------------------------------------------------------------
# Driver side again: from what the node published to the run's facts.
# ---------------------------------------------------------------------------

def facts(cell: dict, node_result: dict, opts: dict) -> dict:
    """The run's facts, which the end-to-end metrics and every layer-metric
    reader are computed from."""
    config_mod = common.load_module("configs", cell["config_name"],
                                    cell["base"])
    m = node_result["measured"]
    chips = node_result["chips"]
    rate = m["steps"] * node_result["samples_per_step"] / m["window_s"] / chips
    out = {
        "sample_unit": config_mod.SAMPLE_UNIT,
        "rate_per_chip": rate,
        "window_s": m["window_s"],
        "steps": m["steps"],
        "chips": chips,
        "samples_per_step": node_result["samples_per_step"],
        "rows_per_step": node_result["rows_per_step"],
        "flops_per_sample": config_mod.flops_per_sample(cell["config"],
                                                        cell["traffic"]),
        "window_epoch_start": m["epoch_start"],
        "dispatched_s": m.get("dispatched_s", []),
        "compilations": m["compilations"],
        "node_seconds": node_result["seconds"],
        "kernels": {},
    }
    for name, cost in getattr(config_mod, "KERNELS", {}).items():
        out["kernels"][name] = cost(cell["config"], cell["traffic"],
                                    node_result["rows_per_step"] // chips)
    if "traced" in node_result:
        out["traced_steps"] = node_result["traced"]["steps"]
        out["window_epoch_start"] = node_result["traced"]["epoch_start"]
        out["compilations"] += node_result["traced"]["compilations"]
    return out


def end_to_end(cell: dict, run_facts: dict) -> dict:
    """This kind's end-to-end number: samples whose step finished inside
    the window, per second per chip, under the name the unit implies.

    On N > 1 chips the same number also goes under ``..._dp<N>``, so that
    BENCHMARK.json can hold such a cell to a metric, and a bound, of its own:
    one node process feeding N chips is bounded by the host and repeats far
    less closely than a cell the device bounds (PERF.md, section 2)."""
    name = f"train_{run_facts['sample_unit']}_rate"
    out = {name: run_facts["rate_per_chip"]}
    if run_facts["chips"] > 1:
        out[f"{name}_dp{run_facts['chips']}"] = run_facts["rate_per_chip"]
    return out
