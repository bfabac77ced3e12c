#!/usr/bin/env python3
"""chip_smoke.py — proof that the framework's main path runs on the TPU.

One command, no arguments:  python3 chip_smoke.py   (through the chip tool).
It drives train -> export -> inference through the entry points a user calls
(``tos.run`` -> node process -> ``ctx.make_mesh`` -> ``ctx.get_data_feed`` ->
``dp.make_batch_iterator`` -> jitted step -> ``checkpoint.export_bundle`` ->
``cluster.inference``), at full width, on whatever the host exposes: one chip
or the four chips of one host (``dp`` over all of them, same per-chip batch).

Four clusters run in sequence, one node process each that computes; the
sequence itself proves the chip is handed from one process to the next:

1. train    ResNet-50 (bf16, 224x224, 256 images/chip), InputMode.DIRECT over
            TFRecord shards written here from a seed; falling finite loss;
            the chief exports a bundle and reference logits for phase 3.
2. lm       the bench-width decoder LM (d_model 1024, 8 heads x 128, 8
            layers, vocab 32k, seq 2048, 8 sequences/chip, bf16) fed
            STREAMING; the compiled step's HLO must contain the Pallas
            ``tpu_custom_call`` on PER-CHIP operands, and the kernel must
            agree with ``mha_reference`` (forward and gradients).
3. infer    ``inference.bundle_inference_loop`` over the phase-1 bundle, two
            batches of 64 through ``cluster.inference``: exact count, order,
            finite logits that match the trainer's reference logits.
4. roles    a trainer beside an evaluator sidecar and an ingest worker on the
            same host: only the trainer may hold the chip.

The driver process (this file's ``main``) never imports jax: one process owns
the chip.  Every node is started with ``JAX_PLATFORMS=tpu`` so it cannot fall
back to the CPU.  Any failed check raises — no phase is wrapped in try/except
— and the exit code is non-zero with no result line.  On success the last
two lines of stdout are JSON objects: the summary (per-phase seconds, cache
entries, ``"claim": null``), then, as the LAST line, the verdict and nothing
else:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The numbers it prints (compile and step seconds, peak HBM) are evidence that
the run happened on the named device; they are not a benchmark baseline.

``--rehearse-cpu`` is a debugging aid for boxes without a chip: tiny sizes on
CPU devices, every line tagged as not a chip run, ``"ok": false`` in the
summary.  It is never the default and proves nothing about the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")   # small: comes back
WORK_DIR = os.path.join(HERE, ".chip_smoke_work")           # large: stays there
SEED = 0

FULL = {
    "platform": "tpu",
    "image_size": 224, "images_per_chip": 256, "num_classes": 1000,
    "train_batches_per_epoch": 2, "train_epochs": 3,
    "lm": {"vocab_size": 32000, "d_model": 1024, "n_layers": 8, "n_heads": 8,
           "bf16": True, "attn_impl": "auto"},
    "seq_len": 2048, "seqs_per_chip": 8, "lm_steps": 5,
    "attn_shape": (8, 2048, 8, 128), "attn_impl": "pallas",
    "infer_batch": 64, "reference_rows": 4,
}
REHEARSAL = {
    "platform": "cpu",
    "image_size": 32, "images_per_chip": 4, "num_classes": 1000,
    "train_batches_per_epoch": 2, "train_epochs": 3,
    "lm": {"vocab_size": 512, "d_model": 64, "n_layers": 2, "n_heads": 2,
           "bf16": True, "attn_impl": "auto"},
    "seq_len": 128, "seqs_per_chip": 2, "lm_steps": 5,
    "attn_shape": (2, 128, 2, 32), "attn_impl": "pallas_interpret",
    "infer_batch": 8, "reference_rows": 4,
}

_TAG = ""


def say(msg: str) -> None:
    print(f"{_TAG}{msg}", flush=True)


def fail(msg: str):
    raise SystemExit(f"{_TAG}chip_smoke: FAILED — {msg}")


# ---------------------------------------------------------------------------
# Data made from the seed (driver and nodes call the same functions).
# ---------------------------------------------------------------------------

def train_image(rng, label: int, size: int):
    """uint8 image whose brightness depends on its label, so a few SGD steps
    can lower the loss (10 of the 1000 classes are in use)."""
    import numpy as np

    return (rng.randint(0, 128, (size, size, 3)) + 12 * label).astype(np.uint8)


def infer_rows(n: int, size: int):
    """float32 images in [0, 1): the inference inputs, and (their first rows)
    the trainer's reference inputs."""
    import numpy as np

    rng = np.random.RandomState(SEED + 1)
    return [rng.rand(size, size, 3).astype(np.float32) for _ in range(n)]


def write_train_shards(data_dir: str, n_images: int, size: int,
                       n_shards: int = 4) -> None:
    import numpy as np

    from tensorflowonspark_tpu import dfutil, tfrecord

    os.makedirs(data_dir, exist_ok=True)
    rng = np.random.RandomState(SEED)
    per = n_images // n_shards
    for si in range(n_shards):
        def gen():
            for j in range(per):
                label = (si * per + j) % 10
                yield dfutil.to_example({
                    "image": train_image(rng, label, size).tobytes(),
                    "label": label})
        tfrecord.write_records(
            os.path.join(data_dir, f"part-{si:05d}.tfrecord"), gen())


# ---------------------------------------------------------------------------
# Node side: helpers shared by the map_funs (run inside the node process).
# ---------------------------------------------------------------------------

def device_facts() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_facts() -> list[dict] | None:
    """Per-device HBM in use / peak, where the backend reports it."""
    import jax

    stats = [d.memory_stats() for d in jax.devices()]
    if any(s is None for s in stats):
        return None
    return [{"bytes_in_use": int(s["bytes_in_use"]),
             "peak_bytes_in_use": int(s["peak_bytes_in_use"])} for s in stats]


class StallWatch:
    """Longest stretch in which no other Python thread got to run, and what
    the map_fun was doing then.  The node's heartbeat thread is one of those
    threads: the driver declares a node dead after 12 s of silence, so a
    long native call that keeps the interpreter lock is a liveness hazard."""

    def __init__(self):
        import threading

        self.stage = "start"
        self.worst = (0.0, self.stage)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="stall-watch")
        self._thread.start()

    def _run(self) -> None:
        last = time.monotonic()
        while not self._stop.wait(0.1):
            now = time.monotonic()
            if now - last - 0.1 > self.worst[0]:
                self.worst = (now - last - 0.1, self.stage)
                if self.worst[0] > 2.0:  # into the node log: survives a kill
                    print(f"stall-watch: other threads stalled "
                          f"{self.worst[0]:.1f}s during {self.stage}",
                          flush=True)
            last = now

    def report(self) -> dict:
        self._stop.set()
        self._thread.join(5.0)
        return {"secs": round(self.worst[0], 2), "during": self.worst[1]}


def program_bytes(compiled) -> dict | None:
    """What the compiled step itself needs on each device (XLA's own
    accounting): its temporaries are not buffers the allocator statistics
    in ``memory_facts`` ever see."""
    m = compiled.memory_analysis()
    if m is None:
        return None
    return {"arguments": int(m.argument_size_in_bytes),
            "outputs": int(m.output_size_in_bytes),
            "aliased": int(m.alias_size_in_bytes),
            "temporaries": int(m.temp_size_in_bytes)}


def check_placement(tree, mesh, what: str, batch_dim: int | None = None) -> None:
    """Every leaf lives on every device of the mesh; a batch is split evenly
    along its leading dim."""
    import jax

    want = set(mesh.devices.flat)
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        have = set(leaf.sharding.device_set)
        if have != want:
            raise AssertionError(
                f"{what}{jax.tree_util.keystr(path)} lives on {len(have)} of "
                f"{len(want)} devices")
        if batch_dim is not None:
            shapes = {s.data.shape[0] for s in leaf.addressable_shards}
            if shapes != {batch_dim // len(want)}:
                raise AssertionError(
                    f"{what}{jax.tree_util.keystr(path)} shard sizes {shapes}, "
                    f"want {batch_dim // len(want)} rows on each device")


def check_memory_balance(mem: list[dict] | None) -> float | None:
    """max/min of bytes in use across devices (None: not reported)."""
    if not mem or len(mem) == 1:
        return None
    used = [m["bytes_in_use"] for m in mem]
    ratio = max(used) / max(1, min(used))
    if ratio > 1.10:
        raise AssertionError(f"HBM in use differs across devices: {used}")
    return round(ratio, 4)


def timed_steps(compiled, state, batches, min_steps: int):
    """Run the compiled step over ``batches``.  Each step is timed twice from
    the same start: to ``block_until_ready`` and on to a host fetch of the
    loss — if the first returned before the device finished, the fetch would
    carry the rest of the step."""
    import jax

    losses, block_secs, fetch_secs, ends = [], [], [], []
    for batch, _n in batches:
        t0 = time.perf_counter()
        state, metrics = compiled(state, batch)
        jax.block_until_ready((state, metrics))
        t1 = time.perf_counter()
        loss = float(metrics["loss"])
        t2 = time.perf_counter()
        losses.append(loss)
        block_secs.append(t1 - t0)
        fetch_secs.append(t2 - t0)
        ends.append(t2)
    if len(losses) < min_steps:
        raise AssertionError(f"only {len(losses)} steps ran, need {min_steps}")
    # end-to-end seconds per warm iteration: the step AND the wait for the
    # feed to hand over the next batch
    iter_secs = (ends[-1] - ends[0]) / (len(ends) - 1)
    return state, batch, losses, block_secs, fetch_secs, iter_secs


def step_report(compiled, compile_secs, losses, block_secs, fetch_secs,
                iter_secs) -> dict:
    import math

    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    warm_block = sorted(block_secs[1:])[len(block_secs[1:]) // 2]
    warm_fetch = sorted(fetch_secs[1:])[len(fetch_secs[1:]) // 2]
    return {
        "steps": len(losses),
        "losses": [round(x, 4) for x in losses],
        "compile_secs": round(compile_secs, 3),
        "first_step_secs": round(compile_secs + fetch_secs[0], 3),
        "warm_step_secs_block_until_ready": round(warm_block, 5),
        "warm_step_secs_loss_fetch": round(warm_fetch, 5),
        "warm_iteration_secs_with_feed_wait": round(iter_secs, 5),
        "program_bytes": program_bytes(compiled),
        # honest when the fetch after the block adds next to nothing
        "block_until_ready_honest": bool(
            warm_fetch - warm_block < max(0.002, 0.05 * warm_fetch)),
    }


# ---------------------------------------------------------------------------
# Phase 1 — ResNet-50: DIRECT TFRecord feed -> bn train step -> export.
# ---------------------------------------------------------------------------

def train_resnet(args, ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflowonspark_tpu import checkpoint, dfutil, tfrecord
    from tensorflowonspark_tpu.models import resnet
    from tensorflowonspark_tpu.parallel import dp as dplib

    watch = StallWatch()
    size = args["image_size"]
    mesh = ctx.make_mesh(dp=-1)
    global_batch = args["images_per_chip"] * mesh.size
    watch.stage = "model init"
    config = {"model": "resnet50", "num_classes": args["num_classes"],
              "bf16": True}
    model = resnet.build_resnet50(config)
    variables = resnet.init_variables(model, jax.random.PRNGKey(SEED), size)
    # a fifth of the bench's rate: from a cold start 0.1 overshoots after
    # the first steps, and this run asserts the loss falls
    optimizer = optax.sgd(0.02, momentum=0.9, nesterov=True)
    state = dplib.BNTrainState.create(
        dplib.replicate(variables["params"], mesh),
        dplib.replicate(variables["batch_stats"], mesh), optimizer)
    del variables
    base_loss = resnet.make_loss_fn(model, weight_decay=1e-4)

    def loss_fn(params, batch_stats, batch):
        # uint8 -> float on the chip: the host never touches a float image
        image = batch["image"].astype(jnp.float32) / 255.0
        return base_loss(params, batch_stats,
                         {"image": image, "label": batch["label"]})

    step_fn = dplib.make_bn_train_step(loss_fn, optimizer)

    def to_arrays(rows):
        return {
            "image": np.stack([
                np.frombuffer(r["image"][0], np.uint8).reshape(size, size, 3)
                for r in rows]),
            "label": np.asarray([r["label"][0] for r in rows], np.int32),
        }

    feed = ctx.get_data_feed(decode=lambda rec: dfutil.from_example(
        rec, binary_features={"image"}))
    batches = dplib.make_batch_iterator(feed, global_batch, to_arrays,
                                        mesh=mesh)
    watch.stage = "first batch"
    first = next(batches)
    watch.stage = "step compile"
    t0 = time.perf_counter()
    compiled = step_fn.lower(state, first[0]).compile()
    compile_secs = time.perf_counter() - t0

    def all_batches():
        yield first
        yield from batches

    watch.stage = "steps"
    state, batch, *timings = timed_steps(compiled, state, all_batches(),
                                         min_steps=4)
    report = step_report(compiled, compile_secs, *timings)

    watch.stage = "checks and export"
    check_placement({"params": state.params, "opt": state.opt_state,
                     "batch_stats": state.batch_stats}, mesh, "state")
    check_placement(batch, mesh, "batch", batch_dim=global_batch)
    if {d.platform for d in batch["image"].devices()} != {args["platform"]}:
        raise AssertionError("batch did not land on the accelerator")
    mem = memory_facts()
    report["memory_balance_max_over_min"] = check_memory_balance(mem)

    host = {"params": jax.device_get(state.params),
            "batch_stats": jax.device_get(state.batch_stats)}
    if not any(np.any(x != 0) for x in jax.tree.leaves(host["batch_stats"])):
        raise AssertionError("batch_stats are all zero after training")
    if ctx.executor_id == 0:
        checkpoint.export_bundle(args["export_dir"], host, config)
    # reference logits for the first inference rows, from the live model
    x = np.stack(infer_rows(args["reference_rows"], size))
    ref = jax.jit(lambda v, x: model.apply(v, x, train=False))(host, x)
    ctx.update_meta({"smoke": {
        **report, "device": device_facts(), "memory": mem,
        "longest_thread_stall": watch.report(),
        "global_batch": global_batch, "tfrecord_native": tfrecord.NATIVE,
        "reference_logits": np.asarray(ref, np.float32).tolist(),
    }})


# ---------------------------------------------------------------------------
# Phase 2 — decoder LM: STREAMING feed -> train step with the Pallas kernel.
# ---------------------------------------------------------------------------

def kernel_operand_rows(hlo: str) -> list[int]:
    """Leading dim of every rank-3 array named on a ``tpu_custom_call``
    instruction line of compiled HLO (operands and results of the flash
    kernel are ``[batch*heads, seq, d_head]``)."""
    import re

    rows = []
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            rows += [int(m.group(1)) for m in re.finditer(
                r"(?:bf16|f32)\[(\d+),\d+,\d+\]", line)]
    return rows


def attention_agreement(mesh, shape, impl: str) -> dict:
    """``flash_attention`` against ``mha_reference`` at highest matmul
    precision, forward and gradients, batch split over the mesh (so on four
    chips the partitioned kernel path is the one checked)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tensorflowonspark_tpu.ops import attention as att

    b, s, h, d = shape
    b *= mesh.size
    rng = np.random.RandomState(SEED)
    sharding = NamedSharding(mesh, P(("dp", "fsdp"), None, None, None))
    # bf16-representable values: the kernel (bf16 in) and the reference
    # (float32 in, highest precision) see identical inputs
    q, k, v = (jax.device_put(
        jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16), sharding)
        for _ in range(3))
    w = jax.device_put(jnp.asarray(rng.randn(b, s, h, d), jnp.float32),
                       sharding)

    def scored(fn):
        # w is an ARGUMENT: closed over, its quarter-gigabyte would be baked
        # into every executable (and its cache entry) as a constant
        def f(q, k, v, w):
            out = fn(q, k, v).astype(jnp.float32)
            return jnp.sum(out * w), out
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True))

    def run_kernel(kv_offset):
        return scored(lambda q, k, v: att.flash_attention(
            q, k, v, causal=True, impl=impl, kv_offset=kv_offset))(q, k, v, w)

    def run_reference(kv_offset):
        f32 = [x.astype(jnp.float32) for x in (q, k, v)]
        with jax.default_matmul_precision("highest"):
            return scored(lambda q, k, v: att.mha_reference(
                q, k, v, causal=True, kv_offset=kv_offset))(*f32, w)

    def rel_err(a, b):
        a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
        return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))

    with jax.set_mesh(mesh):
        (_, out), grads = run_kernel(0)
        (_, ref), ref_grads = run_reference(0)
        # a fully-past KV chunk is entirely visible under the causal mask —
        # the offset contract ring attention composes chunks with
        (_, out_off), _ = run_kernel(-s)
        (_, ref_off), _ = run_reference(-s)
    errs = {"forward": rel_err(out, ref),
            "forward_kv_offset": rel_err(out_off, ref_off),
            "dq": rel_err(grads[0], ref_grads[0]),
            "dk": rel_err(grads[1], ref_grads[1]),
            "dv": rel_err(grads[2], ref_grads[2])}
    # bf16 outputs: 2^-8 relative rounding on values of order 1
    if not all(e < 0.03 for e in errs.values()):
        raise AssertionError(f"{impl} kernel disagrees with mha_reference "
                             f"at {[b, s, h, d]}: {errs}")
    return {"shape": [b, s, h, d], "impl": impl,
            "max_err_over_max_ref": {k: round(e, 5) for k, e in errs.items()}}


def train_lm(args, ctx):
    import jax
    import numpy as np
    import optax

    from tensorflowonspark_tpu.models import transformer as tfm
    from tensorflowonspark_tpu.parallel import dp as dplib

    watch = StallWatch()
    mesh = ctx.make_mesh(dp=-1)
    watch.stage = "attention agreement"
    agreement = attention_agreement(mesh, args["attn_shape"], args["attn_impl"])

    watch.stage = "model init"
    seq_len, per_chip = args["seq_len"], args["seqs_per_chip"]
    global_batch = per_chip * mesh.size
    model = tfm.build_transformer(args["lm"])
    # init outside the ambient mesh: a one-row batch does not split over dp
    params = model.init(jax.random.PRNGKey(SEED),
                        np.zeros((1, seq_len), np.int32))["params"]
    optimizer = optax.adamw(3e-4)
    state = dplib.TrainState.create(dplib.replicate(params, mesh), optimizer)
    del params
    step_fn = dplib.make_train_step(tfm.make_loss_fn(model), optimizer)

    feed = ctx.get_data_feed()
    batches = dplib.make_batch_iterator(
        feed, global_batch,
        lambda rows: {"input_ids": np.stack(rows).astype(np.int32)}, mesh=mesh)
    # ambient mesh: the model's sharding constraints and the flash kernel's
    # per-shard partitioning (ops/attention.py) both read it
    with jax.set_mesh(mesh):
        watch.stage = "first batch"
        first = next(batches)
        watch.stage = "step compile"
        t0 = time.perf_counter()
        compiled = step_fn.lower(state, first[0]).compile()
        compile_secs = time.perf_counter() - t0

        def all_batches():
            yield first
            yield from batches

        watch.stage = "steps"
        state, batch, *timings = timed_steps(compiled, state, all_batches(),
                                             min_steps=4)
    report = step_report(compiled, compile_secs, *timings)

    watch.stage = "checks"
    hlo = compiled.as_text()
    rows = kernel_operand_rows(hlo)
    per_chip_rows = per_chip * args["lm"]["n_heads"]
    report["hlo_tpu_custom_calls"] = hlo.count(
        'custom_call_target="tpu_custom_call"')
    report["kernel_operand_rows"] = sorted(set(rows))
    report["kernel_operand_rows_per_chip"] = per_chip_rows
    if args["platform"] == "tpu":
        if report["hlo_tpu_custom_calls"] < args["lm"]["n_layers"]:
            raise AssertionError(
                "the compiled LM step holds "
                f"{report['hlo_tpu_custom_calls']} tpu_custom_call(s); the "
                "Pallas kernel did not run in every layer")
        if not rows or set(rows) != {per_chip_rows}:
            raise AssertionError(
                f"the Pallas kernel's operands have {sorted(set(rows))} "
                f"batch*head rows; the per-chip shard is {per_chip_rows} "
                f"(global {per_chip_rows * mesh.size})")

    check_placement({"params": state.params, "opt": state.opt_state}, mesh,
                    "state")
    check_placement(batch, mesh, "batch", batch_dim=global_batch)
    mem = memory_facts()
    report["memory_balance_max_over_min"] = check_memory_balance(mem)
    ctx.update_meta({"smoke": {
        **report, "device": device_facts(), "memory": mem,
        "longest_thread_stall": watch.report(),
        "global_batch": global_batch, "attention_agreement": agreement,
    }})


# ---------------------------------------------------------------------------
# Phase 3 — inference over the exported bundle.
# ---------------------------------------------------------------------------

def infer_bundle(args, ctx):
    from tensorflowonspark_tpu import inference

    inference.bundle_inference_loop(args, ctx)
    ctx.update_meta({"smoke": {"device": device_facts(),
                               "memory": memory_facts()}})


# ---------------------------------------------------------------------------
# Phase 4 — one process per chip: sidecar roles must not touch the backend.
# ---------------------------------------------------------------------------

def roles_node(args, ctx):
    done_flag = args["done_flag"]
    if ctx.job_name == "evaluator":
        # stay alive while the trainer computes: had this process claimed
        # the chip at start-up, the trainer could not have it
        deadline = time.monotonic() + 600
        while not os.path.exists(done_flag) and time.monotonic() < deadline:
            if ctx.stop_requested.wait(0.2):
                break
        initialised = False
        if "jax" in sys.modules:
            from jax._src import xla_bridge

            initialised = xla_bridge.backends_are_initialized()
        ctx.update_meta({"smoke": {"backend_initialised": initialised}})
        return

    import jax
    import jax.numpy as jnp

    feed = ctx.get_data_feed()
    lengths = []
    while not feed.should_stop():
        lengths += [len(rec) for rec in feed.next_batch(16)]
    total = int(jax.jit(jnp.sum)(jnp.asarray(lengths, jnp.int32)))
    ctx.update_meta({"smoke": {"device": device_facts(),
                               "records": len(lengths), "bytes": total}})
    with open(done_flag, "w") as f:
        f.write("done")


# ---------------------------------------------------------------------------
# Driver side (never imports jax).
# ---------------------------------------------------------------------------

def await_device(cluster, platform: str, timeout: float = 600.0) -> dict:
    """The chief's ``device`` block from ``cluster_info()``, once the node
    has claimed its accelerator; exits if it found anything but ``platform``."""
    deadline = time.monotonic() + timeout
    while True:
        errors = cluster.coordinator.errors()
        if errors:
            last = " ".join(
                errors[0].get("traceback", "").strip().splitlines()[-1:])
            if "Unable to initialize backend" in last:
                fail(f"no {platform.upper()} here: the node was started with "
                     f"JAX_PLATFORMS={platform} and JAX could not initialise "
                     f"that platform ({last})")
            fail(f"the node failed before reporting its device: {last}")
        device = cluster.coordinator.cluster_info()[0].get("device") or {}
        if device.get("num_devices") is not None:
            break
        if not cluster.launcher.alive():
            fail("the node exited before reporting its device")
        if time.monotonic() > deadline:
            fail(f"the node reported no device within {timeout:.0f}s")
        time.sleep(0.2)
    if device["platform"] != platform:
        fail(f"needs platform {platform!r}; the node found platform "
             f"{device['platform']!r} ({device.get('device_kind')}, "
             f"{device['num_devices']} device(s))")
    return device


def run_phase(name: str, map_fun, args: dict, node_env: dict, drive,
              **run_kwargs):
    """One cluster: start, drive, shut down; returns (node metas, drive's
    result).  A failure anywhere stops every process the cluster started."""
    import tensorflowonspark_tpu as tos

    say(f"--- phase {name} ---")
    t0 = time.perf_counter()
    cluster = tos.run(map_fun, args, env=node_env,
                      log_dir=os.path.join(OUT_DIR, name), **run_kwargs)
    finished = False
    try:
        device = await_device(cluster, node_env["JAX_PLATFORMS"])
        say(f"{name}: cluster_info device: platform={device['platform']} "
            f"device_kind={device['device_kind']} "
            f"count={device['num_devices']} "
            f"(claimed after {time.perf_counter() - t0:.1f}s)")
        result = drive(cluster, device)
        finished = True
    finally:
        if not finished:
            cluster.launcher.terminate()
            cluster.coordinator.stop()
    # STREAMING train() returns once the rows are buffered on the node, which
    # may still be compiling: shutdown's default 120 s of patience before it
    # signals stop is shorter than a cold first compile on the chip
    cluster.shutdown(timeout=900.0)
    metas = cluster.coordinator.cluster_info()
    say(f"{name}: done in {time.perf_counter() - t0:.1f}s")
    return metas, result


def node_smoke(metas: list[dict], executor_id: int = 0) -> dict:
    smoke = metas[executor_id].get("smoke")
    if not smoke:
        fail(f"node {executor_id} published no result")
    return smoke


def peak_hbm(smoke: dict):
    mem = smoke.get("memory")
    return ([m["peak_bytes_in_use"] for m in mem] if mem
            else "not reported by this backend")


def say_steps(name: str, smoke: dict) -> None:
    say(f"{name}: compile {smoke['compile_secs']}s, first step (compile + "
        f"run) {smoke['first_step_secs']}s, warm step "
        f"{smoke['warm_step_secs_block_until_ready']}s by block_until_ready "
        f"vs {smoke['warm_step_secs_loss_fetch']}s by loss fetch "
        f"(block_until_ready honest: {smoke['block_until_ready_honest']}); "
        f"{smoke['steps']} steps, losses {smoke['losses']}")
    say(f"{name}: warm iteration with feed wait "
        f"{smoke['warm_iteration_secs_with_feed_wait']}s; global batch "
        f"{smoke['global_batch']}; allocator peak HBM bytes per device "
        f"{peak_hbm(smoke)} (in-use max/min across devices "
        f"{smoke['memory_balance_max_over_min']}); compiled step bytes per "
        f"device {smoke['program_bytes']}; longest stall of the node's "
        f"other threads {smoke['longest_thread_stall']}")


def verdict_line(ok: bool, device: dict) -> str:
    """The last line of stdout: exactly ``ok`` and the device as JAX reported
    it to the node (``device_facts``) — whoever runs this script reads that
    line and nothing else, so everything else goes on the lines before it."""
    return json.dumps({"ok": bool(ok), "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def main() -> None:
    global _TAG

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--rehearse-cpu", action="store_true",
        help="debugging aid: tiny sizes on CPU devices; NOT a chip run")
    opts = parser.parse_args()
    sizes = REHEARSAL if opts.rehearse_cpu else FULL
    if opts.rehearse_cpu:
        _TAG = "[CPU REHEARSAL - NOT A CHIP RUN] "
    node_env = {"JAX_PLATFORMS": sizes["platform"]}
    if opts.rehearse_cpu:
        node_env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

    import numpy as np

    import tensorflowonspark_tpu as tos
    from tensorflowonspark_tpu import tfrecord
    from xla_cache_bootstrap import enable_persistent_cache

    # Exported before tos.run so every node process inherits it.  Threshold 0:
    # every program is cached, so a second run adds no entry at all.
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    cache_dir = enable_persistent_cache()
    cache_before = cache_entries(cache_dir)
    say(f"compile cache: {cache_dir} ({cache_before} entries)")
    say(f"tfrecord.NATIVE (driver): {tfrecord.NATIVE}")
    if not tfrecord.NATIVE:
        fail("the native TFRecord codec did not build (g++); the smoke does "
             "not run on the pure-Python codec")
    shutil.rmtree(WORK_DIR, ignore_errors=True)
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    os.makedirs(WORK_DIR)
    os.makedirs(OUT_DIR)
    size = sizes["image_size"]
    export_dir = os.path.join(WORK_DIR, "bundle")

    # -- 1. train + export ---------------------------------------------------
    def drive_train(cluster, device):
        n_images = (sizes["images_per_chip"] * device["num_devices"]
                    * sizes["train_batches_per_epoch"])
        t0 = time.perf_counter()
        data_dir = os.path.join(WORK_DIR, "train_shards")
        write_train_shards(data_dir, n_images, size)
        say(f"train: wrote {n_images} uint8 {size}x{size} images as TFRecords "
            f"in {time.perf_counter() - t0:.1f}s")
        # one ledger partition per epoch: batches stay whole inside an epoch
        cluster.train(data_dir, num_epochs=sizes["train_epochs"],
                      num_partitions=1)
        return device

    metas, device = run_phase(
        "train", train_resnet, {**sizes, "export_dir": export_dir},
        node_env, drive_train, num_executors=1,
        input_mode=tos.InputMode.DIRECT)
    train = node_smoke(metas)
    say_steps("train", train)
    say(f"train: tfrecord.NATIVE (node): {train['tfrecord_native']}")
    if not train["tfrecord_native"]:
        fail("the node read TFRecords with the pure-Python codec")
    if not os.path.exists(os.path.join(export_dir, "bundle.json")):
        fail("the chief exported no bundle")

    # -- 2. LM step ----------------------------------------------------------
    def drive_lm(cluster, device):
        n_rows = (sizes["seqs_per_chip"] * device["num_devices"]
                  * sizes["lm_steps"])
        rng = np.random.RandomState(SEED)
        # 64 of the vocabulary's ids appear: learnable within a few steps
        rows = [rng.randint(0, 64, sizes["seq_len"]).astype(np.int32)
                for _ in range(n_rows)]
        cluster.train(tos.PartitionedDataset.from_iterable(rows, 1))

    metas, _ = run_phase(
        "lm", train_lm, sizes, node_env, drive_lm, num_executors=1,
        input_mode=tos.InputMode.STREAMING)
    lm = node_smoke(metas)
    say_steps("lm", lm)
    say(f"lm: tpu_custom_call instructions in the compiled step: "
        f"{lm['hlo_tpu_custom_calls']}; kernel operand batch*head rows "
        f"{lm['kernel_operand_rows']} (per-chip shard "
        f"{lm['kernel_operand_rows_per_chip']}, global "
        f"{lm['kernel_operand_rows_per_chip'] * lm['device']['count']})")
    say(f"lm: flash_attention vs mha_reference: {lm['attention_agreement']}")

    # -- 3. inference --------------------------------------------------------
    infer_batch = sizes["infer_batch"]
    rows = infer_rows(infer_batch, size)
    calls = []

    def drive_infer(cluster, device):
        # second batch = the first reversed: row order must survive the trip
        for batch in (rows, rows[::-1]):
            t0 = time.perf_counter()
            out = cluster.inference(batch)
            calls.append((time.perf_counter() - t0, out))

    metas, _ = run_phase(
        "infer", infer_bundle,
        {"export_dir": export_dir, "batch_size": infer_batch},
        node_env, drive_infer, num_executors=1,
        input_mode=tos.InputMode.STREAMING)
    infer = node_smoke(metas)
    (cold_secs, first), (warm_secs, second) = calls
    for out in (first, second):
        if len(out) != infer_batch:
            fail(f"inference returned {len(out)} results for {infer_batch} rows")
    logits = np.stack(first + second)
    if logits.shape != (2 * infer_batch, sizes["num_classes"]):
        fail(f"inference logits have shape {logits.shape}")
    if not np.isfinite(logits).all():
        fail("inference logits are not finite")
    scale = float(np.abs(logits).max())
    order_err = float(np.abs(np.stack(second)[::-1] - np.stack(first)).max())
    ref = np.asarray(train["reference_logits"], np.float32)
    ref_err = float(np.abs(logits[:len(ref)] - ref).max())
    spread = float(np.abs(logits[0] - logits[1]).max())
    say(f"infer: {len(logits)} results in order; first call (bundle load + "
        f"compile + score, driver clock) {cold_secs:.3f}s, second call "
        f"{warm_secs:.3f}s; max|logit| {scale:.4f}, reversed-batch mismatch "
        f"{order_err:.5f}, vs trainer's reference logits {ref_err:.5f}, "
        f"row0-vs-row1 difference {spread:.5f}; peak HBM bytes per device "
        f"{peak_hbm(infer)}")
    # bf16 activations, different batch sizes: agreement to 2% of the range
    if order_err > 0.02 * scale or ref_err > 0.02 * scale:
        fail("inference results are out of order or disagree with the "
             "trainer's reference logits")
    if not spread > 0:
        fail("inference returned the same logits for different rows")

    # -- 4. roles ------------------------------------------------------------
    n_records = 64

    def drive_roles(cluster, device):
        data_dir = os.path.join(WORK_DIR, "roles_shards")
        os.makedirs(data_dir)
        for si in range(2):
            tfrecord.write_records(
                os.path.join(data_dir, f"part-{si:05d}.tfrecord"),
                (b"x" * (100 + i) for i in range(n_records // 2)))
        cluster.train(data_dir)

    metas, _ = run_phase(
        "roles", roles_node,
        {"done_flag": os.path.join(WORK_DIR, "roles_trainer_done")},
        node_env, drive_roles, num_executors=2, eval_node=True,
        ingest_workers=1, input_mode=tos.InputMode.DIRECT)
    by_role = {m["job_name"]: m for m in metas}
    trainer = node_smoke(metas, by_role["chief"]["executor_id"])
    evaluator = node_smoke(metas, by_role["evaluator"]["executor_id"])
    say(f"roles: trainer on {trainer['device']} consumed "
        f"{trainer['records']} records via the ingest worker; evaluator "
        f"backend initialised: {evaluator['backend_initialised']}; device "
        f"blocks: " + ", ".join(
            f"{m['job_name']}={m['device']['platform']}" for m in metas))
    if trainer["records"] != n_records:
        fail(f"the trainer saw {trainer['records']} of {n_records} records")
    if evaluator["backend_initialised"]:
        fail("the evaluator sidecar initialised a JAX backend")
    if opts.rehearse_cpu:
        say("roles: on CPU the env pins every node's device block, so the "
            "sidecars' blocks say nothing here")
    elif any(by_role[r]["device"]["num_devices"] for r in ("evaluator",
                                                           "ingest")):
        fail("a sidecar role reports accelerator devices")

    # -- summary -------------------------------------------------------------
    if "jax" in sys.modules:
        fail("the driver process imported jax")
    devices = {json.dumps(p["device"], sort_keys=True)
               for p in (train, lm, infer, trainer)}
    if len(devices) != 1:
        fail(f"phases ran on different devices: {sorted(devices)}")
    for name, phase in (("train", train), ("lm", lm)):
        if not phase["block_until_ready_honest"]:
            fail(f"{name}: block_until_ready returned before the step ended")
    cache_after = cache_entries(cache_dir)
    say(f"compile cache: {cache_after} entries, {cache_after - cache_before} "
        "new in this run")
    step_keys = ("compile_secs", "first_step_secs",
                 "warm_step_secs_block_until_ready",
                 "warm_step_secs_loss_fetch",
                 "warm_iteration_secs_with_feed_wait", "global_batch")
    phases = {
        "train": {k: train[k] for k in step_keys},
        "lm": {k: lm[k] for k in (
            *step_keys, "hlo_tpu_custom_calls", "kernel_operand_rows")},
        "infer": {"first_call_secs": round(cold_secs, 3),
                  "second_call_secs": round(warm_secs, 3)},
    }
    summary = {
        "ok": not opts.rehearse_cpu,
        "device": train["device"],
        "phases": phases,
        "cache_dir": cache_dir,
        "cache_entries_new": cache_after - cache_before,
        "tfrecord_native": True,
    }
    if opts.rehearse_cpu:
        summary["not_a_chip_run"] = True
    summary["claim"] = None
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
        json.dump({**summary, "train": train, "lm": lm, "infer": infer,
                   "roles": {"trainer": trainer, "evaluator": evaluator}},
                  f, indent=1, sort_keys=True)
    print(_TAG.strip() if opts.rehearse_cpu else "chip_smoke: all phases passed",
          flush=True)
    print(json.dumps(summary), flush=True)
    print(verdict_line(summary["ok"], summary["device"]), flush=True)


if __name__ == "__main__":
    main()
