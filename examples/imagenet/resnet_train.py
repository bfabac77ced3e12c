"""ResNet-50 / ImageNet data-parallel training — parity config 3
(BASELINE.json:9: the reference ran TF-Keras ResNet-50 under
``MultiWorkerMirroredStrategy``, NCCL all-reduce, one executor per GPU).

TPU-native: one jitted SPMD train step over a ``(dp, fsdp)`` mesh; gradient
all-reduce and cross-replica BatchNorm fall out of GSPMD sharding.  Uses
synthetic ImageNet-shaped data by default (the cell
``resnet50_train_tfrecord`` of BENCHMARK.json measures the same step, fed);
point --tfrecord-dir at real ImageNet
TFRecords to train on data read through the framework's TFRecord bridge.

  python resnet_train.py --steps 50 --batch 256
"""

from __future__ import annotations

import argparse
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--fsdp", type=int, default=1)
    p.add_argument("--model-dir", default="")
    p.add_argument("--tfrecord-dir", default="",
                   help="directory of ImageNet TFRecords (else synthetic)")
    p.add_argument("--profile-dir", default="")
    args = p.parse_args()

    import jax
    import numpy as np
    import optax

    from tensorflowonspark_tpu import profiling
    from tensorflowonspark_tpu.checkpoint import CheckpointManager
    from tensorflowonspark_tpu.models import resnet
    from tensorflowonspark_tpu.parallel import dp as dplib
    from tensorflowonspark_tpu.parallel import mesh as meshlib

    mesh = meshlib.make_mesh(dp=-1, fsdp=args.fsdp)
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"on {jax.default_backend()}")

    model = resnet.build_resnet50({"num_classes": args.num_classes, "bf16": True})
    variables = resnet.init_variables(model, jax.random.PRNGKey(0),
                                      args.image_size)
    optimizer = optax.sgd(args.lr, momentum=0.9, nesterov=True)

    params = meshlib.shard_tree(mesh, variables["params"])
    batch_stats = meshlib.shard_tree(
        mesh, variables["batch_stats"],
        jax.tree.map(lambda _: meshlib.replicated(mesh), variables["batch_stats"]))
    state = dplib.BNTrainState.create(params, batch_stats, optimizer)

    ckpt = CheckpointManager(args.model_dir) if args.model_dir else None
    if ckpt is not None:
        # Full train state: a restart resumes with momentum and the true
        # step counter, not just weights (SURVEY.md §5.4).
        restored = ckpt.restore_latest(state._asdict())
        if restored is not None:
            tree, step_no = restored
            # Restore hands back host arrays; re-place every leaf under the
            # sharding the live state already has (fsdp params must go back
            # sharded, not materialize full-size on every device).
            placed = jax.tree.map(
                lambda x, live: jax.device_put(np.asarray(x), live.sharding),
                tree, state._asdict())
            state = dplib.BNTrainState(**placed)
            print(f"restored checkpoint at step {step_no}")

    step_fn = dplib.make_bn_train_step(
        resnet.make_loss_fn(model, weight_decay=1e-4), optimizer)

    if args.tfrecord_dir:
        # Rows with 'image' (float list, H*W*3) and 'label' (int) features,
        # as written by dfutil.save_as_tfrecords — the reference's TFRecord
        # path (parity config 2 uses the same bridge for MNIST).
        from tensorflowonspark_tpu import dfutil

        dataset, _ = dfutil.load_tfrecords(args.tfrecord_dir)
        shape = (args.image_size, args.image_size, 3)

        def batch_stream():
            rows = []
            while True:  # cycle the dataset forever
                for row in dataset:
                    rows.append(row)
                    if len(rows) == args.batch:
                        yield {
                            "image": np.stack([
                                np.asarray(r["image"], np.float32)
                                .reshape(shape) for r in rows]),
                            "label": np.asarray(
                                [r["label"] for r in rows], np.int32),
                        }
                        rows = []

        batches = batch_stream()
    else:
        rng = np.random.RandomState(0)
        fixed = {
            "image": rng.rand(args.batch, args.image_size, args.image_size, 3)
                        .astype(np.float32),
            "label": (np.arange(args.batch) % args.num_classes).astype(np.int32),
        }
        batches = iter(lambda: fixed, None)

    with mesh:
        it = iter(batches)

        def one_step():
            nonlocal state
            batch = meshlib.shard_batch(mesh, next(it))
            state, m = step_fn(state, batch)
            return m

        metrics = one_step()  # compile + warmup: outside the timed window
        print(f"step 0: loss={float(metrics['loss']):.4f}")
        t0 = time.perf_counter()
        if args.profile_dir:
            metrics = profiling.profile_steps(args.profile_dir, one_step,
                                              warmup=0, steps=args.steps)
        else:
            for _ in range(args.steps):
                metrics = one_step()
        loss = float(metrics["loss"])
        dt = time.perf_counter() - t0
        imgs = args.batch * args.steps / dt
        print(f"step {args.steps}: loss={loss:.4f} "
              f"({imgs:,.0f} images/sec, {imgs / mesh.size:,.0f}/chip)")
        if ckpt is not None:
            ckpt.save(int(jax.device_get(state.step)),
                      jax.device_get(state)._asdict())
            ckpt.wait()
            print("checkpoint saved")


if __name__ == "__main__":
    main()
