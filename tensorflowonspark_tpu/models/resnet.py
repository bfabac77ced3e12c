"""ResNet family (ResNet-50 flagship) — parity config 3 (BASELINE.json:9).

Reference: ``examples/imagenet/resnet`` ran TF-Keras ResNet-50 under
``MultiWorkerMirroredStrategy`` (NCCL all-reduce).  TPU-native redesign:

- bfloat16 activations / float32 params + batch stats — the MXU-friendly
  mixed-precision recipe (conv/matmul FLOPs run on the systolic array in
  bf16; the optimizer and normalization statistics stay in f32 for
  stability).
- NHWC layout (XLA:TPU's native conv layout; no transposes).
- Plain ``flax.linen.BatchNorm`` over the sharded batch axis: under
  ``jit`` + GSPMD a reduction over a dp-sharded axis compiles to a global
  (cross-replica) reduction over ICI automatically — the reference needed
  SyncBatchNorm machinery for this; here it falls out of the sharding.
- No data-dependent control flow; static shapes throughout, so the whole
  train step compiles to one XLA program.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from tensorflowonspark_tpu.models.registry import register
from tensorflowonspark_tpu.parallel.dp import accuracy, cross_entropy_loss


class BottleneckBlock(nn.Module):
    """1x1 -> 3x3 -> 1x1 bottleneck with projection shortcut (v1.5: stride
    on the 3x3, matching the reference Keras application and modern recipes)."""

    filters: int
    strides: int = 1
    compute_dtype: Any = jnp.bfloat16
    norm_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool):
        conv = partial(nn.Conv, use_bias=False, dtype=self.compute_dtype)
        # Norm activations in bf16 (halves the HBM traffic of the most
        # bandwidth-bound op in the net); the batch mean/var reductions and
        # the running stats stay f32 inside flax regardless of this dtype.
        norm = partial(
            nn.BatchNorm, use_running_average=not train, momentum=0.9,
            epsilon=1e-5, dtype=self.norm_dtype,
        )
        residual = x
        y = conv(self.filters, (1, 1))(x)
        y = nn.relu(norm()(y))
        y = conv(self.filters, (3, 3), strides=(self.strides, self.strides))(y)
        y = nn.relu(norm()(y))
        y = conv(self.filters * 4, (1, 1))(y)
        # Zero-init the last BN scale: residual branches start as identity,
        # which stabilises large-batch training (the standard TPU recipe).
        y = norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = conv(self.filters * 4, (1, 1),
                            strides=(self.strides, self.strides))(residual)
            residual = norm()(residual)
        return nn.relu(residual + y.astype(residual.dtype))


class ResNet(nn.Module):
    stage_sizes: Sequence[int] = (3, 4, 6, 3)  # ResNet-50
    num_classes: int = 1000
    width: int = 64
    compute_dtype: Any = jnp.bfloat16
    norm_dtype: Any = jnp.bfloat16
    # "imagenet": 7x7/2 stem + 3x3/2 maxpool (224px inputs);
    # "cifar": 3x3/1 stem, no pool (32px inputs — the reference's cifar10
    # example family, ``examples/cifar10``).
    stem: str = "imagenet"

    @nn.compact
    def __call__(self, x, train: bool = False):
        # one named scope per stage, so that a trace viewer (and a later
        # reduction) finds where the device time goes, whatever XLA fuses
        with jax.named_scope("stem"):
            x = x.astype(self.compute_dtype)
            if self.stem == "cifar":
                x = nn.Conv(self.width, (3, 3), use_bias=False,
                            dtype=self.compute_dtype, name="conv_init")(x)
            else:
                x = nn.Conv(self.width, (7, 7), strides=(2, 2), use_bias=False,
                            dtype=self.compute_dtype, name="conv_init")(x)
            x = nn.BatchNorm(use_running_average=not train, momentum=0.9,
                             epsilon=1e-5, dtype=self.norm_dtype,
                             name="bn_init")(x)
            x = nn.relu(x)
            if self.stem != "cifar":
                x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for stage, size in enumerate(self.stage_sizes):
            with jax.named_scope(f"stage{stage + 1}"):
                for block in range(size):
                    strides = 2 if stage > 0 and block == 0 else 1
                    x = BottleneckBlock(self.width * (2 ** stage), strides,
                                        self.compute_dtype,
                                        self.norm_dtype)(x, train=train)
        with jax.named_scope("head"):
            x = jnp.mean(x, axis=(1, 2))  # global average pool
            return nn.Dense(self.num_classes, dtype=jnp.float32,
                            name="head")(x)


def _dtypes(config: dict) -> dict:
    bf16 = config.get("bf16", True)
    return {
        "compute_dtype": jnp.bfloat16 if bf16 else jnp.float32,
        "norm_dtype": jnp.bfloat16 if bf16 and config.get("bf16_norm", True)
                      else jnp.float32,
    }


@register("resnet50")
def build_resnet50(config: dict) -> ResNet:
    return ResNet(
        stage_sizes=(3, 4, 6, 3),
        num_classes=config.get("num_classes", 1000),
        width=config.get("width", 64),
        stem=config.get("stem", "imagenet"),
        **_dtypes(config),
    )


@register("resnet18")
def build_resnet18(config: dict) -> ResNet:
    """Smaller sibling for tests/CI (same code path, 4x fewer blocks)."""
    return ResNet(
        stage_sizes=(2, 2, 2, 2),
        num_classes=config.get("num_classes", 1000),
        width=config.get("width", 64),
        **_dtypes(config),
    )


@register("resnet_cifar")
def build_resnet_cifar(config: dict) -> ResNet:
    """CIFAR-size ResNet (bottleneck, 3x3 stem, no maxpool) — the TPU
    counterpart of the reference's ``examples/cifar10`` model family.
    ``depth_blocks`` n gives 9n+2 layers (default n=3 → ResNet-29)."""
    n = config.get("depth_blocks", 3)
    return ResNet(
        stage_sizes=(n, n, n),
        num_classes=config.get("num_classes", 10),
        width=config.get("width", 16),
        stem="cifar",
        **_dtypes(config),
    )


def init_variables(model: ResNet, rng: jax.Array, image_size: int = 224):
    """Init {'params', 'batch_stats'} with a single dummy image (jitted,
    see ``registry.jit_init``)."""
    from tensorflowonspark_tpu.models.registry import jit_init

    dummy = jnp.zeros((1, image_size, image_size, 3), jnp.float32)
    return jit_init(model, rng, dummy, train=True)


def make_loss_fn(model: ResNet, weight_decay: float = 1e-4):
    """Loss over (params, batch_stats) with BN-stat mutation.

    Returns ``loss_fn(params, batch_stats, batch) -> (loss, (new_stats, aux))``
    suitable for ``make_bn_train_step``.
    """

    def loss_fn(params, batch_stats, batch):
        logits, mutated = model.apply(
            {"params": params, "batch_stats": batch_stats},
            batch["image"], train=True, mutable=["batch_stats"],
        )
        loss = cross_entropy_loss(logits, batch["label"])
        # L2 on conv/dense kernels only (standard recipe: no decay on BN).
        l2 = sum(jnp.sum(jnp.square(p)) for p in jax.tree.leaves(params)
                 if p.ndim > 1)
        loss = loss + weight_decay * 0.5 * l2
        return loss, (mutated["batch_stats"], {"accuracy": accuracy(logits, batch["label"])})

    return loss_fn


def synthetic_imagenet(n: int, image_size: int = 224, num_classes: int = 1000,
                       seed: int = 0) -> list[tuple[np.ndarray, int]]:
    """Deterministic synthetic images for hermetic benchmarks/tests."""
    rng = np.random.RandomState(seed)
    return [
        (rng.rand(image_size, image_size, 3).astype(np.float32), int(i % num_classes))
        for i in range(n)
    ]


def batch_to_arrays(items: list) -> dict:
    images = np.stack([np.asarray(img, np.float32) for img, _ in items])
    labels = np.asarray([l for _, l in items], np.int32)
    return {"image": images, "label": labels}
