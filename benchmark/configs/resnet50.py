"""ResNet-50 (He et al. 2015, arXiv:1512.03385, Table 1; v1.5 strides).

What the harness takes from a configuration module, all by these names:

* driver side (no jax): ``train_records``;
* node side: ``feed_options``, ``rows_to_arrays``, ``build_train``,
  ``check_train``;
* yardstick: ``flops_per_sample`` (and ``KERNELS`` for configurations whose
  step runs named kernels), ``reference_*`` — the plain float32 ``jax.numpy``
  network written from the paper, independent of ``models/resnet.py``.

The system under test is the program's own ``models/resnet.py`` and
``parallel/dp.py``; nothing here is used by the program.
"""

from __future__ import annotations

SAMPLE_UNIT = "img"


def _arch(cfg: dict) -> dict:
    return cfg["architecture"]


# ---------------------------------------------------------------------------
# Operations, from shapes (2 per multiply-add; no recompute).
# ---------------------------------------------------------------------------

def conv_shapes(cfg: dict) -> list[tuple[int, int, int, int, int]]:
    """Every convolution of the forward pass as ``(out_h, out_w, k, c_in,
    c_out)``, then the classifier as a 1x1 'convolution' on one position."""
    a = _arch(cfg)
    size, width, exp = a["image_size"], a["width"], a["bottleneck_expansion"]
    shapes = []
    h = -(-size // 2)                       # 7x7 stride 2, SAME
    shapes.append((h, h, 7, 3, width))
    h = -(-h // 2)                          # 3x3 stride 2 max pool
    c_in = width
    for stage, blocks in enumerate(a["stage_sizes"]):
        f = width * 2 ** stage
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            h_out = -(-h // stride)
            shapes.append((h, h, 1, c_in, f))             # 1x1 reduce
            shapes.append((h_out, h_out, 3, f, f))        # 3x3 (carries the stride)
            shapes.append((h_out, h_out, 1, f, f * exp))  # 1x1 expand
            if block == 0:                                # projection shortcut
                shapes.append((h_out, h_out, 1, c_in, f * exp))
            c_in, h = f * exp, h_out
    shapes.append((1, 1, 1, c_in, a["num_classes"]))
    return shapes


def forward_macs(cfg: dict) -> int:
    return sum(h * w * k * k * ci * co for h, w, k, ci, co in conv_shapes(cfg))


def flops_per_sample(cfg: dict, traffic: dict | None = None) -> float:
    """Training FLOPs for one image: forward, and a backward of twice the
    forward (gradients to inputs and to weights)."""
    return 3.0 * 2.0 * forward_macs(cfg)


# ---------------------------------------------------------------------------
# Inputs from the seed (driver side: numpy only).
# ---------------------------------------------------------------------------

def train_records(cfg: dict, traffic: dict, rng, n: int):
    """``n`` TFRecord payloads: a uint8 image and a label each.  One bulk
    draw, so writing 4096 records costs a second or two, not a minute."""
    import numpy as np

    from tensorflowonspark_tpu import dfutil

    size = _arch(cfg)["image_size"]
    pixels = rng.integers(0, 256, (n, size * size * 3), dtype=np.uint8)
    labels = rng.integers(0, _arch(cfg)["num_classes"], n)
    for i in range(n):
        yield dfutil.to_example({"image": pixels[i].tobytes(),
                                 "label": int(labels[i])})


# ---------------------------------------------------------------------------
# Node side: the system under test, through the program's own modules.
# ---------------------------------------------------------------------------

def feed_options(cfg: dict, input_mode: str) -> dict:
    from tensorflowonspark_tpu import dfutil

    if input_mode != "direct":
        return {}
    return {"decode": lambda rec: dfutil.from_example(
        rec, binary_features={"image"})}


def rows_to_arrays(cfg: dict):
    import numpy as np

    size = _arch(cfg)["image_size"]

    def to_arrays(rows):
        return {
            "image": np.stack([
                np.frombuffer(r["image"][0], np.uint8).reshape(size, size, 3)
                for r in rows]),
            "label": np.asarray([r["label"][0] for r in rows], np.int32),
        }
    return to_arrays


def _model_and_loss(cfg: dict):
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import registry, resnet

    model = registry.build(dict(cfg["system"]))
    base_loss = resnet.make_loss_fn(
        model, weight_decay=cfg["optimizer"]["weight_decay"])

    def loss_fn(params, batch_stats, batch):
        # uint8 -> float on the chip: the host never touches a float image
        image = batch["image"].astype(jnp.float32) / 255.0
        return base_loss(params, batch_stats,
                         {"image": image, "label": batch["label"]})
    return model, loss_fn


def _optimizer(cfg: dict):
    import optax

    o = cfg["optimizer"]
    return optax.sgd(o["learning_rate"], momentum=o["momentum"],
                     nesterov=o["nesterov"])


def _init_variables(cfg: dict, model, key):
    """``key`` is an ARGUMENT of every jitted caller: a seed closed over
    would be a constant of the program, and every new seed a new compile."""
    import jax.numpy as jnp

    size = _arch(cfg)["image_size"]
    dummy = jnp.zeros((1, size, size, 3), jnp.float32)
    return model.init(key, dummy, train=True)


def build_train(cfg: dict, traffic: dict, mesh, seed: int) -> dict:
    """State on the mesh in ONE jitted call from the seed (no host copy),
    and the program's jitted BN train step."""
    import jax

    from tensorflowonspark_tpu.parallel import dp as dplib
    from tensorflowonspark_tpu.parallel import mesh as meshlib

    model, loss_fn = _model_and_loss(cfg)
    optimizer = _optimizer(cfg)

    def init(key):
        variables = _init_variables(cfg, model, key)
        return dplib.BNTrainState.create(
            variables["params"], variables["batch_stats"], optimizer)

    state = jax.jit(init, out_shardings=meshlib.replicated(mesh))(
        jax.random.PRNGKey(seed))
    return {"state": state,
            "step_fn": dplib.make_bn_train_step(loss_fn, optimizer),
            "rows_per_step": int(cfg["images_per_chip"]) * mesh.size,
            "samples_per_row": 1}


def _check_variables(cfg: dict, model, key):
    """Seeded variables for the reference check.  flax zero-initialises the
    last BatchNorm scale of every block, which would hide each residual
    branch from the comparison; the check sets every scale to one."""
    import jax
    import jax.numpy as jnp

    variables = _init_variables(cfg, model, key)
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.ones_like(x)
        if getattr(path[-1], "key", None) == "scale" else x,
        variables["params"])
    return {"params": params, "batch_stats": variables["batch_stats"]}


def _check_batch(cfg: dict, seed: int, n: int):
    import numpy as np

    size = _arch(cfg)["image_size"]
    rng = np.random.default_rng([seed, 77])
    return {"image": rng.integers(0, 256, (n, size, size, 3), dtype=np.uint8),
            "label": rng.integers(0, _arch(cfg)["num_classes"],
                                  n).astype(np.int32)}


def check_train(cfg: dict, traffic: dict, seed: int) -> dict:
    """System (bf16, ``models/resnet.py``) against the plain float32
    reference on a few images: train-mode logits, loss, gradient norm."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    model, loss_fn = _model_and_loss(cfg)
    n = int(cfg["reference_images"])
    batch = _check_batch(cfg, seed, n)

    def system(variables, batch):
        def f(params):
            loss, (_stats, _aux) = loss_fn(params, variables["batch_stats"],
                                           batch)
            return loss
        loss, grads = jax.value_and_grad(f)(variables["params"])
        image = batch["image"].astype(jnp.float32) / 255.0
        logits, _ = model.apply(variables, image, train=True,
                                mutable=["batch_stats"])
        return loss, logits, _global_norm(grads)

    def reference(variables, batch):
        def f(params):
            logits = reference_forward(cfg, params, batch["image"])
            return reference_loss(cfg, params, logits, batch["label"]), logits
        (loss, logits), grads = jax.value_and_grad(f, has_aux=True)(
            variables["params"])
        return loss, logits, _global_norm(grads)

    variables = jax.jit(lambda key: _check_variables(cfg, model, key))(
        jax.random.PRNGKey(seed))
    sys_loss, sys_logits, sys_norm = jax.jit(system)(variables, batch)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_logits, ref_norm = jax.jit(reference)(variables, batch)
    ref_logits = np.asarray(ref_logits, np.float32)
    diff = np.asarray(sys_logits, np.float32) - ref_logits
    errors = {
        "loss": abs(float(sys_loss) - float(ref_loss)) / abs(float(ref_loss)),
        "logits_l2": float(np.linalg.norm(diff) / np.linalg.norm(ref_logits)),
        "logits_max": float(np.abs(diff).max() / np.abs(ref_logits).max()),
        "grad_norm": abs(float(sys_norm) - float(ref_norm)) / float(ref_norm),
    }
    return {"errors": errors, "tolerance": TOLERANCE,
            "ok": all(errors[k] < TOLERANCE[k] for k in errors)}


# The system computes in bf16 (8 bits of mantissa: 2^-8 = 0.4% per rounding)
# through 50 layers against float32 at "highest" precision.  Measured on the
# chip at full width (PR 22, six seeds): loss 0.0004-0.007, logits 0.107-0.113
# in relative L2 norm and 0.10-0.126 of the largest logit at the worst of 8000
# values (16 residual blocks with every scale at one compound the roundings),
# gradient norm 0.0002-0.024.  The limits leave a factor of two to four, so
# that no seed fails by rounding.  A wrong stride, padding, epsilon or a
# missing layer moves the logits by more than half and the loss by percents;
# a float32 run of the system sits at 1e-5 on all four (tests/benchmark).
TOLERANCE = {"loss": 0.03, "logits_l2": 0.25, "logits_max": 0.30,
             "grad_norm": 0.10}


def _global_norm(tree):
    import jax
    import jax.numpy as jnp

    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


# ---------------------------------------------------------------------------
# The plain reference: float32 jax.numpy / lax, from the paper.
# ---------------------------------------------------------------------------

def _conv(x, kernel, stride: int):
    import jax

    return jax.lax.conv_general_dilated(
        x, kernel, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _batch_norm(x, p, eps: float):
    """Train mode: normalise by the batch's own mean and (biased) variance."""
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x), axis=(0, 1, 2)) - jnp.square(mean)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def reference_forward(cfg: dict, params, image):
    """ResNet-50 train-mode forward in float32 on uint8 pixels.  ``params``
    is read by the names flax gives the program's model; the arithmetic is
    written from the paper."""
    import jax
    import jax.numpy as jnp

    a = _arch(cfg)
    eps = a["batch_norm"]["epsilon"]
    x = image.astype(jnp.float32) / 255.0
    x = _conv(x, params["conv_init"]["kernel"], 2)
    x = jax.nn.relu(_batch_norm(x, params["bn_init"], eps))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    index = 0
    for stage, blocks in enumerate(a["stage_sizes"]):
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            p = params[f"BottleneckBlock_{index}"]

            def conv_bn(x, i, stride=1):
                return _batch_norm(_conv(x, p[f"Conv_{i}"]["kernel"], stride),
                                   p[f"BatchNorm_{i}"], eps)

            y = jax.nn.relu(conv_bn(x, 0))
            y = jax.nn.relu(conv_bn(y, 1, stride))
            y = conv_bn(y, 2)
            if "Conv_3" in p:               # projection shortcut
                x = conv_bn(x, 3, stride)
            x = jax.nn.relu(x + y)
            index += 1
    x = jnp.mean(x, axis=(1, 2))
    return x @ params["head"]["kernel"] + params["head"]["bias"]


def reference_loss(cfg: dict, params, logits, labels):
    """Mean softmax cross-entropy plus L2 (half the squared norm, times the
    weight decay) on convolution and classifier kernels, not on BatchNorm."""
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(logits)
    xent = -jnp.mean(logp[jnp.arange(labels.shape[0]), labels])
    l2 = sum(jnp.sum(jnp.square(p)) for p in jax.tree.leaves(params)
             if p.ndim > 1)
    return xent + cfg["optimizer"]["weight_decay"] * 0.5 * l2
