"""Phi-3-mini-4k-instruct at its published widths, depth cut to 4 layers.

The system under test is the program's ``models/transformer.py`` (the only
decoder block it has: MHA, RoPE, RMSNorm, SwiGLU, no biases, untied head —
what Phi-3-mini computes) through ``parallel/dp.py``'s ``make_train_step``;
the Pallas flash-attention forward of ``ops/attention.py`` runs in every
layer.  See ``resnet50.py`` for the names a configuration module provides.
"""

from __future__ import annotations

import math

SAMPLE_UNIT = "tok"


def system_config(cfg: dict) -> dict:
    """The published keys, as the program's builder names them."""
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("models/transformer.py computes MHA only")
    out = {"model": "transformer",
           "vocab_size": cfg["vocab_size"], "d_model": cfg["hidden_size"],
           "n_layers": cfg["num_hidden_layers"],
           "n_heads": cfg["num_attention_heads"],
           "d_head": cfg["hidden_size"] // cfg["num_attention_heads"],
           "d_ff": cfg["intermediate_size"], "rope_theta": cfg["rope_theta"],
           "bf16": True}
    if "attn_impl" in cfg:
        out["attn_impl"] = cfg["attn_impl"]
    return out


# ---------------------------------------------------------------------------
# Operations and bytes, from shapes (2 per multiply-add; no recompute).
# ---------------------------------------------------------------------------

def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matrix multiplication per token: the
    four attention projections, the three SwiGLU matrices, the head.  The
    embedding is a lookup."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    per_layer = 4 * d * d + 3 * d * ff
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def attention_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward QK^T and PV of one layer for one token under a causal mask:
    on average half of ``seq_len`` keys, 2 matmuls, 2 FLOPs per MAC."""
    return 2 * 2 * cfg["hidden_size"] * seq_len / 2


def flops_per_sample(cfg: dict, traffic: dict) -> float:
    """Training FLOPs per token: 6 per matmul weight (forward 2, backward
    4), and three times the forward attention."""
    seq_len = int(traffic["seq_len"])
    return (6.0 * matmul_params(cfg)
            + 3.0 * cfg["num_hidden_layers"]
            * attention_flops_per_token(cfg, seq_len))


def flash_fwd_cost(cfg: dict, traffic: dict, rows_on_device: int) -> dict:
    """FLOPs and HBM bytes the forward attention kernel NEEDS for one call
    (one layer, this device's rows): causal, so half the score matrix; it
    reads q, k, v once and writes o (bf16) and the log-sum-exp (float32)."""
    s = int(traffic["seq_len"])
    h = cfg["num_attention_heads"]
    dh = cfg["hidden_size"] // h
    bh = rows_on_device * h
    flops = bh * (2 * 2 * s * s * dh) / 2
    bytes_ = bh * (4 * s * dh * 2 + s * 4)
    return {"flops": float(flops), "bytes": float(bytes_)}


KERNELS = {"flash_fwd": flash_fwd_cost}


# ---------------------------------------------------------------------------
# Inputs from the seed (driver side: numpy only).
# ---------------------------------------------------------------------------

def train_records(cfg: dict, traffic: dict, rng, n: int):
    """``n`` rows of ``seq_len`` token ids, uniform over the vocabulary."""
    import numpy as np

    ids = rng.integers(0, cfg["vocab_size"], (n, int(traffic["seq_len"])),
                       dtype=np.int32)
    return [ids[i] for i in range(n)]


# ---------------------------------------------------------------------------
# Node side.
# ---------------------------------------------------------------------------

def feed_options(cfg: dict, input_mode: str) -> dict:
    return {}


def rows_to_arrays(cfg: dict):
    import numpy as np

    return lambda rows: {"input_ids": np.stack(rows).astype(np.int32)}


def _model(cfg: dict):
    from tensorflowonspark_tpu.models import transformer as tfm

    return tfm, tfm.build_transformer(system_config(cfg))


def _init_params(cfg: dict, key):
    """Parameters from the key.  Initialised through a twin of the model
    with plain attention on 8 positions: the parameter shapes do not depend
    on the sequence, and the kernel need not compile to draw weights.
    ``key`` is an ARGUMENT of every jitted caller: a seed closed over would
    be a constant of the program, and every new seed a new compile."""
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import transformer as tfm

    twin = tfm.build_transformer({**system_config(cfg), "attn_impl": "xla"})
    return twin.init(key, jnp.zeros((1, 8), jnp.int32))["params"]


def build_train(cfg: dict, traffic: dict, mesh, seed: int) -> dict:
    import jax
    import optax

    from tensorflowonspark_tpu.parallel import dp as dplib
    from tensorflowonspark_tpu.parallel import mesh as meshlib

    tfm, model = _model(cfg)
    optimizer = optax.adamw(cfg["optimizer"]["learning_rate"])
    state = jax.jit(
        lambda key: dplib.TrainState.create(_init_params(cfg, key), optimizer),
        out_shardings=meshlib.replicated(mesh))(jax.random.PRNGKey(seed))
    return {"state": state,
            "step_fn": dplib.make_train_step(tfm.make_loss_fn(model),
                                             optimizer),
            "rows_per_step": int(traffic["rows_per_chip"]) * mesh.size,
            "samples_per_row": int(traffic["seq_len"])}


def check_train(cfg: dict, traffic: dict, seed: int) -> dict:
    """System against the plain float32 reference on a short batch: logits,
    loss, and the gradient norm of every parameter leaf."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    tfm, model = _model(cfg)
    loss_fn = tfm.make_loss_fn(model)
    b, s = cfg["reference_tokens"]
    ids = jnp.asarray(np.random.default_rng([seed, 78]).integers(
        0, cfg["vocab_size"], (b, s)), jnp.int32)

    def leaf_norms(grads):
        return jax.tree.map(
            lambda g: jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32)))),
            grads)

    def system(params, ids):
        (loss, _aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, {"input_ids": ids})
        return loss, model.apply({"params": params}, ids), leaf_norms(grads)

    def reference(params, ids):
        def f(params):
            logits = reference_forward(cfg, params, ids)
            return reference_loss(logits, ids), logits
        (loss, logits), grads = jax.value_and_grad(f, has_aux=True)(params)
        return loss, logits, leaf_norms(grads)

    params = jax.jit(lambda key: _init_params(cfg, key))(
        jax.random.PRNGKey(seed))
    sys_loss, sys_logits, sys_norms = jax.jit(system)(params, ids)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_logits, ref_norms = jax.jit(reference)(params, ids)
    ref_logits = np.asarray(ref_logits, np.float32)
    diff = np.asarray(sys_logits, np.float32) - ref_logits
    norm_errs = jax.tree.leaves(jax.tree.map(
        lambda a, b: abs(float(a) - float(b)) / max(float(b), 1e-30),
        sys_norms, ref_norms))
    errors = {
        "loss": abs(float(sys_loss) - float(ref_loss)) / abs(float(ref_loss)),
        "logits_l2": float(np.linalg.norm(diff) / np.linalg.norm(ref_logits)),
        "logits_max": float(np.abs(diff).max() / np.abs(ref_logits).max()),
        "leaf_grad_norm_max": float(max(norm_errs)),
    }
    return {"errors": errors, "tolerance": TOLERANCE,
            "ok": all(errors[k] < TOLERANCE[k] for k in errors)}


# bf16 matmuls and a bf16 residual stream (2^-8 = 0.4% per rounding) over 4
# layers against float32 at "highest" precision.  Measured on the chip at
# full width (PR 22, five seeds): loss 0.0001-0.0002 (a mean over 511
# positions averages the roundings out), logits 0.049-0.050 in relative L2
# norm and 0.053-0.062 of the largest logit at the worst of 16 million values,
# largest error of a leaf's gradient norm 0.038.  The limits leave a factor of
# two to three.  A wrong RoPE pairing, mask, norm or a dropped projection
# moves logits by more than half; the eps departure (1e-6 vs 1e-5) by ~1e-5.
TOLERANCE = {"loss": 0.005, "logits_l2": 0.12, "logits_max": 0.15,
             "leaf_grad_norm_max": 0.10}


# ---------------------------------------------------------------------------
# The plain reference: float32 jax.numpy, from the published description
# (Phi-3 technical report, arXiv:2404.14219, and the HF modelling code's
# equations: pre-norm RMSNorm, rotate-half RoPE, causal softmax attention,
# SwiGLU, untied head).
# ---------------------------------------------------------------------------

def _rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * scale


def _rope(x, theta: float):
    """Rotate-half RoPE on ``[B, S, H, D]``: pairs (i, i + D/2) turn by
    ``position * theta^(-2i/D)``."""
    import jax.numpy as jnp

    s, d = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def reference_forward(cfg: dict, params, ids):
    import jax
    import jax.numpy as jnp

    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = cfg["num_attention_heads"]
    dh = cfg["hidden_size"] // h
    s = ids.shape[1]
    x = params["embed"]["embedding"][ids]
    causal = jnp.tril(jnp.ones((s, s), bool))
    for layer in range(cfg["num_hidden_layers"]):
        p = params[f"block_{layer}"]
        a = p["attn"]
        y = _rms_norm(x, p["attn_norm"]["scale"], eps)
        q = _rope(jnp.einsum("bsd,dhk->bshk", y, a["q_proj"]["kernel"]), theta)
        k = _rope(jnp.einsum("bsd,dhk->bshk", y, a["k_proj"]["kernel"]), theta)
        v = jnp.einsum("bsd,dhk->bshk", y, a["v_proj"]["kernel"])
        scores = jnp.einsum("bqhk,bthk->bhqt", q, k) / math.sqrt(dh)
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        out = jnp.einsum("bhqt,bthk->bqhk", jax.nn.softmax(scores, -1), v)
        x = x + jnp.einsum("bqhk,hkd->bqd", out, a["o_proj"]["kernel"])
        m = p["mlp"]
        y = _rms_norm(x, p["mlp_norm"]["scale"], eps)
        gate = jax.nn.silu(y @ m["gate_proj"]["kernel"])
        x = x + (gate * (y @ m["up_proj"]["kernel"])) @ m["down_proj"]["kernel"]
    x = _rms_norm(x, params["final_norm"]["scale"], eps)
    return x @ params["lm_head"]["kernel"]


def reference_loss(logits, ids):
    """Mean next-token negative log-likelihood (the last position predicts
    nothing)."""
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(logits[:, :-1])
    nll = -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll)
