"""SDAR-30B-A3B-Chat trained by block diffusion at its published widths: one
chip's share of an 8-way expert-parallel stage, depth cut to 4 layers.

The system under test is the program's ``models/transformer.py`` with what
SDAR needs of it: grouped-query heads (32 query heads over 4 K/V heads),
Qwen3's per-head QK-norm, dropless top-8-of-128 routing with renormalised
weights of which this chip holds experts 0-15 (``parallel/ep.py``), and the
block-diffusion loss (``make_block_diffusion_loss_fn``: one pass over the
noised and the clean copy of a row under the block-diffusion mask of
``ops/attention.py``) through ``parallel/dp.py``'s ``make_train_step``.  See
``resnet50.py`` for the names a configuration module provides.

Sizes the public config does not give (the JSON file's ``assumed`` says why
each): the placement of QK-norm, ``block_length`` 4, the noise law and its
``noise_level_min``, ``mask_token_id``, no shift between input and target,
the loss's normaliser, ``router_aux_loss_coef``, the learning rate, one
noise word a row, and the three scales of the seeded state
(``seeded_state``: the embedding's and the mask token's standard deviation,
the QK-norm scales) that make seeded routing load the held experts as a
checkpoint's does.
"""

from __future__ import annotations

import math

SAMPLE_UNIT = "tok"


def system_config(cfg: dict) -> dict:
    """The published keys, as the program's builder names them."""
    out = {"model": "transformer",
           "vocab_size": cfg["vocab_size"], "d_model": cfg["hidden_size"],
           "n_layers": cfg["num_hidden_layers"],
           "n_heads": cfg["num_attention_heads"],
           "n_kv_heads": cfg["num_key_value_heads"],
           "d_head": cfg["head_dim"],
           "d_ff": cfg["moe_intermediate_size"],
           "n_experts": cfg["router_experts"],
           "moe_held": cfg["experts_held"],
           "moe_top_k": cfg["num_experts_per_tok"],
           "moe_capacity_factor": None,            # dropless
           "moe_norm_topk_prob": cfg["norm_topk_prob"],
           "qk_norm": cfg["qk_norm"],
           "qk_norm_per_head": cfg["qk_norm_per_head"],
           "norm_eps": cfg["rms_norm_eps"],
           "rope_theta": cfg["rope_theta"], "bf16": True}
    for key in ("attn_impl", "bf16"):       # the rehearsal's and the tests'
        if key in cfg:
            out[key] = cfg[key]
    return out


# ---------------------------------------------------------------------------
# Operations and bytes, from shapes (2 per multiply-add; no recompute).  A
# counted sample is one token id of a row; the device runs two positions for
# it (its noised and its clean copy).
# ---------------------------------------------------------------------------

def visible_pairs(length: int, block: int) -> int:
    """(query, key) pairs the block-diffusion mask leaves visible over the
    ``2 * length`` positions of one row: each of the ``n`` blocks sees itself
    noised (``block²``), as a noised query the clean blocks before it, and as
    a clean query the clean blocks up to itself: ``block² · n · (n + 1)``."""
    n = length // block
    return block * block * n * (n + 1)


def _per_position_params(cfg: dict) -> int:
    """Weights every POSITION multiplies in one layer: the four attention
    projections at 32 query and 4 K/V heads, and the router."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    q = cfg["num_attention_heads"] * dh
    kv = cfg["num_key_value_heads"] * dh
    return 2 * d * q + 2 * d * kv + d * cfg["router_experts"]


def held_pairs_per_position(cfg: dict) -> float:
    """EXPECTED pairs a position sends to the experts held here: its 8
    choices spread evenly over the router's 128 experts, 16 of them here."""
    first, end = cfg["experts_held"]
    return cfg["num_experts_per_tok"] * (end - first) / cfg["router_experts"]


def flops_per_sample(cfg: dict, traffic: dict) -> float:
    """Training FLOPs this chip must do per counted token: 6 per matmul
    weight (forward 2, backward 4) for both copies through the projections,
    the router and the expected held pairs' experts, for the noised copy
    alone through the head (over the held slice of the vocabulary); and
    three times the forward attention over the visible pairs."""
    d, length = cfg["hidden_size"], int(traffic["seq_len"])
    expert = 3 * d * cfg["moe_intermediate_size"]
    per_position = (_per_position_params(cfg)
                    + held_pairs_per_position(cfg) * expert)
    attention = (2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]
                 * visible_pairs(length, cfg["block_length"]) / length)
    return (cfg["num_hidden_layers"] * (6.0 * 2 * per_position
                                        + 3.0 * attention)
            + 6.0 * d * cfg["vocab_size"])


def flash_fwd_cost(cfg: dict, traffic: dict, rows_on_device: int) -> dict:
    """FLOPs and HBM bytes the forward attention kernel NEEDS for one call
    (one layer, this device's rows): QK^T and PV over the VISIBLE pairs of
    the mask, all query heads; it reads q once, k and v at the 4 K/V heads
    once, and writes o (bf16) and the log-sum-exp (float32)."""
    length = int(traffic["seq_len"])
    h, h_kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    flops = (rows_on_device * 2 * 2 * h * dh
             * visible_pairs(length, cfg["block_length"]))
    positions = rows_on_device * 2 * length
    bytes_ = positions * (2 * h * dh * 2 + 2 * h_kv * dh * 2 + h * 4)
    return {"flops": float(flops), "bytes": float(bytes_)}


def moe_experts_cost(cfg: dict, traffic: dict, rows_on_device: int) -> dict:
    """FLOPs and HBM bytes the EXPECTED held pairs need in the expert
    matmuls of one STEP (all layers, forward and backward), counted as
    ``olmoe_1b_7b_d1.py`` counts its routed pairs: three ``d x f`` matrices
    a pair, forward once and backward twice; bytes, bf16: inputs, outputs and
    the held experts' weights forward, inputs, cotangents and weights in and
    two cotangents out backward."""
    pairs = (rows_on_device * 2 * int(traffic["seq_len"])
             * held_pairs_per_position(cfg))
    d, ff = cfg["hidden_size"], cfg["moe_intermediate_size"]
    first, end = cfg["experts_held"]
    weights = (end - first) * 3 * d * ff
    layers = cfg["num_hidden_layers"]
    flops = layers * 3 * 2 * pairs * 3 * d * ff
    bytes_ = layers * 2 * (5 * pairs * d + 3 * weights)
    return {"flops": float(flops), "bytes": float(bytes_)}


KERNELS = {"flash_fwd": flash_fwd_cost, "moe_experts": moe_experts_cost}


# ---------------------------------------------------------------------------
# Inputs from the seed (driver side: numpy only).
# ---------------------------------------------------------------------------

def train_records(cfg: dict, traffic: dict, rng, n: int):
    """``n`` rows of ``seq_len`` token ids, uniform over the held slice of
    the vocabulary without the mask id, each followed by its noise word (the
    bits of a uint32): the row's share of the run's seed, from which the
    loss draws the row's noise levels and masked tokens on the device."""
    import numpy as np

    ids = rng.integers(0, cfg["mask_token_id"], (n, int(traffic["seq_len"])),
                       dtype=np.int32)
    words = rng.integers(0, 2 ** 32, (n, 1), dtype=np.uint32).view(np.int32)
    rows = np.concatenate([ids, words], axis=1)
    return [rows[i] for i in range(n)]


# ---------------------------------------------------------------------------
# Node side.
# ---------------------------------------------------------------------------

def feed_options(cfg: dict, input_mode: str) -> dict:
    return {}


def rows_to_arrays(cfg: dict):
    import numpy as np

    def to_arrays(rows):
        rows = np.stack(rows).astype(np.int32)
        return {"input_ids": rows[:, :-1],
                "noise_seed": rows[:, -1].astype(np.uint32)}

    return to_arrays


def _model(cfg: dict):
    from tensorflowonspark_tpu.models import transformer as tfm

    model = tfm.build_transformer(system_config(cfg))
    # the builder ignores keys it does not know: a program from before these
    # existed would build an MHA model that holds 128 experts and trains on
    # the next token under SDAR's name.  It cannot run this configuration.
    lacking = [key for key in ("n_kv_heads", "qk_norm_per_head", "moe_held")
               if not hasattr(model, key)]
    if not hasattr(tfm, "make_block_diffusion_loss_fn"):
        lacking.append("make_block_diffusion_loss_fn")
    if lacking:
        raise NotImplementedError(
            f"models/transformer.py of this program has no {lacking}: it "
            "cannot build SDAR-30B-A3B or its block-diffusion loss")
    return tfm, model


def _loss_fn(tfm, model, cfg: dict):
    return tfm.make_block_diffusion_loss_fn(
        model, block=cfg["block_length"], mask_id=cfg["mask_token_id"],
        aux_loss_coef=cfg["router_aux_loss_coef"],
        vocab_chunk=int(cfg["vocab_chunk"]), t_min=cfg["noise_level_min"])


def _init_params(cfg: dict, key):
    """Parameters from the key, through a twin of the model with plain
    attention on 8 positions (see ``phi3_mini_d4.py``): parameter shapes do
    not depend on the sequence, and the program's initialisers are what a
    user's job draws from, but for three scales (``seeded_state`` in the
    JSON file, and why: a job that continues from a checkpoint starts with
    peaked attention and balanced routing, and the seeded state has to load
    the held experts as such a job does): the embedding's standard
    deviation, the mask token's, and the QK-norm scales."""
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import transformer as tfm

    twin = tfm.build_transformer({**system_config(cfg), "attn_impl": "xla"})
    params = twin.init(key, jnp.zeros((1, 8), jnp.int32))["params"]
    seeded = cfg["seeded_state"]
    # flax draws the embedding at 1 / sqrt(hidden) and the norms' scales at 1
    unit = params["embed"]["embedding"] * math.sqrt(cfg["hidden_size"])
    std = jnp.full((unit.shape[0], 1), seeded["embedding_std"]).at[
        cfg["mask_token_id"]].set(seeded["mask_embedding_std"])
    params["embed"]["embedding"] = unit * std
    for layer in range(cfg["num_hidden_layers"]):
        attn = params[f"block_{layer}"]["attn"]
        for name in ("q_norm", "k_norm"):
            attn[name]["scale"] = attn[name]["scale"] * seeded["qk_norm_scale"]
    return params


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
            "step_fn": dplib.make_train_step(_loss_fn(tfm, model, cfg),
                                             optimizer),
            "rows_per_step": int(traffic["rows_per_chip"]) * mesh.size,
            # the rate counts the row's ids, not the device's 2L positions:
            # the doubling is the model's cost
            "samples_per_row": int(traffic["seq_len"])}


def check_train(cfg: dict, traffic: dict, seed: int,
                degrade_system: bool = False) -> dict:
    """System against the plain float32 reference on ``reference_tokens``
    (the cell's own ``[1, 4096]`` ids -> 8192 positions, all layers): the
    loss with its auxiliary term, the logits of the noised copy, the norm of
    all gradients, and the routing over the held experts.

    The reference draws nothing: it is handed the noise levels and the
    masked tokens the system's own ``corrupt_blocks`` draws from the row's
    noise word, so both sides train on one corruption.

    Top-k is discontinuous (see ``olmoe_1b_7b_d1.py``): ``routing_agreement``
    is the share of the reference's (position, HELD expert) pairs the system
    also chose.  With four layers a flipped pair moves the residual stream
    of every later layer, so the logit error is not given apart for
    positions with a flip.

    What it cannot see: as the other configurations' checks, it compiles
    ``value_and_grad(loss_fn)`` of its own, not the ``make_train_step`` the
    window drives: the optimizer's update is held to a finite loss only.

    ``degrade_system`` is for setting the limits, not for a run: the system
    gets the parameters rounded to fp8 (``degraded_to_fp8``), the reference
    the true ones, and the result has to come out not ``ok``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    tfm, model = _model(cfg)
    loss_fn = _loss_fn(tfm, model, cfg)
    b, length = cfg["reference_tokens"]
    block = cfg["block_length"]
    rng = np.random.default_rng([seed, 78])
    ids = jnp.asarray(rng.integers(0, cfg["mask_token_id"], (b, length)),
                      jnp.int32)
    words = jnp.asarray(rng.integers(0, 2 ** 32, (b,), dtype=np.uint32))

    def leaf_norms(grads):
        return jax.tree.map(
            lambda g: jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32)))),
            grads)

    def system(params, ids, words):
        (loss, _aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, {"input_ids": ids, "noise_seed": words})
        noised, masked, t = tfm.corrupt_blocks(
            ids, words, block, cfg["mask_token_id"], cfg["noise_level_min"])
        logits, sown = model.apply(
            {"params": params}, jnp.concatenate([noised, ids], axis=1),
            jnp.tile(jnp.arange(length), 2), (length, block),
            mutable=["intermediates"])
        return (loss, logits[:, :length], leaf_norms(grads),
                _sown_routing(sown), (noised, masked, t))

    def reference(params, ids, noised, masked, t):
        def f(params):
            logits, aux, routing = reference_forward(cfg, params, noised, ids)
            return (reference_loss(cfg, logits, aux, ids, masked, t),
                    (logits, routing))
        (loss, (logits, routing)), grads = jax.value_and_grad(
            f, has_aux=True)(params)
        return loss, logits, leaf_norms(grads), routing

    params = jax.jit(lambda key: _init_params(cfg, key))(
        jax.random.PRNGKey(seed))
    sys_loss, sys_logits, sys_norms, sys_routing, draw = jax.jit(system)(
        degraded_to_fp8(params) if degrade_system else params, ids, words)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_logits, ref_norms, ref_routing = jax.jit(reference)(
            params, ids, *draw)
    ref_logits = np.asarray(ref_logits, np.float32).reshape(b * length, -1)
    diff = (np.asarray(sys_logits, np.float32).reshape(b * length, -1)
            - ref_logits)
    norm_errs = jax.tree_util.tree_flatten_with_path(jax.tree.map(
        lambda a, b: abs(float(a) - float(b)) / max(float(b), 1e-30),
        sys_norms, ref_norms))[0]
    worst_leaf, worst = max(norm_errs, key=lambda kv: kv[1])

    def whole(norms):       # the norm of all gradients from the leaves'
        return math.sqrt(sum(float(n) ** 2 for n in jax.tree.leaves(norms)))

    first, end = cfg["experts_held"]
    ref_held = _chosen(ref_routing, cfg["router_experts"])[..., first:end]
    out = {"held_pairs": int(ref_held.sum()),
           "held_pairs_max_over_mean": float(
               ref_held.sum(1).max() / max(ref_held.sum(1).mean(), 1e-30)),
           "masked_share": float(np.asarray(draw[1]).mean())}
    if len(sys_routing) == len(ref_routing):
        sys_held = _chosen(sys_routing, cfg["router_experts"])[..., first:end]
        agreement = float((ref_held & sys_held).sum()
                          / max(ref_held.sum(), 1))
    else:       # a program that does not show its routing cannot pass
        agreement = 0.0
    errors = {
        "loss": abs(float(sys_loss) - float(ref_loss)) / abs(float(ref_loss)),
        "logits_l2": float(np.linalg.norm(diff) / np.linalg.norm(ref_logits)),
        "logits_max": float(np.abs(diff).max() / np.abs(ref_logits).max()),
        "grad_norm": abs(whole(sys_norms) - whole(ref_norms))
        / whole(ref_norms),
        "routing_disagreement": 1.0 - agreement,
    }
    return {"errors": errors, "tolerance": TOLERANCE, **out,
            "routing_agreement": agreement,
            # not held to a limit (see TOLERANCE): which leaf's norm is
            # furthest off, and by how much
            "leaf_grad_norm_max": float(worst),
            "leaf_grad_norm_worst": jax.tree_util.keystr(worst_leaf),
            "ok": all(errors[k] < TOLERANCE[k] for k in errors)}


def _sown_routing(sown) -> list:
    """The ``[n, k]`` expert indices each MoE layer sowed into
    ``intermediates`` (``top_idx``), in layer order."""
    import jax

    found = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            sown.get("intermediates", {}))[0]:
        keys = [str(getattr(p, "key", "")) for p in path]
        if "top_idx" in keys:
            found.append((keys, leaf))
    return [leaf for _keys, leaf in sorted(found, key=lambda kv: kv[0])]


def _chosen(routing, n_experts: int):
    """``[layers, n, n_experts]`` bool: the experts each position chose."""
    import numpy as np

    out = []
    for top_idx in routing:
        top_idx = np.asarray(top_idx)
        chosen = np.zeros((top_idx.shape[0], n_experts), bool)
        chosen[np.arange(top_idx.shape[0])[:, None], top_idx] = True
        out.append(chosen)
    return np.stack(out)


def degraded_to_fp8(params):
    """The parameters rounded to scaled fp8 (e4m3, one scale a leaf): the
    nearest precision below the one the configuration states.  The system on
    these against the reference on the true ones has to fail ``TOLERANCE``
    (how the limits below were set, and a test)."""
    import jax
    import jax.numpy as jnp

    def leaf(x):
        scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
        return (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale

    return jax.tree.map(leaf, params)


# Each limit lies between the largest reading of the system over nine seeds
# and the smallest reading of the system on fp8 weights against the reference
# on the true ones over three (TPU v5e, [1, 4096] ids -> 8192 positions, 4
# layers; PERF.md section 6, PR 31), near the geometric mean of the two:
#   logits_l2            0.0256 .. 0.0271 | fp8 0.2188 .. 0.2257
#   logits_max           0.0441 .. 0.0522 | fp8 0.3797 .. 0.4278
#   routing_disagreement 0.0098 .. 0.0124 | fp8 0.0865 .. 0.0889
# The logits part by 2.6% where OLMoE's one layer reads 0.7%: four expert
# layers, each of whose flipped pairs (1% a layer) moves every later layer,
# under attention that the seeded state makes peaked.  Two numbers hardly
# follow the precision and have heavy tails over seeds, because the loss
# weights a masked token by 1/t with t down to 1e-3: about one row in five
# holds a token whose weight is in the hundreds, which then carries a tenth
# of the loss and most of the gradient, and that ONE token's rounding is the
# whole reading.  Their limits are gross-fault guards (a wrong normaliser, a
# dropped layer, a missing 1/t), far over the readings, and fp8 passes them:
#   loss                 2e-5 .. 2.1e-4    | fp8 2.1e-4 .. 1.6e-3
#   grad_norm            2.4e-4 .. 5.6e-3  | fp8 8.1e-3 .. 6.4e-2 (three seeds)
# grad_norm is the norm of ALL gradients.  The largest error of a single
# leaf's norm (leaf_grad_norm_max, reported beside it) has no limit: it read
# 0.0085 .. 0.0932 over the nine seeds (a router's or a norm scale's leaf,
# which a few flipped pairs move) against 0.157 .. 0.316 on fp8.
TOLERANCE = {"loss": 1e-2, "logits_l2": 0.07, "logits_max": 0.14,
             "grad_norm": 0.1, "routing_disagreement": 0.03}


# ---------------------------------------------------------------------------
# The plain reference: float32 jax.numpy, from the published description
# (a Qwen3-MoE decoder layer: pre-norm RMSNorm, grouped-query attention with
# an RMSNorm per head on q and k before rotate-half RoPE, softmax router,
# top-k WITH renormalisation, SwiGLU experts, no shared expert, untied head)
# under the block-diffusion objective of BD3-LM that SDAR adopts.  No kernel,
# no sort, no cache: the mask is materialised from the two index vectors,
# K and V heads are repeated, attention goes head by head (one head's
# [8192, 8192] float32 scores are 268 MB), and each held expert is applied
# to every position and weighted by the position's routing weight for it,
# which is 0 where it was not chosen.  Departures from the published model,
# all of the cut: only experts ``experts_held`` are summed, the vocabulary is
# the held slice.  It computes its own routing, and draws nothing: the noise
# comes in as arguments.  Nothing here imports the program's ops/ or
# parallel/ep.py.
# ---------------------------------------------------------------------------

def _rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * scale


def _rope(x, positions, theta: float):
    """Rotate-half RoPE on ``[B, T, H, D]``: pairs (i, i + D/2) turn by
    ``position * theta^(-2i/D)``."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def reference_mask(length: int, block: int):
    """``M[q, k]`` over ``[x_t ‖ x_0]`` (the noised copy first): true iff (q
    noised, k noised, same block) or (q noised, k clean, k's block before
    q's) or (q clean, k clean, k's block not after q's)."""
    import jax.numpy as jnp

    index = jnp.arange(2 * length)
    noised = index < length
    blk = (index % length) // block
    qn, kn = noised[:, None], noised[None, :]
    bq, bk = blk[:, None], blk[None, :]
    return ((qn & kn & (bq == bk)) | (qn & ~kn & (bk < bq))
            | (~qn & ~kn & (bk <= bq)))


def _reference_moe(cfg: dict, p: dict, y):
    """``[n, d]`` -> the held experts' part of the layer's output, its
    auxiliary term and the ``[n, k]`` experts each position chose."""
    import jax
    import jax.numpy as jnp

    e, k = cfg["router_experts"], cfg["num_experts_per_tok"]
    first, end = cfg["experts_held"]
    n = y.shape[0]
    probs = jax.nn.softmax(y @ p["router"]["kernel"], axis=-1)      # [n, e]
    top_p, top_idx = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    chosen = jax.nn.one_hot(top_idx, e, dtype=jnp.float32)          # [n, k, e]
    weight = jnp.einsum("nke,nk->ne", chosen, top_p)    # 0 where not chosen

    @jax.checkpoint     # keep one expert's activations at a time
    def expert(out, held):
        w, w_gate, w_up, w_down = held
        return out + w[:, None] * (
            (jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down), None

    # a loop over the held experts, one after the other
    out, _ = jax.lax.scan(expert, jnp.zeros_like(y), (
        weight[:, first:end].T, p["experts_gate"], p["experts_up"],
        p["experts_down"]))
    # all k choices count, over all the router's experts
    pairs_per_position = jnp.sum(chosen, axis=(0, 1)) / n
    load_balance = e * jnp.sum(pairs_per_position * jnp.mean(probs, axis=0))
    return out, load_balance, top_idx


def reference_forward(cfg: dict, params, noised, clean):
    """Logits of the NOISED copy ``[B, L, V]``, the auxiliary term summed
    over layers, and each layer's routing (over all 2L positions)."""
    import jax
    import jax.numpy as jnp

    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h, h_kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    d = cfg["hidden_size"]
    b, length = clean.shape
    t = 2 * length
    positions = jnp.tile(jnp.arange(length), 2)
    mask = reference_mask(length, cfg["block_length"])
    x = params["embed"]["embedding"][jnp.concatenate([noised, clean], axis=1)]

    @jax.checkpoint     # one head's [T, T] scores at a time, again backward
    def head(q, k, v):                                  # [B, T, dh] each
        scores = jnp.einsum("bqd,bkd->bqk", q, k) / math.sqrt(dh)
        scores = jnp.where(mask[None], scores, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(scores, -1), v)

    aux = 0.0
    routing = []
    for layer in range(cfg["num_hidden_layers"]):
        p = params[f"block_{layer}"]
        a = p["attn"]
        y = _rms_norm(x, p["attn_norm"]["scale"], eps)
        q = jnp.einsum("bsd,dhk->bshk", y, a["q_proj"]["kernel"])
        k = jnp.einsum("bsd,dhk->bshk", y, a["k_proj"]["kernel"])
        v = jnp.einsum("bsd,dhk->bshk", y, a["v_proj"]["kernel"])
        if cfg["qk_norm"]:      # per head, one scale of head_dim, before RoPE
            q = _rms_norm(q, a["q_norm"]["scale"], eps)
            k = _rms_norm(k, a["k_norm"]["scale"], eps)
        q, k = _rope(q, positions, theta), _rope(k, positions, theta)
        # query head j reads K/V head j // (h / h_kv): repeat them
        k, v = (jnp.repeat(x_, h // h_kv, axis=2) for x_ in (k, v))
        out = jax.lax.map(lambda qkv: head(*qkv), tuple(
            x_.transpose(2, 0, 1, 3) for x_ in (q, k, v)))      # [h, B, T, dh]
        x = x + jnp.einsum("hbqk,hkd->bqd", out, a["o_proj"]["kernel"])
        y = _rms_norm(x, p["mlp_norm"]["scale"], eps)
        moe_out, load_balance, top_idx = _reference_moe(
            cfg, p["moe"], y.reshape(b * t, d))
        x = x + moe_out.reshape(b, t, d)
        aux = aux + load_balance
        routing.append(top_idx)
    x = _rms_norm(x[:, :length], params["final_norm"]["scale"], eps)
    return x @ params["lm_head"]["kernel"], aux, routing


def reference_loss(cfg: dict, logits, aux, clean, masked, t):
    """``Σ masked · CE(logits_i, clean_i) / t_i / (rows · L)`` (no shift)
    plus the weighted auxiliary term."""
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, clean[..., None], axis=-1)[..., 0]
    return (jnp.sum(jnp.where(masked, nll / t, 0.0)) / nll.size
            + cfg["router_aux_loss_coef"] * aux)
