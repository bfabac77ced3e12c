"""Keye-VL-2.0-30B-A3B's decoder trained at its published widths: one chip's
share of an 8-way expert-parallel stage, depth cut to 4 layers, rows of 16k.

The system under test is the program's ``models/transformer.py`` with what
this model needs of it: ``sdar_30b_a3b_d4_ep8``'s body (grouped-query heads
32 over 4, Qwen3's per-head QK-norm, dropless top-8-of-128 routing of which
this chip holds experts 0-15) and, in every layer, learned sparse attention
(``ops/sparse_attention.py``): an indexer of 16 heads of 64 over ONE index
key a token scores the causal pairs, every query keeps its 2,048 best keys,
all 32 heads attend to those, and the indexer trains by its own loss
(``make_sparse_loss_fn``), through ``parallel/dp.py``'s ``make_train_step``
with ``remat``.  See ``resnet50.py`` for the names a configuration module
provides.

What the public config does not give is listed, each with its reason, under
``assumed`` in the JSON file: the placement of QK-norm, where the indexer's
inputs come from, its LayerNorm, RoPE and scales, the loss ``L_I`` and its
coefficient, the routers' coefficient, the optimizer, ``remat``, and the two
scales of the seeded state.
"""

from __future__ import annotations

import math

SAMPLE_UNIT = "tok"


def system_config(cfg: dict) -> dict:
    """The published keys, as the program's builder names them."""
    sa = cfg["sa_config"]
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
           "rope_theta": cfg["rope_theta"], "bf16": True,
           "remat": cfg["remat"],
           "sparse_attention": {"index_heads": sa["indexer_num_heads"],
                                "index_head_dim": sa["indexer_head_dim"],
                                "topk": sa["topk"]}}
    for key in ("attn_impl", "bf16"):       # the rehearsal's and the tests'
        if key in cfg:
            out[key] = cfg[key]
    return out


# ---------------------------------------------------------------------------
# Operations and bytes, from shapes (2 per multiply-add; USEFUL work only:
# the kept pairs, no recomputation, no tile computed whole for a few pairs).
# ---------------------------------------------------------------------------

def causal_pairs(length: int) -> int:
    return length * (length + 1) // 2


def selected_pairs(length: int, topk: int) -> int:
    """(query, key) pairs the indexer keeps in one row: ``min(topk, t + 1)``
    for the query at place ``t``."""
    short = min(length, topk)
    return short * (short + 1) // 2 + (length - short) * topk


def _indexer_params(cfg: dict) -> int:
    sa = cfg["sa_config"]
    return cfg["hidden_size"] * (
        sa["indexer_num_heads"] * sa["indexer_head_dim"]
        + sa["indexer_head_dim"] + sa["indexer_num_heads"])


def _per_position_params(cfg: dict) -> int:
    """Weights every position multiplies in one layer: the four attention
    projections at 32 query and 4 K/V heads, the router, the indexer's
    three projections."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    q = cfg["num_attention_heads"] * dh
    kv = cfg["num_key_value_heads"] * dh
    return (2 * d * q + 2 * d * kv + d * cfg["router_experts"]
            + _indexer_params(cfg))


def held_pairs_per_position(cfg: dict) -> float:
    first, end = cfg["experts_held"]
    return cfg["num_experts_per_tok"] * (end - first) / cfg["router_experts"]


def _index_pair_flops(cfg: dict) -> int:
    """One multiply-add per index head and index dim of a scored pair."""
    sa = cfg["sa_config"]
    return 2 * sa["indexer_num_heads"] * sa["indexer_head_dim"]


def _attend_pair_flops(cfg: dict) -> int:
    """QKᵀ and PV of one kept pair, all query heads."""
    return 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]


def flops_per_sample(cfg: dict, traffic: dict) -> float:
    """Training FLOPs this chip must do per token of a row: 6 per matmul
    weight (forward 2, backward 4) through the projections, the router, the
    indexer's projections, the expected held pairs' experts and the head
    over the held slice of the vocabulary; three times the forward attention
    over the KEPT pairs; the index scores over the causal pairs forward and
    over the kept pairs, twice, backward."""
    d, length = cfg["hidden_size"], int(traffic["seq_len"])
    kept = selected_pairs(length, cfg["sa_config"]["topk"])
    expert = 3 * d * cfg["moe_intermediate_size"]
    per_position = (_per_position_params(cfg)
                    + held_pairs_per_position(cfg) * expert)
    pairs = (3.0 * _attend_pair_flops(cfg) * kept
             + _index_pair_flops(cfg) * (causal_pairs(length) + 2 * kept))
    return (cfg["num_hidden_layers"] * (6.0 * per_position + pairs / length)
            + 6.0 * d * cfg["vocab_size"])


def dsa_attend_cost(cfg: dict, traffic: dict, rows_on_device: int) -> dict:
    """FLOPs and HBM bytes attention over the KEPT pairs needs in one STEP
    (all layers; forward once, backward 2.5 times the forward: five matmuls
    to its two).  Bytes, bf16: forward reads q and the 4 K/V heads and
    writes o and the log-sum-exp; backward reads q, o, do and the K/V heads
    and writes dq, dk, dv.  Tiles computed whole for the pairs kept in them,
    remat's second forward and the mask's bytes are the formulation's own
    and are not counted."""
    length = int(traffic["seq_len"])
    h, h_kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    kept = selected_pairs(length, cfg["sa_config"]["topk"])
    layers = cfg["num_hidden_layers"] * rows_on_device
    flops = layers * 3.5 * _attend_pair_flops(cfg) * kept
    per_position = (2 * h * dh * 2 + 2 * h_kv * dh * 2 + h * 4
                    + 4 * h * dh * 2 + 4 * h_kv * dh * 2 + h * 4)
    return {"flops": float(flops),
            "bytes": float(layers * length * per_position)}


def dsa_index_cost(cfg: dict, traffic: dict, rows_on_device: int) -> dict:
    """FLOPs and HBM bytes the index scores need in one STEP under the scope
    ``dsa/index`` (all layers): the scores of the CAUSAL pairs, forward, and
    the three projections of the indexer.  The indexer's backward over the
    kept pairs (twice the forward's FLOPs a pair) runs in the loss kernel's
    walk, under ``dsa/index_loss``, and is not counted here, nor is remat's
    second forward.  Bytes: the hidden state in, the projections' weights,
    index queries, key and weights out and in again as the kernel's
    operands."""
    length = int(traffic["seq_len"])
    sa = cfg["sa_config"]
    layers = cfg["num_hidden_layers"] * rows_on_device
    flops = layers * (_index_pair_flops(cfg) * causal_pairs(length)
                      + 2 * _indexer_params(cfg) * length)
    per_position = (cfg["hidden_size"] * 2 + 2 * 2 * (
        sa["indexer_num_heads"] * sa["indexer_head_dim"]
        + sa["indexer_head_dim"]) + 2 * 4 * sa["indexer_num_heads"])
    return {"flops": float(flops),
            "bytes": float(layers * (length * per_position
                                     + 2 * _indexer_params(cfg)))}


def moe_experts_cost(cfg: dict, traffic: dict, rows_on_device: int) -> dict:
    """As ``sdar_30b_a3b_d4_ep8.py`` counts its held pairs' expert matmuls
    of one STEP (one position a token here)."""
    pairs = (rows_on_device * int(traffic["seq_len"])
             * held_pairs_per_position(cfg))
    d, ff = cfg["hidden_size"], cfg["moe_intermediate_size"]
    first, end = cfg["experts_held"]
    weights = (end - first) * 3 * d * ff
    layers = cfg["num_hidden_layers"]
    return {"flops": float(layers * 3 * 2 * pairs * 3 * d * ff),
            "bytes": float(layers * 2 * (5 * pairs * d + 3 * weights))}


KERNELS = {"dsa_attend": dsa_attend_cost, "dsa_index": dsa_index_cost,
           "moe_experts": moe_experts_cost}


# ---------------------------------------------------------------------------
# Inputs from the seed (driver side: numpy only).
# ---------------------------------------------------------------------------

def train_records(cfg: dict, traffic: dict, rng, n: int):
    """``n`` rows of ``seq_len`` token ids, uniform over the held slice of
    the vocabulary."""
    import numpy as np

    rows = rng.integers(0, cfg["vocab_size"], (n, int(traffic["seq_len"])),
                        dtype=np.int32)
    return [rows[i] for i in range(n)]


# ---------------------------------------------------------------------------
# Node side.
# ---------------------------------------------------------------------------

def feed_options(cfg: dict, input_mode: str) -> dict:
    return {}


def rows_to_arrays(cfg: dict):
    import numpy as np

    def to_arrays(rows):
        return {"input_ids": np.stack(rows).astype(np.int32)}

    return to_arrays


def _model(cfg: dict):
    from tensorflowonspark_tpu.models import transformer as tfm

    model = tfm.build_transformer(system_config(cfg))
    # the builder ignores keys it does not know: a program from before these
    # existed would build SDAR's body under causal attention and train it
    # without an indexer under this model's name.  It cannot run this
    # configuration.
    lacking = [key for key in ("n_kv_heads", "moe_held", "sparse")
               if not hasattr(model, key)]
    if not hasattr(tfm, "make_sparse_loss_fn"):
        lacking.append("make_sparse_loss_fn")
    if lacking:
        raise NotImplementedError(
            f"models/transformer.py of this program has no {lacking}: it "
            "cannot build Keye-VL-2.0's sparse attention or its loss")
    return tfm, model


def _loss_fn(tfm, model, cfg: dict):
    return tfm.make_sparse_loss_fn(
        model, aux_loss_coef=cfg["router_aux_loss_coef"],
        vocab_chunk=int(cfg["vocab_chunk"]),
        index_loss_coef=cfg["index_loss_coef"])


def _init_params(cfg: dict, key):
    """Parameters from the key, through a twin of the model on 8 positions
    (see ``phi3_mini_d4.py``): the program's own initialisers but for the two
    scales of ``seeded_state`` (the JSON file says why)."""
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import transformer as tfm

    twin = tfm.build_transformer({**system_config(cfg), "attn_impl": "xla",
                                  "remat": False})
    params = twin.init(key, jnp.zeros((1, 8), jnp.int32))["params"]
    seeded = cfg["seeded_state"]
    # flax draws the embedding at 1 / sqrt(hidden) and the norms' scales at 1
    params["embed"]["embedding"] = (
        params["embed"]["embedding"] * math.sqrt(cfg["hidden_size"])
        * seeded["embedding_std"])
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
            "samples_per_row": int(traffic["seq_len"])}


def is_indexer(path) -> bool:
    """Whether a parameter's path names one of the indexer's leaves."""
    return any(str(getattr(p, "key", "")).startswith("index_") for p in path)


def check_train(cfg: dict, traffic: dict, seed: int,
                degrade_system=False) -> dict:
    """System against the plain float32 reference on ``reference_tokens``
    (the cell's own ``[1, 16384]`` row, all layers): cross-entropy and the
    indexers' loss ``L_I`` each, the logits, the norm of the gradients of
    the indexers' leaves and of all the others separately, the routing over
    the held experts, and the SELECTION: ``selection_disagreement`` is the
    share of the reference's kept (query, key) pairs, over all layers, that
    the system did not keep.

    Top-k is discontinuous, the routers' and the indexers' alike (see
    ``olmoe_1b_7b_d1.py``): a pair whose score lies within rounding of its
    row's threshold may flip, and from the second layer on the hidden state
    that is scored differs by the first layer's rounding and flips.

    What it cannot see: as the other configurations' checks, it compiles
    ``value_and_grad(loss_fn)`` of its own, not the ``make_train_step`` the
    window drives: the optimizer's update is held to a finite loss only.

    ``degrade_system`` is for setting the limits, not for a run: ``"fp8"``
    (or True) hands the system the parameters rounded to fp8
    (``degraded_to_fp8``), ``"bf16_index"`` rounds its index scores to bf16
    before the selection; the reference gets the true parameters, and the
    result has to come out not ``ok``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    tfm, model = _model(cfg)      # refuses a program without the mechanism
    from tensorflowonspark_tpu.ops import sparse_attention as dsa

    loss_fn = _loss_fn(tfm, model, cfg)
    b, length = cfg["reference_tokens"]
    rng = np.random.default_rng([seed, 78])
    ids = jnp.asarray(rng.integers(0, cfg["vocab_size"], (b, length)),
                      jnp.int32)

    def norms(grads):
        flat = jax.tree_util.tree_flatten_with_path(grads)[0]

        def norm(own: bool):
            return jnp.sqrt(sum(
                jnp.sum(jnp.square(g.astype(jnp.float32)))
                for p, g in flat if is_indexer(p) == own))

        return norm(True), norm(False)

    def system(params, ids):
        (_loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, {"input_ids": ids})
        return metrics["lm_loss"], metrics["index_loss"], norms(grads)

    def system_forward(params, ids):
        logits, sown = model.apply({"params": params}, ids,
                                   mutable=["intermediates"])
        return logits, _sown(sown, "top_idx"), _sown(sown, "dsa_mask")

    def reference(params, ids):
        def f(params):
            logits, aux, index_loss, routing, masks = reference_forward(
                cfg, params, ids)
            lm_loss = reference_lm_loss(logits, ids)
            return (lm_loss + cfg["router_aux_loss_coef"] * aux
                    + cfg["index_loss_coef"] * index_loss,
                    (lm_loss, index_loss, logits, routing, masks))
        (_loss, out), grads = jax.value_and_grad(f, has_aux=True)(params)
        return (*out, norms(grads))

    params = jax.jit(lambda key: _init_params(cfg, key))(
        jax.random.PRNGKey(seed))
    sys_params = (degraded_to_fp8(params)
                  if degrade_system in (True, "fp8") else params)
    true_scores = dsa.index_scores
    if degrade_system == "bf16_index":
        dsa.index_scores = _index_scores_in_bf16
    try:
        sys_lm, sys_index, sys_norms = jax.jit(system)(sys_params, ids)
        sys_out = jax.jit(system_forward)(sys_params, ids)
    finally:
        dsa.index_scores = true_scores
    # the system's logits and selections wait on the HOST: at 16k the
    # reference needs the chip to itself (2.3 GB of them, 10 GB of its own)
    sys_logits, sys_routing, sys_masks = jax.tree.map(np.asarray, sys_out)
    del sys_out, sys_params
    with jax.default_matmul_precision("highest"):
        ref_out = jax.jit(reference)(params, ids)
    (ref_lm, ref_index, ref_logits, ref_routing, ref_masks,
     ref_norms) = jax.tree.map(np.asarray, ref_out)
    del ref_out, params
    kept = [int(np.count_nonzero(m)) for m in ref_masks]
    if len(sys_masks) == len(ref_masks):
        both = [int(np.count_nonzero(r & (s.reshape(r.shape) != 0)))
                for r, s in zip(ref_masks, sys_masks)]
    else:       # a program that does not show its selection cannot pass
        both = [0] * len(kept)
    del sys_masks, ref_masks
    ref_logits = ref_logits.astype(np.float32).reshape(b * length, -1)
    diff = sys_logits.astype(np.float32).reshape(b * length, -1) - ref_logits

    first, end = cfg["experts_held"]
    ref_held = _chosen(ref_routing, cfg["router_experts"])[..., first:end]
    if len(sys_routing) == len(ref_routing):
        sys_held = _chosen(sys_routing, cfg["router_experts"])[..., first:end]
        agreement = float((ref_held & sys_held).sum()
                          / max(ref_held.sum(), 1))
    else:
        agreement = 0.0

    def rel(a, b):
        return abs(float(a) - float(b)) / max(abs(float(b)), 1e-30)

    errors = {
        "lm_loss": rel(sys_lm, ref_lm),
        "index_loss": rel(sys_index, ref_index),
        "logits_l2": float(np.linalg.norm(diff) / np.linalg.norm(ref_logits)),
        "logits_max": float(np.abs(diff).max() / np.abs(ref_logits).max()),
        "grad_norm_index": rel(sys_norms[0], ref_norms[0]),
        "grad_norm_rest": rel(sys_norms[1], ref_norms[1]),
        "routing_disagreement": 1.0 - agreement,
        "selection_disagreement": 1.0 - sum(both) / max(sum(kept), 1),
        # the first layer scores the same embeddings on both sides: what
        # parts them there is the precision of the scores alone
        "selection_disagreement_first": 1.0 - both[0] / max(kept[0], 1),
    }
    return {"errors": errors, "tolerance": TOLERANCE,
            "held_pairs": int(ref_held.sum()),
            "held_pairs_max_over_mean": float(
                ref_held.sum(1).max() / max(ref_held.sum(1).mean(), 1e-30)),
            "selected_pairs": sum(kept),
            "selection_disagreement_by_layer": [
                1.0 - b_ / max(k_, 1) for b_, k_ in zip(both, kept)],
            "lm_loss": float(ref_lm), "index_loss": float(ref_index),
            "grad_norm_index": float(ref_norms[0]),
            "grad_norm_rest": float(ref_norms[1]),
            "ok": all(errors[k] < TOLERANCE[k] for k in errors)}


def _index_scores_in_bf16(a, b, c, row0=0, *, impl=None):
    """``ops/sparse_attention.index_scores`` with everything after the
    products of the 64-deep dots kept in bf16: each head's dot, its ReLU times
    its weight and the running sum over the 16 heads are rounded to bf16
    (``reduce_precision``: a pair of converts is what XLA's excess-precision
    rule removes on the chip).  For setting the limits only."""
    import jax
    import jax.numpy as jnp

    def bf16(x):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    weights = bf16(c.astype(jnp.float32))
    total = jnp.zeros((a.shape[0], b.shape[0]), jnp.float32)
    for j in range(a.shape[1]):
        dots = bf16(jnp.einsum("td,sd->ts", a[:, j], b,
                               preferred_element_type=jnp.float32))
        total = bf16(total + bf16(weights[:, j:j + 1]
                                  * jnp.maximum(dots, 0.0)))
    return total


def _sown(sown, name: str) -> list:
    """What each layer sowed into ``intermediates`` under ``name``, in layer
    order."""
    import jax

    found = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            sown.get("intermediates", {}))[0]:
        keys = [str(getattr(p, "key", "")) for p in path]
        if name in keys:
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
    nearest precision below the one the configuration states."""
    import jax
    import jax.numpy as jnp

    def leaf(x):
        scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
        return (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale

    return jax.tree.map(leaf, params)


# Each limit that follows the precision lies near the geometric mean of the
# largest reading of the system over nine seeds and the smallest reading of a
# degraded system over three (TPU v5e, the cell's own [1, 16384] row, 4
# layers; PERF.md section 6, PR 33): fp8 = the system on weights rounded to
# fp8; bf16 = its index scores kept in bf16 after each head's dot, product
# and running sum (``_index_scores_in_bf16``); both against the reference on
# the true weights:
#   logits_l2                    0.0913 .. 0.0931   | fp8 0.1834 .. 0.1846
#   routing_disagreement         0.0396 .. 0.0425   | fp8 0.1073 .. 0.1091
#   selection_disagreement       0.0244 .. 0.0249   | fp8 0.0795 .. 0.0798
#   selection_disagreement_first 0.00314 .. 0.00316 | bf16 0.00393 .. 0.00394,
#                                                     fp8 0.0341
# The logits part by 9% where SDAR's read 2.6%, on the same body: here a
# flipped pair is a key in or out of a query's attention, which the seeded
# state makes peaked, and the flips compound (kept pairs the system lacks,
# layer by layer: 0.3%, 1.3%, 3.0%, 5.3%; held routing pairs 4%).  The first
# layer scores the same embeddings on both sides, so there the reading is
# the scores' precision alone (the bf16 hidden state and the bf16 operands
# of the score matmuls, as ``compute`` states: 0.31%); it is steady to 0.4%
# of itself over seeds, and its limit is the one that scores in bf16 fail (by
# 12%; over all layers they read 0.0266 .. 0.0270 and pass).  Rounding only
# the finished float32 sum to bf16 reads 0.00332 .. 0.00334 and passes: the
# check cannot tell that from the stated precision.
# Five numbers hardly follow the precision: the two losses and the two
# gradient norms are means over 16k tokens or norms over millions of weights,
# in which roundings cancel, and the largest logit error is one entry's.
# Their limits are gross-fault guards (a dropped term, a wrong normaliser, a
# missing stop-gradient), far over the readings, and fp8 passes them:
#   lm_loss          4e-5 .. 1.2e-4    | fp8 5e-5 .. 9e-5
#   index_loss       2e-5 .. 1.6e-4    | fp8 1.2e-4 .. 3.7e-4
#   grad_norm_index  3e-5 .. 4.5e-4    | fp8 7.6e-4 .. 1.7e-3
#   grad_norm_rest   4e-5 .. 4.7e-4    | fp8 2.2e-4 .. 3.7e-4
#   logits_max       0.217 .. 0.266    | fp8 0.274 .. 0.303
TOLERANCE = {"lm_loss": 1e-2, "index_loss": 1e-2, "logits_l2": 0.13,
             "logits_max": 0.5, "grad_norm_index": 0.05,
             "grad_norm_rest": 0.05, "routing_disagreement": 0.065,
             "selection_disagreement": 0.045,
             "selection_disagreement_first": 0.0035}


# ---------------------------------------------------------------------------
# The plain reference: float32 jax.numpy, from the descriptions the JSON
# file names (a Qwen3-MoE decoder layer; DeepSeek-V3.2-Exp's lightning
# indexer and sparse training stage).  No kernel, no counting passes
# (``jax.checkpoint`` around a layer and around a block of queries changes no
# number: it is how a 16k row's float32 activations fit beside the system's): a block of queries at a time (so that a 16k row fits), the
# index scores of the block against every key are materialised, each query's
# threshold is read off a SORT of its causal scores, attention is a dense
# softmax with -inf outside the kept set, a group's 8 query heads read their
# K/V head by an einsum over the group axis, and each
# held expert is applied to every position and weighted by the position's
# routing weight for it.  Departures from the published model, all of the
# cut: only experts ``experts_held`` are summed, the vocabulary is the held
# slice.  Nothing here imports the program's ops/ or parallel/ep.py.
# ---------------------------------------------------------------------------

def _rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * scale


def _layer_norm(x, scale, bias, eps):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def _rope(x, positions, theta: float):
    """Rotate-half RoPE on ``[B, T, H, D]``."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _reference_moe(cfg: dict, p: dict, y):
    """``[n, d]`` -> the held experts' part of the layer's output, its
    auxiliary term and the ``[n, k]`` experts each position chose."""
    import jax
    import jax.numpy as jnp

    e, k = cfg["router_experts"], cfg["num_experts_per_tok"]
    first, end = cfg["experts_held"]
    n = y.shape[0]
    probs = jax.nn.softmax(y @ p["router"]["kernel"], axis=-1)
    top_p, top_idx = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    chosen = jax.nn.one_hot(top_idx, e, dtype=jnp.float32)
    weight = jnp.einsum("nke,nk->ne", chosen, top_p)

    @jax.checkpoint     # one expert's activations at a time, again backward
    def expert(held):
        w, w_gate, w_up, w_down = held
        return w[:, None] * ((jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down)

    # a loop over the held experts, one after the other (the running sum is
    # outside the checkpoint: a sum needs no residual)
    out, _ = jax.lax.scan(lambda out, held: (out + expert(held), None),
                          jnp.zeros_like(y), (
        weight[:, first:end].T, p["experts_gate"], p["experts_up"],
        p["experts_down"]))
    pairs_per_position = jnp.sum(chosen, axis=(0, 1)) / n
    load_balance = e * jnp.sum(pairs_per_position * jnp.mean(probs, axis=0))
    return out, load_balance, top_idx


def reference_selection(scores, first_query: int, topk: int):
    """``scores [T, L]`` of the queries from ``first_query`` on -> bool
    ``[T, L]``: each query's ``min(topk, t + 1)`` causal keys with the
    largest score, equal scores by the lower position.  The threshold is read
    off a descending sort of the row."""
    import jax.numpy as jnp

    rows, length = scores.shape
    t = first_query + jnp.arange(rows)[:, None]
    causal = jnp.arange(length)[None, :] <= t
    scores = jnp.where(causal, scores, -jnp.inf)
    ranked = -jnp.sort(-scores, axis=1)
    k_t = jnp.minimum(topk, t + 1)
    threshold = jnp.take_along_axis(ranked, k_t - 1, axis=1)
    above = scores > threshold
    ties = causal & (scores == threshold)
    need = k_t - jnp.sum(above, axis=1, keepdims=True)
    return above | (ties & (jnp.cumsum(ties, axis=1) <= need))


def _reference_attention(cfg: dict, a, u, u_detached, positions):
    """One layer's sparse attention on ``u [L, d]`` (the normed hidden
    state): ``(heads' outputs [L, H, dh], Σ_t KL_t, kept [L, L] bool)``."""
    import jax
    import jax.numpy as jnp

    sa = cfg["sa_config"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h, h_kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    j, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    length = u.shape[0]
    block = min(int(cfg["reference_query_block"]), length)

    q = jnp.einsum("sd,dhk->shk", u, a["q_proj"]["kernel"])
    k = jnp.einsum("sd,dhk->shk", u, a["k_proj"]["kernel"])
    v = jnp.einsum("sd,dhk->shk", u, a["v_proj"]["kernel"])
    if cfg["qk_norm"]:
        q = _rms_norm(q, a["q_norm"]["scale"], eps)
        k = _rms_norm(k, a["k_norm"]["scale"], eps)
    q, k = (_rope(x[None], positions, theta)[0] for x in (q, k))
    # query head j reads K/V head j // (h / h_kv): [T, h_kv, h / h_kv, dh]
    q = q.reshape(length, h_kv, h // h_kv, dh)

    # the indexer, on the hidden state with the gradient stopped
    iq = jnp.einsum("sd,djk->sjk", u_detached, a["index_q"]["kernel"])
    ik = _layer_norm(u_detached @ a["index_k"]["kernel"],
                     a["index_k_norm"]["scale"], a["index_k_norm"]["bias"],
                     eps)
    iw = (u_detached @ a["index_w"]["kernel"]) * j ** -0.5 * di ** -0.5
    iq = _rope(iq[None], positions, theta)[0]
    ik = _rope(ik[None, :, None, :], positions, theta)[0, :, 0]

    @jax.checkpoint     # one block of queries against every key, again backward
    def queries(first, q_blk, iq_blk, iw_blk):
        scores = jnp.einsum("tj,tjs->ts", iw_blk, jax.nn.relu(
            jnp.einsum("tjd,sd->tjs", iq_blk, ik)))
        kept = jax.lax.stop_gradient(
            reference_selection(scores, first, sa["topk"]))
        logits = jnp.einsum("tgrd,sgd->grts", q_blk, k) / math.sqrt(dh)
        alpha = jax.nn.softmax(jnp.where(kept, logits, -jnp.inf), -1)
        out = jnp.einsum("grts,sgd->tgrd", alpha, v)
        p = jax.lax.stop_gradient(jnp.mean(alpha, axis=(0, 1)))
        log_r = jax.nn.log_softmax(jnp.where(kept, scores, -jnp.inf), -1)
        # 0 · log 0 = 0, and off the kept set p is 0
        kl = jnp.sum(jnp.where(p > 0, p * (jnp.log(jnp.where(p > 0, p, 1.0))
                                           - jnp.where(kept, log_r, 0.0)),
                               0.0))
        return out, kl, kept

    n = length // block
    out, kl, kept = jax.lax.map(lambda xs: queries(*xs), (
        jnp.arange(n) * block, q.reshape(n, block, h_kv, h // h_kv, dh),
        iq.reshape(n, block, j, di), iw.reshape(n, block, j)))
    return (out.reshape(length, h, dh), jnp.sum(kl),
            kept.reshape(length, length))


def reference_forward(cfg: dict, params, ids):
    """``(logits [B, L, V], the routers' term summed over layers, L_I, each
    layer's routing, each layer's kept pairs [B, L, L])``."""
    import jax
    import jax.numpy as jnp

    eps = cfg["rms_norm_eps"]
    d = cfg["hidden_size"]
    b, length = ids.shape
    positions = jnp.arange(length)

    @jax.checkpoint     # a layer's activations (2 GB at 16k) again backward
    def layer(p, x):
        u = _rms_norm(x, p["attn_norm"]["scale"], eps)
        out, kl, kept = jax.vmap(
            lambda u_row: _reference_attention(
                cfg, p["attn"], u_row, jax.lax.stop_gradient(u_row),
                positions))(u)
        x = x + jnp.einsum("bqhk,hkd->bqd", out, p["attn"]["o_proj"]["kernel"])
        y = _rms_norm(x, p["mlp_norm"]["scale"], eps)
        moe_out, load_balance, top_idx = _reference_moe(
            cfg, p["moe"], y.reshape(b * length, d))
        return (x + moe_out.reshape(b, length, d), load_balance,
                jnp.sum(kl) / (b * length), top_idx, kept)

    x = params["embed"]["embedding"][ids]
    aux, index_loss = 0.0, 0.0
    routing, masks = [], []
    for i in range(cfg["num_hidden_layers"]):
        x, load_balance, kl, top_idx, kept = layer(params[f"block_{i}"], x)
        aux = aux + load_balance
        index_loss = index_loss + kl
        routing.append(top_idx)
        masks.append(kept)
    x = _rms_norm(x, params["final_norm"]["scale"], eps)
    return (x @ params["lm_head"]["kernel"], aux,
            index_loss / cfg["num_hidden_layers"], routing, masks)


def reference_lm_loss(logits, ids):
    """Next-token cross-entropy: position i predicts token i + 1; the mean
    over the L - 1 targets of every row."""
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(logits[:, :-1])
    nll = -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll)
