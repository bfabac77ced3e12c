"""Kanana-2-30B-A3B (``model_type`` ``deepseek_v3``) trained at its published
widths: one chip's share of an 8-way expert-parallel stage, depth cut to the
leading dense layer and four expert layers.

The system under test is the program's ``models/transformer.py`` with what
Kanana-2 needs of it: latent attention (``Attention.latent``: 32 query heads
of 128 + 64 over per-head keys of 128 and values of 128 that are
up-projections of ONE normed latent of 512, and ONE rotary key head of 64 that
every query head meets, through the three flash kernels of
``ops/attention.py`` as their shared key), a leading dense layer
(``Transformer.layer_ffn``), and DeepSeek-V3's router in ``parallel/ep.py``
(sigmoid scores, a selection bias that is a buffer, top 6 of 128, the unbiased
scores of the chosen renormalised and scaled by 2.448, two shared experts as
one SwiGLU of 1536) of which this chip holds experts 0-15; next-token
cross-entropy fused with the head, through ``parallel/dp.py``'s
``make_train_step`` under adamw, which is never shown the bias buffers (the
train state carries them beside the parameters).  See ``resnet50.py`` for
the names a configuration module provides.

Sizes the public config does not give (the JSON file's ``assumed`` says why
each): the job (a fine-tune with the published modeling code: no auxiliary
term, a bias nobody updates), the learning rate, ``vocab_chunk``, and the
three scales of the seeded state (``seeded_state``: the embedding's standard
deviation, the factor on ``W_q``, the bias buffer's standard deviation).
"""

from __future__ import annotations

import math

SAMPLE_UNIT = "tok"


def system_config(cfg: dict) -> dict:
    """The published keys, as the program's builder names them."""
    dense = cfg["first_k_dense_replace"]
    out = {"model": "transformer",
           "vocab_size": cfg["vocab_size"], "d_model": cfg["hidden_size"],
           "n_layers": cfg["num_hidden_layers"],
           "n_heads": cfg["num_attention_heads"],
           "latent_attention": {key: cfg[key] for key in (
               "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
               "v_head_dim")},
           "layer_ffn": [cfg["intermediate_size"]] * dense
           + [0] * (cfg["num_hidden_layers"] - dense),
           "d_ff": cfg["moe_intermediate_size"],
           "n_experts": cfg["router_experts"],
           "moe_held": cfg["experts_held"],
           "moe_top_k": cfg["num_experts_per_tok"],
           "moe_capacity_factor": None,            # dropless
           "moe_norm_topk_prob": cfg["norm_topk_prob"],
           "moe_router": {"scoring": cfg["scoring_func"],
                          "selection_bias": cfg["topk_method"] == "noaux_tc",
                          "routed_scale": cfg["routed_scaling_factor"],
                          "n_group": cfg["n_group"]},
           "moe_shared_d_ff": (cfg["n_shared_experts"]
                               * cfg["moe_intermediate_size"]),
           "norm_eps": cfg["rms_norm_eps"],
           "rope_theta": cfg["rope_theta"], "bf16": True,
           "remat": bool(cfg.get("remat", False))}
    for key in ("attn_impl", "bf16"):       # the rehearsal's and the tests'
        if key in cfg:
            out[key] = cfg[key]
    return out


# ---------------------------------------------------------------------------
# Operations and bytes, from shapes (2 per multiply-add; no recompute).
# ---------------------------------------------------------------------------

def causal_pairs(length: int) -> int:
    return length * (length + 1) // 2


def _attention_params(cfg: dict) -> int:
    """Weights every position multiplies in a layer's latent attention: W_q,
    W_kva, W_kvb and W_o."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rank, nope, rope, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                            cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    return (d * h * (nope + rope) + d * (rank + rope)
            + rank * h * (nope + dv) + h * dv * d)


def held_pairs_per_position(cfg: dict) -> float:
    """EXPECTED pairs a position sends to the experts held here: its 6
    choices spread evenly over the router's 128 experts, 16 of them here."""
    first, end = cfg["experts_held"]
    return cfg["num_experts_per_tok"] * (end - first) / cfg["router_experts"]


def _expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def flops_per_sample(cfg: dict, traffic: dict) -> float:
    """Training FLOPs this chip must do per token: 6 per matmul weight a
    position passes (forward 2, backward 4): the latent projections in every
    layer, the dense SwiGLU in the leading layer, the router, the shared
    SwiGLU and the EXPECTED held pairs' experts in the expert layers, the
    head over the held slice of the vocabulary; and attention's forward over
    the causal pairs (192-wide scores, 128-wide values) forward, and the
    backward's five matmuls at their widths (``mla_flash_bwd_cost``: 2.6
    times the forward's)."""
    d, length = cfg["hidden_size"], int(traffic["seq_len"])
    ff = cfg["moe_intermediate_size"]
    expert_layer = (d * cfg["router_experts"]
                    + 3 * d * ff * cfg["n_shared_experts"]
                    + held_pairs_per_position(cfg) * 3 * d * ff)
    weights = (cfg["num_hidden_layers"] * _attention_params(cfg)
               + cfg["first_k_dense_replace"] * 3 * d
               * cfg["intermediate_size"]
               + _expert_layers(cfg) * expert_layer
               + d * cfg["vocab_size"])
    attention = (mla_flash_fwd_cost(cfg, traffic, 1)["flops"]
                 + mla_flash_bwd_cost(cfg, traffic, 1)["flops"]) / length
    return 6.0 * weights + cfg["num_hidden_layers"] * attention


def mla_flash_fwd_cost(cfg: dict, traffic: dict, rows_on_device: int) -> dict:
    """FLOPs and HBM bytes the forward kernel NEEDS for one call (one layer,
    this device's rows): over the causal pairs, all 32 heads, the score at
    192 (128 + 64) and the values at 128; it reads q, ``k_nope`` and v once,
    the ONE rotary key once (not once a head), and writes o (bf16) and the
    log-sum-exp (float32).  The rotary columns' pad to 128 lanes, diagonal
    tiles computed whole and the layout around the kernel are the
    formulation's own."""
    length, h = int(traffic["seq_len"]), cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    flops = rows_on_device * 2 * causal_pairs(length) * h * (nope + rope + dv)
    positions = rows_on_device * length
    bytes_ = positions * (2 * (h * (nope + rope) + h * nope + rope
                               + 2 * h * dv) + 4 * h)
    return {"flops": float(flops), "bytes": float(bytes_)}


def mla_flash_bwd_cost(cfg: dict, traffic: dict, rows_on_device: int) -> dict:
    """The same for both backward passes of one layer: the scores once and
    dq and dk at 192, dp and dv at 128 (``2 · pairs · 32 · (3 · 192 + 2 ·
    128)``); it reads q, ``k_nope``, the rotary key, v, o, dO and the
    log-sum-exp and writes dq, ``dk_nope``, dv and the ONE rotary key's
    gradient.  The second recompute of the scores (two passes) and the
    float32 shares of the rotary key's gradient are the formulation's own."""
    length, h = int(traffic["seq_len"]), cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    flops = (rows_on_device * 2 * causal_pairs(length) * h
             * (3 * (nope + rope) + 2 * dv))
    positions = rows_on_device * length
    reads = 2 * (h * (nope + rope) + h * nope + rope + 3 * h * dv) + 4 * h
    writes = 2 * (h * (nope + rope) + h * nope + rope + h * dv)
    return {"flops": float(flops), "bytes": float(positions * (reads + writes))}


def moe_experts_cost(cfg: dict, traffic: dict, rows_on_device: int) -> dict:
    """FLOPs and HBM bytes the EXPECTED held pairs need in the routed
    experts' matmuls of one STEP (the four expert layers, forward and
    backward), counted as ``sdar_30b_a3b_d4_ep8.py`` counts them: three ``d x
    f`` matrices a pair, forward once and backward twice.  The shared SwiGLU
    is not the routed experts' (scope ``moe/shared``)."""
    pairs = (rows_on_device * int(traffic["seq_len"])
             * held_pairs_per_position(cfg))
    d, ff = cfg["hidden_size"], cfg["moe_intermediate_size"]
    first, end = cfg["experts_held"]
    weights = (end - first) * 3 * d * ff
    layers = _expert_layers(cfg)
    flops = layers * 3 * 2 * pairs * 3 * d * ff
    bytes_ = layers * 2 * (5 * pairs * d + 3 * weights)
    return {"flops": float(flops), "bytes": float(bytes_)}


KERNELS = {"mla_flash_fwd": mla_flash_fwd_cost,
           "mla_flash_bwd": mla_flash_bwd_cost,
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
    from tensorflowonspark_tpu.parallel import dp as dplib

    model = tfm.build_transformer(system_config(cfg))
    # the builder ignores keys it does not know: a program from before these
    # existed would build grouped-query attention over softmax-routed experts
    # in every layer under Kanana's name.  It cannot run this configuration.
    lacking = [key for key in ("latent", "layer_ffn", "moe_router",
                               "moe_shared_d_ff", "moe_held")
               if not hasattr(model, key)]
    if "buffers" not in dplib.TrainState._fields:
        lacking.append("parallel/dp.TrainState.buffers")
    if lacking:
        raise NotImplementedError(
            f"this program has no {lacking}: it cannot build "
            "Kanana-2-30B-A3B's latent attention, its router or its leading "
            "dense layer")
    return tfm, model


def _loss_fn(tfm, model, cfg: dict):
    # no auxiliary term: the router sows none under its selection bias
    return tfm.make_loss_fn(model, aux_loss_coef=0.0,
                            vocab_chunk=int(cfg["vocab_chunk"]),
                            router_z_coef=0.0)


def _optimizer(cfg: dict):
    import optax

    # adamw decays every leaf it is given (1e-4 by optax's default): it is
    # given the parameters, never the routers' bias buffers
    return optax.adamw(cfg["optimizer"]["learning_rate"])


def _init_state(cfg: dict, key):
    """``(params, buffers)`` from the key, through a twin of the model with
    plain attention on 8 positions (see ``phi3_mini_d4.py``), by the
    program's own initialisers but for three scales (``seeded_state`` in the
    JSON file, and why: a job that continues from a checkpoint starts with
    token identity in the residual stream, peaked attention and a bias that
    has moved): the embedding's standard deviation, a factor on ``W_q``, and
    the standard deviation of the routers' bias buffers (flax draws them
    0)."""
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import transformer as tfm

    twin = tfm.build_transformer({**system_config(cfg), "attn_impl": "xla",
                                  "remat": False})
    variables = twin.init(key, jnp.zeros((1, 8), jnp.int32))
    params, buffers = variables["params"], variables["buffers"]
    seeded = cfg["seeded_state"]
    # flax draws the embedding at 1 / sqrt(hidden)
    params["embed"]["embedding"] = (
        params["embed"]["embedding"] * math.sqrt(cfg["hidden_size"])
        * seeded["embedding_std"])
    for layer in range(cfg["num_hidden_layers"]):
        attn = params[f"block_{layer}"]["attn"]
        attn["q_proj"]["kernel"] = (
            attn["q_proj"]["kernel"] * seeded["q_proj_scale"])
        if f"block_{layer}" in buffers:
            moe = buffers[f"block_{layer}"]["moe"]
            bias = moe["e_score_correction_bias"]
            moe["e_score_correction_bias"] = (
                seeded["selection_bias_std"] * jax.random.normal(
                    jax.random.fold_in(key, 1000 + layer), bias.shape,
                    bias.dtype))
    return params, buffers


def build_train(cfg: dict, traffic: dict, mesh, seed: int) -> dict:
    import jax

    from tensorflowonspark_tpu.parallel import dp as dplib
    from tensorflowonspark_tpu.parallel import mesh as meshlib

    tfm, model = _model(cfg)
    optimizer = _optimizer(cfg)
    def create(key):
        params, buffers = _init_state(cfg, key)
        return dplib.TrainState.create(params, optimizer, buffers)

    state = jax.jit(create, out_shardings=meshlib.replicated(mesh))(
        jax.random.PRNGKey(seed))
    return {"state": state,
            "step_fn": dplib.make_train_step(_loss_fn(tfm, model, cfg),
                                             optimizer),
            "rows_per_step": int(traffic["rows_per_chip"]) * mesh.size,
            "samples_per_row": int(traffic["seq_len"])}


def check_train(cfg: dict, traffic: dict, seed: int,
                degrade_system: bool = False) -> dict:
    """System against the plain float32 reference on ``reference_tokens``
    (the cell's own ``[1, 8192]`` ids, all five layers): the logits, the
    routing over the held experts, and the parameters' change in one
    optimizer step; the loss and the norm of all gradients beside them.

    The reference is handed the system's parameters in the PUBLISHED layout:
    the rotary columns of ``W_q`` and ``W_kva`` interleaved
    (``published_layout``), which it turns by the published pairing, and the
    bias buffers beside them.

    Top-k is discontinuous (see ``olmoe_1b_7b_d1.py``): ``routing_agreement``
    is the share of the reference's (position, HELD expert) pairs the system
    also chose.  A flipped pair moves the residual stream of every later
    layer, so the logit error is not given apart for positions with a flip.

    ``update_l2``: the system's gradients go through the cell's own
    optimizer (``_optimizer``, from fresh moments, as the window's first
    step), the reference's through adamw written out here
    (``reference_adamw_step``); the number is the norm of the difference of
    the two changes of ALL parameters over the norm of the reference's.  A
    state left unchanged reads 1; ``update_leaf_max`` is the same by leaf,
    the largest, and reads 1 where the optimizer froze a leaf.  adamw's first
    step is ``-lr g / (|g| + eps)``, the gradient's SIGN wherever ``|g|`` is
    well over ``eps``: a reading is twice the root of the share of elements
    whose gradient the two sides sign differently, not a relative rounding
    error.  The bias buffers are no parameters: neither side has a gradient
    or a change for them.

    What it cannot see: as the other configurations' checks, it compiles
    programs of its own from the cell's loss and optimizer, not the
    ``make_train_step`` program the window drives (a test holds the buffers
    through that step at a small size: ``tests/test_moe_sigmoid.py``).

    ``degrade_system`` is for setting the limits, not for a run: the system
    gets the parameters rounded to fp8 (``degraded_to_fp8``), the reference
    the true ones, and the result has to come out not ``ok``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    tfm, model = _model(cfg)
    loss_fn = _loss_fn(tfm, model, cfg)
    optimizer = _optimizer(cfg)
    b, length = cfg["reference_tokens"]
    rng = np.random.default_rng([seed, 78])
    ids = jnp.asarray(rng.integers(0, cfg["vocab_size"], (b, length)),
                      jnp.int32)

    def whole_norm(tree):
        return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                            for g in jax.tree.leaves(tree)))

    def system(params, buffers, ids):
        (loss, _aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, {"input_ids": ids}, buffers)
        logits, sown = model.apply({"params": params, "buffers": buffers},
                                   ids, mutable=["intermediates"])
        change, _ = optimizer.update(grads, optimizer.init(params), params)
        return (loss, logits, published_layout(cfg, change),
                _sown_routing(sown), whole_norm(grads))

    def reference(params, buffers, ids):
        def f(params):
            logits, routing = reference_forward(cfg, params, buffers, ids)
            return reference_loss(logits, ids), (logits, routing)
        (loss, (logits, routing)), grads = jax.value_and_grad(
            f, has_aux=True)(params)
        return (loss, logits, reference_adamw_step(cfg, params, grads),
                routing, whole_norm(grads))

    params, buffers = jax.jit(lambda key: _init_state(cfg, key))(
        jax.random.PRNGKey(seed))
    sys_loss, sys_logits, sys_change, sys_routing, sys_gnorm = jax.jit(
        system)(degraded_to_fp8(params) if degrade_system else params,
                buffers, ids)
    # the system's change waits on the host: the reference needs the room
    sys_change = jax.device_get(sys_change)
    sys_logits = np.asarray(sys_logits, np.float32).reshape(b * length, -1)
    published = jax.jit(lambda p: published_layout(cfg, p))(params)
    del params          # the reference needs the room
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_logits, ref_change, ref_routing, ref_gnorm = jax.jit(
            reference)(published, buffers, ids)
    del published
    ref_logits = np.asarray(ref_logits, np.float32).reshape(b * length, -1)
    diff = sys_logits - ref_logits

    by_leaf = []        # (path, |sys - ref|^2, |ref|^2) in float64
    for (path, ref), own in zip(
            jax.tree_util.tree_flatten_with_path(ref_change)[0],
            jax.tree.leaves(sys_change)):
        ref = np.asarray(ref)
        by_leaf.append((jax.tree_util.keystr(path),
                        float(np.sum(np.square(own - ref), dtype=np.float64)),
                        float(np.sum(np.square(ref), dtype=np.float64))))
    del sys_change, ref_change
    worst_leaf, worst_d, worst_r = max(
        by_leaf, key=lambda row: row[1] / max(row[2], 1e-300))

    first, end = cfg["experts_held"]
    ref_held = _chosen(ref_routing, cfg["router_experts"])[..., first:end]
    per_expert = ref_held.sum(1)                        # [layers, held]
    out = {"held_pairs": int(ref_held.sum()),
           "held_pairs_by_layer": [int(x) for x in per_expert.sum(1)],
           "held_pairs_max_over_mean": float(
               (per_expert.max(1) / np.maximum(per_expert.mean(1), 1e-30))
               .max())}
    if len(sys_routing) == len(ref_routing):
        sys_held = _chosen(sys_routing, cfg["router_experts"])[..., first:end]
        agreement = float((ref_held & sys_held).sum()
                          / max(ref_held.sum(), 1))
    else:       # a program that does not show its routing cannot pass
        agreement = 0.0
    errors = {
        "logits_l2": float(np.linalg.norm(diff) / np.linalg.norm(ref_logits)),
        "logits_max": float(np.abs(diff).max() / np.abs(ref_logits).max()),
        "routing_disagreement": 1.0 - agreement,
        "update_l2": math.sqrt(sum(row[1] for row in by_leaf)
                               / sum(row[2] for row in by_leaf)),
        "update_leaf_max": math.sqrt(worst_d / max(worst_r, 1e-300)),
    }
    return {"errors": errors, "tolerance": TOLERANCE, **out,
            "routing_agreement": agreement,
            "update_leaf_worst": worst_leaf,
            # held to no limit (see TOLERANCE): the loss and the norm of all
            # gradients
            "loss": abs(float(sys_loss) - float(ref_loss))
            / abs(float(ref_loss)),
            "grad_norm": abs(float(sys_gnorm) - float(ref_gnorm))
            / float(ref_gnorm),
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


# Every limit lies between two readings on the chip (TPU v5e, [1, 8192] ids, 5
# layers; PERF.md section 6, PR 39): the largest of the system over its seeds
# (twenty on this tree, fifteen more before the review's repairs for the
# first three) and the smallest of the system on fp8 weights against the
# reference on the true ones (three seeds, six for the first three), near the
# geometric mean of the two; fp8 fails all five:
#   logits_l2            0.01314 .. 0.01325 | fp8 0.1132 .. 0.1135
#   logits_max           0.0161 .. 0.0197   | fp8 0.145 .. 0.170
#   routing_disagreement 0.0090 .. 0.0115   | fp8 0.0803 .. 0.0890
#   update_l2            0.262 .. 0.278     | fp8 0.638 .. 0.642
#   update_leaf_max      0.365 .. 0.423     | fp8 0.851 .. 0.860
# The logits part by 1.3% where SDAR's four layers read 2.6%: no QK-norm
# rounds q and k again, and a sigmoid's top 6 of 128 flip as often as a
# softmax's top 8 (1% of the held pairs a layer).  update_l2 reads a quarter
# and that is no rounding: adamw's first step is the gradient's sign, so it
# is twice the root of the share of the elements with a gradient that the two
# sides sign differently (1.8% on bf16, 10% on fp8; float32 on both sides
# reads 1e-5 at a small size on the CPU); a state left unchanged reads 1.
# update_leaf_max is the same by leaf, the largest (always a late router's
# kernel): a leaf the optimizer froze reads 1.
# Two numbers are reported beside the limits and held to none, because the
# control's readings overlap the system's (a mean over 8,191 targets and a
# norm over 576 M gradients average the rounding away), so no reading stands
# above a limit:
#   loss                 9e-8 .. 2.1e-5     | fp8 1.2e-5 .. 1.8e-4
#   grad_norm            1.7e-6 .. 2.0e-4   | fp8 1.0e-4 .. 1.3e-3
TOLERANCE = {"logits_l2": 0.04, "logits_max": 0.055,
             "routing_disagreement": 0.03, "update_l2": 0.45,
             "update_leaf_max": 0.65}


# ---------------------------------------------------------------------------
# The plain reference: float32 jax.numpy, from the published description
# (transformers' ``deepseek_v3`` modeling code for this config: pre-norm
# RMSNorm; attention with no query latent, keys and values up-projected from
# an RMS-normed latent of 512, RoPE in the INTERLEAVED pairing on the 64
# rotary columns of a query and on ONE rotary key head that is broadcast to
# all 32 heads, softmax scale 192^-1/2 (``rope_scaling`` null: no mscale); a
# dense SwiGLU in layer 0; from layer 1 on sigmoid router scores, the top 6 of
# score + bias, the unbiased scores of the chosen divided by their sum + 1e-20
# and multiplied by 2.448, SwiGLU experts, a shared SwiGLU of 2 x 768; untied
# head; next-token cross-entropy, no auxiliary term).  No kernel, no sort, no
# cache: the rotary key IS broadcast and concatenated, attention goes head by
# head (one head's [8192, 8192] float32 scores are 268 MB) and layer by layer
# (``jax.checkpoint``: the backward computes a layer again), each held expert is
# applied to every position and weighted by the position's routing weight for
# it, which is 0 where it was not chosen, and the logits are whole.
# Departures from the published model, all of the cut: only experts
# ``experts_held`` are summed, the vocabulary is the held slice.  Nothing
# here imports the program's ops/ or parallel/ep.py.
# ---------------------------------------------------------------------------

def published_layout(cfg: dict, params):
    """The program's parameters as the published modeling code lays them out:
    the program turns the rotary columns in half-split pairs ``(i, i + 32)``,
    the published code in interleaved pairs ``(2i, 2i + 1)``; column ``i`` of
    the program's first half is the published column ``2i``, column ``i`` of
    its second half the published ``2i + 1``.  The same permutation on the
    rotary columns of ``W_q`` (every head) and of ``W_kva``: no score sees
    it."""
    import jax.numpy as jnp

    rope = cfg["qk_rope_head_dim"]
    half = jnp.arange(rope // 2)
    # published column j holds the program's column source[j]
    source = jnp.stack([half, half + rope // 2], axis=1).reshape(-1)

    def turned(kernel):     # the last ``rope`` columns of the last axis
        own = kernel[..., :-rope]
        return jnp.concatenate(
            [own, kernel[..., kernel.shape[-1] - rope + source]], axis=-1)

    out = dict(params)
    for layer in range(cfg["num_hidden_layers"]):
        block = dict(out[f"block_{layer}"])
        attn = dict(block["attn"])
        for name in ("q_proj", "kv_a_proj"):
            attn[name] = {"kernel": turned(attn[name]["kernel"])}
        block["attn"] = attn
        out[f"block_{layer}"] = block
    return out


def _rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * scale


def _rope_interleaved(x, theta: float):
    """Interleaved RoPE on ``[B, T, H, D]`` at positions ``0 .. T-1``: the
    pair (2i, 2i + 1) turns by ``position * theta^(-2i/D)``."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def _swiglu(p, y):
    import jax

    return ((jax.nn.silu(y @ p["gate_proj"]["kernel"])
             * (y @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"])


def _reference_moe(cfg: dict, p: dict, bias, y):
    """``[n, d]`` -> the held experts' part of the routed output, and the
    ``[n, k]`` experts each position chose.  ``bias``: the layer's
    ``e_score_correction_bias``, a buffer."""
    import jax
    import jax.numpy as jnp

    e, k = cfg["router_experts"], cfg["num_experts_per_tok"]
    first, end = cfg["experts_held"]
    scores = jax.nn.sigmoid(y @ p["router"]["kernel"])              # [n, e]
    _, top_idx = jax.lax.top_k(scores + bias, k)
    chosen = jax.nn.one_hot(top_idx, e, dtype=jnp.float32).sum(1)   # [n, e]
    weight = scores * chosen                            # the UNBIASED scores
    if cfg["norm_topk_prob"]:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    weight = weight * cfg["routed_scaling_factor"]

    @jax.checkpoint     # keep one expert's activations at a time
    def expert(out, held):
        w, w_gate, w_up, w_down = held
        return out + w[:, None] * (
            (jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down), None

    # a loop over the held experts, one after the other
    out, _ = jax.lax.scan(expert, jnp.zeros_like(y), (
        weight[:, first:end].T, p["experts_gate"], p["experts_up"],
        p["experts_down"]))
    return out, top_idx


def reference_forward(cfg: dict, params, buffers, ids):
    """Logits ``[B, T, V]`` and each expert layer's routing.  ``params`` in
    the published layout (``published_layout``); ``buffers``: the routers'
    selection biases."""
    import jax
    import jax.numpy as jnp

    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h, rank, nope, rope = (cfg["num_attention_heads"], cfg["kv_lora_rank"],
                           cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"])
    d = cfg["hidden_size"]
    b, t = ids.shape
    causal = jnp.tril(jnp.ones((t, t), bool))
    x = params["embed"]["embedding"][ids]

    @jax.checkpoint     # one head's [T, T] scores at a time, again backward
    def head(q, k, v):                                  # [B, T, *] each
        scores = jnp.einsum("bqd,bkd->bqk", q, k) / math.sqrt(nope + rope)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(scores, -1), v)

    def block(x, p, bias, dense: bool):
        """One layer; ``bias``: its router's selection bias (None: dense)."""
        a = p["attn"]
        u = _rms_norm(x, p["attn_norm"]["scale"], eps)
        q = jnp.einsum("bsd,dhk->bshk", u, a["q_proj"]["kernel"])
        kv_a = u @ a["kv_a_proj"]["kernel"]
        c = _rms_norm(kv_a[..., :rank], a["kv_a_norm"]["scale"], eps)
        kv = jnp.einsum("bsr,rhk->bshk", c, a["kv_b_proj"]["kernel"])
        q = jnp.concatenate(
            [q[..., :nope], _rope_interleaved(q[..., nope:], theta)], -1)
        k_r = _rope_interleaved(kv_a[:, :, None, rank:], theta)
        k = jnp.concatenate(        # the one rotary key, copied to each head
            [kv[..., :nope], jnp.broadcast_to(k_r, (b, t, h, rope))], -1)
        out = jax.lax.map(lambda qkv: head(*qkv), tuple(
            x_.transpose(2, 0, 1, 3) for x_ in (q, k, kv[..., nope:])))
        x = x + jnp.einsum("hbqk,hkd->bqd", out, a["o_proj"]["kernel"])
        y = _rms_norm(x, p["mlp_norm"]["scale"], eps)
        if dense:
            return x + _swiglu(p["mlp"], y), None
        moe_out, top_idx = _reference_moe(cfg, p["moe"], bias,
                                          y.reshape(b * t, d))
        return (x + moe_out.reshape(b, t, d) + _swiglu(p["shared"], y),
                top_idx)

    routing = []
    for layer in range(cfg["num_hidden_layers"]):
        dense = layer < cfg["first_k_dense_replace"]
        bias = None if dense else buffers[f"block_{layer}"]["moe"][
            "e_score_correction_bias"]
        # a layer's activations at a time: the backward computes them again
        x, top_idx = jax.checkpoint(block, static_argnums=3)(
            x, params[f"block_{layer}"], bias, dense)
        if not dense:
            routing.append(top_idx)
    x = _rms_norm(x, params["final_norm"]["scale"], eps)
    return x @ params["lm_head"]["kernel"], routing


def reference_loss(logits, ids):
    """Mean next-token cross-entropy: position i predicts id i + 1."""
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(logits[:, :-1])
    return -jnp.mean(jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1))


def reference_adamw_step(cfg: dict, params, grads):
    """The change adamw makes to every parameter in its FIRST step (moments
    from zero, so their bias correction gives back ``g`` and ``g^2``), optax's
    defaults written out: ``-lr (g / (sqrt(g^2) + 1e-8) + 1e-4 p)``."""
    import jax
    import jax.numpy as jnp

    rate = cfg["optimizer"]["learning_rate"]
    return jax.tree.map(
        lambda p, g: -rate * (g / (jnp.sqrt(jnp.square(g)) + 1e-8)
                              + 1e-4 * p), params, grads)
