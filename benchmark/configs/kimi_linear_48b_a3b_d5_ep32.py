"""Kimi-Linear-48B-A3B-Instruct (``model_type`` ``kimi_linear``) trained at
its published widths: one chip's share of a 32-way expert-parallel stage,
depth cut to layers 1-5 of the published lists (KDA, KDA, KDA, MLA, KDA: the
leading dense layer and one whole period of the 3 : 1 pattern), rows of 16k.

The system under test is the program's ``models/transformer.py`` with what
this model needs of it: a ``Block`` whose attention slot differs in KIND
layer by layer (``Transformer.layer_attention``): Kimi Delta Attention
(``KimiDeltaAttention``: 32 heads of 128 key and value channels, a
depthwise causal conv of 4 taps and SiLU on q, k and v, L2-normed q and k,
a decay that is a VECTOR over a head's 128 key channels through a low-rank
pair, the gated delta rule of ``ops/kda.py`` in chunks of 64, an output norm
gated through a second low-rank pair) in four of the five layers, and latent
attention WITHOUT rotation in the fourth (``Attention.latent`` with ``rope``
False: 32 query heads of 128 + 64 over per-head keys of 128 and values of 128
from ONE normed latent of 512, and ONE 64-wide key head that every query head
meets, through the three flash kernels of ``ops/attention.py`` as their shared
key); a leading dense layer (``Transformer.layer_ffn``) and DeepSeek-V3's
router in ``parallel/ep.py`` (sigmoid scores, a selection bias that is a
buffer, top 8 of 256, the unbiased scores of the chosen renormalised and
scaled by 2.446, one shared expert of 1024) of which this chip holds experts
0-7; next-token cross-entropy fused with the head, through
``parallel/dp.py``'s ``make_train_step`` under adamw with ``remat``.  See
``resnet50.py`` for the names a configuration module provides.

What the public config does not give is listed, each with its reason, under
``assumed`` in the JSON file: the decay's parametrisation, the two low-rank
widths, no bias on the gate's second map, the L2 norm's epsilon, the
initialisers, what ``mla_use_nope`` means beside a non-zero
``qk_rope_head_dim``, the selection bias, the job, the learning rate,
``vocab_chunk``, ``remat``, the chunk and the scales of the seeded state.
"""

from __future__ import annotations

import functools
import json
import math

SAMPLE_UNIT = "tok"


def layer_kinds(cfg: dict) -> list:
    """``"kda"`` or ``"latent"`` for each layer that runs: the published
    1-based lists' entries up to ``num_hidden_layers``."""
    linear = cfg["linear_attn_config"]
    kinds = {layer: "kda" for layer in linear["kda_layers"]}
    kinds.update({layer: "latent" for layer in linear["full_attn_layers"]})
    return [kinds[layer + 1] for layer in range(cfg["num_hidden_layers"])]


def system_config(cfg: dict) -> dict:
    """The published keys, as the program's builder names them."""
    dense, linear = cfg["first_k_dense_replace"], cfg["linear_attn_config"]
    out = {"model": "transformer",
           "vocab_size": cfg["vocab_size"], "d_model": cfg["hidden_size"],
           "n_layers": cfg["num_hidden_layers"],
           "n_heads": cfg["num_attention_heads"],
           # mla_use_nope: neither kind of layer turns anything
           "layer_attention": [[0, False, kind] for kind in layer_kinds(cfg)],
           "kda": {"n_heads": linear["num_heads"],
                   "head_dim": linear["head_dim"],
                   "conv_kernel": linear["short_conv_kernel_size"],
                   "chunk_size": cfg["kda_chunk"]},
           "latent_attention": {key: cfg[key] for key in (
               "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
               "v_head_dim")},
           "layer_ffn": [cfg["intermediate_size"]] * dense
           + [0] * (cfg["num_hidden_layers"] - dense),
           "d_ff": cfg["moe_intermediate_size"],
           "n_experts": cfg["router_experts"],
           "moe_held": cfg["experts_held"],
           "moe_top_k": cfg["num_experts_per_token"],
           "moe_capacity_factor": None,            # dropless
           "moe_norm_topk_prob": cfg["moe_renormalize"],
           "moe_router": {"scoring": cfg["moe_router_activation_func"],
                          "selection_bias": True,
                          "routed_scale": cfg["routed_scaling_factor"],
                          "n_group": cfg["num_expert_group"]},
           "moe_shared_d_ff": (cfg["num_shared_experts"]
                               * cfg["moe_intermediate_size"]),
           "norm_eps": cfg["rms_norm_eps"],
           "rope_theta": cfg["rope_theta"], "bf16": True,
           "remat": bool(cfg.get("remat", False))}
    for key in ("attn_impl", "bf16", "kda_state_dtype"):    # rehearsal, tests
        if key in cfg:
            out[key] = cfg[key]
    return out


# ---------------------------------------------------------------------------
# Operations and bytes, from shapes (2 per multiply-add; no recompute).
# ---------------------------------------------------------------------------

def causal_pairs(length: int) -> int:
    return length * (length + 1) // 2


def layers_of(cfg: dict, kind: str) -> int:
    return layer_kinds(cfg).count(kind)


def _kda_weights(cfg: dict) -> int:
    """Matmul weights a position passes in a KDA mixer: W_q, W_k, W_v, W_o,
    the two low-rank pairs and β's map."""
    d, linear = cfg["hidden_size"], cfg["linear_attn_config"]
    head, inner = linear["head_dim"], linear["num_heads"] * linear["head_dim"]
    return 4 * d * inner + 2 * (d * head + head * inner) + d * linear[
        "num_heads"]


def _latent_weights(cfg: dict) -> int:
    """W_q, W_kva, W_kvb and W_o of the latent layer."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rank, nope, rope, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                            cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    return (d * h * (nope + rope) + d * (rank + rope)
            + rank * h * (nope + dv) + h * dv * d)


def held_pairs_per_position(cfg: dict) -> float:
    """EXPECTED pairs a position sends to the experts held here: its 8
    choices spread evenly over the router's 256 experts, 8 of them here."""
    first, end = cfg["experts_held"]
    return (cfg["num_experts_per_token"] * (end - first)
            / cfg["router_experts"])


def _expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def flops_per_sample(cfg: dict, traffic: dict) -> float:
    """Training FLOPs this chip must do per token: 6 per matmul weight a
    position passes (forward 2, backward 4): the KDA mixers' projections and
    maps, the latent layer's projections, the dense SwiGLU in the leading
    layer, the router, the shared expert and the EXPECTED held pairs' experts
    in the expert layers, the head over the held slice of the vocabulary; the
    latent layer's kernels over the causal pairs (``mla1_flash_*_cost``) and
    the KDA layers' chunked products and solves (``kda_scan_cost``)."""
    d, length = cfg["hidden_size"], int(traffic["seq_len"])
    ff = cfg["moe_intermediate_size"]
    expert_layer = (d * cfg["router_experts"]
                    + 3 * d * ff * cfg["num_shared_experts"]
                    + held_pairs_per_position(cfg) * 3 * d * ff)
    weights = (layers_of(cfg, "kda") * _kda_weights(cfg)
               + layers_of(cfg, "latent") * _latent_weights(cfg)
               + cfg["first_k_dense_replace"] * 3 * d
               * cfg["intermediate_size"]
               + _expert_layers(cfg) * expert_layer
               + d * cfg["vocab_size"])
    kernels = (layers_of(cfg, "latent")
               * (mla1_flash_fwd_cost(cfg, traffic, 1)["flops"]
                  + mla1_flash_bwd_cost(cfg, traffic, 1)["flops"])
               + kda_scan_cost(cfg, traffic, 1)["flops"])
    return 6.0 * weights + kernels / length


def kda_scan_cost(cfg: dict, traffic: dict, rows_on_device: int) -> dict:
    """FLOPs and HBM bytes the gated delta rule NEEDS in one STEP (the four
    KDA layers, forward and backward), from the MATHEMATICS at the stated
    chunk ``C``, whatever computes it.  A chunk and head, forward, in
    multiply-adds, with ``d`` = ``d_k`` = ``d_v``: the strictly lower scores
    of k with k (``C(C-1)/2 · d``) and the lower ones of q with k
    (``C(C+1)/2 · d``); the unit lower triangular solve for ``W`` and ``U``
    by substitution (``C(C-1)/2 · 2d``); ``W·S``, ``Q·S`` and the state's
    ``Kᵀ·Ũ`` (``C · d · d`` each); the scores' product with ``Ũ`` (``C(C+1)/2
    · d``).  The backward is two products for each of the forward's.  Bytes:
    q, k, v and o (``H · d`` wide, bf16), g (``H · d``, float32) and β (``H``,
    float32) through HBM once forward, and their cotangents once backward.
    The sub-blocks that keep the exponents <= 0, the explicit inverse,
    layouts, chunk states and the backward's recompute are the formulation's
    own and are not counted."""
    length, c = int(traffic["seq_len"]), cfg["kda_chunk"]
    linear = cfg["linear_attn_config"]
    h, d = linear["num_heads"], linear["head_dim"]
    positions = rows_on_device * length
    # multiply-adds a position and head: the sums above over C
    macs = ((c - 1) / 2 * d + (c + 1) / 2 * d + (c - 1) / 2 * 2 * d
            + 3 * d * d + (c + 1) / 2 * d)
    forward = 2 * positions * h * macs
    once = positions * (2 * 4 * h * d + 4 * h * d + 4 * h)
    layers = layers_of(cfg, "kda")
    return {"flops": float(layers * 3 * forward),
            "bytes": float(layers * 2 * once)}


def mla1_flash_fwd_cost(cfg: dict, traffic: dict, rows_on_device: int) -> dict:
    """FLOPs and HBM bytes the latent layer's forward kernel NEEDS for one
    call, counted as ``kanana2_30b_a3b_d5_ep8.py`` counts them: over the
    causal pairs, all 32 heads, the score at 192 (128 + 64) and the values at
    128; q, each head's key and value, the ONE shared key once, the output
    (bf16) and the log-sum-exp (float32)."""
    length, h = int(traffic["seq_len"]), cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    flops = rows_on_device * 2 * causal_pairs(length) * h * (nope + rope + dv)
    positions = rows_on_device * length
    bytes_ = positions * (2 * (h * (nope + rope) + h * nope + rope
                               + 2 * h * dv) + 4 * h)
    return {"flops": float(flops), "bytes": float(bytes_)}


def mla1_flash_bwd_cost(cfg: dict, traffic: dict, rows_on_device: int) -> dict:
    """The same for the backward of the ONE latent layer in a step: the
    scores once and dq and dk at 192, dp and dv at 128; it reads q, each
    head's key, the shared key, v, o, dO and the log-sum-exp and writes dq,
    the heads' dk, dv and the ONE shared key's gradient."""
    length, h = int(traffic["seq_len"]), cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    flops = (rows_on_device * 2 * causal_pairs(length) * h
             * (3 * (nope + rope) + 2 * dv))
    positions = rows_on_device * length
    reads = 2 * (h * (nope + rope) + h * nope + rope + 3 * h * dv) + 4 * h
    writes = 2 * (h * (nope + rope) + h * nope + rope + h * dv)
    layers = layers_of(cfg, "latent")
    return {"flops": float(layers * flops),
            "bytes": float(layers * positions * (reads + writes))}


def moe_experts_cost(cfg: dict, traffic: dict, rows_on_device: int) -> dict:
    """FLOPs and HBM bytes the EXPECTED held pairs need in the routed
    experts' matmuls of one STEP (the four expert layers, forward and
    backward), counted as ``kanana2_30b_a3b_d5_ep8.py`` counts them: three ``d
    x f`` matrices a pair, forward once and backward twice; five passes of
    the pairs' rows and three of the held weights in bf16."""
    pairs = (rows_on_device * int(traffic["seq_len"])
             * held_pairs_per_position(cfg))
    d, ff = cfg["hidden_size"], cfg["moe_intermediate_size"]
    first, end = cfg["experts_held"]
    weights = (end - first) * 3 * d * ff
    layers = _expert_layers(cfg)
    flops = layers * 3 * 2 * pairs * 3 * d * ff
    bytes_ = layers * 2 * (5 * pairs * d + 3 * weights)
    return {"flops": float(flops), "bytes": float(bytes_)}


KERNELS = {"kda_scan": kda_scan_cost,
           "mla1_flash_fwd": mla1_flash_fwd_cost,
           "mla1_flash_bwd": mla1_flash_bwd_cost,
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

    # the builder ignores keys it does not know: a program from before these
    # existed would build rotating latent attention in all five layers under
    # this model's name.  It cannot run this configuration, and says so at
    # once.
    lacking = [key for key in ("kda", "kda_state_dtype")
               if key not in getattr(tfm.Transformer, "__dataclass_fields__",
                                     {})]
    if lacking or not hasattr(tfm, "KimiDeltaAttention"):
        raise NotImplementedError(
            f"models/transformer.py of this program has no {lacking or 'KDA'}"
            ": it cannot build Kimi-Linear's Kimi Delta Attention layers nor "
            "its latent attention without rotation")
    return tfm, tfm.build_transformer(system_config(cfg))


def _loss_fn(tfm, model, cfg: dict):
    # no auxiliary term: the router sows none under its selection bias
    return tfm.make_loss_fn(model, aux_loss_coef=0.0,
                            vocab_chunk=int(cfg["vocab_chunk"]),
                            router_z_coef=0.0)


def _init_state(cfg: dict, key):
    """``(params, buffers)`` from the key, through a twin of the model with
    XLA attention on one chunk of positions (see ``phi3_mini_d4.py``), by the
    program's own initialisers (the public layer's for ``A_log`` and
    ``dt_bias``) but for three scales (``seeded_state`` in the JSON file, and
    why): the embedding's standard deviation, a factor on the latent layer's
    ``W_q``, and the standard deviation of the routers' bias buffers (flax
    draws them 0)."""
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import transformer as tfm

    twin = tfm.build_transformer({**system_config(cfg), "attn_impl": "xla",
                                  "remat": False})
    variables = twin.init(key, jnp.zeros((1, cfg["kda_chunk"]), jnp.int32))
    params, buffers = variables["params"], variables["buffers"]
    seeded = cfg["seeded_state"]
    # flax draws the embedding at 1 / sqrt(hidden)
    params["embed"]["embedding"] = (
        params["embed"]["embedding"] * math.sqrt(cfg["hidden_size"])
        * seeded["embedding_std"])
    for layer, kind in enumerate(layer_kinds(cfg)):
        if kind == "latent":
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


# optax.adamw's defaults, as ``reference_adamw_change`` writes them out
_ADAM_B1, _ADAM_EPS, _WEIGHT_DECAY = 0.9, 1e-8, 1e-4


@functools.cache
def _program(config: str):
    """``(model, optimizer, step)`` of a configuration (its JSON text), ONE
    jitted ``make_train_step`` in a process: the check steps it and the
    window lowers it again, the same trace and so the cache's entry
    (``smallthinker_21b_a3b_d8_ep8.py`` says why two functions would not
    do)."""
    import optax

    from tensorflowonspark_tpu.parallel import dp as dplib

    cfg = json.loads(config)
    tfm, model = _model(cfg)            # refuses a program that lacks them
    # adamw decays every leaf it is given (1e-4 by optax's default): it is
    # given the parameters, never the routers' bias buffers
    optimizer = optax.adamw(cfg["optimizer"]["learning_rate"])
    return model, optimizer, dplib.make_train_step(
        _loss_fn(tfm, model, cfg), optimizer)


def _train(cfg: dict, mesh, seed: int):
    """``(model, state, step)``: the seeded train state on ``mesh`` and the
    cell's step program, for the window and for the check alike."""
    import jax

    from tensorflowonspark_tpu.parallel import dp as dplib
    from tensorflowonspark_tpu.parallel import mesh as meshlib

    model, optimizer, step = _program(json.dumps(cfg, sort_keys=True))

    def create(key):
        params, buffers = _init_state(cfg, key)
        return dplib.TrainState.create(params, optimizer, buffers)

    state = jax.jit(create, out_shardings=meshlib.replicated(mesh))(
        jax.random.PRNGKey(seed))
    return model, state, step


def build_train(cfg: dict, traffic: dict, mesh, seed: int) -> dict:
    _, state, step = _train(cfg, mesh, seed)
    return {"state": state, "step_fn": step,
            "rows_per_step": int(traffic["rows_per_chip"]) * mesh.size,
            "samples_per_row": int(traffic["seq_len"])}


CONTROLS = ("fp8", "bf16_state", "no_erase", "frozen")


def check_train(cfg: dict, traffic: dict, seed: int,
                degrade_system=False) -> dict:
    """System against the plain float32 reference on ``reference_tokens``
    (the cell's own ``[1, 16384]`` row, all five layers): the logits, the
    routing over the held experts (``routing_disagreement``: the share of the
    reference's (position, HELD expert) pairs that the system did not
    choose), and ONE STEP OF THE WINDOW'S OWN PROGRAM from the seeded state
    (``_train``'s ``make_train_step`` on the row, compiled here and loaded
    from the cache by the window), as ``smallthinker_21b_a3b_d8_ep8.py``
    reads it: from the state that step leaves, its gradients (adamw's first
    moment after one step from zero is ``(1 - b1) g``) against the
    reference's by leaf, the worst of the KDA mixers' leaves of at least
    ``_LEAF_MIN`` values (``grad_kda_leaf_max``: what the chunked op's
    backward feeds: the projections, the convs, both low-rank pairs, β's
    map) and the worst of all such leaves (``grad_leaf_max``), and the
    parameters' change against adamw written out on the reference's
    gradients: ``update_l2`` over all parameters, ``update_leaf_max`` by
    leaf.  A state left unchanged reads 1 in all four.  adamw's first step
    is the gradient's SIGN wherever ``|g|`` is well over ``eps``, so an
    update's reading is twice the root of the share of elements the two
    sides sign differently (``kanana2_30b_a3b_d5_ep8.py``).  The leaves of a
    value a head (``A_log``, 32) or a channel of one head (``o_norm``, 128)
    are in ``update_l2`` and their worst gradient is reported beside the
    limits (``grad_small_leaf_max``), held to none: ONE value signed
    differently reads what a control reads (``nemotron3_super_d11_tp8_
    ep64.py``).  The bias buffers are no parameters: neither side has a
    gradient or a change for them.

    ``degrade_system`` is for setting the limits and for the negative
    controls, not for a run, and each has to come out not ``ok``: ``"fp8"``
    (or True) hands the system the parameters rounded to fp8
    (``degraded_to_fp8``); ``"bf16_state"`` builds the system with the KDA
    op's running sums, decays and carried state held in bf16
    (``Transformer.kda_state_dtype``); ``"no_erase"`` hands the REFERENCE a
    recurrence without the delta rule's erase, ``S_t = Diag(α_t) S_{t-1} +
    β_t k_t v_tᵀ`` (a plain gated linear attention: what a system that left
    the term out would compute, so the sound system has to read as far from
    it as that system from the sound reference); ``"frozen"`` leaves the
    state as it was in place of the step's."""
    import jax
    import numpy as np
    import optax

    from tensorflowonspark_tpu.parallel import mesh as meshlib

    if degrade_system == "bf16_state":
        cfg = {**cfg, "kda_state_dtype": "bfloat16"}
    b, length = cfg["reference_tokens"]
    ids = np.random.default_rng([seed, 78]).integers(
        0, cfg["vocab_size"], (b, length)).astype(np.int32)
    # the node's mesh on a one-chip machine, so the node's program
    mesh = meshlib.make_mesh(jax.devices()[:1], dp=-1)
    model, state, step = _train(cfg, mesh, seed)
    # the chip holds one side at a time: what the other needs waits on the host
    params = before = jax.device_get(state.params)
    buffers = jax.device_get(state.buffers)
    if degrade_system in (True, "fp8"):
        # op by op: inside one program the compiler may drop a cast down and
        # up again as excess precision, and the control would be the system
        state = state._replace(params=degraded_to_fp8(state.params))
        before = jax.device_get(state.params)
    batch = meshlib.shard_batch(mesh, {"input_ids": ids})

    def system_forward(params, buffers, ids):
        logits, sown = model.apply({"params": params, "buffers": buffers},
                                   ids, mutable=["intermediates"])
        return logits, _sown(sown, "top_idx")

    with jax.set_mesh(mesh):        # as the window: the kernels read it
        sys_logits, sys_routing = jax.device_get(jax.jit(system_forward)(
            state.params, state.buffers, batch["input_ids"]))
        if degrade_system == "frozen":
            metrics = {"lm_loss": np.nan}
        else:
            state, metrics = step.lower(state, batch).compile()(state, batch)
    sys_loss = float(metrics["lm_loss"])
    moved = jax.tree.map(np.subtract, jax.device_get(state.params), before)
    first_moment = jax.device_get(
        optax.tree_utils.tree_get(state.opt_state, "mu"))
    del state, before

    erase = degrade_system != "no_erase"

    def reference(params, buffers, ids):
        def f(params):
            logits, routing = reference_forward(cfg, params, buffers, ids,
                                                erase=erase)
            return reference_loss(logits, ids), (logits, routing)
        (loss, (logits, routing)), grads = jax.value_and_grad(
            f, has_aux=True)(params)
        return loss, logits, routing, grads

    params = jax.device_put(params)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_logits, ref_routing, ref_grads = jax.jit(reference)(
            params, buffers, ids)
    ref_loss, ref_logits, ref_routing = jax.device_get(
        (ref_loss, ref_logits, ref_routing))
    by_leaf = jax.device_get(jax.jit(_step_errors, static_argnums=0)(
        cfg["optimizer"]["learning_rate"], params, ref_grads, first_moment,
        moved))
    del params, ref_grads, first_moment, moved
    ref_logits = ref_logits.astype(np.float32).reshape(b * length, -1)
    diff = sys_logits.astype(np.float32).reshape(b * length, -1) - ref_logits

    readings = _step_readings(cfg, [
        (jax.tree_util.keystr(path), *(float(x) for x in sums))
        for path, sums in jax.tree_util.tree_flatten_with_path(by_leaf)[0]])

    first, end = cfg["experts_held"]
    ref_held = _chosen(ref_routing, cfg["router_experts"])[..., first:end]
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
        **readings["errors"],
    }
    by_expert = ref_held.sum(1)                 # [layers, held experts]
    return {"errors": errors, "tolerance": TOLERANCE, **readings["beside"],
            # beside the limits and held to none (see TOLERANCE)
            "loss": abs(sys_loss - float(ref_loss)) / abs(float(ref_loss)),
            # pairs a LAYER sends the held experts (the even share: 4,096)
            "held_pairs": float(ref_held.sum() / len(ref_routing)),
            "held_pairs_by_layer": [int(x) for x in by_expert.sum(1)],
            "held_pairs_max_over_mean": float(
                (by_expert.max(1) / np.maximum(by_expert.mean(1), 1e-30))
                .max()),
            "lm_loss": float(ref_loss),
            "ok": all(errors[k] < TOLERANCE[k] for k in errors)}


_LEAF_MIN = 1024        # the readings by leaf take leaves of at least so many


def _step_readings(cfg: dict, rows) -> dict:
    """The step's errors from ``_step_errors``' sums by leaf, rows of (leaf,
    |g - g_ref|^2, |g_ref|^2, |change - ref|^2, |ref change|^2, size): the
    worst leaf's gradient among the KDA mixers' and among all leaves of at
    least ``_LEAF_MIN`` values, the update over all parameters and its worst
    such leaf; beside them the smaller leaves' worst gradient and the
    gradients' error over all leaves at once."""
    def share(part, whole):
        return math.sqrt(part / max(whole, 1e-300))

    def worst(rows, part, whole):
        row = max(rows, key=lambda r: r[part] / max(r[whole], 1e-300))
        return row[0], share(row[part], row[whole])

    kda_blocks = [f"['block_{layer}']['attn']"
                  for layer, kind in enumerate(layer_kinds(cfg))
                  if kind == "kda"]
    large = [r for r in rows if r[5] >= _LEAF_MIN]
    small = [r for r in rows if r[5] < _LEAF_MIN]
    kda = [r for r in large if any(r[0].startswith(p) for p in kda_blocks)]
    (kda_leaf, kda_grad), (grad_leaf, grad), (update_leaf, update) = (
        worst(kda, 1, 2), worst(large, 1, 2), worst(large, 3, 4))
    small_leaf, small_grad = worst(small, 1, 2)
    return {"errors": {"grad_kda_leaf_max": kda_grad, "grad_leaf_max": grad,
                       "update_l2": share(sum(r[3] for r in rows),
                                          sum(r[4] for r in rows)),
                       "update_leaf_max": update},
            "beside": {"grad_kda_leaf_worst": kda_leaf,
                       "grad_leaf_worst": grad_leaf,
                       "update_leaf_worst": update_leaf,
                       "grad_small_leaf_max": small_grad,
                       "grad_small_leaf_worst": small_leaf,
                       "grad_l2": share(sum(r[1] for r in rows),
                                        sum(r[2] for r in rows))}}


def reference_adamw_change(rate: float, p, g):
    """What adamw adds to a parameter in its FIRST step (moments from zero,
    so their bias correction gives back ``g`` and ``g^2``), optax's defaults
    written out: ``-lr (g / (sqrt(g^2) + 1e-8) + 1e-4 p)``."""
    import jax.numpy as jnp

    return -rate * (g / (jnp.sqrt(jnp.square(g)) + _ADAM_EPS)
                    + _WEIGHT_DECAY * p)


def reference_adamw_step(cfg: dict, params, grads):
    """``reference_adamw_change`` over a whole tree."""
    import jax

    rate = cfg["optimizer"]["learning_rate"]
    return jax.tree.map(lambda p, g: reference_adamw_change(rate, p, g),
                        params, grads)


def _step_errors(rate: float, params, ref_grads, first_moment, moved):
    """By leaf, four squared norms and the leaf's size: the system's gradient
    (from the first moment its step left) less the reference's, the
    reference's gradient, the system's change of the parameter less the
    reference's, the reference's change."""
    import jax
    import jax.numpy as jnp

    def leaf(p, g, m, d):
        change = reference_adamw_change(rate, p, g)
        return jnp.stack([
            jnp.sum(jnp.square(m / (1.0 - _ADAM_B1) - g)),
            jnp.sum(jnp.square(g)),
            jnp.sum(jnp.square(d - change)), jnp.sum(jnp.square(change)),
            jnp.asarray(float(p.size))])

    return jax.tree.map(leaf, params, ref_grads, first_moment, moved)


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


# Every limit lies between two readings on the chip (TPU v5e, the cell's own
# [1, 16384] row, 5 layers; PERF.md section 6, PR 52), near their geometric
# mean: the largest of the system over its eight seeds and the smallest of a
# control (one seed each): the system on weights rounded to fp8 against the
# reference on the true ones, which fails all seven; the system whose KDA op
# holds its running sums, decays and carried state in bf16, which fails
# ``grad_kda_leaf_max`` alone (the KDA layers' gradients are what the op's
# backward feeds; its other readings lie inside or just above the system's);
# the reference WITHOUT the delta rule's erase, which fails all seven by far
# (the seeded state tells the rule from its absence).
# System | fp8 weights | bf16 op state | no erase:
#   logits_l2            0.01226 .. 0.01241 | 0.1269 | 0.0158 | 0.404
#   logits_max           0.0119 .. 0.0137   | 0.1258 | 0.0195 | 0.416
#   routing_disagreement 0.0101 .. 0.0128   | 0.1010 | 0.0153 | 0.265
#   grad_kda_leaf_max    0.0261 .. 0.0270   | 0.2981 | 0.0455 | 1.19
#   grad_leaf_max        0.197 .. 0.242     | 0.640  | 0.2635 | 1.19
#   update_l2            0.2228 .. 0.2376   | 0.6066 | 0.2631 | 0.984
#   update_leaf_max      0.356 .. 0.372     | 0.862  | 0.3916 | 1.26
# The logits part by 1.2%, as Kanana-2's and Nemotron-3's.  The KDA mixers'
# gradients part by 2.7% (the worst always the last KDA layer's ``k_conv`` or
# a map of its decay's pair): the chunked op's backward against the scan over
# positions.  The worst leaf of all is always a late ROUTER's kernel at 20 to
# 24%, and that is the routing, not a rounding: 1.0 to 1.3% of the held pairs
# flip, a flipped pair takes a position's whole share out of one expert's
# gradient and puts it into another's, and the router's own gradient is the
# sum of what those choices weigh.  update_l2 reads 0.23 as in the four
# configurations before: adamw's first step is the gradient's sign, so it is
# twice the root of the share of elements that the two sides sign differently
# (1.3% on bf16, 9% on fp8); a state left unchanged reads 1 in the step's
# four readings by construction.
# Reported beside the limits and held to none, because the controls' readings
# overlap the system's or a single value decides them:
#   loss                 1.6e-6 .. 1.6e-5   | 9.9e-5 | 9.4e-6 | 1.9e-4
#   grad_small_leaf_max  0.025 .. 0.035     | 0.298  | 0.051  | 1.03
#   grad_l2              0.0203 .. 0.0206   | 0.2167 | 0.0273 | 0.706
# At a small size on the CPU, in float32, every reading is under 3e-6 (the
# update's 7e-3 and 3e-2: the change is read off float32 parameters at a rate
# of 1e-6) and fp8, the missing erase and a frozen state fail; the bf16 op
# state reads a thousand times the sound system's logits and KDA gradients
# (tests/benchmark/test_benchmark_kimi_linear.py).
TOLERANCE = {"logits_l2": 0.04, "logits_max": 0.04,
             "routing_disagreement": 0.035, "grad_kda_leaf_max": 0.035,
             "grad_leaf_max": 0.39, "update_l2": 0.38,
             "update_leaf_max": 0.56}


# ---------------------------------------------------------------------------
# The plain reference: float32 jax.numpy, from the layers' equations (ISSUE
# 52: the catalog's row, the paper arXiv:2510.26692 §3-4, the public modeling
# code's order of operations).  KDA as its EQUATION: the three short convs as
# four shifted adds each, SiLU, the L2 norm written out, the decay ``g =
# -exp(A_log) softplus(f + dt_bias)``, and the recurrence ``S_t = (I - β_t k_t
# k_tᵀ) Diag(exp g_t) S_{t-1} + β_t k_t v_tᵀ``, ``o_t = S_tᵀ q_t`` as a
# ``lax.scan`` over POSITIONS (not the chunked form the system computes: no
# chunk is said anywhere below), the gated norm written out.  Latent attention
# a head at a time and a block of queries at a time over dense ``[block, L]``
# float32 scores, the ONE shared key broadcast and concatenated, nothing
# rotated.  The experts one at a time over the held range, each applied to
# every position and weighted by the position's routing weight for it (0
# where it was not chosen); the logits whole.  Computed in blocks so that it
# fits (``jax.checkpoint`` changes no number): the scan over positions is an
# outer scan over blocks of ``_SCAN_BLOCK`` positions whose inner scan runs
# again in the backward (every state of 16,384 positions kept would be 34 GB
# a layer), and a layer's activations at a time.  Departures from the
# published model, all of the cut: only experts ``experts_held`` are summed,
# the vocabulary is the held slice, 5 of the 27 layers run.  Nothing here
# imports the program's ops/, models/ or parallel/ep.py.
# ---------------------------------------------------------------------------

_SCAN_BLOCK = 128       # positions an inner scan keeps states for: memory only


def _rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * scale


def _silu(x):
    import jax.numpy as jnp

    return x / (1.0 + jnp.exp(-x))


def _sigmoid(x):
    import jax.numpy as jnp

    return 1.0 / (1.0 + jnp.exp(-x))


def _swiglu(p, y):
    return ((_silu(y @ p["gate_proj"]["kernel"])
             * (y @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"])


def _short_conv(x, kernel):
    """Depthwise causal conv, no bias: tap ``k`` of ``kernel`` ``[taps,
    channels]`` meets the position ``taps - 1 - k`` before; then SiLU."""
    import jax.numpy as jnp

    taps, length = kernel.shape[0], x.shape[1]
    out = jnp.zeros_like(x)
    for k in range(taps):
        back = taps - 1 - k
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, :length - back]], axis=1)
        out = out + shifted * kernel[k]
    return _silu(out)


def _reference_kda(cfg: dict, p: dict, u, erase: bool = True):
    """``[B, L, d]`` -> a KDA mixer's output.  ``erase`` False leaves the
    delta rule's ``- β k kᵀ`` out (``check_train``'s control)."""
    import jax
    import jax.numpy as jnp

    linear = cfg["linear_attn_config"]
    h, d = linear["num_heads"], linear["head_dim"]
    b, length, _ = u.shape
    heads = lambda x: x.reshape(b, length, h, d)    # noqa: E731
    project = lambda name: jnp.einsum(              # noqa: E731
        "bld,dhk->blhk", u, p[name]["kernel"]).reshape(b, length, h * d)
    unit = lambda x: x / jnp.sqrt(                  # noqa: E731
        jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)
    q = unit(heads(_short_conv(project("q_proj"), p["q_conv"]))) / math.sqrt(d)
    k = unit(heads(_short_conv(project("k_proj"), p["k_conv"])))
    v = heads(_short_conv(project("v_proj"), p["v_conv"]))
    pair = lambda name: (u @ p[f"{name}_a_proj"]["kernel"]  # noqa: E731
                         ) @ p[f"{name}_b_proj"]["kernel"]
    g = (-jnp.exp(p["A_log"])[:, None]
         * heads(jnp.logaddexp(pair("f") + p["dt_bias"], 0.0)))  # softplus
    beta = _sigmoid(u @ p["b_proj"]["kernel"])                  # [B, L, h]

    def position(state, inputs):                    # state [B, h, d_k, d_v]
        q_t, k_t, v_t, g_t, beta_t = inputs
        state = jnp.exp(g_t)[..., None] * state
        write = v_t
        if erase:       # what the decayed state already holds under k_t
            write = v_t - jnp.einsum("bhk,bhkv->bhv", k_t, state)
        state = state + (beta_t[..., None, None] * k_t[..., None]
                         * write[..., None, :])
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    @jax.checkpoint     # the block's states are made again backward
    def block(state, inputs):
        return jax.lax.scan(position, state, inputs)

    blocks = length // _SCAN_BLOCK if length % _SCAN_BLOCK == 0 else 1
    by_block = lambda t: t.swapaxes(0, 1).reshape(  # noqa: E731
        (blocks, length // blocks) + t.shape[:1] + t.shape[2:])
    _, o = jax.lax.scan(block, jnp.zeros((b, h, d, d), jnp.float32),
                        tuple(by_block(t) for t in (q, k, v, g, beta)))
    o = o.reshape((length, b, h, d)).swapaxes(0, 1)
    # the gated norm: over each head's channels, one weight a channel
    o = o / jnp.sqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                     + cfg["rms_norm_eps"])
    o = o * p["o_norm"] * _sigmoid(heads(pair("g")))
    return jnp.einsum("blhk,hkd->bld", o, p["o_proj"]["kernel"])


def _reference_latent(cfg: dict, p: dict, u):
    """Causal latent attention without rotation: the score of head ``j`` is
    ``(q_a · k_j + q_b · k_s) / sqrt(192)``, ``k_s`` the ONE shared key."""
    import jax
    import jax.numpy as jnp

    h, rank, nope, rope = (cfg["num_attention_heads"], cfg["kv_lora_rank"],
                           cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"])
    b, t, _ = u.shape
    block = min(int(cfg["reference_query_block"]), t)
    q = jnp.einsum("bsd,dhk->bshk", u, p["q_proj"]["kernel"])
    kv_a = u @ p["kv_a_proj"]["kernel"]
    c = _rms_norm(kv_a[..., :rank], p["kv_a_norm"]["scale"],
                  cfg["rms_norm_eps"])
    kv = jnp.einsum("bsr,rhk->bshk", c, p["kv_b_proj"]["kernel"])
    k = jnp.concatenate(            # the one shared key, copied to each head
        [kv[..., :nope],
         jnp.broadcast_to(kv_a[:, :, None, rank:], (b, t, h, rope))], -1)
    v = kv[..., nope:]

    @jax.checkpoint     # one block of queries of one head against every key
    def queries(first, q_blk, k_head, v_head):
        visible = (jnp.arange(t)[None, :]
                   <= first + jnp.arange(block)[:, None])
        scores = jnp.einsum("bqd,bkd->bqk", q_blk, k_head) / math.sqrt(
            nope + rope)
        alpha = jax.nn.softmax(jnp.where(visible[None], scores, -jnp.inf), -1)
        return jnp.einsum("bqk,bkd->bqd", alpha, v_head)

    def head(qkv):
        q_head, k_head, v_head = qkv                # [B, T, *]
        n = t // block
        out = jax.lax.map(
            lambda xs: queries(xs[0], xs[1], k_head, v_head),
            (jnp.arange(n) * block,
             q_head.reshape(b, n, block, -1).swapaxes(0, 1)))
        return out.swapaxes(0, 1).reshape(b, t, -1)

    out = jax.lax.map(head, tuple(
        x.transpose(2, 0, 1, 3) for x in (q, k, v)))        # [h, B, T, dv]
    return jnp.einsum("hbqk,hkd->bqd", out, p["o_proj"]["kernel"])


def _reference_moe(cfg: dict, p: dict, bias, y, held=None):
    """``[n, d]`` -> the part of the routed output that the experts
    ``held`` (``experts_held`` where None) of ``p`` give, and the ``[n, k]``
    experts each position chose.  ``bias``: the layer's
    ``e_score_correction_bias``, a buffer."""
    import jax
    import jax.numpy as jnp

    e, k = cfg["router_experts"], cfg["num_experts_per_token"]
    first, end = held or cfg["experts_held"]
    scores = _sigmoid(y @ p["router"]["kernel"])                    # [n, e]
    _, top_idx = jax.lax.top_k(scores + bias, k)
    chosen = jax.nn.one_hot(top_idx, e, dtype=jnp.float32).sum(1)   # [n, e]
    weight = scores * chosen                            # the UNBIASED scores
    if cfg["moe_renormalize"]:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    weight = weight * cfg["routed_scaling_factor"]

    @jax.checkpoint     # keep one expert's activations at a time
    def expert(out, one):
        w, w_gate, w_up, w_down = one
        return out + w[:, None] * (
            (_silu(y @ w_gate) * (y @ w_up)) @ w_down), None

    # a loop over the held experts, one after the other
    out, _ = jax.lax.scan(expert, jnp.zeros_like(y), (
        weight[:, first:end].T, p["experts_gate"], p["experts_up"],
        p["experts_down"]))
    return out, top_idx


def reference_forward(cfg: dict, params, buffers, ids, erase: bool = True):
    """Logits ``[B, T, V]`` and each expert layer's routing."""
    import jax

    eps, d = cfg["rms_norm_eps"], cfg["hidden_size"]
    b, t = ids.shape
    x = params["embed"]["embedding"][ids]

    def layer(x, p, bias, kind: str, dense: bool):
        u = _rms_norm(x, p["attn_norm"]["scale"], eps)
        x = x + (_reference_kda(cfg, p["attn"], u, erase) if kind == "kda"
                 else _reference_latent(cfg, p["attn"], u))
        y = _rms_norm(x, p["mlp_norm"]["scale"], eps)
        if dense:
            return x + _swiglu(p["mlp"], y), None
        routed, top_idx = _reference_moe(cfg, p["moe"], bias,
                                         y.reshape(b * t, d))
        return (x + routed.reshape(b, t, d) + _swiglu(p["shared"], y),
                top_idx)

    routing = []
    for index, kind in enumerate(layer_kinds(cfg)):
        dense = index < cfg["first_k_dense_replace"]
        bias = None if dense else buffers[f"block_{index}"]["moe"][
            "e_score_correction_bias"]
        # a layer's activations at a time: the backward computes them again
        x, top_idx = jax.checkpoint(layer, static_argnums=(3, 4))(
            x, params[f"block_{index}"], bias, kind, dense)
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
