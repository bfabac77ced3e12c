"""NVIDIA-Nemotron-3-Super-120B-A12B (``model_type`` ``nemotron_h``) trained
at its published widths: one chip's share of a 64-chip stage (8-way tensor
parallel over heads x 8 data-parallel groups, the 512 experts over all 64),
depth cut to the first period of the layer pattern, ``MEMEMEM*EME``.

The system under test is the program's ``models/transformer.py`` with what
this model needs of it: ONE mixer a layer (``Transformer.layer_mixer``,
``MixerBlock``: one pre-norm, one mixer, one residual add), the mixer by the
pattern: ``M`` a Mamba-2 mixer (``Mamba2``: 16 heads of 64 over ONE B/C group
of 128 state columns, a depthwise causal conv of 4 taps, the chunked scan of
``ops/ssd.py`` at ``chunk_size`` 128, a gated group norm), ``*``
grouped-query attention WITHOUT rotation (``Attention.rope`` False: 4 query
heads over 1 K/V head of 128, through the flash trio), ``E`` LatentMoE
(``parallel/ep.MoEMLP(expert_act="relu2", latent=1024)``: sigmoid scores, a
selection bias that is a buffer, top 22 of 512, the unbiased scores of the
chosen renormalised and scaled by 5, experts ``relu(l U)^2 V`` of 1024 -> 2688
-> 1024 between two latent maps, of which this chip holds experts 0-7, beside
a whole shared relu2 expert of 5376); next-token cross-entropy fused with the
head, through ``parallel/dp.py``'s ``make_train_step`` under adamw, which is
never shown the bias buffers.  See ``resnet50.py`` for the names a
configuration module provides.

Sizes the public config does not give (the JSON file's ``assumed`` says why
each): no rotation in attention, plain latent maps, what the router reads,
the router's scoring, no multi-token prediction, the job, the learning rate,
``vocab_chunk``, ``remat`` and the scales of the seeded state.
"""

from __future__ import annotations

import math

SAMPLE_UNIT = "tok"


def system_config(cfg: dict) -> dict:
    """The published keys, as the program's builder names them."""
    out = {"model": "transformer",
           "vocab_size": cfg["vocab_size"], "d_model": cfg["hidden_size"],
           "n_layers": cfg["num_hidden_layers"],
           "layer_mixer": list(cfg["hybrid_override_pattern"]),
           "n_heads": cfg["num_attention_heads"], "d_head": cfg["head_dim"],
           "n_kv_heads": cfg["num_key_value_heads"],
           "rope": False,           # nemotron_h's attention turns nothing
           "ssm": {"n_heads": cfg["mamba_num_heads"],
                   "head_dim": cfg["mamba_head_dim"],
                   "n_groups": cfg["n_groups"],
                   "state_size": cfg["ssm_state_size"],
                   "conv_kernel": cfg["conv_kernel"],
                   "chunk_size": cfg["chunk_size"],
                   "dt_min": cfg["time_step_min"],
                   "dt_max": cfg["time_step_max"],
                   "dt_floor": cfg["time_step_floor"]},
           "d_ff": cfg["moe_intermediate_size"],
           "n_experts": cfg["router_experts"],
           "moe_held": cfg["experts_held"],
           "moe_top_k": cfg["num_experts_per_tok"],
           "moe_capacity_factor": None,            # dropless
           "moe_norm_topk_prob": cfg["norm_topk_prob"],
           "moe_router": {"scoring": "sigmoid", "selection_bias": True,
                          "routed_scale": cfg["routed_scaling_factor"],
                          "n_group": cfg["n_group"]},
           "moe_expert_act": cfg["mlp_hidden_act"],
           "moe_latent": cfg["moe_latent_size"],
           "moe_shared_d_ff": (cfg["n_shared_experts"]
                               * cfg["moe_shared_expert_intermediate_size"]),
           "norm_eps": cfg["layer_norm_epsilon"], "bf16": True,
           "remat": bool(cfg.get("remat", False))}
    for key in ("attn_impl", "bf16", "ssm_state_dtype"):   # rehearsal, tests
        if key in cfg:
            out[key] = cfg[key]
    return out


# ---------------------------------------------------------------------------
# Operations and bytes, from shapes (2 per multiply-add; no recompute).
# ---------------------------------------------------------------------------

def causal_pairs(length: int) -> int:
    return length * (length + 1) // 2


def layers_of(cfg: dict, kind: str) -> int:
    return cfg["hybrid_override_pattern"].count(kind)


def held_pairs_per_position(cfg: dict) -> float:
    """EXPECTED pairs a position sends to the experts held here: its 22
    choices spread evenly over the router's 512 experts, 8 of them here."""
    first, end = cfg["experts_held"]
    return cfg["num_experts_per_tok"] * (end - first) / cfg["router_experts"]


def _mamba_weights(cfg: dict) -> int:
    """Matmul weights a position passes in a Mamba-2 mixer held here:
    ``W_in`` to ``[z | xBC | dt]`` and ``W_out``, and the conv's 4 taps a
    channel."""
    d = cfg["hidden_size"]
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    xbc = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    return (d * (inner + xbc + cfg["mamba_num_heads"]) + inner * d
            + cfg["conv_kernel"] * xbc)


def _attention_weights(cfg: dict) -> int:
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    return d * dh * 2 * (cfg["num_attention_heads"]
                         + cfg["num_key_value_heads"])


def _expert_layer_weights(cfg: dict) -> float:
    """The router, the two latent maps, the shared relu2 expert and the
    EXPECTED held pairs' experts (two matrices each)."""
    d, latent = cfg["hidden_size"], cfg["moe_latent_size"]
    return (d * cfg["router_experts"] + 2 * d * latent
            + 2 * d * cfg["n_shared_experts"]
            * cfg["moe_shared_expert_intermediate_size"]
            + held_pairs_per_position(cfg) * 2 * latent
            * cfg["moe_intermediate_size"])


def _attention_flops(cfg: dict, length: int) -> float:
    """One attention layer's kernels, forward and backward, for one row: the
    scores and the values over the causal pairs at ``head_dim`` each, the
    backward 2.5 times the forward (five products for two)."""
    forward = (2 * causal_pairs(length) * cfg["num_attention_heads"]
               * 2 * cfg["head_dim"])
    return 3.5 * forward


def flops_per_sample(cfg: dict, traffic: dict) -> float:
    """Training FLOPs this chip must do per token over what is HELD here: 6
    per matmul weight a position passes (forward 2, backward 4): the Mamba-2
    projections of 16 heads, attention's of 4 query heads over 1 K/V head,
    the router, latent maps, shared expert and the EXPECTED held pairs'
    experts, the head over the held slice of the vocabulary; attention's
    kernels over the causal pairs; the scan's four products a chunk
    (``ssd_scan_cost``)."""
    length = int(traffic["seq_len"])
    weights = (layers_of(cfg, "M") * _mamba_weights(cfg)
               + layers_of(cfg, "*") * _attention_weights(cfg)
               + layers_of(cfg, "E") * _expert_layer_weights(cfg)
               + cfg["hidden_size"] * cfg["vocab_size"])
    return (6.0 * weights
            + layers_of(cfg, "*") * _attention_flops(cfg, length) / length
            + ssd_scan_cost(cfg, traffic, 1)["flops"] / length)


def ssd_scan_cost(cfg: dict, traffic: dict, rows_on_device: int) -> dict:
    """FLOPs and HBM bytes the state-space scan NEEDS in one STEP (the five
    Mamba-2 layers, forward and backward), from the MATHEMATICS at
    ``chunk_size`` Q, whatever computes it.  A chunk's four products: the
    causal half of ``C·Bᵀ`` (``Q(Q+1)/2 · N`` multiply-adds a GROUP) and of
    its product with ``x`` (``Q(Q+1)/2 · P`` a head), the chunk's state
    ``Bᵀ·x`` and the carried state's ``C·S`` (``Q · P · N`` a head each);
    the backward is two products for each of the forward's.  Bytes: each of
    x, z and y (``H · P`` wide), B and C (``G · N``), in bf16, and Δ (``H``
    float32) through HBM once forward, and their cotangents once backward:
    the gate ``z`` is counted because a kernel that fuses the gated norm
    reads it there.  Decay matrices, chunk states, transposes and the
    backward's recompute are the formulation's own."""
    length, q = int(traffic["seq_len"]), cfg["chunk_size"]
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    positions = rows_on_device * length
    half = (q + 1) / 2                  # causal pairs a position of a chunk
    forward = 2 * positions * (half * (g * n + h * p) + 2 * h * p * n)
    once = positions * (2 * (3 * h * p + 2 * g * n) + 4 * h)
    layers = layers_of(cfg, "M")
    return {"flops": float(layers * 3 * forward),
            "bytes": float(layers * 2 * once)}


def moe_experts_cost(cfg: dict, traffic: dict, rows_on_device: int) -> dict:
    """FLOPs and HBM bytes the EXPECTED held pairs need in the routed
    experts' matmuls of one STEP (the five expert layers, forward and
    backward), counted as ``kanana2_30b_a3b_d5_ep8.py`` counts them, for TWO
    ``latent x f`` matrices a pair (relu2: no gate): forward once and
    backward twice; five passes of the pairs' rows and three of the held
    weights in bf16.  The latent maps (``moe/latent``) and the shared expert
    (``moe/shared``) are not the routed experts'."""
    pairs = (rows_on_device * int(traffic["seq_len"])
             * held_pairs_per_position(cfg))
    latent, ff = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    first, end = cfg["experts_held"]
    weights = (end - first) * 2 * latent * ff
    layers = layers_of(cfg, "E")
    flops = layers * 3 * 2 * pairs * 2 * latent * ff
    bytes_ = layers * 2 * (5 * pairs * latent + 3 * weights)
    return {"flops": float(flops), "bytes": float(bytes_)}


KERNELS = {"ssd_scan": ssd_scan_cost, "moe_experts": moe_experts_cost}


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
    # existed would build RoPE attention and SwiGLU experts of the hidden
    # width in every layer under this model's name.  It cannot run this
    # configuration, and says so at once.
    lacking = [key for key in ("layer_mixer", "ssm", "rope", "moe_expert_act",
                               "moe_latent") if not hasattr(model, key)]
    if lacking:
        raise NotImplementedError(
            f"this program has no {lacking}: it cannot build Nemotron-3's "
            "layers of one mixer each (Mamba-2, attention without rotation, "
            "LatentMoE)")
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
    plain attention on one chunk of positions (see ``phi3_mini_d4.py``), by
    the program's own initialisers (Mamba-2's for ``A_log``, ``D`` and
    ``dt_bias``, from the published ``time_step_*``) but for two scales
    (``seeded_state`` in the JSON file, and why: a job that continues from a
    checkpoint starts with token identity in the residual stream and a bias
    that has moved): the embedding's standard deviation and the standard
    deviation of the routers' bias buffers (flax draws them 0)."""
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import transformer as tfm

    twin = tfm.build_transformer({**system_config(cfg), "attn_impl": "xla",
                                  "remat": False})
    variables = twin.init(key, jnp.zeros((1, cfg["chunk_size"]), jnp.int32))
    params, buffers = variables["params"], variables["buffers"]
    seeded = cfg["seeded_state"]
    # flax draws the embedding at 1 / sqrt(hidden)
    params["embed"]["embedding"] = (
        params["embed"]["embedding"] * math.sqrt(cfg["hidden_size"])
        * seeded["embedding_std"])
    for layer, kind in enumerate(cfg["hybrid_override_pattern"]):
        if kind == "E":
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
                degrade_system=False) -> dict:
    """System against the plain float32 reference on ``reference_tokens``
    (the cell's own ``[1, 8192]`` ids, all eleven layers): the logits, the
    routing over the held experts, and the parameters' change in one
    optimizer step; the loss and the norm of all gradients beside them.

    The reference is handed the system's own parameter tree (the published
    layout but for the conv's weight, ``[taps, channels]`` here and
    ``[channels, 1, taps]`` there: a transpose no product sees) and the bias
    buffers beside it.

    Top-k is discontinuous (see ``olmoe_1b_7b_d1.py``): ``routing_agreement``
    is the share of the reference's (position, HELD expert) pairs the system
    also chose.  A flipped pair moves the residual stream of every later
    layer, so the logit error is not given apart for positions with a flip.

    ``update_l2``, ``update_leaf_max``: as ``kanana2_30b_a3b_d5_ep8.py``
    (the system's gradients through the cell's own optimizer from fresh
    moments, the reference's through adamw written out here; the norm of the
    difference of the two changes over the norm of the reference's, over all
    parameters and by leaf; a state left unchanged reads 1; adamw's first
    step is the gradient's SIGN, so a reading is twice the root of the share
    of elements the two sides sign differently).  ``update_leaf_max`` is
    over the leaves of at least ``_LEAF_MIN`` values: a Mamba-2 layer's
    ``A_log``, ``D`` and ``dt_bias`` are 16 values each, ONE of them signed
    differently reads 0.5 and three 0.87, which is what the fp8 control reads
    there: no limit parts the two.  They are in ``update_l2`` with every
    other parameter, and their largest reading is given beside the limits
    (``update_small_leaf_max``), held to none.

    What it cannot see: as the other configurations' checks, it compiles
    programs of its own from the cell's loss and optimizer, not the
    ``make_train_step`` program the window drives.

    ``degrade_system`` is for setting the limits, not for a run, and either
    control has to come out not ``ok``: ``True`` / ``"fp8"`` hands the system
    the parameters rounded to fp8 (``degraded_to_fp8``), the reference the
    true ones; ``"bf16_state"`` builds the system with the scan's decay, its
    running sums and its carried state held in bf16
    (``Transformer.ssm_state_dtype``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if degrade_system == "bf16_state":
        cfg = {**cfg, "ssm_state_dtype": "bfloat16"}
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
        return loss, logits, change, _sown_routing(sown), whole_norm(grads)

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
    fp8 = degrade_system in (True, "fp8")
    sys_loss, sys_logits, sys_change, sys_routing, sys_gnorm = jax.jit(
        system)(degraded_to_fp8(params) if fp8 else params, buffers, ids)
    # the system's change waits on the host: the reference needs the room
    sys_change = jax.device_get(sys_change)
    sys_logits = np.asarray(sys_logits, np.float32).reshape(b * length, -1)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_logits, ref_change, ref_routing, ref_gnorm = jax.jit(
            reference)(params, buffers, ids)
    del params
    ref_logits = np.asarray(ref_logits, np.float32).reshape(b * length, -1)
    diff = sys_logits - ref_logits

    by_leaf = []        # (path, |sys - ref|^2, |ref|^2) in float64
    for (path, ref), own in zip(
            jax.tree_util.tree_flatten_with_path(ref_change)[0],
            jax.tree.leaves(sys_change)):
        ref = np.asarray(ref)
        by_leaf.append((jax.tree_util.keystr(path),
                        float(np.sum(np.square(own - ref), dtype=np.float64)),
                        float(np.sum(np.square(ref), dtype=np.float64)),
                        ref.size))
    del sys_change, ref_change
    ratio = lambda row: row[1] / max(row[2], 1e-300)        # noqa: E731
    worst_leaf, worst_d, worst_r, _size = max(
        (row for row in by_leaf if row[3] >= _LEAF_MIN), key=ratio)
    small_leaf, small_d, small_r, _size = max(
        (row for row in by_leaf if row[3] < _LEAF_MIN), key=ratio)

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
            # held to no limit (see TOLERANCE): the leaves of a few values a
            # head, the loss and the norm of all gradients
            "update_small_leaf_max": math.sqrt(small_d / max(small_r, 1e-300)),
            "update_small_leaf_worst": small_leaf,
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
            layer = int(next(k for k in keys
                             if k.startswith("block_")).split("_")[1])
            found.append((layer, leaf))
    return [leaf for _layer, leaf in sorted(found, key=lambda kv: kv[0])]


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


# Every limit lies between two readings on the chip (TPU v5e, [1, 8192] ids,
# 11 layers; PERF.md section 6, PR 41): the largest of the system over its
# twenty seeds and the smallest of the system on fp8 weights against the
# reference on the true ones (four seeds), near the geometric mean of the
# two; fp8 fails all five.  The other control, the system whose scan holds
# its decay, its running sums and its carried state in bf16 (four seeds), is
# the third column: it fails ``logits_max`` alone (a few positions late in a
# chunk, where a running sum near 20 holds an eighth in bf16 and ``exp`` of
# the difference of two of them is off by a tenth), by 2.7 times the limit.
# System | fp8 weights | bf16 scan state:
#   logits_l2        0.01235 .. 0.01251 | 0.1132 .. 0.1145 | 0.0187 .. 0.0244
#   logits_max       0.0119 .. 0.0144   | 0.114 .. 0.124   | 0.110 .. 0.166
#   routing_disagr.  0.0089 .. 0.0109   | 0.0744 .. 0.0816 | 0.0122 .. 0.0176
#   update_l2        0.214 .. 0.230     | 0.540 .. 0.547   | 0.260 .. 0.285
#   update_leaf_max  0.370 .. 0.415     | 0.782 .. 0.788   | 0.426 .. 0.438
# update_l2 reads a fifth and that is no rounding: adamw's first step is the
# gradient's sign, so it is twice the root of the share of the elements with
# a gradient that the two sides sign differently (1.3% on bf16, 7.3% on fp8;
# float32 on both sides reads 1e-4 at a small size on the CPU); a state left
# unchanged reads 1.  update_leaf_max is the same by leaf over the leaves of
# at least 1,024 values (always a late layer's ``latent_down``): a leaf the
# optimizer froze reads 1.
# Reported beside the limits and held to none, because the controls'
# readings overlap the system's, so no reading stands above a limit:
#   update_small_leaf_max 0.013 .. 0.696 | 0.707 .. 0.866 | 0.500 .. 0.704
#     (a leaf of 16 values a head: ONE signed differently reads 0.5, which
#     seventeen of nineteen seeds of the system read, two 0.707, three 0.866)
#   loss             1.8e-6 .. 3.1e-5   | 7.3e-5 .. 2.1e-4 | 1.1e-5 .. 2.5e-5
#   grad_norm        1.4e-4 .. 1.8e-4   | 8.1e-5 .. 3.4e-4 | 4.9e-5 .. 1.0e-4
TOLERANCE = {"logits_l2": 0.038, "logits_max": 0.04,
             "routing_disagreement": 0.028, "update_l2": 0.35,
             "update_leaf_max": 0.56}


# ---------------------------------------------------------------------------
# The plain reference: float32 jax.numpy, from the published description
# (``nemotron_h``: every layer ``h + mixer(RMSNorm(h))``, eps 1e-5, the mixer
# by the pattern; ``norm_f``, an untied head, next-token cross-entropy with no
# auxiliary term).  Mamba-2 as its EQUATION: the conv as four shifted adds,
# the recurrence ``S_t = exp(Δ_t a) S_{t-1} + Δ_t x_t ⊗ B_t``, ``y_t = S_t C_t
# + D x_t`` as a ``lax.scan`` over POSITIONS (not the chunked dual form the
# system computes: ``chunk_size`` is said nowhere below), the gated group norm
# written out.  Attention head by head over whole ``[L, L]`` float32 scores,
# no rotation.  LatentMoE with each held expert applied to every position and
# weighted by the position's routing weight for it (0 where it was not
# chosen), logits whole.  Computed in blocks so that it fits: the scan over
# positions is an outer scan over blocks of ``_SCAN_BLOCK`` positions whose
# inner scan runs again in the backward (``jax.checkpoint``: every state of
# 8,192 positions kept would be 4.3 GB a layer), attention a head at a time,
# the experts one at a time, and a layer's activations at a time.
# Departures from the published model, all of the cut: heads 0-15 of 128
# (B/C group 0 of 8), query heads 0-3 over K/V head 0, experts
# ``experts_held`` of 512 summed, the vocabulary the held slice.  Nothing
# here imports the program's ops/ or parallel/ep.py.
# ---------------------------------------------------------------------------

_SCAN_BLOCK = 128       # positions an inner scan keeps states for: memory only
_LEAF_MIN = 1024        # ``update_leaf_max`` reads leaves of at least so many


def _rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * scale


def _silu(x):
    import jax.numpy as jnp

    return x / (1.0 + jnp.exp(-x))


def _relu2_mlp(y, up, down):
    import jax.numpy as jnp

    return jnp.square(jnp.maximum(y @ up, 0.0)) @ down


def _reference_mamba(cfg: dict, p: dict, u):
    """``[B, L, d]`` -> the held heads' part of a Mamba-2 mixer's output."""
    import jax
    import jax.numpy as jnp

    h, dim = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    taps, inner = cfg["conv_kernel"], h * dim
    b, length, _ = u.shape
    proj = u @ p["in_proj"]["kernel"]
    z, xbc, dt = (proj[..., :inner], proj[..., inner:2 * inner + 2 * g * n],
                  proj[..., 2 * inner + 2 * g * n:])
    # depthwise causal conv: tap k meets the position taps-1-k before
    conv = jnp.zeros_like(xbc) + p["conv_bias"]
    for k in range(taps):
        back = taps - 1 - k
        shifted = jnp.concatenate(
            [jnp.zeros_like(xbc[:, :back]), xbc[:, :length - back]], axis=1)
        conv = conv + shifted * p["conv_kernel"][k]
    xbc = _silu(conv)
    x = xbc[..., :inner].reshape(b, length, h, dim)
    # head j reads group j // (h / g)
    bm = jnp.repeat(xbc[..., inner:inner + g * n].reshape(b, length, g, n),
                    h // g, axis=2)
    cm = jnp.repeat(xbc[..., inner + g * n:].reshape(b, length, g, n),
                    h // g, axis=2)
    delta = jnp.logaddexp(dt + p["dt_bias"], 0.0)       # softplus, [B, L, h]
    a = -jnp.exp(p["A_log"])

    def position(state, inputs):                        # state [B, h, dim, n]
        x_t, b_t, c_t, delta_t = inputs
        state = (jnp.exp(delta_t * a)[..., None, None] * state
                 + (delta_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return state, jnp.sum(state * c_t[..., None, :], axis=-1)

    @jax.checkpoint     # the block's 128 states are made again backward
    def block(state, inputs):
        return jax.lax.scan(position, state, inputs)

    blocks = length // _SCAN_BLOCK if length % _SCAN_BLOCK == 0 else 1
    by_block = lambda t: t.swapaxes(0, 1).reshape(  # noqa: E731
        (blocks, length // blocks) + t.shape[:1] + t.shape[2:])
    _, y = jax.lax.scan(block, jnp.zeros((b, h, dim, n), jnp.float32),
                        tuple(by_block(t) for t in (x, bm, cm, delta)))
    y = y.reshape((length, b, h, dim)).swapaxes(0, 1) + p["D"][:, None] * x
    # the gated norm: over each group's channels, one weight a channel
    y = (y.reshape(b, length, inner) * _silu(z)).reshape(b, length, g, -1)
    y = y / jnp.sqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                     + cfg["layer_norm_epsilon"])
    return (y.reshape(b, length, inner) * p["norm_scale"]) @ p["out_proj"][
        "kernel"]


def _reference_attention(cfg: dict, p: dict, u):
    """Causal grouped-query attention without rotation: query head ``j``
    reads K/V head ``j // (heads / kv heads)``."""
    import jax
    import jax.numpy as jnp

    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    t = u.shape[1]
    causal = jnp.tril(jnp.ones((t, t), bool))
    q = jnp.einsum("bsd,dhk->bshk", u, p["q_proj"]["kernel"])
    k = jnp.einsum("bsd,dhk->bshk", u, p["k_proj"]["kernel"])
    v = jnp.einsum("bsd,dhk->bshk", u, p["v_proj"]["kernel"])

    @jax.checkpoint     # one head's [T, T] scores at a time, again backward
    def head(q, k, v):                                  # [B, T, dh] each
        scores = jnp.einsum("bqd,bkd->bqk", q, k) / math.sqrt(cfg["head_dim"])
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(lambda qkv: head(*qkv), (
        q.transpose(2, 0, 1, 3),
        jnp.repeat(k, heads // kv, axis=2).transpose(2, 0, 1, 3),
        jnp.repeat(v, heads // kv, axis=2).transpose(2, 0, 1, 3)))
    return jnp.einsum("hbqk,hkd->bqd", out, p["o_proj"]["kernel"])


def _reference_moe(cfg: dict, p: dict, bias, y):
    """``[n, d]`` -> the held experts' part of the routed output, through
    both latent maps, and the ``[n, k]`` experts each position chose.
    ``bias``: the layer's ``e_score_correction_bias``, a buffer.  The router
    reads ``y``, the experts its latent image."""
    import jax
    import jax.numpy as jnp

    e, k = cfg["router_experts"], cfg["num_experts_per_tok"]
    first, end = cfg["experts_held"]
    scores = 1.0 / (1.0 + jnp.exp(-(y @ p["router"]["kernel"])))    # [n, e]
    _, top_idx = jax.lax.top_k(scores + bias, k)
    chosen = jax.nn.one_hot(top_idx, e, dtype=jnp.float32).sum(1)   # [n, e]
    weight = scores * chosen                            # the UNBIASED scores
    if cfg["norm_topk_prob"]:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    weight = weight * cfg["routed_scaling_factor"]
    latent = y @ p["latent_down"]["kernel"]

    @jax.checkpoint     # keep one expert's activations at a time
    def expert(out, held):
        w, up, down = held
        return out + w[:, None] * _relu2_mlp(latent, up, down), None

    # a loop over the held experts, one after the other
    out, _ = jax.lax.scan(expert, jnp.zeros_like(latent), (
        weight[:, first:end].T, p["experts_up"], p["experts_down"]))
    return out @ p["latent_up"]["kernel"], top_idx


def reference_forward(cfg: dict, params, buffers, ids):
    """Logits ``[B, T, V]`` and each expert layer's routing."""
    import jax

    eps, d = cfg["layer_norm_epsilon"], cfg["hidden_size"]
    b, t = ids.shape
    x = params["embed"]["embedding"][ids]

    def layer(x, p, bias, kind: str):
        u = _rms_norm(x, p["norm"]["scale"], eps)
        if kind == "M":
            return x + _reference_mamba(cfg, p["ssm"], u), None
        if kind == "*":
            return x + _reference_attention(cfg, p["attn"], u), None
        routed, top_idx = _reference_moe(cfg, p["moe"], bias,
                                         u.reshape(b * t, d))
        shared = _relu2_mlp(u, p["shared"]["up_proj"]["kernel"],
                            p["shared"]["down_proj"]["kernel"])
        return x + routed.reshape(b, t, d) + shared, top_idx

    routing = []
    for index, kind in enumerate(cfg["hybrid_override_pattern"]):
        bias = (buffers[f"block_{index}"]["moe"]["e_score_correction_bias"]
                if kind == "E" else None)
        # a layer's activations at a time: the backward computes them again
        x, top_idx = jax.checkpoint(layer, static_argnums=3)(
            x, params[f"block_{index}"], bias, kind)
        if kind == "E":
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
