"""SmallThinker-21BA3B-Instruct trained at its published widths: one chip's
share of an 8-way expert-parallel stage, depth cut to 8 layers (two periods
of its layer pattern), rows of 16k, the published context.

The system under test is the program's ``models/transformer.py`` with what
this model needs of it: attention that differs layer by layer
(``Transformer.layer_attention``: a global layer WITHOUT rotation under the
full causal mask, then three layers that rotate inside a window of 4,096,
``ops/attention.py``'s ``window``), 28 query heads over 4 K/V heads (a group
of 7), a router that reads the layer's INPUT, before the norm and before
attention (``moe_router_input`` = ``"layer"``; ``parallel/ep.MoEMLP``'s
``router_input``), dropless top-6-of-64 routing of which this chip holds
experts 0-7, and ReGLU experts (``moe_expert_act`` = ``"reglu"``), under the
next-token loss of ``make_loss_fn`` fused with the head, through
``parallel/dp.py``'s ``make_train_step`` with ``remat``.  See ``resnet50.py``
for the names a configuration module provides.

What the public config does not give is listed, each with its reason, under
``assumed`` in the JSON file: where the router reads (``router_input``), the
form of the routing weights, the experts' activation (``expert_act``), no
bias and no QK-norm in attention, the window's convention, the job and its
loss, the optimizer, ``remat``, and the two scales of the seeded state
(``seeded_state``: ``embedding_std`` and ``qk_proj_scale``).
"""

from __future__ import annotations

import functools
import json
import math

SAMPLE_UNIT = "tok"


def layer_kinds(cfg: dict) -> list:
    """``(window, rope)`` of each layer that runs: the first
    ``num_hidden_layers`` entries of the two published layouts."""
    n = cfg["num_hidden_layers"]
    return [(cfg["sliding_window_size"] if windowed else 0, bool(rope))
            for windowed, rope in zip(cfg["sliding_window_layout"][:n],
                                      cfg["rope_layout"][:n])]


def system_config(cfg: dict) -> dict:
    """The published keys, as the program's builder names them."""
    out = {"model": "transformer",
           "vocab_size": cfg["vocab_size"], "d_model": cfg["hidden_size"],
           "n_layers": cfg["num_hidden_layers"],
           "n_heads": cfg["num_attention_heads"],
           "n_kv_heads": cfg["num_key_value_heads"],
           "d_head": cfg["head_dim"],
           "d_ff": cfg["moe_ffn_hidden_size"],
           "n_experts": cfg["router_experts"],
           "moe_held": cfg["experts_held"],
           "moe_top_k": cfg["moe_num_active_primary_experts"],
           "moe_capacity_factor": None,            # dropless
           "moe_norm_topk_prob": cfg["norm_topk_prob"],
           "moe_expert_act": cfg["expert_act"],
           "moe_router_input": cfg["router_input"],
           "layer_attention": [list(kind) for kind in layer_kinds(cfg)],
           "qk_norm": cfg["qk_norm"],
           "norm_eps": cfg["rms_norm_eps"],
           "rope_theta": cfg["rope_theta"], "bf16": True,
           "remat": cfg["remat"]}
    for key in ("attn_impl", "bf16"):       # the rehearsal's and the tests'
        if key in cfg:
            out[key] = cfg[key]
    return out


# ---------------------------------------------------------------------------
# Operations and bytes, from shapes (2 per multiply-add; USEFUL work only:
# the visible pairs, no tile computed whole for the pairs the mask leaves of
# it).  A forward kernel's need is ONE call's: under ``remat`` it runs twice a
# layer under one scope, and its reader counts the executions in the trace;
# ``flops_per_sample`` (what ``lm_mfu`` reads) counts no recomputation.
# ---------------------------------------------------------------------------

def causal_pairs(length: int) -> int:
    return length * (length + 1) // 2


def band_pairs(length: int, window: int) -> int:
    """(query, key) pairs with ``0 <= i - j < window`` in one row: the first
    ``window`` queries see all before them, the rest ``window`` each
    (``4096 * 4097 / 2 + (L - 4096) * 4096`` at the published window)."""
    short = min(length, window)
    return short * (short + 1) // 2 + (length - short) * window


def visible_pairs(cfg: dict, length: int) -> list:
    """The visible pairs of each layer that runs."""
    return [band_pairs(length, window) if window else causal_pairs(length)
            for window, _rope in layer_kinds(cfg)]


def _per_position_params(cfg: dict) -> int:
    """Weights every position multiplies in one layer: the four attention
    projections at 28 query and 4 K/V heads, and the router."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    q = cfg["num_attention_heads"] * dh
    kv = cfg["num_key_value_heads"] * dh
    return 2 * d * q + 2 * d * kv + d * cfg["router_experts"]


def held_pairs_per_position(cfg: dict) -> float:
    """EXPECTED pairs a position sends to the experts held here: its 6
    choices spread evenly over the router's 64 experts, 8 of them here."""
    first, end = cfg["experts_held"]
    return (cfg["moe_num_active_primary_experts"] * (end - first)
            / cfg["router_experts"])


def _pair_flops(cfg: dict) -> int:
    """QKᵀ and PV of one visible pair, all query heads."""
    return 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]


def flops_per_sample(cfg: dict, traffic: dict) -> float:
    """Training FLOPs this chip must do per token of a row: 6 per matmul
    weight (forward 2, backward 4) through the projections, the router, the
    expected held pairs' experts (three matrices an expert) and the head over
    the held slice of the vocabulary; three times the forward attention over
    each layer's VISIBLE pairs, the band's in a window layer."""
    d, length = cfg["hidden_size"], int(traffic["seq_len"])
    expert = 3 * d * cfg["moe_ffn_hidden_size"]
    per_position = (_per_position_params(cfg)
                    + held_pairs_per_position(cfg) * expert)
    pairs = sum(visible_pairs(cfg, length))
    return (cfg["num_hidden_layers"] * 6.0 * per_position
            + 3.0 * _pair_flops(cfg) * pairs / length
            + 6.0 * d * cfg["vocab_size"])


def _attention_bytes(cfg: dict, positions: int) -> int:
    """What one forward call moves, bf16: q in and o out at 28 heads, k and v
    at the 4 K/V heads, the log-sum-exp in float32."""
    h, h_kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    return positions * (2 * h * dh * 2 + 2 * h_kv * dh * 2 + h * 4)


def flash_fwd_cost(cfg: dict, traffic: dict, rows_on_device: int) -> dict:
    """FLOPs and HBM bytes ONE call of the forward kernel of a GLOBAL layer
    needs (the scopes ``flash_fwd`` / ``flash_bwd`` hold those layers alone
    here): QKᵀ and PV over the causal pairs, as ``sdar_30b_a3b_d4_ep8.py``
    counts its mask's."""
    length = int(traffic["seq_len"])
    return {"flops": float(rows_on_device * _pair_flops(cfg)
                           * causal_pairs(length)),
            "bytes": float(_attention_bytes(cfg, rows_on_device * length))}


def _layers(cfg: dict, windowed: bool) -> int:
    return sum(1 for window, _rope in layer_kinds(cfg)
               if bool(window) == windowed)


def swa_flash_fwd_cost(cfg: dict, traffic: dict, rows_on_device: int) -> dict:
    """FLOPs and HBM bytes ONE call of the band's FORWARD kernel needs (scope
    ``flash_fwd_window``): the VISIBLE pairs of the band (``band_pairs``).
    How often it runs in a step is the program's (six layers, each twice
    while ``remat`` runs the forward again): the reader counts the kernel's
    executions in the trace.  Tiles computed whole on both masked edges of a
    query block's run (252 tiles of 512 x 512 hold 66.1 M pairs, 58.7 M of
    them visible) are the formulation's own and show as a loss."""
    length = int(traffic["seq_len"])
    return {"flops": float(rows_on_device * _pair_flops(cfg) * band_pairs(
                length, cfg["sliding_window_size"])),
            "bytes": float(_attention_bytes(cfg, rows_on_device * length))}


def _flash_bwd_cost(forward: dict, layers: int) -> dict:
    """The backward of ``layers`` layers in one step: 2.5 times one forward's
    FLOPs (five matmuls to its two) and twice its bytes (q, k, v, o and the
    cotangent in, three gradients out), as ``flash_bwd_roofline`` counts."""
    return {"flops": layers * 2.5 * forward["flops"],
            "bytes": layers * 2.0 * forward["bytes"]}


def swa_flash_bwd_cost(cfg: dict, traffic: dict, rows_on_device: int) -> dict:
    """The band's BACKWARD in one step (scope ``flash_bwd_window``): the six
    window layers', over the visible pairs of the band."""
    return _flash_bwd_cost(swa_flash_fwd_cost(cfg, traffic, rows_on_device),
                           _layers(cfg, windowed=True))


def global_flash_bwd_cost(cfg: dict, traffic: dict,
                          rows_on_device: int) -> dict:
    """The GLOBAL layers' backward in one step (scope ``flash_bwd``): the two
    layers without a window, over the causal pairs."""
    return _flash_bwd_cost(flash_fwd_cost(cfg, traffic, rows_on_device),
                           _layers(cfg, windowed=False))


def moe_experts_cost(cfg: dict, traffic: dict, rows_on_device: int) -> dict:
    """As ``sdar_30b_a3b_d4_ep8.py`` counts its held pairs' expert matmuls
    of one STEP: THREE ``d x f`` products a pair for ``reglu`` (gate, up,
    down), forward once and backward twice; one position a token."""
    pairs = (rows_on_device * int(traffic["seq_len"])
             * held_pairs_per_position(cfg))
    d, ff = cfg["hidden_size"], cfg["moe_ffn_hidden_size"]
    first, end = cfg["experts_held"]
    weights = (end - first) * 3 * d * ff
    layers = cfg["num_hidden_layers"]
    return {"flops": float(layers * 3 * 2 * pairs * 3 * d * ff),
            "bytes": float(layers * 2 * (5 * pairs * d + 3 * weights))}


KERNELS = {"flash_fwd": flash_fwd_cost, "swa_flash_fwd": swa_flash_fwd_cost,
           "swa_flash_bwd": swa_flash_bwd_cost,
           "global_flash_bwd": global_flash_bwd_cost,
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


# What a faulty system computes, for the negative controls of ``check_train``
# (``degrade_system``): each is another model under this one's parameters.
WRONG_SYSTEMS = {
    "no_window": lambda kinds: [[0, rope] for _window, rope in kinds],
    "rope_everywhere": lambda kinds: [[window, True] for window, _r in kinds],
    "router_after_attention": None,     # the router reads what the experts do
}


def _model(cfg: dict, wrong: str | None = None):
    from tensorflowonspark_tpu.models import transformer as tfm

    config = system_config(cfg)
    if wrong == "router_after_attention":
        config["moe_router_input"] = "ffn"
    elif wrong is not None:
        config["layer_attention"] = WRONG_SYSTEMS[wrong](
            config["layer_attention"])
    model = tfm.build_transformer(config)
    # the builder ignores keys it does not know: a program from before these
    # existed would build a GQA MoE with full rotating attention in every
    # layer, a router on the normed state and SwiGLU experts, and train it
    # under this model's name.  It cannot run this configuration.
    lacking = [key for key in ("layer_attention", "moe_router_input",
                               "moe_held") if not hasattr(model, key)]
    if lacking:
        raise NotImplementedError(
            f"models/transformer.py of this program has no {lacking}: it "
            "cannot build SmallThinker's window and global layers, its "
            "router on the layer's input or its ReGLU experts")
    return tfm, model


def _loss_fn(tfm, model, cfg: dict):
    return tfm.make_loss_fn(model, aux_loss_coef=0.0, router_z_coef=0.0,
                            vocab_chunk=int(cfg["vocab_chunk"]))


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
    # flax draws the embedding at 1 / sqrt(hidden)
    params["embed"]["embedding"] = (
        params["embed"]["embedding"] * math.sqrt(cfg["hidden_size"])
        * seeded["embedding_std"])
    for layer in range(cfg["num_hidden_layers"]):
        attn = params[f"block_{layer}"]["attn"]
        for name in ("q_proj", "k_proj"):
            attn[name]["kernel"] = (attn[name]["kernel"]
                                    * seeded["qk_proj_scale"])
    return params


# optax.adamw's defaults, as ``reference_adamw_change`` writes them out
_ADAM_B1, _ADAM_EPS, _WEIGHT_DECAY = 0.9, 1e-8, 1e-4


@functools.cache
def _program(config: str, wrong: str | None = None):
    """``(model, optimizer, step)`` of a configuration (its JSON text), ONE
    jitted ``make_train_step`` in a process.  The check steps it and the
    window lowers it again: jax keeps a jitted function's trace, so the
    second lowering is the first's module byte for byte and its compilation
    the cache's entry.  Two functions would not do: a Pallas kernel's
    serialised module carries the frames of its call stack (file and line),
    the caller of ``lower`` among them, and is part of the cache's key
    (PERF.md section 6, PR 28)."""
    import optax

    from tensorflowonspark_tpu.parallel import dp as dplib

    cfg = json.loads(config)
    tfm, model = _model(cfg, wrong)     # refuses a program that lacks them
    optimizer = optax.adamw(cfg["optimizer"]["learning_rate"])
    return model, optimizer, dplib.make_train_step(
        _loss_fn(tfm, model, cfg), optimizer)


def _train(cfg: dict, mesh, seed: int, wrong: str | None = None):
    """``(model, state, step)``: the seeded train state on ``mesh`` and the
    cell's step program, for the window and for the check alike."""
    import jax

    from tensorflowonspark_tpu.parallel import dp as dplib
    from tensorflowonspark_tpu.parallel import mesh as meshlib

    model, optimizer, step = _program(json.dumps(cfg, sort_keys=True), wrong)
    state = jax.jit(
        lambda key: dplib.TrainState.create(_init_params(cfg, key), optimizer),
        out_shardings=meshlib.replicated(mesh))(jax.random.PRNGKey(seed))
    return model, state, step


def build_train(cfg: dict, traffic: dict, mesh, seed: int) -> dict:
    _, state, step = _train(cfg, mesh, seed)
    return {"state": state, "step_fn": step,
            "rows_per_step": int(traffic["rows_per_chip"]) * mesh.size,
            "samples_per_row": int(traffic["seq_len"])}


def check_train(cfg: dict, traffic: dict, seed: int,
                degrade_system=False) -> dict:
    """System against the plain float32 reference on ``reference_tokens``
    (the cell's own ``[1, 16384]`` row, all layers): the logits, the routing
    over the held experts (``routing_disagreement``: the share of the
    reference's (position, HELD expert) pairs that the system did not
    choose), and ONE STEP OF THE WINDOW'S OWN PROGRAM from the seeded state:
    ``_train``'s ``make_train_step`` on the row, compiled here and loaded
    from the cache by the window (``_program``).

    From the state that step leaves: its gradients (adamw's first moment
    after one step from zero is ``(1 - b1) g``) against the reference's by
    leaf (the relative L2 error of a leaf's gradient, as
    ``olmoe_1b_7b_d1.py`` holds its): the worst of the attention
    projections' (``grad_attn_leaf_max``: what the flash backward's dq, dk
    and dv feed) and the worst of all (``grad_leaf_max``: a late layer's
    experts, where a flipped pair takes a whole position's share out of one
    expert's gradient and puts it into another's: 0.7% of the pairs flipped
    read near sqrt(2 x 0.007)); and the parameters' change against adamw
    written out on the reference's gradients (``reference_adamw_change``):
    ``update_l2`` over all parameters and ``update_leaf_max`` by leaf, the
    worst.  A state left unchanged reads 1 in all four.  adamw's first step
    is ``-lr g / (|g| + eps)``, the gradient's SIGN wherever ``|g|`` is well
    over ``eps``: an update's reading is twice the root of the share of
    elements the two sides sign differently (``kanana2_30b_a3b_d5_ep8.py``),
    so the gradients' own reading is the sharp one for the backward kernels
    and the update's says that the optimizer moved every leaf by them.  The
    system's change is new minus old float32 parameters: under 1% rounding.

    Top-k is discontinuous (see ``olmoe_1b_7b_d1.py``): a pair whose logit
    lies within rounding of its row's sixth may flip, and a flipped pair
    moves the residual stream that every later layer's router reads, here
    without a norm in between.

    ``degrade_system`` is for setting the limits and for the negative
    controls, not for a run: ``"fp8"`` (or True) hands the system the
    parameters rounded to fp8 (``degraded_to_fp8``); ``"frozen"`` leaves the
    state as it was in place of the step's; a key of ``WRONG_SYSTEMS`` builds
    another model in its place (the window dropped, the global layers
    rotated, the router on the post-attention state).  The reference gets
    the true parameters and this model, and the result has to come out not
    ``ok``."""
    import jax
    import numpy as np
    import optax

    from tensorflowonspark_tpu.parallel import mesh as meshlib

    wrong = degrade_system if degrade_system in WRONG_SYSTEMS else None
    b, length = cfg["reference_tokens"]
    ids = np.random.default_rng([seed, 78]).integers(
        0, cfg["vocab_size"], (b, length)).astype(np.int32)
    # the node's mesh on a one-chip machine, so the node's program
    mesh = meshlib.make_mesh(jax.devices()[:1], dp=-1)
    model, state, step = _train(cfg, mesh, seed, wrong)
    # the chip holds one side at a time: what the other needs waits on the host
    params = before = jax.device_get(state.params)
    if degrade_system in (True, "fp8"):
        # op by op: inside one program the compiler may drop a cast down and
        # up again as excess precision, and the control would be the system
        state = state._replace(params=degraded_to_fp8(state.params))
        before = jax.device_get(state.params)
    batch = meshlib.shard_batch(mesh, {"input_ids": ids})

    def system_forward(params, ids):
        logits, sown = model.apply({"params": params}, ids,
                                   mutable=["intermediates"])
        return logits, _sown(sown, "top_idx")

    with jax.set_mesh(mesh):        # as the window: the kernels read it
        sys_logits, sys_routing = jax.device_get(jax.jit(system_forward)(
            state.params, batch["input_ids"]))
        if degrade_system == "frozen":
            metrics = {"lm_loss": np.nan}
        else:
            state, metrics = step.lower(state, batch).compile()(state, batch)
    sys_loss = float(metrics["lm_loss"])
    moved = jax.tree.map(np.subtract, jax.device_get(state.params), before)
    first_moment = jax.device_get(
        optax.tree_utils.tree_get(state.opt_state, "mu"))
    del state, before

    def reference(params, ids):
        def f(params):
            logits, routing = reference_forward(cfg, params, ids)
            return reference_lm_loss(logits, ids), (logits, routing)
        (loss, (logits, routing)), grads = jax.value_and_grad(
            f, has_aux=True)(params)
        return loss, logits, routing, grads

    params = jax.device_put(params)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_logits, ref_routing, ref_grads = jax.jit(reference)(
            params, ids)
    ref_loss, ref_logits, ref_routing = jax.device_get(
        (ref_loss, ref_logits, ref_routing))
    by_leaf = jax.device_get(jax.jit(_step_errors, static_argnums=0)(
        cfg["optimizer"]["learning_rate"], params, ref_grads, first_moment,
        moved))
    del params, ref_grads, first_moment, moved
    ref_logits = ref_logits.astype(np.float32).reshape(b * length, -1)
    diff = sys_logits.astype(np.float32).reshape(b * length, -1) - ref_logits

    readings = _step_readings([
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
    return {"errors": errors, "tolerance": TOLERANCE, **readings["worst"],
            # beside the limits and held to none (see TOLERANCE)
            "loss": abs(sys_loss - float(ref_loss)) / abs(float(ref_loss)),
            "grad_l2": readings["grad_l2"],
            # pairs a LAYER sends the held experts (the even share: 12,288)
            "held_pairs": float(ref_held.sum() / len(ref_routing)),
            "held_pairs_by_layer": [int(x) for x in by_expert.sum(1)],
            "held_pairs_max_over_mean": float(
                by_expert.max() / max(by_expert.mean(), 1e-30)),
            "lm_loss": float(ref_loss),
            "ok": all(errors[k] < TOLERANCE[k] for k in errors)}


def _step_readings(rows) -> dict:
    """The step's errors from ``_step_errors``' sums by leaf, rows of (leaf,
    |g - g_ref|^2, |g_ref|^2, |change - ref|^2, |ref change|^2): the worst
    leaf's gradient among the ATTENTION projections (what the flash
    backward's dq, dk, dv and the cotangent of its output feed, and no
    expert's flipped pairs directly) and among all leaves, the update over
    all parameters and its worst leaf."""
    def share(part, whole):
        return math.sqrt(part / max(whole, 1e-300))

    def worst(rows, part, whole):
        row = max(rows, key=lambda r: r[part] / max(r[whole], 1e-300))
        return row[0], share(row[part], row[whole])

    attention = [r for r in rows if "['attn']" in r[0]]
    (attn_leaf, attn), (grad_leaf, grad), (update_leaf, update) = (
        worst(attention, 1, 2), worst(rows, 1, 2), worst(rows, 3, 4))
    return {"errors": {"grad_attn_leaf_max": attn, "grad_leaf_max": grad,
                       "update_l2": share(sum(r[3] for r in rows),
                                          sum(r[4] for r in rows)),
                       "update_leaf_max": update},
            "worst": {"grad_attn_leaf_worst": attn_leaf,
                      "grad_leaf_worst": grad_leaf,
                      "update_leaf_worst": update_leaf},
            "grad_l2": share(sum(r[1] for r in rows), sum(r[2] for r in rows))}


def reference_adamw_change(rate: float, p, g):
    """What adamw adds to a parameter in its FIRST step (moments from zero,
    so their bias correction gives back ``g`` and ``g^2``), optax's defaults
    written out: ``-lr (g / (sqrt(g^2) + 1e-8) + 1e-4 p)``."""
    import jax.numpy as jnp

    return -rate * (g / (jnp.sqrt(jnp.square(g)) + _ADAM_EPS)
                    + _WEIGHT_DECAY * p)


def _step_errors(rate: float, params, ref_grads, first_moment, moved):
    """By leaf, four squared norms: the system's gradient (from the first
    moment its step left) less the reference's, the reference's gradient,
    the system's change of the parameter less the reference's, the
    reference's change."""
    import jax
    import jax.numpy as jnp

    def leaf(p, g, m, d):
        change = reference_adamw_change(rate, p, g)
        return jnp.stack([
            jnp.sum(jnp.square(m / (1.0 - _ADAM_B1) - g)),
            jnp.sum(jnp.square(g)),
            jnp.sum(jnp.square(d - change)), jnp.sum(jnp.square(change))])

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
# [1, 16384] row, 8 layers; PERF.md section 6, PR 48), near their geometric
# mean (the update's two a little over it, as the three configurations
# before): the largest of the system over its seeds (seventeen, twelve for
# the attention leaves, and seventeen more before the review's repairs for
# the first three) and the smallest of the system on weights rounded to fp8
# against the reference on the true ones (three seeds); fp8 fails all seven:
#   logits_l2            0.01389 .. 0.01424 | fp8 0.1093 .. 0.1114
#   logits_max           0.0168 .. 0.0216   | fp8 0.1546 .. 0.1878
#   routing_disagreement 0.00668 .. 0.00807 | fp8 0.0563 .. 0.0590
#   grad_attn_leaf_max   0.0460 .. 0.0485   | fp8 0.3880 .. 0.3894
#   grad_leaf_max        0.1226 .. 0.1321   | fp8 0.3928 .. 0.3990
#   update_l2            0.2370 .. 0.2417   | fp8 0.6136 .. 0.6168
#   update_leaf_max      0.3640 .. 0.3908   | fp8 0.7158 .. 0.7254
# The logits part by 1.4% where Keye's read 9% at the same length: no mask
# here depends on the data, so a rounding moves a logit and never a key in or
# out of a query's attention, and the flipped pairs (0.7% of the held ones
# over eight layers, on a router that reads the bf16 stream un-normed) are
# what compounds.  The gradients come from the window's own step.  The
# attention projections' part by under 5% (the worst always layer 0's q_proj
# or k_proj).  The worst leaf of all is always the last layer's experts_gate
# at 12 to 13%, and that is the routing, not a rounding: a flipped pair takes
# a position's whole share out of one expert's gradient and puts it into
# another's, so 0.7% of the pairs flipped read sqrt(2 x 0.007) = 0.12.
# update_l2 reads a quarter as in the three configurations before: adamw's
# first step is the gradient's sign, so it is twice the root of the share of
# elements that the two sides sign differently (1.4% on bf16, 9% on fp8); its
# worst leaf is always a late router's kernel.  Planted faults, each on the
# chip at the timed size, one seed: a state the step left unchanged reads
# exactly 1 in all four of the step's readings by construction; a band
# BACKWARD that builds its masked tiles without the window (the forward, and
# so the first three readings, the sound system's to the digit) reads 1.01 |
# 1.01 | 0.70 | 0.98; a model without the window 0.87 in the gradients and
# 0.96 | 1.11 in the update, with logits_l2 0.35.
# Two numbers are reported beside the limits and held to none: the loss,
# a mean over 16,383 targets in which the control's readings overlap the
# system's, and the gradients' error over all leaves at once, which the two
# readings by leaf hold already:
#   loss                 2.8e-7 .. 2.8e-5   | fp8 8.0e-6 .. 1.4e-4
#   grad_l2              0.0378 .. 0.0398   | fp8 0.3174 .. 0.3183
# At a small size on the CPU, in float32 (readings under 2e-6, the update's
# 6e-4 and 2e-3: the change is read off float32 parameters), a system that
# drops the window reads logits_l2 0.50, one that rotates the global layers
# 0.57, one that routes on the post-attention state 0.048 with 20% of the
# held pairs moved, and each fails in the step's readings too
# (tests/benchmark/test_benchmark_smallthinker.py).
TOLERANCE = {"logits_l2": 0.04, "logits_max": 0.06,
             "routing_disagreement": 0.02, "grad_attn_leaf_max": 0.14,
             "grad_leaf_max": 0.22, "update_l2": 0.40, "update_leaf_max": 0.57}


# ---------------------------------------------------------------------------
# The plain reference: float32 jax.numpy, from the equations of ISSUE 48's
# motivation (the catalog's row, the paper arXiv:2507.20984, the public
# modeling code's order of operations).  No kernel, no sort, no fused loss
# (``jax.checkpoint`` around a layer and around a block of queries changes no
# number: it is how a 16k row's float32 activations fit): a block of queries
# at a time against EVERY key, the mask a dense ``[block, L]`` array built
# from the positions, attention a dense softmax with -inf outside it, a
# group's 7 query heads read their K/V head by an einsum over the group
# axis, the router's weights are the softmax over the six largest LOGITS (the
# published form), and each held expert is applied to every position and
# weighted by the position's routing weight for it, 0 where it was not
# chosen.  Departures from the published model, all of the cut: only experts
# ``experts_held`` are summed, the vocabulary is the held slice, 8 of the 52
# layers run.  Nothing here imports the program's ops/, models/ or
# parallel/ep.py.
# ---------------------------------------------------------------------------

def _rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * scale


def _rope(x, positions, theta: float):
    """Rotate-half RoPE on ``[T, H, D]``: pairs (i, i + D/2) turn by
    ``position * theta^(-2i/D)``."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def reference_mask(first_query: int, rows: int, length: int, window: int):
    """``M[i, j]`` for the queries ``first_query ..`` against all ``length``
    keys: true iff ``0 <= i - j`` and, under a window, ``i - j < window``."""
    import jax.numpy as jnp

    i = first_query + jnp.arange(rows)[:, None]
    j = jnp.arange(length)[None, :]
    mask = j <= i
    return mask & (i - j < window) if window else mask


def _reference_attention(cfg: dict, a, h, window: int, rope: bool):
    """One layer's attention on ``h [L, d]`` (the normed hidden state) ->
    the heads' outputs ``[L, H, dh]``."""
    import jax
    import jax.numpy as jnp

    n_h, n_kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    length = h.shape[0]
    block = min(int(cfg["reference_query_block"]), length)
    q = jnp.einsum("sd,dhk->shk", h, a["q_proj"]["kernel"])
    k = jnp.einsum("sd,dhk->shk", h, a["k_proj"]["kernel"])
    v = jnp.einsum("sd,dhk->shk", h, a["v_proj"]["kernel"])
    if rope:        # a global layer turns nothing
        positions = jnp.arange(length)
        q, k = (_rope(x, positions, cfg["rope_theta"]) for x in (q, k))
    # query head j reads K/V head j // (28 / 4): [T, 4, 7, dh]
    q = q.reshape(length, n_kv, n_h // n_kv, dh)

    @jax.checkpoint     # one block of queries against every key
    def queries(first, q_blk):
        mask = reference_mask(first, block, length, window)
        scores = jnp.einsum("tgrd,sgd->grts", q_blk, k) / math.sqrt(dh)
        alpha = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
        return jnp.einsum("grts,sgd->tgrd", alpha, v)

    n = length // block
    out = jax.lax.map(lambda xs: queries(*xs), (
        jnp.arange(n) * block, q.reshape(n, block, n_kv, n_h // n_kv, dh)))
    return out.reshape(length, n_h, dh)


def _reference_moe(cfg: dict, p: dict, routed, u):
    """The held experts' part of the layer's output for the rows ``u [n,
    d]``, routed on ``routed [n, d]``, and the ``[n, 6]`` experts each
    position chose."""
    import jax
    import jax.numpy as jnp

    e, k = cfg["router_experts"], cfg["moe_num_active_primary_experts"]
    first, end = cfg["experts_held"]
    logits = routed @ p["router"]["kernel"]                         # [n, 64]
    top_logits, top_idx = jax.lax.top_k(logits, k)
    top_w = jax.nn.softmax(top_logits, axis=-1)     # over the six chosen
    chosen = jax.nn.one_hot(top_idx, e, dtype=jnp.float32)      # [n, 6, 64]
    weight = jnp.einsum("nke,nk->ne", chosen, top_w)    # 0 where not chosen

    @jax.checkpoint     # one expert's activations at a time, again backward
    def expert(held):
        w, w_gate, w_up, w_down = held
        return w[:, None] * ((jax.nn.relu(u @ w_gate) * (u @ w_up)) @ w_down)

    out, _ = jax.lax.scan(lambda out, held: (out + expert(held), None),
                          jnp.zeros_like(u), (
        weight[:, first:end].T, p["experts_gate"], p["experts_up"],
        p["experts_down"]))
    return out, top_idx


def reference_forward(cfg: dict, params, ids):
    """``(logits [B, L, V], each layer's routing [B·L, 6])``."""
    import jax
    import jax.numpy as jnp

    eps, d = cfg["rms_norm_eps"], cfg["hidden_size"]
    b, length = ids.shape

    def layer(p, x, window, rope):
        h = _rms_norm(x, p["attn_norm"]["scale"], eps)
        out = jax.vmap(lambda row: _reference_attention(
            cfg, p["attn"], row, window, rope))(h)
        x1 = x + jnp.einsum("bqhk,hkd->bqd", out,
                            p["attn"]["o_proj"]["kernel"])
        u = _rms_norm(x1, p["mlp_norm"]["scale"], eps)
        # the router reads the layer's INPUT x, un-normed
        moe_out, top_idx = _reference_moe(
            cfg, p["moe"], x.reshape(b * length, d), u.reshape(b * length, d))
        return x1 + moe_out.reshape(b, length, d), top_idx

    x = params["embed"]["embedding"][ids]
    routing = []
    for i, (window, rope) in enumerate(layer_kinds(cfg)):
        # a layer's activations (GBs at 16k) again backward
        x, top_idx = jax.checkpoint(layer, static_argnums=(2, 3))(
            params[f"block_{i}"], x, window, rope)
        routing.append(top_idx)
    x = _rms_norm(x, params["final_norm"]["scale"], eps)
    return x @ params["lm_head"]["kernel"], routing


def reference_lm_loss(logits, ids):
    """Next-token cross-entropy: position i predicts token i + 1; the mean
    over the L - 1 targets of every row."""
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(logits[:, :-1])
    nll = -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll)
