"""Tensor (model) parallelism: GSPMD sharding rules over the ``tp`` axis.

The reference has no model parallelism at all (SURVEY.md §2.3 — "leave a
model axis as an extension point"); here it is first-class.  TPU-idiomatic
TP is *not* explicit collectives: params get Megatron-style layouts
(column-parallel up-projections, row-parallel down-projections) as
``PartitionSpec`` annotations, activations get ``with_sharding_constraint``
hints, and XLA/GSPMD inserts the all-reduces over ICI.

Rules are ``(path_regex, PartitionSpec)`` pairs matched against the
``/``-joined param path; first match wins, no match ⇒ replicated-over-tp
(then fsdp sharding may still apply via ``compose_fsdp``).
"""

from __future__ import annotations

import re
from typing import Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Rules = Sequence[tuple[str, P]]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def rule_shardings(mesh: Mesh, tree, rules: Rules, *, default: P = P()):
    """Per-leaf NamedShardings from path-regex rules (first match wins)."""
    compiled = [(re.compile(pat), spec) for pat, spec in rules]

    def leaf(path, x):
        s = _path_str(path)
        for pat, spec in compiled:
            if pat.search(s):
                return NamedSharding(mesh, spec)
        return NamedSharding(mesh, default)

    return jax.tree_util.tree_map_with_path(leaf, tree)


def compose_fsdp(mesh: Mesh, tree, shardings):
    """Layer fsdp sharding on top of tp rules: any leaf dim not already
    tp-sharded is split over ``fsdp`` (largest divisible dim), so TP and
    ZeRO-3 compose the way Megatron-LM + FSDP do."""
    from tensorflowonspark_tpu.parallel.mesh import pick_shard_dim

    axis_size = dict(zip(mesh.axis_names, mesh.devices.shape)).get("fsdp", 1)

    def leaf(x, sharding):
        if axis_size == 1 or not hasattr(x, "shape") or x.ndim == 0:
            return sharding
        spec = list(sharding.spec) + [None] * (x.ndim - len(sharding.spec))
        used = {a for s in spec if s is not None
                for a in (s if isinstance(s, tuple) else (s,))}
        if "fsdp" in used:
            return sharding
        taken = tuple(d for d, s in enumerate(spec) if s is not None)
        d = pick_shard_dim(x.shape, axis_size, taken)
        if d is None:
            return sharding
        spec[d] = "fsdp"
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(leaf, tree, shardings)


def constrain(x, spec: P):
    """Activation sharding hint; no-op when no mesh context is active (so
    models run unchanged on a bare single device / in unit tests).

    Axes that are in MANUAL mode — i.e. we are inside a ``shard_map`` body,
    e.g. a transformer Block running as a GPipe pipeline stage — are dropped
    from the spec: per-device code already sees local shards, and
    ``with_sharding_constraint`` rejects Manual axes outright.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    known = set(mesh.axis_names) - set(mesh.manual_axes)
    if not known:
        return x
    clean = P(*(
        (tuple(a for a in s if a in known) or None)
        if isinstance(s, tuple) else (s if s in known else None)
        for s in spec
    ))
    return jax.lax.with_sharding_constraint(x, clean)


# Megatron-style rule set for the transformer family (models/transformer.py
# param tree): attention q/k/v shard the heads dim (column-parallel), o_proj
# the heads-input dim (row-parallel); MLP up/gate column-, down row-parallel;
# embeddings/lm_head shard the vocab; norms replicate.
# q/k/v kernels are DenseGeneral 3-D [d_model, heads, d_head]; o_proj is
# [heads, d_head, d_model].
TRANSFORMER_TP_RULES: Rules = (
    (r"(q_proj|k_proj|v_proj)/kernel$", P(None, "tp", None)),
    (r"o_proj/kernel$", P("tp", None, None)),
    (r"(up_proj|gate_proj)/kernel$", P(None, "tp")),
    (r"down_proj/kernel$", P("tp", None)),
    (r"embed/embedding$", P("tp", None)),
    (r"lm_head/kernel$", P(None, "tp")),
    # MoE expert-stacked weights: leading dim is the expert axis (ep), the
    # per-expert matrices keep Megatron layouts over tp.
    (r"experts_(up|gate)$", P("ep", None, "tp")),
    (r"experts_down$", P("ep", "tp", None)),
    (r"router/kernel$", P()),
    # QK-norm scales span all heads (the norm is over n_heads·d_head)
    (r"(q_norm|k_norm)/scale$", P()),
    # a per-layer-mixer model (``Transformer.layer_mixer``): the state-space
    # mixer and the experts' latent maps are replicated over tp (sharding a
    # Mamba-2 layer's heads and B/C groups over tp is not built); the shared
    # relu2 expert's ``up_proj`` / ``down_proj`` take the rules above
    (r"ssm/(in_proj|out_proj)/kernel$", P()),
    (r"ssm/(conv_kernel|conv_bias|A_log|D|dt_bias|norm_scale)$", P()),
    (r"moe/latent_(down|up)/kernel$", P()),
    # residual streams (``Transformer.hyper``): a hyper-connection's maps
    # read all ``n·C`` values of a token and are replicated over tp, as the
    # carry ``[B, S, n·C]`` is (``P(BATCH, "sp", None)``); so is the
    # multi-token-prediction module's ``[2·C, C]`` projection, and latent
    # attention's query latent follows no rule (``q_a_proj``, ``q_b_proj``:
    # replicated, as ``kv_a_proj`` / ``kv_b_proj`` are)
    (r"hc_(attn|mlp)/(phi|bias|alpha|norm_scale)$", P()),
    (r"mtp_eh_proj/kernel$", P()),
    # a Kimi Delta Attention layer (``Transformer.layer_attention`` kind
    # ``"kda"``): its four big projections are ``[d_model, heads, d]`` /
    # ``[heads, d, d_model]`` under attention's names and take attention's
    # rules above; the low-rank pairs of the decay and of the output gate,
    # ``β``'s map, the three short convs and the per-head and per-channel
    # vectors are replicated over tp (a KDA layer whose heads' op, convs and
    # gates are sharded over tp is not built: GSPMD gathers the heads)
    (r"attn/(f_a_proj|f_b_proj|g_a_proj|g_b_proj|b_proj)/kernel$", P()),
    (r"attn/(q_conv|k_conv|v_conv|A_log|dt_bias|o_norm)$", P()),
)
