"""Expert parallelism: mixture-of-experts FFN sharded over the ``ep`` axis.

Absent in the reference (SURVEY.md §2.3).  TPU-idiomatic MoE keeps the
GShard/Switch *static-capacity* contract (top-k routing, per-expert capacity
``c``, overflow dropped — no dynamic shapes anywhere) but dispatches with
**sorted indices** instead of the classic one-hot einsums: the einsum
formulation materializes ``[n, e, c]`` dispatch/combine tensors, which at
serious shapes (16k tokens × 64 experts × c=512) is ~2 GB *per tensor per
layer*; the sort formulation carries only ``[n·k]`` index/gate vectors and
scatters straight into the ``[e, c, d]`` expert buffers — the MegaBlocks /
modern-maxtext-style dropping dispatch, here with slot assignment matched
bit-for-bit to the GShard priority rule (see ``_sorted_dispatch``).

Expert-stacked weights keep the expert dimension sharded over ``ep``; the
``P('ep', …)`` constraints on the expert buffers make GSPMD materialize the
token shuffle as all-to-alls over ICI exactly as before.

That is the routing rule when ``MoEMLP`` is given a ``capacity_factor``.
Given ``None`` it routes **droplessly**, as the published open MoEs do
(OLMoE, arXiv:2409.02060): every one of the ``n·k`` (token, expert) pairs is
computed whatever the routing, with shapes that do not depend on it.  With
nothing dropped there is no priority to keep, so the pairs are sorted ONCE
by expert; each expert's group is laid out from a block boundary
(``_block_layout``), so that every block of rows belongs to one expert, and
a scan over the blocks runs the three expert matmuls of each against its
expert's weights — the grouped matmul in plain XLA, at most ``e`` blocks of
padding in all, the same work whatever the routing.  Tokens go to rows and
rows back to tokens by gathers in both directions (``_dispatch`` /
``_combine``).  Of the XLA formulations timed on a v5e at OLMoE's shape
(PERF.md, PR 25) this was the fastest that is not a custom call.

``MoEMLP`` is a flax module usable standalone or inside
``models/transformer.py``.  Two auxiliary losses are sown into the
``"aux_loss"`` collection (fetch with ``mutable=["aux_loss"]``):
``load_balance`` (Switch eq. 4) and ``router_z`` (ST-MoE z-loss,
``mean(logsumexp(router_logits)^2)`` — keeps router logits from drifting
into f32-overflow territory); ``models.transformer.make_loss_fn`` weights
them independently.  The ``"moe_stats"`` collection gets ``max_load`` and
``min_load``: pairs routed to the fullest and to the emptiest expert over
the mean — what shows a router collapsing.
"""

from __future__ import annotations

import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tensorflowonspark_tpu.parallel.tp import constrain


def _sorted_dispatch(top_idx, top_p, capacity: int, n_experts: int):
    """GShard slot assignment without one-hot tensors.

    Returns ``(slots, token_ids, gates, keep)``, each ``[n · k]`` flat over
    (choice-round j, sorted-token) pairs: ``slots`` is the flat
    expert-buffer slot (``expert · capacity + position``, or ``e ·
    capacity`` for dropped pairs), ``token_ids`` the source token of each
    pair, ``gates`` its normalized routing weight.

    Slot semantics are IDENTICAL to the classic priority-loop formulation
    (mesh-tf Switch / GShard): within round j, positions are assigned in
    token order (stable sort by expert id = rank within expert); rounds are
    processed in priority order, and only KEPT assignments from earlier
    rounds advance an expert's fill counter.  All shapes static; the sorts
    are ``[n]``-sized and jit-friendly.
    """
    n, k = top_idx.shape
    e = n_experts
    counts = jnp.zeros((e,), jnp.int32)       # kept fills per expert so far
    slots, toks, gates, keeps = [], [], [], []
    for j in range(k):
        eid = top_idx[:, j]
        order = jnp.argsort(eid, stable=True)
        sorted_eid = eid[order]
        starts = jnp.searchsorted(sorted_eid, jnp.arange(e), side="left")
        # rank of this pair within its expert (token order) + prior fills
        pos = jnp.arange(n) - starts[sorted_eid] + counts[sorted_eid]
        keep = pos < capacity
        slots.append(jnp.where(keep, sorted_eid * capacity + pos, e * capacity))
        toks.append(order)
        gates.append(top_p[order, j])
        keeps.append(keep)
        counts = counts.at[sorted_eid].add(keep.astype(jnp.int32))
    return (jnp.concatenate(slots), jnp.concatenate(toks),
            jnp.concatenate(gates), jnp.concatenate(keeps))


# ---------------------------------------------------------------------------
# Dropless routing: one sort, groups laid out from block boundaries.
# ---------------------------------------------------------------------------

def _block_rows(n_pairs: int, n_experts: int) -> int:
    """Rows per block: the mean group size rounded up to a power of two,
    at most 512 (one block's three matmuls are then 6 GFLOP at OLMoE's
    widths against 12 MB of weights: compute-bound on the MXU)."""
    mean = max(1, n_pairs // n_experts)
    return min(512, 1 << (mean - 1).bit_length())


def _block_layout(top_idx, sizes, block: int):
    """Index maps between the ``n·k`` pairs (token-major: pair ``t·k + j``
    is token ``t``'s j-th choice; ``sizes[e]`` of them chose expert e) and
    the rows of the padded layout, in which expert 0's pairs come first, in
    token order, then expert 1's from the next multiple of ``block``, and so
    on.  ``n·k/block + e`` blocks always hold them, so every shape is static.

    Returns ``(block_expert, pair_of_row, valid, row_of_pair)``: each
    block's expert ``[blocks]``; for every row the pair that lives there and
    whether one does ``[blocks·block]``; for every pair its row ``[n, k]``.
    """
    n, k = top_idx.shape
    e = sizes.shape[0]
    n_blocks = n * k // block + e
    flat = top_idx.reshape(-1)
    order = jnp.argsort(flat, stable=True)       # the one sort
    starts = jnp.cumsum(sizes) - sizes
    blocks = (sizes + block - 1) // block
    block_ends = jnp.cumsum(blocks)
    block_starts = block_ends - blocks
    block_expert = jnp.minimum(
        jnp.searchsorted(block_ends, jnp.arange(n_blocks), side="right"),
        e - 1).astype(jnp.int32)
    row = jnp.arange(n_blocks * block)
    row_expert = block_expert[row // block]
    within = row - block_starts[row_expert] * block
    valid = (within < sizes[row_expert]) & (row // block < block_ends[-1])
    pair_of_row = order[jnp.where(valid, starts[row_expert] + within, 0)]
    sorted_expert = flat[order]
    row_sorted = (block_starts[sorted_expert] * block
                  + jnp.arange(n * k) - starts[sorted_expert])
    row_of_pair = (jnp.zeros((n * k,), jnp.int32)
                   .at[order].set(row_sorted.astype(jnp.int32))
                   .reshape(n, k))
    return block_expert, pair_of_row, valid, row_of_pair


# Tokens to rows and rows back to tokens.  Both maps are known in both
# directions, so the transpose of each gather is written as a gather too
# (autodiff would scatter-add 2048-wide rows).

@jax.custom_vjp
def _dispatch(x, pair_of_row, valid, row_of_pair):
    """``[n, d]`` tokens -> ``[rows, d]``: row r holds its pair's token,
    padding rows hold zeros."""
    k = row_of_pair.shape[1]
    return x[pair_of_row // k] * valid[:, None].astype(x.dtype)


def _dispatch_fwd(x, pair_of_row, valid, row_of_pair):
    return _dispatch(x, pair_of_row, valid, row_of_pair), row_of_pair


def _dispatch_bwd(row_of_pair, g):
    return jnp.sum(g[row_of_pair], axis=1), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(out, weights, pair_of_row, valid, row_of_pair):
    """``[rows, d]`` expert outputs -> ``[n, d]``: each token's k rows,
    weighted by its routing weights ``[n, k]``, summed."""
    return jnp.einsum("nkd,nk->nd", out[row_of_pair],
                      weights.astype(out.dtype))


def _combine_fwd(out, weights, pair_of_row, valid, row_of_pair):
    return (_combine(out, weights, pair_of_row, valid, row_of_pair),
            (out, weights, pair_of_row, valid, row_of_pair))


def _combine_bwd(res, dy):
    out, weights, pair_of_row, valid, row_of_pair = res
    k = row_of_pair.shape[1]
    row_weight = jnp.where(valid, weights.reshape(-1)[pair_of_row], 0.0)
    d_out = dy[pair_of_row // k] * row_weight[:, None].astype(dy.dtype)
    d_weights = jnp.einsum("nkd,nd->nk", out[row_of_pair], dy,
                           preferred_element_type=jnp.float32)
    return d_out, d_weights.astype(weights.dtype), None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


class MoEMLP(nn.Module):
    """Top-k routed SwiGLU MoE FFN, ``[B, S, D] -> [B, S, D]``.

    Param layout (matched by ``tp.TRANSFORMER_TP_RULES``): ``router/kernel``
    replicated; ``experts_gate``/``experts_up`` ``[E, D, F]`` and
    ``experts_down`` ``[E, F, D]`` sharded ``P('ep', …)`` (+ tp on F).

    The routing rule is a property of the model, chosen by
    ``capacity_factor``: a number is the GShard/Switch static capacity
    (overflow dropped, see ``_sorted_dispatch``); ``None`` is dropless
    routing (module docstring), whose load-balance term counts all k
    choices of a token, as OLMoE and the HF ``load_balancing_loss_func``
    define it, where the capacity rule counts the first (Switch eq. 4).
    ``norm_topk_prob`` renormalises the k routing weights to sum to 1
    (HF's key of that name; OLMoE publishes ``false``).
    """

    d_model: int
    d_ff: int
    n_experts: int
    top_k: int = 2
    capacity_factor: Optional[float] = 1.25
    compute_dtype: jnp.dtype = jnp.float32
    norm_topk_prob: bool = True

    @nn.compact
    def __call__(self, x):
        b, s, d = x.shape
        n = b * s
        e = self.n_experts
        dropless = self.capacity_factor is None
        xf = x.reshape(n, d)

        with jax.named_scope("moe/router"):
            router = nn.Dense(e, use_bias=False, name="router",
                              dtype=jnp.float32)  # routing always f32
            router_logits = router(xf.astype(jnp.float32))
            probs = jax.nn.softmax(router_logits, axis=-1)
            top_p, top_idx = jax.lax.top_k(probs, self.top_k)     # [n, k]
            if self.norm_topk_prob:
                top_p = top_p / jnp.maximum(
                    jnp.sum(top_p, -1, keepdims=True), 1e-9)

            # Load-balancing aux loss, e · Σ_e f_e · P_e: f_e the share of
            # tokens whose FIRST choice is e (Switch eq. 4) or, dropless,
            # the pairs routed to e per token (all k choices).
            pairs = jnp.sum(jax.nn.one_hot(top_idx, e, dtype=jnp.int32),
                            axis=(0, 1))                           # [e]
            counted = pairs if dropless else jnp.sum(
                jax.nn.one_hot(top_idx[:, 0], e, dtype=jnp.float32), axis=0)
            frac_probs = jnp.mean(probs, axis=0)
            self.sow("aux_loss", "load_balance",
                     e * jnp.sum(counted / n * frac_probs))
            # Router z-loss (ST-MoE): keeps router logits bounded.
            z = jax.scipy.special.logsumexp(router_logits, axis=-1)
            self.sow("aux_loss", "router_z", jnp.mean(z * z))
            mean_pairs = n * self.top_k / e
            self.sow("moe_stats", "max_load", jnp.max(pairs) / mean_pairs)
            self.sow("moe_stats", "min_load", jnp.min(pairs) / mean_pairs)
            # for a caller that asks (mutable=["intermediates"]): the routing
            self.sow("intermediates", "top_idx", top_idx)

        w_gate = self.param("experts_gate", nn.initializers.lecun_normal(),
                            (e, d, self.d_ff))
        w_up = self.param("experts_up", nn.initializers.lecun_normal(),
                          (e, d, self.d_ff))
        w_down = self.param("experts_down", nn.initializers.lecun_normal(),
                            (e, self.d_ff, d))
        if dropless:
            y = self._dropless(xf, top_idx, top_p, pairs, w_gate, w_up,
                               w_down)
        else:
            y = self._capacity(xf, top_idx, top_p, w_gate, w_up, w_down)
        return y.reshape(b, s, d).astype(x.dtype)

    def _dropless(self, xf, top_idx, top_p, pairs, w_gate, w_up, w_down):
        mesh = jax.sharding.get_abstract_mesh()
        if not mesh.empty and dict(mesh.shape).get("ep", 1) > 1:
            raise NotImplementedError(
                "dropless routing (capacity_factor=None) over an ep axis of "
                f"size {mesh.shape['ep']}: sending uneven groups to the "
                "ranks that hold their experts needs a ragged all-to-all, "
                "which parallel/ep.py does not have; GSPMD would gather "
                "every expert onto every rank instead.  Use ep=1 (experts "
                "replicated or tp-sharded) or give a capacity_factor")
        n, d = xf.shape
        e, cdt = self.n_experts, self.compute_dtype
        block = _block_rows(n * self.top_k, e)
        with jax.named_scope("moe/dispatch"):
            block_expert, pair_of_row, valid, row_of_pair = _block_layout(
                top_idx, pairs, block)
            rows = _dispatch(xf.astype(cdt), pair_of_row, valid, row_of_pair)
            rows = rows.reshape(-1, block, d)
        with jax.named_scope("moe/experts"):
            w_gate, w_up, w_down = (w.astype(cdt)
                                    for w in (w_gate, w_up, w_down))

            def one_block(_, block_in):
                x_block, expert = block_in
                h = (jax.nn.silu(x_block @ w_gate[expert])
                     * (x_block @ w_up[expert]))
                return None, h @ w_down[expert]

            _, out = jax.lax.scan(one_block, None, (rows, block_expert))
        with jax.named_scope("moe/combine"):
            return _combine(out.reshape(-1, d), top_p, pair_of_row, valid,
                            row_of_pair)

    def _capacity(self, xf, top_idx, top_p, w_gate, w_up, w_down):
        n, d = xf.shape
        e, cdt = self.n_experts, self.compute_dtype
        capacity = max(1, int(math.ceil(n * self.capacity_factor
                                        * self.top_k / e)))
        with jax.named_scope("moe/dispatch"):
            slots, toks, gates, keeps = _sorted_dispatch(
                top_idx, top_p, capacity, e)
            x_pairs = xf[toks].astype(cdt) * keeps[..., None].astype(cdt)
            expert_in = (jnp.zeros((e * capacity, d), cdt)
                         .at[slots].add(x_pairs, mode="drop")
                         .reshape(e, capacity, d))
            # The ep constraints make GSPMD materialise the token shuffle
            # as all-to-alls over the ep axis (tokens in, outputs back).
            expert_in = constrain(expert_in, P("ep", None, None))
        with jax.named_scope("moe/experts"):
            h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in,
                                        w_gate.astype(cdt)))
                 * jnp.einsum("ecd,edf->ecf", expert_in, w_up.astype(cdt)))
            h = constrain(h, P("ep", None, "tp"))
            out = jnp.einsum("ecf,efd->ecd", h, w_down.astype(cdt))
            out = constrain(out, P("ep", None, None))
        with jax.named_scope("moe/combine"):
            # gather each kept pair's expert output, weight by its gate,
            # scatter-add back to its source token
            out_flat = out.reshape(e * capacity, d)
            safe = jnp.minimum(slots, e * capacity - 1)
            contrib = (out_flat[safe]
                       * gates[..., None].astype(cdt)
                       * keeps[..., None].astype(cdt))
            return jnp.zeros((n, d), cdt).at[toks].add(contrib)
