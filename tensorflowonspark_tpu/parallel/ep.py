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
by expert (``_sorted_layout``: the stable sort and its inverse) and the three
expert matmuls run as a grouped matmul over the sorted rows, driven by the
group sizes (``ops/grouped_matmul.py``: Pallas kernels on TPU,
``jax.lax.ragged_dot`` elsewhere): no row is padding, and the program is the
same whatever the routing.  Tokens go to rows and rows back to tokens by
gathers in both directions (``_dispatch`` / ``_combine``).

``MoEMLP`` is a flax module usable standalone or inside
``models/transformer.py``.  Two auxiliary losses are sown into the
``"aux_loss"`` collection (fetch with ``mutable=["aux_loss"]``):
``load_balance`` (Switch eq. 4) and ``router_z`` (ST-MoE z-loss,
``mean(logsumexp(router_logits)^2)`` — keeps router logits from drifting
into f32-overflow territory); ``models.transformer.make_loss_fn`` weights
them independently.  The ``"moe_stats"`` collection gets ``max_load`` and
``min_load``: pairs routed to the fullest and to the emptiest expert over
the mean — what shows a router collapsing.  Dropless routing adds
``executed_rows``: the rows the grouped matmul's tiles compute over the
``n·k`` routed ones (a row tile that holds rows of several experts is
visited once for each), which is what uneven routing costs the kernel.

**The chip's share of a layer's experts** (``MoEMLP.held``): under expert
parallelism a chip holds a range of the layer's experts.  The router stays
as wide as the layer and routes over all of it; this chip computes the part
of the result that its own experts give, and what the absent ones would add
is left out (on one chip the layer runs without its exchange).  Every pair
MAY land here, so a buffer of sorted rows that fits whatever the routing is
``n·k`` long, while on average ``held / n_experts`` of the pairs come: such
buffers would cost eight layers' worth of gathers and memory for one
layer's work.  So one sort over all the pairs puts the held ones first, in
token order, and they are taken ``piece`` rows at a time (twice the even
share, a static size): the first piece always, the further ones by a loop
whose trip count follows the pairs that came (``_held_experts``): none on
even routing, ``n·k / piece - 1`` if every pair lands here, the same program
either way.  Within a piece the pairs are sorted by expert for the grouped
matmul, whose tiles past the piece's held rows are not visited.  That sort
and its inverse are the piece's ONE index plan (``_Plan``, built once under
``moe/dispatch``): tokens go to the expert-ordered rows by one gather
(``_take``: row r is token ``src[r]``'s), and the combine's cotangent goes
the same way by the same index, weighted by the routing weights in expert
order; rows go back to tokens by ``ops/sum_tokens.py`` (``_give``, and
``_take``'s transpose): one gather to token order, where a token's pairs
lie next to each other, and one kernel that reads the tiles that hold a pair
and writes each token's weighted sum once.  The routing weights' cotangent
is the row sum of the experts' output times that gathered cotangent, taken
back to the pairs by a piece-long scatter.  ``moe_stats/held_pairs`` is the
held pairs' share of ``n·k``, ``moe_stats/held_max_load`` the pairs at the
busiest held expert over the held experts' mean, ``moe_stats/moved_rows``
the share of the first piece's rows that the sum back to tokens reads (whole
tiles; about a half on even routing, 1 off the TPU, where the specification
passes over the whole piece).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tensorflowonspark_tpu import telemetry
from tensorflowonspark_tpu.ops.grouped_matmul import (executed_rows,
                                                       grouped_matmul)
from tensorflowonspark_tpu.ops.sum_tokens import moved_rows, sum_tokens
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
# Dropless routing: one sort, a grouped matmul over the sorted pairs.
# ---------------------------------------------------------------------------

def _sorted_layout(top_idx):
    """The ``n·k`` pairs (token-major: pair ``t·k + j`` is token ``t``'s j-th
    choice) in expert order, token order within an expert.  Returns
    ``(order, row_of_pair)``: the pair that sorted row r holds ``[n·k]``,
    and its inverse, every pair's row ``[n, k]``."""
    n, k = top_idx.shape
    order = jnp.argsort(top_idx.reshape(-1), stable=True)    # the one sort
    row_of_pair = (jnp.zeros((n * k,), jnp.int32)
                   .at[order].set(jnp.arange(n * k, dtype=jnp.int32))
                   .reshape(n, k))
    return order, row_of_pair


# Tokens to rows and rows back to tokens.  Both maps are known in both
# directions, so the transpose of each gather is written as a gather too
# (autodiff would scatter-add 2048-wide rows).

@jax.custom_vjp
def _dispatch(x, order, row_of_pair):
    """``[n, d]`` tokens -> ``[n·k, d]``: row r holds its pair's token."""
    return x[order // row_of_pair.shape[1]]


def _dispatch_fwd(x, order, row_of_pair):
    return _dispatch(x, order, row_of_pair), row_of_pair


def _dispatch_bwd(row_of_pair, g):
    return jnp.sum(g[row_of_pair], axis=1), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(out, weights, order, row_of_pair):
    """``[n·k, d]`` expert outputs -> ``[n, d]``: each token's k rows,
    weighted by its routing weights ``[n, k]``, summed."""
    return jnp.einsum("nkd,nk->nd", out[row_of_pair],
                      weights.astype(out.dtype))


def _combine_fwd(out, weights, order, row_of_pair):
    return (_combine(out, weights, order, row_of_pair),
            (out, weights, order, row_of_pair))


def _combine_bwd(res, dy):
    out, weights, order, row_of_pair = res
    k = row_of_pair.shape[1]
    d_out = (dy[order // k]
             * weights.reshape(-1)[order][:, None].astype(dy.dtype))
    d_weights = jnp.einsum("nkd,nd->nk", out[row_of_pair], dy,
                           preferred_element_type=jnp.float32)
    return d_out, d_weights.astype(weights.dtype), None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


# ---------------------------------------------------------------------------
# A chip's share of the experts: the held pairs, a piece at a time.
# ---------------------------------------------------------------------------

class _Plan(NamedTuple):
    """One piece's index plan, built once (under ``moe/dispatch``): every
    way a ``[piece, d]`` array is read or written, forward and backward.
    Row ``r`` of the piece in EXPERT order (the grouped matmul's) is token
    ``src[r]``'s and lies at position ``order[r]`` of the piece in TOKEN
    order, where position ``s`` is row ``inverse[s]`` and a token's
    positions end at ``end[t]`` (its run: ``end[t - 1] .. end[t] - 1``; the
    positions from ``end[-1]`` on hold no pair)."""

    src: jax.Array
    order: jax.Array
    inverse: jax.Array
    end: jax.Array


# The piece's two maps.  Each is the other's transpose up to the weights, so
# both directions of both are ONE gather by ``src`` (tokens to rows) or one
# ``sum_tokens`` (rows to tokens): autodiff would scatter-add rows, and a
# gather to token order and back is two where one does.  ``how``: the
# longest run, and the mesh's axes that GSPMD partitions over (``(run,
# axes)``, static).

def _token_sums(how, rows, plan: "_Plan", *gates):
    run, axes = how
    sums = functools.partial(sum_tokens, run=run)
    if axes:    # as the experts' kernels: every rank sums all the rows
        sums = jax.shard_map(sums, in_specs=P(), out_specs=P(),
                             axis_names=frozenset(axes), check_vma=False)
    return sums(rows, plan.inverse, plan.end, *gates)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _take(how, x, plan: _Plan):
    """``[n, d]`` tokens -> ``[piece, d]``: row r holds its pair's token."""
    return x[plan.src]


def _take_fwd(how, x, plan):
    return x[plan.src], plan


def _take_bwd(how, plan, g):
    return _token_sums(how, g, plan), None


_take.defvjp(_take_fwd, _take_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _give(how, out, gates, plan: _Plan):
    """``[piece, d]`` expert outputs -> ``[n, d]``: each token's rows,
    weighted by its routing weights (``gates`` ``[piece]``, in token order,
    0 where a position holds no pair), summed."""
    return _token_sums(how, out, plan, gates)


def _give_fwd(how, out, gates, plan):
    return _give(how, out, gates, plan), (out, gates, plan)


def _give_bwd(how, res, dy):
    out, gates, plan = res
    g = dy[plan.src]                        # the dispatch's gather
    d_out = g * gates[plan.order][:, None].astype(g.dtype)
    d_gates = jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32), -1)
    return d_out, d_gates[plan.inverse].astype(gates.dtype), None


_give.defvjp(_give_fwd, _give_bwd)


def _piece_rows(pairs: int, share: float) -> int:
    """Rows of one piece of held pairs: twice the even share of the ``pairs``
    (token, expert) pairs, in whole row tiles, at most all of them."""
    return min(pairs, -(-int(2 * pairs * share) // 256) * 256)


def _held_piece(c, ints, xf, top_p, *weights, ffn, first: int, n_held: int,
                piece: int, axes: tuple):
    """What the held pairs ``c·piece .. (c+1)·piece`` (in token order) add to
    the layer's output ``[n, d]``; ``weights``: the experts' matrices, as
    ``ffn`` takes them."""
    flat_idx, ends, held_first = ints
    pairs = flat_idx.shape[0]
    k = pairs // xf.shape[0]
    # a token's held rows: at most one an expert held (8 of a top 22)
    how = (min(k, n_held), axes)
    lo = c * piece
    with jax.named_scope("moe/dispatch"):
        at = jnp.arange(piece, dtype=jnp.int32)
        valid = lo + at < ends[-1]
        # the pairs that are the piece's held ones, their tokens and experts
        pair = jax.lax.dynamic_slice(held_first, (lo,), (piece,))
        expert = jnp.where(valid, flat_idx[pair] - first, n_held)
        order = jnp.argsort(expert, stable=True)      # the piece's one sort
        sizes = jnp.sum(jax.nn.one_hot(expert, n_held, dtype=jnp.int32), 0)
        plan = _Plan(src=(pair // k)[order], order=order,
                     inverse=jnp.zeros((piece,), jnp.int32).at[order].set(at),
                     end=jnp.clip(ends - lo, 0, piece))
        rows = _take(how, xf, plan)
    with jax.named_scope("moe/experts"):
        out = ffn(rows, *weights, sizes)
    with jax.named_scope("moe/combine"):
        # this piece's pairs' routing weights, in token order; a position
        # past the held ones reads none and sends no cotangent (the indices
        # ascend and none comes twice: the transpose is a piece-long scatter)
        gates = top_p.reshape(-1).at[jnp.where(valid, pair, pairs + at)].get(
            mode="fill", fill_value=0, indices_are_sorted=True,
            unique_indices=True)
        return _give(how, out, gates, plan)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _held_experts(piece_fn, piece: int, ints, args):
    """The sum of ``piece_fn(c, ints, *args)`` over the pieces of ``piece``
    rows AFTER THE FIRST that hold a held pair (``ints[1][-1]`` of them: the
    trip count is the routing's, no shape is).  The backward runs each piece
    again."""
    n_pieces = -(-ints[1][-1] // piece)
    return jax.lax.fori_loop(
        1, n_pieces, lambda c, y: y + piece_fn(c, ints, *args),
        jnp.zeros_like(args[0]))


def _held_experts_fwd(piece_fn, piece, ints, args):
    return _held_experts(piece_fn, piece, ints, args), (ints, args)


def _held_experts_bwd(piece_fn, piece, res, g):
    ints, args = res
    n_pieces = -(-ints[1][-1] // piece)

    def body(c, grads):
        _, vjp = jax.vjp(lambda *a: piece_fn(c, ints, *a), *args)
        return jax.tree.map(jnp.add, grads, vjp(g))

    return None, jax.lax.fori_loop(
        1, n_pieces, body, jax.tree.map(jnp.zeros_like, args))


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def _pick(probs, top_idx):
    """``(take_along_axis(probs, top_idx, -1), hit)`` with no gather: ``hit
    [n, k, e]`` is the one-hot of the choice, and the chosen scores are a
    compare, a select and a sum over the experts' lanes.  The chip has no
    fast element gather: XLA's moves a value at a time, on a v5e 1.84 ms a
    layer for 22 of 512 a token of 8,192 tokens, and 1.70 for its transpose
    (a sort of the indices and a scatter-add, which carry no scope).  A
    dense pass over every (token, choice, expert) triple takes 0.15 ms, and
    its transpose, the same pass summed over the choices, 0.11: no
    scatter-add in the backward either.  The values are the gather's to the
    bit both ways (a token's chosen experts are distinct: every sum has ONE
    term that is not 0).  XLA fuses each direction into one reduce, so
    ``[n, k, e]`` is in no memory.  The result stands behind a barrier: the
    renormalisation's sum over the choices would merge into the pick's
    reduce, one over ``(k, e)`` that adds in another order (a weight's last
    bit) and is five times slower at 8 of 128."""
    hit = top_idx[:, :, None] == jnp.arange(probs.shape[-1],
                                            dtype=top_idx.dtype)
    top_p = jnp.sum(jnp.where(hit, probs[:, None, :], 0), -1)
    return jax.lax.optimization_barrier(top_p), hit


class MoEMLP(nn.Module):
    """Top-k routed gated (SwiGLU, ReGLU) or relu² MoE FFN, ``[B, S, D] ->
    [B, S, D]``.

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

    The router (scope ``moe/router``): a float32 product, the scores,
    ``lax.top_k`` for the INDICES of the choice alone, then ONE one-hot of
    the choice, ``hit [n, k, e]``, which gives the chosen scores (``_pick``:
    a select and a sum over the experts, with or without a selection bias;
    ``top_k``'s own values and their JVP are not used) and the pairs an
    expert is sent (``sum(hit, (0, 1))``).  Neither direction holds a gather
    or a scatter-add over ``[n, e]``, ``hit`` is fused into the passes that
    read it, and the counter ``moe.router.picks`` goes up by ``n·k`` for
    each router traced.

    ``held = (first, end)`` is this chip's share of the layer's experts
    (module docstring): the router and the routing stay ``n_experts`` wide,
    the expert weights are ``[end - first, ...]`` and the output is the held
    experts' part of the sum.  Dropless routing only.

    The router of DeepSeek-V3 (arXiv:2412.19437; transformers'
    ``deepseek_v3``): ``scoring="sigmoid"`` scores an expert by the sigmoid
    of its logit, on its own; ``selection_bias`` adds a bias a expert
    (``e_score_correction_bias``) to the scores FOR THE CHOICE of the top k
    only, the weights being the UNBIASED scores of the chosen, renormalised
    (``norm_topk_prob``: over their sum + 1e-20) and multiplied by
    ``routed_scale``.  The bias is a BUFFER, a variable of the collection
    ``buffers`` beside ``params``: the loss is not differentiated by it and
    no optimizer is handed it (``parallel/dp.TrainState.buffers`` carries
    the collection through a step as it is); balance is its business, so
    neither the load-balance nor the z term is sown under it, and
    ``moe_stats/bias_moved`` is the share of the (token, expert) choices
    that the unbiased scores would not have made.

    LatentMoE (Nemotron-3's ``E`` layers): ``latent`` > 0 puts the routed
    experts in a latent of that width between two linear maps that are the
    layer's own, ``ℓ = x·latent_down`` (``d_model`` -> ``latent``) before the
    dispatch and ``·latent_up`` after the combine, both under the scope
    ``moe/latent``; the ROUTER still reads ``x``.  ``expert_act="relu2"``
    gives an expert two matrices and no gate, ``relu(ℓ·U_e)²·V_e``
    (``experts_up``, ``experts_down``; ``"swiglu"``: the three of a SwiGLU).
    ``expert_act="reglu"`` is the SwiGLU's three matrices with ``relu`` on the
    gate, ``(relu(x·G_e) ⊙ (x·U_e))·D_e`` (SmallThinker's "sparse ReGLU",
    arXiv:2507.20984).  All under dropless routing only.

    ``__call__(x, router_input=None)``: the router may read ANOTHER array of
    the tokens than the experts do (SmallThinker takes the logits from the
    layer's input, before attention, and feeds the experts the normed state
    after it): logits, scores, choice, auxiliary terms and statistics are
    ``router_input``'s, the rows that are dispatched are ``x``'s, and each
    gets its own cotangent.  The scope stays ``moe/router``; XLA is free to
    run that product as early as its operand is there.  None: ``x`` itself,
    the program as it was.
    """

    d_model: int
    d_ff: int
    n_experts: int
    top_k: int = 2
    capacity_factor: Optional[float] = 1.25
    compute_dtype: jnp.dtype = jnp.float32
    norm_topk_prob: bool = True
    held: Optional[tuple] = None
    scoring: str = "softmax"
    selection_bias: bool = False
    routed_scale: float = 1.0
    expert_act: str = "swiglu"
    latent: int = 0

    @nn.compact
    def __call__(self, x, router_input=None):
        b, s, d = x.shape
        n = b * s
        e = self.n_experts
        dropless = self.capacity_factor is None
        held = (0, e) if self.held is None else tuple(self.held)
        if not 0 <= held[0] < held[1] <= e or (self.held and not dropless):
            raise ValueError(
                f"held={self.held} of {e} experts, capacity_factor="
                f"{self.capacity_factor}: a chip's share is a range of the "
                "layer's experts under dropless routing")
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring={self.scoring!r}")
        gated = self.expert_act in ("swiglu", "reglu")
        if self.expert_act not in ("swiglu", "reglu", "relu2") or (
                not dropless and (self.latent or self.expert_act != "swiglu"
                                  or router_input is not None)):
            raise ValueError(
                f"expert_act={self.expert_act!r}, latent={self.latent}, "
                f"capacity_factor={self.capacity_factor}: relu2 experts and "
                "a latent, reglu experts and a router input of its own are "
                "the dropless path's")
        if router_input is not None and router_input.shape != x.shape:
            raise ValueError(f"router_input {router_input.shape} beside "
                             f"tokens {x.shape}")
        xf = x.reshape(n, d)
        router_rows = (xf if router_input is None
                       else router_input.reshape(n, d))

        with jax.named_scope("moe/router"):
            router = nn.Dense(e, use_bias=False, name="router",
                              dtype=jnp.float32)  # routing always f32
            router_logits = router(router_rows.astype(jnp.float32))
            probs = (jax.nn.sigmoid(router_logits)
                     if self.scoring == "sigmoid"
                     else jax.nn.softmax(router_logits, axis=-1))
            if self.selection_bias:
                bias = self.variable(
                    "buffers", "e_score_correction_bias",
                    lambda: jnp.zeros((e,), jnp.float32)).value
                _, top_idx = jax.lax.top_k(probs + bias, self.top_k)
            else:
                _, top_idx = jax.lax.top_k(probs, self.top_k)      # [n, k]
            top_p, hit = _pick(probs, top_idx)
            telemetry.counter("moe.router.picks").inc(n * self.top_k)
            if self.selection_bias:
                # a choice the unbiased scores would not have made: k or
                # more experts score higher than the chosen one
                higher = jnp.sum(probs[:, None, :] > top_p[:, :, None], -1)
                self.sow("moe_stats", "bias_moved",
                         jnp.mean(higher >= self.top_k))
            if self.norm_topk_prob:
                top_p = top_p / (
                    jnp.sum(top_p, -1, keepdims=True) + 1e-20
                    if self.scoring == "sigmoid" else
                    jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9))
            if self.routed_scale != 1.0:
                top_p = top_p * self.routed_scale

            pairs = jnp.sum(hit, axis=(0, 1), dtype=jnp.int32)     # [e]
            if not self.selection_bias:
                # Load-balancing aux loss, e · Σ_e f_e · P_e: f_e the share
                # of tokens whose FIRST choice is e (Switch eq. 4) or,
                # dropless, the pairs routed to e per token (all k choices).
                counted = pairs if dropless else jnp.sum(
                    hit[:, 0], axis=0, dtype=jnp.float32)
                frac_probs = jnp.mean(probs, axis=0)
                self.sow("aux_loss", "load_balance",
                         e * jnp.sum(counted / n * frac_probs))
                # Router z-loss (ST-MoE): keeps router logits bounded.
                z = jax.scipy.special.logsumexp(router_logits, axis=-1)
                self.sow("aux_loss", "router_z", jnp.mean(z * z))
            mean_pairs = n * self.top_k / e
            self.sow("moe_stats", "max_load", jnp.max(pairs) / mean_pairs)
            self.sow("moe_stats", "min_load", jnp.min(pairs) / mean_pairs)
            # the groups of the sorted rows, and how many rows they are
            sizes, routed = pairs, n * self.top_k
            if self.held:
                sizes = pairs[held[0]:held[1]]
                routed = jnp.sum(sizes)
                self.sow("moe_stats", "held_pairs",
                         routed / (n * self.top_k))
                self.sow("moe_stats", "held_max_load",
                         jnp.max(sizes) / jnp.maximum(jnp.mean(sizes), 1e-9))
            if dropless:
                self.sow("moe_stats", "executed_rows",
                         executed_rows(sizes, n * self.top_k)
                         / jnp.maximum(routed, 1))
            # for a caller that asks (mutable=["intermediates"]): the routing
            self.sow("intermediates", "top_idx", top_idx)

        e_here = held[1] - held[0]
        width = self.latent or d            # what an expert maps from and to
        shapes = {"experts_gate": (e_here, width, self.d_ff),
                  "experts_up": (e_here, width, self.d_ff),
                  "experts_down": (e_here, self.d_ff, width)}
        weights = tuple(
            self.param(name, nn.initializers.lecun_normal(), shapes[name])
            for name in shapes if gated or name != "experts_gate")
        if self.latent:
            with jax.named_scope("moe/latent"):
                xf = nn.Dense(self.latent, use_bias=False, name="latent_down",
                              dtype=self.compute_dtype)(xf)
        if dropless:
            y = self._dropless(xf, top_idx, top_p, pairs, weights)
        else:
            y = self._capacity(xf, top_idx, top_p, *weights)
        if self.latent:
            with jax.named_scope("moe/latent"):
                y = nn.Dense(d, use_bias=False, name="latent_up",
                             dtype=self.compute_dtype)(y)
        return y.reshape(b, s, d).astype(x.dtype)

    def _dropless(self, xf, top_idx, top_p, pairs, weights):
        mesh = jax.sharding.get_abstract_mesh()
        if not mesh.empty and dict(mesh.shape).get("ep", 1) > 1:
            raise NotImplementedError(
                "dropless routing (capacity_factor=None) over an ep axis of "
                f"size {mesh.shape['ep']}: sending uneven groups to the "
                "ranks that hold their experts needs a ragged all-to-all, "
                "which parallel/ep.py does not have; GSPMD would gather "
                "every expert onto every rank instead.  Use ep=1 (experts "
                "replicated or tp-sharded) or give a capacity_factor")
        cdt = self.compute_dtype
        auto = [] if mesh.empty else [a for a in mesh.axis_names
                                      if a not in mesh.manual_axes]
        tp = "tp" if "tp" in auto else None

        gate_act = jax.nn.relu if self.expert_act == "reglu" else jax.nn.silu

        def glu(rows, w_gate, w_up, w_down, sizes):
            if self.held is None:
                h = (gate_act(grouped_matmul(rows, w_gate, sizes))
                     * grouped_matmul(rows, w_up, sizes))
            else:   # gate and up as ONE product: one cotangent for the rows
                gate_up = grouped_matmul(
                    rows, jnp.concatenate([w_gate, w_up], axis=-1), sizes)
                h = (gate_act(gate_up[:, :w_gate.shape[-1]])
                     * gate_up[:, w_gate.shape[-1]:])
            out = grouped_matmul(h, w_down, sizes)
            return jax.lax.psum(out, tp) if tp else out

        def relu2(rows, w_up, w_down, sizes):
            h = jnp.square(jax.nn.relu(grouped_matmul(rows, w_up, sizes)))
            out = grouped_matmul(h, w_down, sizes)
            return jax.lax.psum(out, tp) if tp else out

        ffn = relu2 if self.expert_act == "relu2" else glu
        if auto:
            # GSPMD cannot partition a Mosaic kernel (see
            # ``flash_attention``): every rank runs the kernels on all the
            # rows against its ``tp`` slice of each expert's width, and the
            # slices' outputs are summed
            wide = (P(None, None, tp),) * (len(weights) - 1)
            ffn = jax.shard_map(
                ffn, in_specs=(P(), *wide, P(None, tp, None), P()),
                out_specs=P(), axis_names=frozenset(auto), check_vma=False)
        with jax.named_scope("moe/experts"):    # the casts are the experts'
            weights = tuple(w.astype(cdt) for w in weights)
        if self.held:
            return self._held(xf.astype(cdt), top_idx, top_p, weights, ffn,
                              tuple(auto))
        with jax.named_scope("moe/dispatch"):
            order, row_of_pair = _sorted_layout(top_idx)
            rows = _dispatch(xf.astype(cdt), order, row_of_pair)
        with jax.named_scope("moe/experts"):
            out = ffn(rows, *weights, pairs)
        with jax.named_scope("moe/combine"):
            return _combine(out, top_p, order, row_of_pair)

    def _held(self, xf, top_idx, top_p, weights, ffn, axes: tuple):
        """The held experts' part of the output (module docstring); ``axes``:
        the mesh's, that ``ffn`` is mapped over."""
        n, k = top_idx.shape
        first, end = self.held
        with jax.named_scope("moe/dispatch"):
            flat_idx = top_idx.reshape(-1)
            # pairs in token order: how many held ones up to and with each
            # token's (where its run among them ends)
            held = (flat_idx >= first) & (flat_idx < end)
            ends = jnp.cumsum(held, dtype=jnp.int32).reshape(n, k)[:, -1]
            piece = _piece_rows(n * k, (end - first) / self.n_experts)
            # the one sort over all the pairs: the held ones first, in token
            # order (padded to whole pieces; what follows them is masked)
            held_first = jnp.pad(
                jnp.argsort(jnp.logical_not(held), stable=True)
                .astype(jnp.int32), (0, -(n * k) % piece))
            self.sow("moe_stats", "moved_rows", moved_rows(
                piece, jnp.minimum(ends, piece)) / piece)
        ints = (flat_idx, ends, held_first)
        args = (xf, top_p) + weights
        piece_fn = functools.partial(_held_piece, ffn=ffn, first=first,
                                     n_held=end - first, piece=piece,
                                     axes=axes)
        # the first piece by plain autodiff, which keeps its residuals; the
        # further ones, which only uneven routing fills, are run again
        # backward (their number is not a shape)
        y = piece_fn(0, ints, *args)
        if piece < n * k:
            y = y + _held_experts(piece_fn, piece, ints, args)
        return y

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
