"""Learned sparse attention: DeepSeek Sparse Attention (the "lightning
indexer" of the DeepSeek-V3.2-Exp report) as Keye-VL-2.0's decoder trains it.

A small second attention, the INDEXER, scores every causal pair

    I[t, s] = Σ_j c[t, j] · ReLU(a[t, j] · b[s])        j = 1..J index heads

with ``J`` index queries ``a`` and ONE index key ``b`` a token, and each
query token keeps the ``topk`` keys with the largest score (equal scores: the
lower position first; a query with fewer causal keys than ``topk`` keeps them
all).  The main grouped-query attention then sees the kept pairs only, the
same set for every head, and the indexer is trained by the KL divergence from
the head-averaged attention probabilities on the kept set to its own softmax
there.  Four steps, each a function here, one row ``[L, ...]`` at a time
(``per_row`` maps them over a batch):

- ``index_scores``   ``[rows, L]`` float32 scores of a chunk of queries
- ``select_topk``    the EXACT selection as an int8 mask ``[rows, L]``, and
                     the log-sum-exp of the kept scores
- ``sparse_attention``  attention under that mask: ``(out, lse)``,
                     differentiable in q, k, v
- ``index_kl``       ``Σ_t KL(p[t] ‖ softmax_{kept} I[t])``, differentiable
                     in a, b, c alone; ``p`` is a constant

``lightning_select`` runs the first two over chunks of queries, so that the
``[L, L]`` float32 scores are never whole in memory (the mask is: one byte a
pair, 268 MB at 16k, alive inside its layer only).

The selection is exact and a function of the data, so which tiles of the
score matrix hold a kept pair is known on the device only: the two attention
kernels (the forward, and ONE backward that takes dq, dk and dv from a tile's
scores computed once) and the loss kernel walk the same RUNTIME visit table
(``_visit_table``, as ``grouped_matmul._visits`` is built from sizes), query
block by query block; the mask decides inside a tile.  A selection that is
scattered over the past, as seeded weights give, leaves every causal tile
live: the walk then skips nothing and each tile costs a whole tile (PERF.md
§7).

A VISIT of an attention kernel is one live tile for one K/V head and ALL the
query heads of its group (grid ``(K/V heads, visits)``): K, V and the int8
mask tile are fetched once, the mask is decoded once into a float32 bias
that every head of the group adds, and dk / dv sum over the group inside the
visit.  A visit of the backward adds its tile's share to dk and dv of the
K/V head's WHOLE row, which stay in VMEM from the head's first visit to its
last (float32, beside the blocks they are written to then): a row too long
for that (past 40k at 128 + 128 wide) is refused when the backward is
traced.  The group is read from the shapes (32 over 4 heads: 8; one query
head a K/V head: 1, the same path); ``dsa.visit_heads`` over the attention
kernels built says which it was.  The loss kernel's visit holds every head of
every group.

The exact threshold of a row is the ``topk``-th largest score, found by 32
counting passes over the order-preserving integer image of the float32 scores
(one bit of the answer a pass), never by a sort; ties at the threshold are
cut by position with 15 more passes, only where a row has them.

Off the TPU every step is dense ``jax.numpy`` (``impl="xla"``: O(L²) memory,
the CPU's path and the kernels' specification); ``pallas_interpret`` runs the
kernels in interpreter mode.
"""

from __future__ import annotations

import functools
import math
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensorflowonspark_tpu import telemetry
from tensorflowonspark_tpu.ops.attention import (
    NEG_INF, _NT, _TN, _VMEM_BODY)

Impl = Literal["pallas", "pallas_interpret", "xla"]

INT_MIN = -2 ** 31
# What a layer's backward needs of its forward, by ``checkpoint_name``: the
# selection (one byte a pair) with its log-sum-exp, attention's output and
# log-sum-exp, and the indexer's loss with its gradient (taken in the same
# walk).  A caller that recomputes the layer (``jax.checkpoint``) and saves
# these names runs none of the kernels a second time.
SAVED_NAMES = ("dsa_mask", "dsa_lse_i", "dsa_out", "dsa_lse", "dsa_kl")
# a visit's flags: first / last of its output block, of the whole walk
_FIRST, _LAST, _OPEN, _CLOSE = 1, 2, 4, 8
_VMEM_LIMIT = 96 << 20      # of a v5e's 128 MiB; the default scope is 16


def _impl(impl):
    if impl is None:
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in ("pallas", "pallas_interpret", "xla"):
        raise ValueError(f"unknown sparse attention impl {impl!r}")
    return impl


def _tile(length: int, tile: int = 512) -> int:
    """The side of a score tile: 512 (the published ``q_chunk_size`` /
    ``kv_chunk_size``), or the whole of a shorter row."""
    tile = min(tile, length)
    if length % tile or tile % 8:
        raise ValueError(f"sparse attention over rows of {length}: whole "
                         f"tiles of {tile} positions, a multiple of 8")
    return tile


def _count_kernel(name: str, visit_heads: int = 0) -> None:
    """Counted once per kernel built (trace time), as ``flash.tiles`` is;
    an attention kernel adds the query heads one visit of it serves, so
    ``dsa.visit_heads`` over the attention kernels built reads the group."""
    telemetry.counter("dsa.kernels").inc(1)
    telemetry.counter(f"dsa.kernels.{name}").inc(1)
    if visit_heads:
        telemetry.counter("dsa.visit_heads").inc(visit_heads)


# ---------------------------------------------------------------------------
# The order-preserving integer image of float32, and the exact k-th largest.
# ---------------------------------------------------------------------------

def ordered_key(x):
    """int32 whose signed order is the float32 order of ``x`` (finite, or
    infinite); -0.0 is taken as +0.0 first, since they are equal scores."""
    x = jnp.where(x == 0.0, 0.0, x.astype(jnp.float32))
    bits = lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def _kth_largest(count_ge, k, shape):
    """The largest int32 ``v`` with ``count_ge(v) >= k``, elementwise over
    ``shape``: the k-th largest key.  One bit a pass from the top, in offset
    binary (``u = key ^ INT_MIN`` orders as unsigned)."""

    def bit(i, prefix):
        trial = prefix | (jnp.int32(1) << (31 - i))
        return jnp.where(count_ge(trial ^ INT_MIN) >= k, trial, prefix)

    prefix = lax.fori_loop(0, 32, bit, jnp.zeros(shape, jnp.int32))
    return prefix ^ INT_MIN


# ---------------------------------------------------------------------------
# The dense path: the CPU's, and what the kernels are tested against.
# ---------------------------------------------------------------------------

def _scores_dense(a, b, c):
    """``a [T, J, Di]``, ``b [L, Di]``, ``c [T, J]`` -> ``[T, L]`` float32:
    operands as they come, accumulation, ReLU and the weighted sum in
    float32."""
    dots = jnp.einsum("tjd,sd->tjs", a, b,
                      preferred_element_type=jnp.float32)
    return jnp.sum(c.astype(jnp.float32)[:, :, None]
                   * jnp.maximum(dots, 0.0), axis=1)


def _select_dense(scores, row0, topk: int):
    """The exact selection of ``scores [T, L]`` (queries ``row0 ..``): the
    same counting passes as the kernel, the ties cut by a running count."""
    rows, length = scores.shape
    t = row0 + jnp.arange(rows)[:, None]
    s = jnp.arange(length)[None, :]
    keys = jnp.where(s <= t, ordered_key(scores), INT_MIN)
    k_t = jnp.minimum(topk, t + 1)
    v = _kth_largest(
        lambda trial: jnp.sum(keys >= trial, axis=1, keepdims=True),
        k_t, (rows, 1))
    above, ties = keys > v, keys == v
    need = k_t - jnp.sum(above, axis=1, keepdims=True)
    kept = above | (ties & (jnp.cumsum(ties, axis=1) <= need))
    lse = jax.nn.logsumexp(jnp.where(kept, scores, -jnp.inf), axis=1)
    return kept.astype(jnp.int8), lse


def _attend_dense(q, k, v, mask, sm_scale: float):
    """``q [L, H, D]``, ``k``/``v`` ``[L, Hkv, D]``, ``mask [L, L]`` ->
    ``(out [L, H, D], lse [H, L])``: a dense softmax over the kept pairs."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    logits = jnp.einsum("qhd,khd->hqk", q, k,
                        preferred_element_type=jnp.float32) * sm_scale
    logits = jnp.where(mask[None] != 0, logits, NEG_INF)
    lse = jax.nn.logsumexp(logits, axis=-1)
    p = jnp.exp(logits - lse[..., None])
    out = jnp.einsum("hqk,khd->qhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype), lse


def head_mean_probs(q, k, lse, mask, sm_scale: float):
    """``p[t, s] = 1/H Σ_h α[t, h, s]`` on the kept pairs, ``[L, L]`` float32
    (dense: the loss's target as the specification has it)."""
    group = q.shape[1] // k.shape[1]
    logits = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, group, axis=1),
                        preferred_element_type=jnp.float32) * sm_scale
    p = jnp.where(mask[None] != 0, jnp.exp(logits - lse[..., None]), 0.0)
    return jnp.mean(p, axis=0)


def _index_kl_dense(a, b, c, q, k, lse, mask, sm_scale: float):
    p = lax.stop_gradient(head_mean_probs(q, k, lse, mask, sm_scale))
    scores = _scores_dense(a, b, c)
    log_r = jax.nn.log_softmax(jnp.where(mask != 0, scores, NEG_INF), axis=1)
    return jnp.sum(jnp.where(p > 0.0, p * (jnp.log(jnp.maximum(p, 1e-37))
                                           - log_r), 0.0))


# ---------------------------------------------------------------------------
# Kernel 1: index scores of a chunk of queries, tile by tile.
# ---------------------------------------------------------------------------

def _scores_pallas(a, b, c, row0, interpret: bool):
    """``[T, L]`` float32 scores of queries ``row0 .. row0 + T``; a tile that
    lies wholly in the future is not computed (its values are whatever the
    buffer held: ``select_topk`` never reads a key after its query)."""
    rows, heads, dim = a.shape
    length = b.shape[0]
    bq, bk = _tile(rows), _tile(length)
    a_hm = a.transpose(1, 0, 2)                     # [J, T, Di]
    b_t = b.T                                       # [Di, L]: lane-dense

    def kernel(row0_ref, a_ref, b_ref, c_ref, out_ref):
        iq, ik = pl.program_id(0), pl.program_id(1)

        @pl.when(ik * bk <= row0_ref[0] + iq * bq + bq - 1)
        def _():
            acc = jnp.zeros((bq, bk), jnp.float32)
            weights = c_ref[...].astype(jnp.float32)
            for j in range(heads):
                dots = jnp.dot(a_ref[j], b_ref[...],
                               preferred_element_type=jnp.float32)
                acc = acc + weights[:, j:j + 1] * jnp.maximum(dots, 0.0)
            out_ref[...] = acc

    _count_kernel("index_scores")
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows // bq, length // bk),
            in_specs=[
                pl.BlockSpec((heads, bq, dim), lambda iq, ik, r: (0, iq, 0)),
                pl.BlockSpec((dim, bk), lambda iq, ik, r: (0, ik)),
                pl.BlockSpec((bq, heads), lambda iq, ik, r: (iq, 0)),
            ],
            out_specs=pl.BlockSpec((bq, bk), lambda iq, ik, r: (iq, ik))),
        out_shape=jax.ShapeDtypeStruct((rows, length), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.reshape(row0, (1,)).astype(jnp.int32), a_hm, b_t, c)


def index_scores(a, b, c, row0=0, *, impl: Impl | None = None):
    """``I[t, s]`` for the queries ``a [T, J, Di]``, ``c [T, J]`` at rows
    ``row0 ..`` against every index key ``b [L, Di]``: ``[T, L]`` float32."""
    impl = _impl(impl)
    if impl == "xla":
        return _scores_dense(a, b, c)
    return _scores_pallas(a, b, c, jnp.asarray(row0),
                          impl == "pallas_interpret")


# ---------------------------------------------------------------------------
# Kernel 2: the exact selection of a block of query rows held in VMEM.
# ---------------------------------------------------------------------------

def _select_rows(rows: int) -> int:
    """Query rows a step of the selection holds in VMEM with all their keys
    (int8 tiles are 32 rows high): at 16k keys 64 rows are 4 MiB of scores."""
    for tq in (64, 32):
        if rows % tq == 0:
            return tq
    return rows


def _select_pallas(scores, row0, topk: int, interpret: bool):
    rows, length = scores.shape
    tq, ck = _select_rows(rows), _tile(length)

    def kernel(row0_ref, scores_ref, mask_ref, lse_ref, keys_ref, cut_ref):
        first = row0_ref[0] + pl.program_id(0) * tq
        t = first + lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
        # chunks of keys that hold a causal key of some row of the block
        live = (first + tq - 1) // ck + 1

        def s_of(chunk):
            return chunk * ck + lax.broadcasted_iota(jnp.int32, (tq, ck), 1)

        def at(chunk):
            return pl.ds(pl.multiple_of(chunk * ck, ck), ck)

        def fill(chunk, carry):
            keys_ref[:, at(chunk)] = jnp.where(
                s_of(chunk) <= t, ordered_key(scores_ref[:, at(chunk)]),
                INT_MIN)
            return carry

        lax.fori_loop(0, live, fill, 0)

        def count(pred):
            """Keys of each row for which ``pred(keys, positions)``: summed
            lane by lane over the chunks, across lanes once."""
            def add(chunk, acc):
                return acc + pred(keys_ref[:, at(chunk)],
                                  s_of(chunk)).astype(jnp.int32)
            acc = lax.fori_loop(0, live, add, jnp.zeros((tq, ck), jnp.int32))
            return jnp.sum(acc, axis=1, keepdims=True)

        k_t = jnp.minimum(topk, t + 1)
        v = _kth_largest(
            lambda trial: count(lambda keys, s: keys >= trial), k_t, (tq, 1))
        need = k_t - count(lambda keys, s: keys > v)
        ties = count(lambda keys, s: keys == v)
        # ties at the threshold are kept up to position ``cut``: all of them
        # unless a row has more than it needs (then the need-th, by position)
        cut_ref[...] = jnp.full((tq, 1), length, jnp.int32)

        @pl.when(jnp.max(ties - need) > 0)
        def _():
            def bit(i, lo):
                trial = lo + (jnp.int32(1) << (30 - i))
                below = count(lambda keys, s: (keys == v) & (s < trial))
                return jnp.where(below < need, trial, lo)
            cut_ref[...] = lax.fori_loop(
                0, 31, bit, jnp.zeros((tq, 1), jnp.int32))

        cut = cut_ref[...]

        def kept(chunk):
            keys = keys_ref[:, at(chunk)]
            return (keys > v) | ((keys == v) & (s_of(chunk) <= cut))

        def peak(chunk, m):
            return jnp.maximum(m, jnp.max(
                jnp.where(kept(chunk), scores_ref[:, at(chunk)], NEG_INF),
                axis=1, keepdims=True))

        m = lax.fori_loop(0, live, peak, jnp.full((tq, 1), NEG_INF))

        def write(chunk, total):
            keep = kept(chunk)
            mask_ref[:, at(chunk)] = keep.astype(jnp.int32).astype(jnp.int8)
            return total + jnp.sum(
                jnp.where(keep, jnp.exp(scores_ref[:, at(chunk)] - m), 0.0),
                axis=1, keepdims=True)

        total = lax.fori_loop(0, live, write, jnp.zeros((tq, 1), jnp.float32))

        def clear(chunk, carry):
            mask_ref[:, at(chunk)] = jnp.zeros((tq, ck), jnp.int8)
            return carry

        lax.fori_loop(live, length // ck, clear, 0)
        lse_ref[...] = jnp.broadcast_to(m + jnp.log(total), (tq, 128))

    _count_kernel("select")
    mask, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows // tq,),
            in_specs=[pl.BlockSpec((tq, length), lambda i, r: (i, 0))],
            out_specs=[pl.BlockSpec((tq, length), lambda i, r: (i, 0)),
                       pl.BlockSpec((tq, 128), lambda i, r: (i, 0))],
            scratch_shapes=[pltpu.VMEM((tq, length), jnp.int32),
                            pltpu.VMEM((tq, 1), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct((rows, length), jnp.int8),
                   jax.ShapeDtypeStruct((rows, 128), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(jnp.reshape(row0, (1,)).astype(jnp.int32), scores)
    return mask, lse[:, 0]


def select_topk(scores, topk: int, row0=0, *, impl: Impl | None = None):
    """``scores [T, L]`` of the queries at rows ``row0 ..`` -> ``(mask [T, L]
    int8, lse [T] float32)``: 1 on the ``min(topk, t + 1)`` causal keys with
    the largest score (equal scores: the lower position), and the
    log-sum-exp of the kept scores."""
    impl = _impl(impl)
    if impl == "xla":
        return _select_dense(scores, jnp.asarray(row0), topk)
    return _select_pallas(scores, jnp.asarray(row0), topk,
                          impl == "pallas_interpret")


def lightning_select(a, b, c, topk: int, *, impl: Impl | None = None,
                     chunk: int = 2048):
    """The indexer's selection for one row: ``a [L, J, Di]``, ``b [L, Di]``,
    ``c [L, J]`` -> ``(mask [L, L] int8, lse [L])``.  Scores and selection go
    chunk by chunk of queries, so ``chunk x L`` float32 scores are all that
    is ever held (the dense path holds them whole).  A constant of the step:
    nothing here is differentiated."""
    impl = _impl(impl)
    a, b, c = (lax.stop_gradient(x) for x in (a, b, c))
    length = a.shape[0]
    if impl == "xla":
        chunk = length
    chunk = min(chunk, length)
    if length % chunk:
        raise ValueError(f"rows of {length} in chunks of {chunk} queries")

    def one(args):
        row0, a_rows, c_rows = args
        with jax.named_scope("dsa/index"):
            scores = index_scores(a_rows, b, c_rows, row0, impl=impl)
        with jax.named_scope("dsa/select"):
            return select_topk(scores, topk, row0, impl=impl)

    n = length // chunk
    mask, lse = lax.map(one, (
        jnp.arange(n, dtype=jnp.int32) * chunk,
        a.reshape(n, chunk, *a.shape[1:]), c.reshape(n, chunk, c.shape[1])))
    return (checkpoint_name(mask.reshape(length, length), "dsa_mask"),
            checkpoint_name(lse.reshape(length), "dsa_lse_i"))


# ---------------------------------------------------------------------------
# The runtime visit table.
# ---------------------------------------------------------------------------

def live_tiles(mask, tile: int):
    """``[nq, nk]`` bool: the tiles of ``mask`` that hold a kept pair."""
    n = mask.shape[0] // tile
    return jnp.any(mask.reshape(n, tile, n, tile) != 0, axis=(1, 3))


def selection_stats(mask):
    """``(kept pairs, tiles with a kept pair over causal tiles)`` of one
    row's selection: what the walk of its kernels has to visit."""
    with jax.named_scope("dsa/select"):
        n = mask.shape[0] // _tile(mask.shape[0])
        live = live_tiles(mask, mask.shape[0] // n)
        return (jnp.sum(mask, dtype=jnp.int32),
                jnp.sum(live) / (n * (n + 1) // 2))


def _visit_table(live):
    """The walk of one kernel's sequential axis over the live tiles of a
    causal ``[n, n]`` tile map, built on the device: ``(block, tile, flags,
    count)`` — for every output block (a row of ``live``: a block of
    queries) its live tiles in ascending order.  The diagonal tile is always
    visited, so every block has a first and a last visit; the table has room
    for every causal tile and ``count`` says how many visits it holds.  The
    walk's own first and last visit carry ``_OPEN`` and ``_CLOSE`` too, for
    the kernel that keeps something over the whole walk."""
    n = live.shape[0]
    visit = live | jnp.eye(n, dtype=bool)
    room = n * (n + 1) // 2
    flat = jnp.nonzero(visit.reshape(-1), size=room, fill_value=0)[0]
    count = jnp.sum(visit).astype(jnp.int32)
    flat = flat.astype(jnp.int32)
    block, tile = flat // n, flat % n
    index = jnp.arange(room)
    real = index < count
    prev = jnp.where(index > 0, jnp.roll(block, 1), -1)
    nxt = jnp.where(index + 1 < count, jnp.roll(block, -1), -1)
    flags = ((block != prev) * _FIRST + (block != nxt) * _LAST) * real
    flags += (index == 0) * _OPEN + (index == count - 1) * _CLOSE
    return block, tile, flags.astype(jnp.int32), count


def _walk_call(kernel, name: str, grid: tuple, tables, *, out_shape,
               interpret: bool, scratch_shapes, in_specs, out_specs,
               visit_heads: int = 0):
    """``kernel`` over ``grid``, whose LAST axis is the walk (its length the
    table's ``count``, a value of the run); the axes before it run in
    parallel.  The tables are the scalar-prefetch operands."""
    _count_kernel(name, visit_heads)
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables), grid=grid,
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * (len(grid) - 1)
            + ("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)
    return functools.partial(call, *tables)


def _on(flags, flag, run):
    pl.when((flags & flag) != 0)(run)


def _kept(mask_ref):
    return mask_ref[...].astype(jnp.int32) != 0


# ---------------------------------------------------------------------------
# Kernels 3-4: attention over the kept pairs, the forward and the one-pass
# backward, over ONE q-major visit table.  A VISIT is one live tile for one
# K/V head and ALL the query heads of its group: the grid is ``(K/V heads,
# visits)``, the K/V tiles and the int8 mask tile are fetched once a visit,
# the mask is decoded once (into an additive float32 bias, 0 on a kept pair
# and ``NEG_INF`` elsewhere: ``logits + bias`` is ``where(kept, logits,
# NEG_INF)`` to the bit for finite logits) and the group's heads are a loop
# inside the visit over the ``[group, tile, d]`` blocks of the query side.
# ``group`` is read from the shapes; with one query head a K/V head the loop
# has one turn.  A visit of the backward holds resident, beside its blocks:
# dq's float32 accumulator of the query block, and dk and dv of the K/V head
# over the whole row (float32, written once, on the head's last visit).
# ---------------------------------------------------------------------------

def _decode(mask_ref, bias_ref, turned: bool = False):
    bias = jnp.where(_kept(mask_ref), 0.0, NEG_INF)
    bias_ref[...] = bias.T if turned else bias


def _lanes(x, width: int):
    """A lane-replicated statistic ``[rows, 128]`` as ``[rows, width]``:
    whole copies of its 128 lanes side by side (the first lanes of one for a
    narrower tile).  Not ``x[:, 0:1]`` broadcast: that one-lane column cost
    the forward kernel as much as its matmuls (PERF.md §6, PR 34)."""
    return jnp.tile(x, (1, pl.cdiv(width, 128)))[:, :width]


def _fwd_kernel(iq_ref, ik_ref, flags_ref, q_ref, k_ref, v_ref, mask_ref,
                o_ref, lse_ref, acc_ref, m_ref, l_ref, bias_ref, *,
                sm_scale: float):
    del iq_ref, ik_ref
    flags = flags_ref[pl.program_id(1)]
    group, tile, d = q_ref.shape

    def init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def finalize():
        for h in range(group):
            l = jnp.maximum(l_ref[h], 1e-30)
            o_ref[h] = (acc_ref[h] / _lanes(l, d)).astype(o_ref.dtype)
            lse = jnp.where(l_ref[h] > 0.0, m_ref[h] + jnp.log(l), NEG_INF)
            lse_ref[h] = lse.T[0:1]

    _on(flags, _FIRST, init)
    _decode(mask_ref, bias_ref)
    k, v = k_ref[0], v_ref[0]
    for h in range(group):
        logits = lax.dot_general(
            q_ref[h], k, _NT,
            preferred_element_type=jnp.float32) * sm_scale + bias_ref[...]
        m_prev = m_ref[h]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        # a row that has kept nothing yet: exp(NEG_INF - NEG_INF / 2) = 0
        m_safe = jnp.maximum(m_new, NEG_INF / 2)
        p = jnp.exp(logits - _lanes(m_safe, tile))
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0,
                          jnp.exp(m_prev - m_safe))
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * _lanes(alpha, d) + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[h] = m_new
    _on(flags, _LAST, finalize)


def _bwd_kernel(iq_ref, ik_ref, flags_ref, q_ref, do_ref, lse_ref, delta_ref,
                k_ref, v_ref, mask_ref, dq_ref, dk_ref, dv_ref, dq_acc,
                dk_acc, dv_acc, bias_ref, *, sm_scale: float):
    # ONE pass, as ops/attention.py's ``_flash_bwd_kernel``: for every head of
    # the visit the tile's scores and dP ONCE, on the TRANSPOSED tile [keys,
    # queries], and the three gradients from them: lse and delta are rows,
    # lane-dense as they lie in memory, dv += p_t dO and dk += ds_t q are
    # plain matmuls, dq += ds_t^T k is the one product over the tile's other
    # side.  The mask tile is the forward's: its bias is turned once a visit.
    # dq accumulates over a query block's visits; dk and dv accumulate over
    # the WHOLE key length of the grid row's K/V head, at the tile's rows:
    # zeroed on the walk's first visit, scaled, cast and written on its last.
    del iq_ref
    visit = pl.program_id(1)
    flags = flags_ref[visit]
    tile = k_ref.shape[1]
    keys = pl.ds(pl.multiple_of(ik_ref[visit] * tile, tile), tile)

    def open_row():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def finalize():
        dq_ref[...] = (dq_acc[...] * sm_scale).astype(dq_ref.dtype)

    def close_row():
        dk_ref[0] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    _on(flags, _OPEN, open_row)
    _on(flags, _FIRST, init)
    _decode(mask_ref, bias_ref, turned=True)
    k, v = k_ref[0], v_ref[0]
    for h in range(q_ref.shape[0]):
        q, do = q_ref[h], do_ref[h]
        logits_t = lax.dot_general(
            k, q, _NT,
            preferred_element_type=jnp.float32) * sm_scale + bias_ref[...]
        p_t = jnp.exp(logits_t - lse_ref[h])
        dv_acc[keys] += jnp.dot(p_t.astype(do.dtype), do,
                                preferred_element_type=jnp.float32)
        dp_t = lax.dot_general(v, do, _NT,
                               preferred_element_type=jnp.float32)
        ds_t = (p_t * (dp_t - delta_ref[h])).astype(q.dtype)
        dk_acc[keys] += jnp.dot(ds_t, q, preferred_element_type=jnp.float32)
        dq_acc[h] += lax.dot_general(ds_t, k, _TN,
                                     preferred_element_type=jnp.float32)
    _on(flags, _LAST, finalize)
    _on(flags, _CLOSE, close_row)


def _head_major(x):
    """``[L, H, D]`` -> ``[H, L, D]``: a K/V group's query heads are
    neighbours, so one block holds them."""
    return x.transpose(1, 0, 2)


def _fwd_pallas(qt, kt, vt, mask, table, *, sm_scale, tile, interpret):
    """Head-major ``[H, L, D]`` queries against ``[Hkv, L, D]`` keys and
    values under ``mask``: ``(out [H, L, D], lse [H, L] float32)``."""
    heads, length, d = qt.shape
    group = heads // kt.shape[0]
    block, col, flags, count = table
    q_at = lambda g, v, iq, ik, f: (g, iq[v], 0)                # noqa: E731
    kv_at = lambda g, v, iq, ik, f: (g, ik[v], 0)               # noqa: E731
    out, lse = _walk_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale), "attend_fwd",
        (heads // group, count), (block, col, flags),
        in_specs=[
            pl.BlockSpec((group, tile, d), q_at),
            pl.BlockSpec((1, tile, d), kv_at),
            pl.BlockSpec((1, tile, d), kv_at),
            pl.BlockSpec((tile, tile),
                         lambda g, v, iq, ik, f: (iq[v], ik[v])),
        ],
        out_specs=[
            pl.BlockSpec((group, tile, d), q_at),
            pl.BlockSpec((group, 1, tile),
                         lambda g, v, iq, ik, f: (g, 0, iq[v])),
        ],
        out_shape=[jax.ShapeDtypeStruct((heads, length, d), qt.dtype),
                   jax.ShapeDtypeStruct((heads, 1, length), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((group, tile, d), jnp.float32),
                        pltpu.VMEM((group, tile, 128), jnp.float32),
                        pltpu.VMEM((group, tile, 128), jnp.float32),
                        pltpu.VMEM((tile, tile), jnp.float32)],
        interpret=interpret, visit_heads=group,
    )(qt, kt, vt, mask)
    return out, lse[:, 0]


def _bwd_bytes(group: int, tile: int, length: int, d: int,
               itemsize: int) -> int:
    """VMEM of the one-pass backward: dk and dv of a K/V head over the whole
    row (float32 accumulators and the two buffers of each output block), and
    a visit's blocks and scratch: q, dO and dq twice, dq's accumulator, K
    and V, the mask tile and its bias."""
    resident = 2 * length * d * (4 + 2 * itemsize)
    heads = group * tile * d * (6 * itemsize + 4)
    return (resident + heads + 4 * tile * d * itemsize
            + tile * tile * (2 + 4))


def _bwd_pallas(qt, kt, vt, do_t, lse, delta, mask, table, *, sm_scale,
                tile, interpret):
    heads, length, d = qt.shape
    group = heads // kt.shape[0]
    held = _bwd_bytes(group, tile, length, d, qt.dtype.itemsize)
    if held > _VMEM_LIMIT - _VMEM_BODY:
        raise ValueError(
            f"sparse attention's backward keeps dk and dv of a row of "
            f"{length} positions in VMEM: {held:,} bytes beside the tile "
            f"body's {_VMEM_BODY:,}, over the limit of {_VMEM_LIMIT:,}")
    block, col, flags, count = table
    q_block, whole = (group, tile, d), (1, length, d)
    q_at = lambda g, v, iq, ik, f: (g, iq[v], 0)                # noqa: E731
    row_at = lambda g, v, iq, ik, f: (g, 0, iq[v])              # noqa: E731
    kv_at = lambda g, v, iq, ik, f: (g, ik[v], 0)               # noqa: E731
    row_of = lambda g, v, iq, ik, f: (g, 0, 0)                  # noqa: E731
    return _walk_call(
        functools.partial(_bwd_kernel, sm_scale=sm_scale), "attend_bwd",
        (heads // group, count), (block, col, flags),
        in_specs=[
            pl.BlockSpec(q_block, q_at),
            pl.BlockSpec(q_block, q_at),
            pl.BlockSpec((group, 1, tile), row_at),
            pl.BlockSpec((group, 1, tile), row_at),
            pl.BlockSpec((1, tile, d), kv_at),
            pl.BlockSpec((1, tile, d), kv_at),
            pl.BlockSpec((tile, tile),
                         lambda g, v, iq, ik, f: (iq[v], ik[v])),
        ],
        out_specs=[pl.BlockSpec(q_block, q_at),
                   pl.BlockSpec(whole, row_of), pl.BlockSpec(whole, row_of)],
        out_shape=[jax.ShapeDtypeStruct(qt.shape, qt.dtype),
                   jax.ShapeDtypeStruct(kt.shape, kt.dtype),
                   jax.ShapeDtypeStruct(vt.shape, vt.dtype)],
        scratch_shapes=[pltpu.VMEM(q_block, jnp.float32),
                        pltpu.VMEM((length, d), jnp.float32),
                        pltpu.VMEM((length, d), jnp.float32),
                        pltpu.VMEM((tile, tile), jnp.float32)],
        interpret=interpret, visit_heads=group,
    )(qt, do_t, lse[:, None, :], delta[:, None, :], kt, vt, mask)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _attend_tpu(q, k, v, mask, sm_scale, interpret):
    return _attend_fwd(q, k, v, mask, sm_scale, interpret)[0]


def _attend_fwd(q, k, v, mask, sm_scale, interpret):
    tile = _tile(q.shape[0])
    qt, kt, vt = (_head_major(x) for x in (q, k, v))
    table = _visit_table(live_tiles(mask, tile))
    ot, lse = _fwd_pallas(qt, kt, vt, mask, table, sm_scale=sm_scale,
                          tile=tile, interpret=interpret)
    out = checkpoint_name(ot.transpose(1, 0, 2), "dsa_out")
    lse = checkpoint_name(lse, "dsa_lse")
    return (out, lse), (qt, kt, vt, out, lse, mask, table)


def _attend_bwd(sm_scale, interpret, res, cotangents):
    g, _g_lse = cotangents      # the lse feeds a constant of the step only
    qt, kt, vt, out, lse, mask, table = res
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).T                                   # [H, L]
    dq, dk, dv = _bwd_pallas(
        qt, kt, vt, _head_major(g), lse, delta, mask, table,
        sm_scale=sm_scale, tile=_tile(qt.shape[1]), interpret=interpret)
    return (*(x.transpose(1, 0, 2) for x in (dq, dk, dv)),
            np.zeros(mask.shape, jax.dtypes.float0))


_attend_tpu.defvjp(_attend_fwd, _attend_bwd)


def sparse_attention(q, k, v, mask, *, sm_scale: float | None = None,
                     impl: Impl | None = None):
    """Grouped-query attention of one row over the kept pairs: ``q [L, H,
    D]``, ``k`` / ``v`` ``[L, Hkv, D]``, ``mask [L, L]`` int8 (a causal
    selection: every query keeps at least one key) -> ``(out [L, H, D], lse
    [H, L] float32)``.  Differentiable in q, k and v; the log-sum-exp comes
    out as a constant (it feeds the indexer's target)."""
    impl = _impl(impl)
    if q.shape[1] % k.shape[1] or k.shape != v.shape:
        raise ValueError(f"{q.shape[1]} query heads over {k.shape[1]} key "
                         f"and {v.shape[1]} value heads")
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    with jax.named_scope("dsa/attend"):
        if impl == "xla":
            out, lse = _attend_dense(q, k, v, mask, scale)
        else:
            out, lse = _attend_tpu(q, k, v, mask, scale,
                                   impl == "pallas_interpret")
        return out, lax.stop_gradient(lse)


# ---------------------------------------------------------------------------
# Kernel 5: the indexer's loss and, in the same walk, its gradient.
# ---------------------------------------------------------------------------

def _kl_kernel(iq_ref, ik_ref, flags_ref, q_ref, k_ref, lse_ref, a_ref,
               b_ref, c_ref, lse_i_ref, mask_ref, kl_ref, da_ref, dc_ref,
               db_ref, kl_acc, da_acc, dc_acc, *, sm_scale: float,
               tile: int, grads: bool):
    # One tile of the score matrix, every head: the head-averaged attention
    # probabilities p (from q, k and the forward's log-sum-exp), the index
    # scores and their softmax r on the kept set (from the selection's
    # log-sum-exp): KL's tile sum and dI = r - p, taken on through the
    # indexer's three inputs.  The ONE index key's gradient is small enough
    # (L x Di float32) to stay in VMEM for the whole walk.
    visit = pl.program_id(0)
    flags = flags_ref[visit]
    heads, group = q_ref.shape[0], q_ref.shape[0] // k_ref.shape[0]
    index_heads = a_ref.shape[0]

    def init():
        kl_acc[:] = jnp.zeros_like(kl_acc)
        da_acc[:] = jnp.zeros_like(da_acc)
        dc_acc[:] = jnp.zeros_like(dc_acc)

    def finalize():
        kl_ref[...] = kl_acc[:]
        da_ref[...] = da_acc[:]
        dc_ref[...] = dc_acc[:]

    @pl.when(visit == 0)
    def _():
        db_ref[...] = jnp.zeros_like(db_ref)

    _on(flags, _FIRST, init)
    kept = _kept(mask_ref)
    lse = lse_ref[...]                                   # [tile, H]
    p = jnp.zeros((tile, tile), jnp.float32)
    for h in range(heads):
        logits = lax.dot_general(
            q_ref[h], k_ref[h // group], _NT,
            preferred_element_type=jnp.float32) * sm_scale
        p = p + jnp.exp(jnp.where(kept, logits, NEG_INF) - lse[:, h:h + 1])
    p = p * (1.0 / heads)

    b = b_ref[...]                                       # [tile, Di]
    weights = c_ref[...].astype(jnp.float32)             # [tile, J]
    scores = jnp.zeros((tile, tile), jnp.float32)
    for j in range(index_heads):
        dots = lax.dot_general(a_ref[j], b, _NT,
                               preferred_element_type=jnp.float32)
        scores = scores + weights[:, j:j + 1] * jnp.maximum(dots, 0.0)
    log_r = jnp.where(kept, scores, NEG_INF) - lse_i_ref[...][:, 0:1]
    kl_acc[:] += jnp.sum(
        jnp.where(p > 0.0, p * (jnp.log(jnp.maximum(p, 1e-37)) - log_r), 0.0),
        axis=1, keepdims=True)
    if grads:
        d_scores = jnp.exp(log_r) - p                    # 0 off the kept set
        rows = pl.ds(pl.multiple_of(ik_ref[visit] * tile, tile), tile)
        db = jnp.zeros(b.shape, jnp.float32)
        for j in range(index_heads):
            a_j = a_ref[j]
            dots = lax.dot_general(a_j, b, _NT,
                                   preferred_element_type=jnp.float32)
            dc_acc[:, j:j + 1] += jnp.sum(
                d_scores * jnp.maximum(dots, 0.0), axis=1, keepdims=True)
            through = jnp.where(dots > 0.0, d_scores * weights[:, j:j + 1],
                                0.0).astype(b.dtype)
            da_acc[j] += jnp.dot(through, b,
                                 preferred_element_type=jnp.float32)
            db = db + lax.dot_general(
                through, a_j, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        db_ref[rows, :] += db
    _on(flags, _LAST, finalize)


def _kl_pallas(a, b, c, qt, kt, lse, lse_i, mask, table, *, sm_scale,
               interpret, grads: bool):
    """``(Σ_t KL_t, da, db, dc)`` of one row; without ``grads`` the three
    gradients are not computed (zeros)."""
    length, index_heads, dim = a.shape
    heads, _, d = qt.shape
    tile = _tile(length)
    block, col, flags, count = table
    q_rows = lambda v, iq, ik, f: (iq[v], 0)                    # noqa: E731
    kl, da, dc, db = _walk_call(
        functools.partial(_kl_kernel, sm_scale=sm_scale, tile=tile,
                          grads=grads), "index_loss",
        (count,), (block, col, flags),
        in_specs=[
            pl.BlockSpec((heads, tile, d), lambda v, iq, ik, f: (0, iq[v], 0)),
            pl.BlockSpec((kt.shape[0], tile, d),
                         lambda v, iq, ik, f: (0, ik[v], 0)),
            pl.BlockSpec((tile, heads), q_rows),
            pl.BlockSpec((index_heads, tile, dim),
                         lambda v, iq, ik, f: (0, iq[v], 0)),
            pl.BlockSpec((tile, dim), lambda v, iq, ik, f: (ik[v], 0)),
            pl.BlockSpec((tile, index_heads), q_rows),
            pl.BlockSpec((tile, 128), q_rows),
            pl.BlockSpec((tile, tile), lambda v, iq, ik, f: (iq[v], ik[v])),
        ],
        out_specs=[
            pl.BlockSpec((tile, 1), q_rows),
            pl.BlockSpec((index_heads, tile, dim),
                         lambda v, iq, ik, f: (0, iq[v], 0)),
            pl.BlockSpec((tile, index_heads), q_rows),
            pl.BlockSpec((length, dim), lambda v, iq, ik, f: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((length, 1), jnp.float32),
            jax.ShapeDtypeStruct((index_heads, length, dim), jnp.float32),
            jax.ShapeDtypeStruct((length, index_heads), jnp.float32),
            jax.ShapeDtypeStruct((length, dim), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((tile, 1), jnp.float32),
                        pltpu.VMEM((index_heads, tile, dim), jnp.float32),
                        pltpu.VMEM((tile, index_heads), jnp.float32)],
        interpret=interpret,
    )(qt, kt, lse.T, a.transpose(1, 0, 2), b, c,
      jnp.broadcast_to(lse_i[:, None], (length, 128)), mask)
    return jnp.sum(kl), da.transpose(1, 0, 2), db, dc


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _index_kl_tpu(a, b, c, q, k, lse, lse_i, mask, sm_scale, interpret):
    table = _visit_table(live_tiles(mask, _tile(a.shape[0])))
    return _kl_pallas(a, b, c, _head_major(q), _head_major(k), lse, lse_i,
                      mask, table, sm_scale=sm_scale, interpret=interpret,
                      grads=False)[0]


def _index_kl_fwd(a, b, c, q, k, lse, lse_i, mask, sm_scale, interpret):
    table = _visit_table(live_tiles(mask, _tile(a.shape[0])))
    kl, da, db, dc = _kl_pallas(
        a, b, c, _head_major(q), _head_major(k), lse, lse_i, mask, table,
        sm_scale=sm_scale, interpret=interpret, grads=True)
    kl, *grads = checkpoint_name(
        (kl, *(g.astype(x.dtype) for g, x in zip((da, db, dc), (a, b, c)))),
        "dsa_kl")
    return kl, (tuple(grads), (q, k, lse, lse_i, mask))


def _index_kl_bwd(sm_scale, interpret, res, g):
    grads, constants = res
    grads = tuple((g * x).astype(x.dtype) for x in grads)
    zeros = tuple(
        np.zeros(x.shape, jax.dtypes.float0)
        if not jnp.issubdtype(x.dtype, jnp.floating) else jnp.zeros_like(x)
        for x in constants)
    return (*grads, *zeros)


_index_kl_tpu.defvjp(_index_kl_fwd, _index_kl_bwd)


def index_kl(a, b, c, q, k, lse, lse_i, mask, *,
             sm_scale: float | None = None, impl: Impl | None = None):
    """``Σ_t KL(p[t] ‖ r[t])`` of one row over the kept pairs: ``p`` the
    attention probabilities averaged over the heads (from ``q``, ``k`` and
    ``sparse_attention``'s ``lse``: a constant), ``r`` the softmax of the
    index scores of ``a``, ``b``, ``c`` on the kept set (``lse_i``:
    ``select_topk``'s).  Differentiable in a, b and c alone."""
    impl = _impl(impl)
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    q, k, lse, lse_i = (lax.stop_gradient(x) for x in (q, k, lse, lse_i))
    with jax.named_scope("dsa/index_loss"):
        if impl == "xla":
            return _index_kl_dense(a, b, c, q, k, lse, mask, scale)
        return _index_kl_tpu(a, b, c, q, k, lse, lse_i, mask, scale,
                             impl == "pallas_interpret")


def per_row(fn, *rows):
    """``fn`` over the leading (batch) axis of every array of ``rows``, one
    row after the other: a row's visit tables are its own."""
    if rows[0].shape[0] == 1:
        return jax.tree.map(lambda x: x[None],
                            fn(*(x[0] for x in rows)))
    return lax.map(lambda xs: fn(*xs), rows)
