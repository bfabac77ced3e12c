"""Attention ops: Pallas TPU flash attention + blockwise-JAX fallback.

The reference framework has no attention anywhere (SURVEY.md §5.7 — its
models are CNNs/wide-and-deep), but long-context support is first-class in
this build, so the hot op gets a real TPU kernel:

- ``flash_attention`` — public entry.  On TPU it runs Pallas kernels in both
  directions: an online-softmax forward that keeps its log-sum-exp, and a
  backward of ONE pass over the same tiles that recomputes each tile's
  softmax weights from it once and takes all three gradients from them, dk
  and dv resident in VMEM over the whole key length (where they do not fit:
  two passes, dk/dv with the q blocks sequential, dq with the KV blocks
  sequential; ``_plan`` decides from the shapes).  No kernel has a dense (q
  tile, k tile) grid:
  each walks a trace-time table of the LIVE tiles of its mask, and a visit
  of the walk is one live tile for one K/V head and the query heads of its
  group (``_visit_heads``).  Elsewhere it lowers to ``blockwise_attention``
  (a ``lax.scan`` over KV blocks with per-block rematerialisation, so memory
  stays O(S·block) instead of O(S²)).
- ``chunk_attention`` / ``merge_attention`` — the (output, logsumexp)
  chunk-compute and online-softmax merge primitives that
  ``parallel/sp.py``'s ring attention composes over ICI neighbours.

Array convention: ``[batch, seq, heads, head_dim]`` (flax-style).  Every
matmul of the kernels takes its operands as the model holds them (bf16
inputs keep the MXU fed; the softmax weights round to that dtype for their
product with V or dO) and accumulates in float32; the running max, the sum,
the rescale and the log-sum-exp are float32 regardless of input dtype (the
VPU-side accumulators must not lose mass).

Grouped-query heads: ``k`` and ``v`` may carry fewer heads than ``q`` (a
divisor); query head ``j`` reads K/V head ``j // group``.  A group's query
heads are neighbouring head-major rows, so one block of a visit holds them:
K and V are fetched once for the group and never repeated in memory, and
dk and dv are summed over it inside the visit.

Masks: ``causal``, or ``block_diffusion=(length, block)``, the training mask
of block diffusion (BD3-LM, arXiv:2503.09573; SDAR, arXiv:2510.06303) over
the concatenation of a noised and a clean copy of one row
(``block_diffusion_visible``).  ``window=W`` narrows ``causal`` to a band: a
query sees itself and the ``W - 1`` keys before it (``0 <= i - j < W``,
transformers' sliding window).  It is static like the other two, so the
kernels' tables hold the BAND's tiles alone: a tile wholly older than the
window is dead, as one wholly in the future is, and a query block's run of
live tiles has masked tiles at both ends (the diagonal and the band's far
edge) around interior ones.  A window that reaches over the whole row is no
window: the tables, and the programs, are plain causal's.
"""

from __future__ import annotations

import functools
import math
from typing import Literal, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from tensorflowonspark_tpu import telemetry

NEG_INF = -1e30  # finite stand-in for -inf: keeps exp() exactly 0 without nan


def match_vma(x, like):
    """Mark a freshly-created array as device-varying over the same shard_map
    axes as ``like`` (no-op outside shard_map).  Scan carries must type-match
    their per-step outputs under jax's varying-manual-axes tracking."""
    vma = getattr(jax.typeof(like), "vma", frozenset())
    if vma:
        return jax.lax.pcast(x, axis_name=tuple(vma), to="varying")
    return x


# ---------------------------------------------------------------------------
# Reference (dense) attention — the spec the kernels are tested against.
# ---------------------------------------------------------------------------

def block_diffusion_visible(qpos, kpos, length: int, block: int):
    """The block-diffusion training mask, elementwise on index arrays.

    The sequence is ``[x_t ‖ x_0]``: indices below ``length`` are the NOISED
    copy of a row of ``length`` tokens, the rest its CLEAN copy; a token's
    position is its index within its copy and its block ``position //
    block``.  A noised query sees the noised keys of its own block and the
    clean keys of earlier blocks; a clean query sees the clean keys of its
    own and earlier blocks; no query sees a noised key outside its block."""
    q_noised, k_noised = qpos < length, kpos < length
    qp = jnp.where(q_noised, qpos, qpos - length)
    kp = jnp.where(k_noised, kpos, kpos - length)
    start = qp - qp % block                 # where the query's block starts
    # no select between masks: Mosaic has none for vectors of booleans
    own_block = k_noised & q_noised & (kp >= start) & (kp < start + block)
    clean_past = jnp.logical_not(k_noised) & (
        kp < jnp.where(q_noised, start, start + block))
    return own_block | clean_past


def _repeat_kv(q, k, v):
    """K and V at the query's head count (the paths off the kernels)."""
    group = q.shape[2] // k.shape[2]
    if group == 1:
        return k, v
    return jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)


def mha_reference(q, k, v, *, causal: bool = True, sm_scale: float | None = None,
                  kv_offset: int = 0, block_diffusion=None,
                  window: int | None = None):
    """Dense O(S²) attention.  ``kv_offset`` is the global position of
    ``k[:, 0]`` relative to ``q[:, 0]`` (ring attention passes non-zero
    offsets so causal masks stay globally consistent across chunks);
    ``window`` hides the keys ``window`` or more places before their query."""
    *_, d = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    k, v = _repeat_kv(q, k, v)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    sq, sk = q.shape[1], k.shape[1]
    qpos = jnp.arange(sq)[:, None]
    kpos = kv_offset + jnp.arange(sk)[None, :]
    if causal:
        logits = jnp.where(kpos <= qpos, logits, NEG_INF)
    if window is not None:
        logits = jnp.where(qpos - kpos < window, logits, NEG_INF)
    if block_diffusion:
        logits = jnp.where(block_diffusion_visible(qpos, kpos,
                                                   *block_diffusion),
                           logits, NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# Chunk + merge primitives (shared with ring attention in parallel/sp.py).
# ---------------------------------------------------------------------------

def chunk_attention(q, k, v, *, causal: bool = True, sm_scale: float | None = None,
                    kv_offset=0, window: int | None = None):
    """Attend q over one KV chunk; return ``(out, lse)``.

    ``out`` is the softmax-normalised output **for this chunk alone** and
    ``lse`` its log-sum-exp (``[B, Sq, H]``, float32).  Two chunk results
    combine exactly via ``merge_attention`` — the online-softmax identity
    ring attention is built on.  ``kv_offset`` may be a traced scalar;
    ``window`` as ``mha_reference`` takes it.
    """
    *_, d = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    sq, sk = q.shape[1], k.shape[1]
    qpos = jnp.arange(sq)[:, None]
    kpos = kv_offset + jnp.arange(sk)[None, :]
    if causal:
        logits = jnp.where(kpos <= qpos, logits, NEG_INF)
    if window is not None:
        logits = jnp.where(qpos - kpos < window, logits, NEG_INF)
    m = jnp.max(logits, axis=-1)                                   # [B,H,Sq]
    # Rows with every position masked (pure-future chunk): exp underflows to
    # 0 row-wise; guard the max so exp(NEG_INF - NEG_INF) doesn't become 1.
    m_safe = jnp.maximum(m, NEG_INF / 2)
    p = jnp.exp(logits - m_safe[..., None])
    p = jnp.where(logits <= NEG_INF / 2, 0.0, p)
    l = jnp.sum(p, axis=-1)                                        # [B,H,Sq]
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    lse = m_safe + jnp.log(jnp.maximum(l, 1e-30))
    lse = jnp.where(l > 0.0, lse, NEG_INF)
    o = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return o.astype(q.dtype), lse.transpose(0, 2, 1)               # [B,Sq,H]


def merge_attention(o1, lse1, o2, lse2):
    """Merge two chunk results (online-softmax combine); fully-masked chunks
    (lse == NEG_INF) drop out exactly."""
    lse = jnp.logaddexp(lse1, lse2)
    lse = jnp.maximum(lse, NEG_INF)  # logaddexp(-inf,-inf) guard
    w1 = jnp.exp(lse1 - lse)[..., None]
    w2 = jnp.exp(lse2 - lse)[..., None]
    o = o1.astype(jnp.float32) * w1 + o2.astype(jnp.float32) * w2
    return o.astype(o1.dtype), lse


# ---------------------------------------------------------------------------
# Blockwise attention — differentiable lax.scan over KV blocks (any backend).
# ---------------------------------------------------------------------------

def blockwise_attention(q, k, v, *, causal: bool = True,
                        sm_scale: float | None = None, block_k: int = 512,
                        kv_offset: int = 0, block_diffusion=None,
                        k_shared=None, window: int | None = None):
    """Flash-style attention as a ``lax.scan`` over KV blocks.

    Differentiable, runs on every backend, and with the per-block
    ``jax.checkpoint`` memory is O(Sq·block_k) — ``impl="xla"``: the path off
    the TPU.  ``k_shared`` and ``window`` as ``flash_attention`` takes them
    (every KV block is scanned, a window's dead ones too: the specification,
    not the kernels).
    """
    b, sq, h, d = q.shape
    sk, d_k = k.shape[1], k.shape[-1]
    k, v = _repeat_kv(q, k, v)
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    block_k = min(block_k, sk)
    nblocks = -(-sk // block_k)
    pad = nblocks * block_k - sk

    def blocks(x):          # [B, Sk, ...] -> [nblocks, B, block_k, ...]
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(
            x.reshape(b, nblocks, block_k, *x.shape[2:]), 1, 0)

    kb, vb = blocks(k), blocks(v)
    shared = () if k_shared is None else (blocks(k_shared),)
    q, q_shared = q[..., :d_k], q[..., d_k:]

    qpos = jnp.arange(sq)[:, None]

    @jax.checkpoint
    def block(carry, inputs):
        o_acc, m_acc, l_acc = carry
        kc, vc, start, *kc_shared = inputs
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, kc).astype(jnp.float32)
        if kc_shared:       # the one key head every query head also meets
            logits = logits + jnp.einsum(
                "bqhd,bkd->bhqk", q_shared, kc_shared[0]).astype(jnp.float32)
        logits = logits * scale
        kpos = kv_offset + start + jnp.arange(block_k)[None, :]
        mask = kpos < kv_offset + sk  # padded tail
        if causal:
            mask = mask & (kpos <= qpos)
        if window is not None:
            mask = mask & (qpos - kpos < window)
        if block_diffusion:
            mask = mask & block_diffusion_visible(qpos, kpos,
                                                  *block_diffusion)
        logits = jnp.where(mask, logits, NEG_INF)
        m_blk = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m_acc, m_blk)
        m_safe = jnp.maximum(m_new, NEG_INF / 2)
        p = jnp.exp(logits - m_safe[..., None])
        p = jnp.where(logits <= NEG_INF / 2, 0.0, p)
        alpha = jnp.where(m_acc <= NEG_INF / 2, 0.0, jnp.exp(m_acc - m_safe))
        l_new = l_acc * alpha + jnp.sum(p, axis=-1)
        o_new = (o_acc * alpha.transpose(0, 2, 1)[..., None]
                 + jnp.einsum("bhqk,bkhd->bqhd", p, vc.astype(jnp.float32)))
        return (o_new, m_new, l_new), None

    o0 = match_vma(jnp.zeros((b, sq, h, v.shape[-1]), jnp.float32), q)
    m0 = match_vma(jnp.full((b, h, sq), NEG_INF, jnp.float32), q)
    l0 = match_vma(jnp.zeros((b, h, sq), jnp.float32), q)
    starts = jnp.arange(nblocks) * block_k
    (o, m, l), _ = jax.lax.scan(block, (o0, m0, l0),
                                (kb, vb, starts, *shared))
    o = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas TPU kernels — forward (online softmax over the live k blocks of a q
# block) and backward (one pass on the forward's lse; a dk/dv pass and a dq
# pass where one pass's accumulators do not fit).
# ---------------------------------------------------------------------------

_NT = (((1,), (1,)), ((), ()))      # a @ b.T: contract the last dim of both
_TN = (((0,), (0,)), ((), ()))      # a.T @ b: contract the first dim of both


def _block_diffusion_tile(q_lo, q_hi, k_lo, k_hi, length: int, block: int):
    """``block_diffusion_visible`` on the tile of queries ``q_lo..q_hi`` and
    keys ``k_lo..k_hi`` (index ranges, ends included; integers or numpy
    arrays of them): whether SOME pair is visible and whether EVERY pair is.
    A tile may straddle the two copies, so each range is taken apart into
    its noised and its clean positions and the four combinations are judged
    on their own."""

    def copies(lo, hi):     # (noised?, first position, last position)
        return ((True, lo, np.minimum(hi, length - 1)),
                (False, np.maximum(lo, length) - length, hi - length))

    some, every = False, True
    for q_noised, qa, qb in copies(q_lo, q_hi):
        # where the blocks of the first and of the last query start
        first, last = qa // block * block, qb // block * block
        for k_noised, ka, kb in copies(k_lo, k_hi):
            there = (qa <= qb) & (ka <= kb)
            if k_noised and q_noised:
                any_ = (ka < last + block) & (kb >= first)
                all_ = (first == last) & (ka >= first) & (kb < first + block)
            elif k_noised:
                any_ = all_ = False
            else:
                reach = 0 if q_noised else block
                any_, all_ = ka < last + reach, kb < first + reach
            some = some | (there & any_)
            every = every & (np.logical_not(there) | all_)
    return some, every


def _tile_live(q_start, k_start, *, causal: bool, kv_offset: int,
               block_q: int, block_k: int, sk: int, block_diffusion=None,
               window=None):
    """Whether the tile of queries from ``q_start`` and keys from ``k_start``
    (a global position) holds any visible pair: not wholly padding and, under
    ``causal``, not wholly in the future, nor under ``window`` wholly older
    than its first query's window (its last real key against that query).
    No kernel visits a dead tile (``_walk``); a further mask adds its
    condition here, as ``block_diffusion`` does.  Judged at trace time, on
    integers or numpy arrays of them, as ``_tile_interior`` is."""
    live = k_start < kv_offset + sk
    if causal:
        live = live & (k_start <= q_start + block_q - 1)
    if window is not None:
        last_key = np.minimum(k_start + block_k, kv_offset + sk) - 1
        live = live & (q_start - last_key < window)
    if block_diffusion:
        live = live & _block_diffusion_tile(
            q_start, q_start + block_q - 1, k_start,
            np.minimum(k_start + block_k, sk) - 1, *block_diffusion)[0]
    return live


def _tile_interior(q_start, k_start, *, causal: bool, kv_offset: int,
                   block_q: int, block_k: int, sk: int,
                   block_diffusion=None, window=None):
    """Whether EVERY pair of the tile is visible (it holds no padding and,
    under ``causal``, lies wholly in the past, and under ``window`` its
    first key is inside its last query's window): the kernels build no mask
    there.  A further mask narrows this as it narrows ``_tile_live``."""
    interior = k_start + block_k <= kv_offset + sk
    if causal:
        interior = interior & (k_start + block_k - 1 <= q_start)
    if window is not None:
        interior = interior & (q_start + block_q - 1 - k_start < window)
    if block_diffusion:
        interior = interior & _block_diffusion_tile(
            q_start, q_start + block_q - 1, k_start, k_start + block_k - 1,
            *block_diffusion)[1]
    return interior


def _tile_visible(shape, q_dim: int, *, q_start, k_start, causal: bool,
                  kv_offset: int, sk: int, block_diffusion=None, window=None):
    """The visible pairs of one live tile, queries along ``q_dim`` of
    ``shape`` and keys along the other: the padded tail of the keys is never
    visible, nor under ``causal`` a key after its query, nor under ``window``
    one that many places or more before it."""
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_dim)
    mask = kpos < kv_offset + sk
    if causal or block_diffusion or window is not None:
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, q_dim)
    if causal:
        mask = jnp.logical_and(mask, kpos <= qpos)
    if window is not None:
        mask = jnp.logical_and(mask, qpos - kpos < window)
    if block_diffusion:
        mask = jnp.logical_and(mask, block_diffusion_visible(
            qpos, kpos, *block_diffusion))
    return mask


# What a visit of a kernel's walk is told (bits of its ``flags``): it is the
# first / the last of its output block (initialise / write the block), and
# its tile is masked (some pair of it is visible: the mask is built) or
# interior (every pair is: none is).  A visit with neither attends nothing.
# The first and the last visit of the whole table open and close a grid row:
# what is resident over the row is zeroed there and written there.
_FIRST, _LAST, _MASKED, _INTERIOR, _OPEN, _CLOSE = 1, 2, 4, 8, 16, 32


def _tile_kinds(nq: int, nk: int, *, block_q: int, block_k: int,
                kv_offset: int, **mask_args):
    """``[nq, nk]``: ``_MASKED``, ``_INTERIOR`` or 0 (dead) for every tile of
    the score matrix.  The mask and the shape are static, so this is a
    constant of the program, worked out in numpy at trace time."""
    q_start = np.arange(nq)[:, None] * block_q
    k_start = kv_offset + np.arange(nk)[None, :] * block_k
    tile = dict(block_q=block_q, block_k=block_k, kv_offset=kv_offset,
                **mask_args)
    live = np.broadcast_to(_tile_live(q_start, k_start, **tile), (nq, nk))
    interior = live & _tile_interior(q_start, k_start, **tile)
    return np.where(interior, _INTERIOR, live * _MASKED)


def _walk(kinds):
    """The visits of one kernel's sequential grid axis, in order, as the int32
    rows ``(block, tile, flags)``: for every output block (a row of
    ``kinds``, in order) its live tiles in ascending order, each ONCE: a visit
    serves the query heads of a K/V group together (``_visit_heads``).  A
    block with no live tile gets ONE visit, which attends nothing, so that
    its zeros are written.  A dead tile is no visit: no grid step and no
    fetch.  The table's first visit also carries ``_OPEN`` and its last
    ``_CLOSE``.

    The rows are the kernel's scalar-prefetch operands (SMEM, 4 bytes a
    visit each): at SDAR's 16 x 16 tiles 80 visits in all three kernels; a
    causal row of 32k, 2,080."""
    visits = []
    for block, row in enumerate(kinds):
        run = [[block, tile, row[tile]]
               for tile in np.flatnonzero(row)] or [[block, 0, 0]]
        run[0][2] |= _FIRST
        run[-1][2] |= _LAST
        visits += run
    visits[0][2] |= _OPEN
    visits[-1][2] |= _CLOSE
    return np.asarray(visits, np.int32).T


# A visit of several heads outgrows Mosaic's default VMEM scope of 16 MiB
# (_VMEM_DEFAULT): its kernels get _VMEM_LIMIT (of a v5e's 128 MiB), of which
# _VMEM_BLOCKS are for the visit's blocks and scratch and the rest for the
# tile body's score tiles.  A visit of one head keeps the default scope: under
# the raised one the forward and the two passes ran 4-14% slower (PERF.md §6,
# PR 36).
#
# The one-pass backward also keeps dk and dv (and the shared key's gradient)
# of its grid row resident over the WHOLE padded key length: float32
# accumulators beside the output blocks they are written to once a row, which
# the pipeline holds twice.  Blocks and resident set together may take the
# limit less _VMEM_BODY (SDAR's 8,192 positions of 128 + 128 under 8 heads of
# blocks: 16 + 16 MiB; Mosaic's own count came out 2.5 MiB over this sum's
# parts there and under the sum for a shared key); what does not fit takes the
# two passes.  One head a visit stays in the default scope while the sum
# leaves the body's room there too (the dense LM's 2,048 positions: 4 + 2 MiB;
# the one-pass kernel ran alike under either scope, PERF.md §6, PR 40).
_VMEM_DEFAULT = 16 << 20
_VMEM_LIMIT = 96 << 20
_VMEM_BLOCKS = 24 << 20
_VMEM_BODY = 6 << 20


def _head_bytes(block_q: int, d_p: int, itemsize: int, more: int = 0) -> int:
    """What one query head of a visit holds in the kernel that holds most,
    the dq pass: q, dO and dq double-buffered, the two float32 column blocks
    of 128 lanes likewise, the float32 accumulator, and ``more`` bytes of
    what else it holds (the one-pass backward holds the same less the column
    blocks)."""
    tile = block_q * d_p
    return 6 * tile * itemsize + 4 * block_q * 128 * 4 + tile * 4 + more


def _visit_heads(group: int, block_q: int, d_p: int, itemsize: int,
                 more: int = 0) -> int:
    """How many query heads of a K/V group one visit serves: the largest
    divisor of ``group`` whose query-side blocks and scratch
    (``_head_bytes``) fit ``_VMEM_BLOCKS``.  8 heads of 512 x 128 in bf16
    hold 16 MiB; a group of 32 is served in several visits a tile."""
    a_head = _head_bytes(block_q, d_p, itemsize, more)
    return max(heads for heads in range(1, group + 1)
               if group % heads == 0
               and (heads == 1 or heads * a_head <= _VMEM_BLOCKS))


class _Plan(NamedTuple):
    """What is static of one call of the kernels, worked out from the shapes
    and the mask at trace time (``_plan``).  Hashable: it keys the trace
    caches of ``_flash_fwd_pallas`` and ``_flash_bwd_pallas``."""
    tile: tuple         # the mask and the tile sides, items of _tile_kinds' kwargs
    sq_p: int           # the tiled lengths
    sk_p: int
    heads: int          # query heads a visit serves
    kv_heads: int       # K/V heads a visit holds: 1, or one a query head
    dense: int          # tiles of the dense grid
    walk: tuple         # rows (iq, ik, flags): the forward's, the one-pass
    #                     backward's and the dq pass's
    walk_t: tuple       # rows (ik, iq, flags): the dk/dv pass's
    fused: int          # query heads a visit of the one-pass backward serves;
    #                     0: its resident set does not fit, the two passes run
    fused_limit: int | None     # that kernel's VMEM limit (None: the default)


def _plan(qt, kt, vt, kr=None, *, causal, kv_offset, block_q, block_k,
          block_diffusion, window=None) -> _Plan:
    """The plan of the kernels over head-major ``qt``, ``kt`` and ``vt``:
    numpy and integers only, worked out in each rule of the VJP (which counts
    by it).

    Where every query head has K and V of its own AND all meet one shared
    key ``kr`` (latent attention), a visit serves several (query, K/V) heads
    of one row beside one fetch of the shared key's block, and sums the
    shared key's gradient over them in the visit.

    The backward is ONE pass where a visit's blocks and the gradients of a
    grid row's K/V heads (and of the shared key) over the whole padded key
    length leave the tile body its room in the VMEM limit: float32
    accumulators, and the output blocks twice (the pipeline's two buffers;
    float32 too where several grid rows share a K/V head and their shares
    are added outside).  With K and V of its own a head, fewer heads a visit
    hold less: the largest divisor of the forward's that fits.  Nothing fits
    a 128k row: ``fused`` is 0 and the two passes run.

    Under a ``window`` the rule is the same one, over the WHOLE key length,
    though a key's queries span only the window: the walk is q-major and an
    output block is written where its index changes, so dk and dv of a key
    tile could leave VMEM early only in a kernel whose outputs follow the
    key tile, and that kernel is the dk/dv pass of the two.  One pass over
    the band keeps the scores computed once a tile, which is worth more than
    the memory while the row fits (a 16k row of 128 + 128 under a group of
    7: 14 + 32 MiB); where it does not, the two passes walk the band."""
    bh, sq, d_p = qt.shape
    sk = kt.shape[1]
    group = bh // kt.shape[0]
    block_q, block_k, sq_p, sk_p = _blocks(sq, sk, block_q, block_k)
    tile = dict(causal=causal, kv_offset=kv_offset, block_q=block_q,
                block_k=block_k, sk=sk, block_diffusion=block_diffusion,
                window=window)
    kinds = _tile_kinds(sq_p // block_q, sk_p // block_k, **tile)
    rows = lambda table: tuple(map(tuple, table.tolist()))      # noqa: E731
    size = qt.dtype.itemsize
    own = kr is not None and group == 1     # K and V of its own a query head
    more = 0
    if own:
        # beside the query side: the shared product's q, dq (double-buffered)
        # and accumulator, and the head's own K and V blocks
        shared = block_q * kr.shape[-1]
        group = bh // kr.shape[0]
        more = 4 * shared * size + shared * 4 + 4 * block_k * d_p * size
    heads = _visit_heads(group, block_q, d_p, size, more)
    kv_heads = heads if own else 1

    def one_pass(visit: int) -> int:
        """Bytes of the one-pass backward at ``visit`` heads a visit: their
        blocks, and accumulator and two output buffers of every gradient
        that is resident over the grid row."""
        out = lambda rows: 4 + 2 * (size if rows == 1 else 4)   # noqa: E731
        own_width = sk_p * (d_p + vt.shape[2])
        if own:
            resident = (visit * own_width * out(1)
                        + sk_p * kr.shape[-1] * out(group // visit))
        else:
            resident = own_width * out(group // visit)
            if kr is not None:
                resident += sk_p * kr.shape[-1] * out(
                    bh // kr.shape[0] // visit)
        return visit * _head_bytes(block_q, d_p, size, more) + resident

    fused = max((visit for visit in range(1, heads + 1)
                 if heads % visit == 0 and (own or visit == heads)
                 and one_pass(visit) <= _VMEM_LIMIT - _VMEM_BODY), default=0)
    default = fused == 1 and one_pass(1) <= _VMEM_DEFAULT - _VMEM_BODY
    return _Plan(tuple(tile.items()), sq_p, sk_p, heads, kv_heads,
                 kinds.size, rows(_walk(kinds)), rows(_walk(kinds.T)),
                 fused, None if default or not fused else _VMEM_LIMIT)


def _count(plan: _Plan, latent: bool, heads: int, *tables) -> None:
    """For the run report, once for each kernel a traced program holds (the
    check's included; ``heads`` query heads a visit, one table a kernel):
    ``flash.tiles_walked`` over ``flash.tiles`` is the share of the dense
    grid's steps that the walks keep (the one-pass backward is ONE kernel
    over one table), ``flash.visit_heads`` over ``flash.kernels`` the query
    heads a visit serves.  Of these, the kernels that take a shared key are
    counted again as ``flash.latent_kernels`` and
    ``flash.latent_visit_heads``."""
    for table in tables:
        telemetry.counter("flash.kernels").inc()
        telemetry.counter("flash.visit_heads").inc(heads)
        telemetry.counter("flash.tiles").inc(plan.dense)
        telemetry.counter("flash.tiles_walked").inc(len(table[0]))
        if latent:
            telemetry.counter("flash.latent_kernels").inc()
            telemetry.counter("flash.latent_visit_heads").inc(heads)
    tile = dict(plan.tile)
    if tile["window"] is None:
        return
    # the band's kernels again, on their own: ``flash.window.visits`` over
    # ``flash.window.causal_visits`` is the share of plain causal's visits
    # (same shapes, same tiles) that the band keeps, and
    # ``flash.window.masked_tiles`` its visits that build a mask, on the
    # diagonal and on the band's far edge
    causal = int(np.count_nonzero(_tile_kinds(
        plan.sq_p // tile["block_q"], plan.sk_p // tile["block_k"],
        **{**tile, "window": None})))
    for table in tables:
        telemetry.counter("flash.window.visits").inc(len(table[0]))
        telemetry.counter("flash.window.causal_visits").inc(causal)
        telemetry.counter("flash.window.masked_tiles").inc(
            sum(1 for flags in table[2] if flags & _MASKED))


def _walk_call(kernel, table, rows: int, vmem_limit: int | None, *,
               out_shape, interpret: bool, **specs):
    """``kernel`` over the grid ``(rows, visits)``: the walk of ``table`` for
    each grid row (a visit's query heads); the index maps and the kernel
    read the table's rows from SMEM.  The kernel is told which flags EVERY
    visit carries and which ANY does (``_visit``).

    v5e has one TensorCore: the q (or k) block axis, which could run in
    parallel, loses nothing by being folded into the sequential walk."""
    table = np.asarray(table, np.int32)
    call = pl.pallas_call(
        functools.partial(kernel,
                          every=int(np.bitwise_and.reduce(table[-1])),
                          some=int(np.bitwise_or.reduce(table[-1]))),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(table), grid=(rows, table.shape[1]),
            **specs),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        interpret=interpret)
    return functools.partial(call, *(jnp.asarray(row) for row in table))


def _visit(iq_ref, ik_ref, flags_ref, init, attend, finalize, *,
           block_q: int, block_k: int, kv_offset: int, every: int, some: int,
           open_row=None, close_row=None, **mask_args):
    """This grid step's visit of the walk, as its flags say: ``init()`` on
    the first visit of an output block, ``attend(visible)`` on a live tile
    (with None on an interior one, else with the tile's mask as a function
    of the score tile's shape and the dimension its queries lie along),
    ``finalize()`` on the block's last; around them ``open_row()`` on the
    table's first visit and ``close_row()`` on its last, where a kernel keeps
    something over the whole grid row.  A flag that ``every`` visit of the
    table carries is no branch, and one that not even ``some`` do is no
    code: rows of one tile (the walk's shortest) run straight through."""
    visit = pl.program_id(1)
    flags = flags_ref[visit]

    def on(flag, run):
        if every & flag:
            run()
        elif some & flag:
            pl.when((flags & flag) != 0)(run)

    if open_row is not None:
        on(_OPEN, open_row)
    on(_FIRST, init)
    on(_MASKED, lambda: attend(functools.partial(
        _tile_visible, q_start=iq_ref[visit] * block_q,
        k_start=kv_offset + ik_ref[visit] * block_k, kv_offset=kv_offset,
        **mask_args)))
    on(_INTERIOR, lambda: attend(None))
    on(_LAST, finalize)
    if close_row is not None:
        on(_CLOSE, close_row)


def _lanes(x, width: int):
    """A lane-replicated statistic ``[rows, 128]`` as ``[rows, width]``:
    whole copies of its 128 lanes side by side (the first lanes of one for a
    narrower tile).  Not ``x[:, 0:1]`` broadcast: that one-lane column cost
    a forward kernel as much as its matmuls (PERF.md §6, PR 34).  As
    ``ops/sparse_attention.py``'s, which stays as it is (ROADMAP D18)."""
    return jnp.tile(x, (1, pl.cdiv(width, 128)))[:, :width]


def _bias(visible, shape, q_dim: int):
    """A masked tile's mask as what every head of the visit ADDS to its
    scores: 0 on a visible pair, ``NEG_INF`` elsewhere, built once a visit
    (``logits + bias`` is ``where(visible, logits, NEG_INF)`` to the bit for
    finite logits).  None on an interior tile."""
    if visible is None:
        return None
    return jnp.where(visible(shape, q_dim), 0.0, NEG_INF)


def _scores(a, b, sm_scale: float, bias, shared=None):
    """``a @ b.T`` scaled, float32 from the operands as the model holds them
    (the products of bf16 values are exact in float32), plus the bias.
    ``shared`` is a second pair of operands whose product joins the first
    before the scale: the columns of a query that meet the shared key."""
    logits = jax.lax.dot_general(
        a, b, _NT, preferred_element_type=jnp.float32)
    if shared is not None:
        logits = logits + jax.lax.dot_general(
            *shared, _NT, preferred_element_type=jnp.float32)
    logits = logits * sm_scale
    return logits if bias is None else logits + bias


def _kv_of(k_ref, v_ref):
    """``h -> (k, v)`` of a visit's head ``h``: the one K/V head the visit's
    query heads share, read once, or the head's own."""
    if k_ref.shape[0] == 1:
        kv = k_ref[0], v_ref[0]
        return lambda h: kv
    return lambda h: (k_ref[h], v_ref[h])


# A VISIT of the kernels (the forward, the one-pass backward, and the two
# passes that run where its accumulators do not fit) is one live tile for one
# K/V head and the query heads of its group that share a grid row
# (``_visit_heads``: all of them where they fit): the query-side blocks are
# ``[heads, block, d]``, K and V are fetched once a visit, a masked tile's
# bias is built once, and the heads are a static loop inside the visit.  With
# one query head a K/V head the loop has one turn.  Under a shared key
# (``latent``) the refs ``qr`` (the queries' columns that meet it) and ``kr``
# (its block, one fetch a visit) follow q, k and v, every head of the visit
# has a K/V block of its own, and the backward writes dqr and the visit's sum
# of dkr.

def _flash_fwd_kernel(iq_ref, ik_ref, flags_ref, q_ref, k_ref, v_ref, *refs,
                      sm_scale: float, latent: bool, **walk):
    # m/l scratch is lane-replicated to 128 lanes — TPU tiling requires the
    # last dim be 128-aligned — and read as whole lanes (``_lanes``).  The lse
    # goes out as a ROW per (batch, head): a residual of the backward, 128
    # times smaller than the replicated columns and lane-dense as its dk/dv
    # pass reads it.
    if latent:
        qr_ref, kr_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    else:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    heads, block_q, _ = q_ref.shape
    block_k, d = k_ref.shape[1], v_ref.shape[2]

    def init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def attend(visible):
        kv = _kv_of(k_ref, v_ref)                      # [block_k, d] each
        bias = _bias(visible, (block_q, block_k), 0)
        for h in range(heads):
            k, v = kv(h)
            logits = _scores(q_ref[h], k, sm_scale, bias,
                             (qr_ref[h], kr_ref[0]) if latent else None)
            m_prev = m_ref[h]                           # [block_q, 128]
            m_new = jnp.maximum(m_prev,
                                jnp.max(logits, axis=-1, keepdims=True))
            # a row that has seen nothing yet: exp(NEG_INF - NEG_INF / 2) = 0
            m_safe = jnp.maximum(m_new, NEG_INF / 2)
            p = jnp.exp(logits - _lanes(m_safe, block_k))
            alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0,
                              jnp.exp(m_prev - m_safe))
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            # the weights round to V's dtype, as the backward's do
            acc_ref[h] = acc_ref[h] * _lanes(alpha, d) + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    def finalize():
        for h in range(heads):
            l = jnp.maximum(l_ref[h], 1e-30)
            o_ref[h] = (acc_ref[h] / _lanes(l, d)).astype(o_ref.dtype)
            lse = jnp.where(l_ref[h] > 0.0, m_ref[h] + jnp.log(l), NEG_INF)
            lse_ref[h] = lse.T[0:1]              # a row, as it lies in memory

    _visit(iq_ref, ik_ref, flags_ref, init, attend, finalize, **walk)


def _head_major(x, interpret: bool):
    """``[B, S, H, D]`` -> ``[B*H, S, D_p]``, the layout all three kernels
    read: a (batch, head) a row, the query heads of a K/V group neighbours
    (one block holds them), D zero-padded to the 128-lane width (not under
    ``interpret``, which has no tiling)."""
    b, s, h, d = x.shape
    d_p = d if interpret else -(-d // 128) * 128
    x = x.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    return jnp.pad(x, ((0, 0), (0, 0), (0, d_p - d)))


def _from_head_major(x, b: int, d: int):
    bh, s, _ = x.shape
    return x[:, :, :d].reshape(b, bh // b, s, d).transpose(0, 2, 1, 3)


def _blocks(sq: int, sk: int, block_q: int, block_k: int):
    """The tile sides and the tiled lengths of a ``[sq, sk]`` score matrix:
    a sequence shorter than a block is one block, and every other length is
    zero-padded up to whole blocks (``_pad_seq``) and masked as padding."""
    block_q = min(block_q, max(8, sq))
    block_k = min(block_k, max(8, sk))
    return (block_q, block_k,
            -(-sq // block_q) * block_q, -(-sk // block_k) * block_k)


def _pad_seq(x, s_p: int):
    return jnp.pad(x, ((0, 0), (0, s_p - x.shape[1]), (0, 0)))


# The two wrappers of the kernels are jitted on what is static of a call: a
# program's layers, and a process's programs, share ONE trace and one
# lowering a program of each kernel.  A visit's static head loop is 8 tile
# bodies to trace and lower: built anew for every layer of every program it
# added 13 s to SDAR's set-up (PERF.md §6, PR 36).
_STATIC = ("plan", "sm_scale", "interpret")


def _visit_rows(heads: int, kv_heads: int, qt, kt, kr):
    """How a kernel's grid rows (``heads`` query heads each) meet the rows
    of the other operands: ``parts`` grid rows share one block of
    ``kv_heads`` K/V heads, and ``batch_rows`` of them are one batch row's
    and meet its shared key (without one: all of them)."""
    rows = qt.shape[0] // heads
    return (rows * kv_heads // kt.shape[0],
            rows // (1 if kr is None else kr.shape[0]))


@functools.partial(jax.jit, static_argnames=_STATIC)
def _flash_fwd_pallas(qt, kt, vt, qr=None, kr=None, *, plan: _Plan, sm_scale,
                      interpret):
    """Run the Pallas forward on head-major ``[B*H, S, D_p]`` operands
    (``[B*H_kv, S, D_p]`` keys and values: query head ``j`` reads the K/V
    row ``j // group``, head-major rows being ``batch * heads + head`` on
    both sides; ``vt`` may be of another width than ``qt`` and ``kt``);
    returns ``(out [B*H, Sq, Dv_p], lse [B*H, Sq] float32)``.  ``qr`` ``[B*H,
    S, Dr_p]`` and ``kr`` ``[B, S, Dr_p]``: the queries' columns for a key
    that all heads of a batch row share, and that key."""
    bh, sq, d_p = qt.shape
    dv_p, latent = vt.shape[2], kr is not None
    static, heads = dict(plan.tile), plan.heads
    block_q, block_k, sq_p = static["block_q"], static["block_k"], plan.sq_p
    qt = _pad_seq(qt, sq_p)
    kt, vt = _pad_seq(kt, plan.sk_p), _pad_seq(vt, plan.sk_p)
    parts, batch_rows = _visit_rows(heads, plan.kv_heads, qt, kt, kr)
    shared, shared_specs = (), []
    if latent:
        shared = _pad_seq(qr, sq_p), _pad_seq(kr, plan.sk_p)
        shared_specs = [
            pl.BlockSpec((heads, block_q, qr.shape[2]),
                         lambda r, v, iq, ik, flags: (r, iq[v], 0)),
            pl.BlockSpec((1, block_k, kr.shape[2]),
                         lambda r, v, iq, ik, flags: (
                             r // batch_rows, ik[v], 0))]

    q_at = lambda r, v, iq, ik, flags: (r, iq[v], 0)            # noqa: E731
    kv_at = lambda r, v, iq, ik, flags: (r // parts, ik[v], 0)  # noqa: E731
    out, lse = _walk_call(
        functools.partial(_flash_fwd_kernel, sm_scale=sm_scale,
                          latent=latent, **static),
        plan.walk, bh // heads, _VMEM_LIMIT if heads > 1 else None,
        in_specs=[
            pl.BlockSpec((heads, block_q, d_p), q_at),
            pl.BlockSpec((plan.kv_heads, block_k, d_p), kv_at),
            pl.BlockSpec((plan.kv_heads, block_k, dv_p), kv_at),
            *shared_specs,
        ],
        out_specs=[
            pl.BlockSpec((heads, block_q, dv_p), q_at),
            pl.BlockSpec((heads, 1, block_q),
                         lambda r, v, iq, ik, flags: (r, 0, iq[v])),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq_p, dv_p), qt.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq_p), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((heads, block_q, dv_p), jnp.float32),
            pltpu.VMEM((heads, block_q, 128), jnp.float32),
            pltpu.VMEM((heads, block_q, 128), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt, *shared)
    return out[:, :sq], lse[:, 0, :sq]


def _flash_bwd_dkv_kernel(ik_ref, iq_ref, flags_ref,
                          q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                          *refs, sm_scale: float, latent: bool, **walk):
    # One KV block against the q blocks that see it, summed over the visit's
    # query heads, on the TRANSPOSED tile [block_k, block_q]: lse and delta
    # are then rows, lane-dense as they lie in memory, and both
    # accumulations are plain matmuls.  The softmax weights of a tile come
    # from the forward's log-sum-exp: no running max, no rescale.  ``lse``
    # is at least ``NEG_INF / 2``, so a pair that is not visible gets exactly
    # 0, also in a row that sees no key at all.  The shared key's gradient is
    # the sum over the visit's heads, as dk and dv are over a group's.
    if latent:
        qr_ref, kr_ref, dk_ref, dv_ref, dkr_ref, dk_acc, dv_acc, dkr_acc = refs
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = refs
    heads, block_q, _ = q_ref.shape
    kv_heads, block_k, _ = k_ref.shape

    def init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        if latent:
            dkr_acc[...] = jnp.zeros_like(dkr_acc)

    def attend(visible):
        kv = _kv_of(k_ref, v_ref)
        bias = _bias(visible, (block_k, block_q), 1)
        for h in range(heads):
            q, do = q_ref[h], do_ref[h]                # [block_q, d]
            (k, v), at = kv(h), h % kv_heads
            p_t = jnp.exp(_scores(
                k, q, sm_scale, bias,
                (kr_ref[0], qr_ref[h]) if latent else None) - lse_ref[h])
            dv_acc[at] += jnp.dot(p_t.astype(do.dtype), do,
                                  preferred_element_type=jnp.float32)
            dp_t = jax.lax.dot_general(
                v, do, _NT, preferred_element_type=jnp.float32)
            ds_t = (p_t * (dp_t - delta_ref[h])).astype(q.dtype)
            dk_acc[at] += jnp.dot(ds_t, q,
                                  preferred_element_type=jnp.float32)
            if latent:
                dkr_acc[0] += jnp.dot(ds_t, qr_ref[h],
                                      preferred_element_type=jnp.float32)

    def finalize():
        dk_ref[...] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)
        if latent:
            dkr_ref[...] = (dkr_acc[...] * sm_scale).astype(dkr_ref.dtype)

    _visit(iq_ref, ik_ref, flags_ref, init, attend, finalize, **walk)


def _flash_bwd_dq_kernel(iq_ref, ik_ref, flags_ref,
                         q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                         *refs, sm_scale: float, latent: bool, **walk):
    # One q block of the visit's heads against the KV blocks it sees; lse and
    # delta are columns here, replicated over 128 lanes like the forward's m
    # and l, and read as whole lanes.
    if latent:
        qr_ref, kr_ref, dq_ref, dqr_ref, dq_acc, dqr_acc = refs
    else:
        dq_ref, dq_acc = refs
    heads, block_q, _ = q_ref.shape
    block_k = k_ref.shape[1]

    def init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        if latent:
            dqr_acc[...] = jnp.zeros_like(dqr_acc)

    def attend(visible):
        kv = _kv_of(k_ref, v_ref)                      # [block_k, d] each
        bias = _bias(visible, (block_q, block_k), 0)
        for h in range(heads):
            k, v = kv(h)
            p = jnp.exp(_scores(
                q_ref[h], k, sm_scale, bias,
                (qr_ref[h], kr_ref[0]) if latent else None)
                - _lanes(lse_ref[h], block_k))
            dp = jax.lax.dot_general(
                do_ref[h], v, _NT, preferred_element_type=jnp.float32)
            ds = (p * (dp - _lanes(delta_ref[h], block_k))).astype(k.dtype)
            dq_acc[h] += jnp.dot(ds, k, preferred_element_type=jnp.float32)
            if latent:
                dqr_acc[h] += jnp.dot(ds, kr_ref[0],
                                      preferred_element_type=jnp.float32)

    def finalize():
        dq_ref[...] = (dq_acc[...] * sm_scale).astype(dq_ref.dtype)
        if latent:
            dqr_ref[...] = (dqr_acc[...] * sm_scale).astype(dqr_ref.dtype)

    _visit(iq_ref, ik_ref, flags_ref, init, attend, finalize, **walk)


def _flash_bwd_kernel(iq_ref, ik_ref, flags_ref,
                      q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, *refs,
                      sm_scale: float, latent: bool, **walk):
    # ONE pass: a live tile of the q-major walk and, for every head of the
    # visit, its scores and dP ONCE, on the TRANSPOSED tile [block_k, block_q]
    # as the dk/dv pass has it: lse and delta are rows, lane-dense as they lie
    # in memory, dv += p_t dO and dk += ds_t q are plain matmuls, and dq +=
    # ds_t^T k is the one product that contracts over the tile's other side.
    # dq (and dqr) accumulate over a q block's visits, as in the dq pass; dk
    # and dv (and dkr, summed over the visit's heads) accumulate over the
    # WHOLE key length of the grid row's K/V heads, at the tile's rows: zeroed
    # where the row opens, scaled, cast and written where it closes.  The
    # weights come from the forward's log-sum-exp (see the dk/dv pass).
    if latent:
        (qr_ref, kr_ref, dq_ref, dk_ref, dv_ref, dqr_ref, dkr_ref,
         dq_acc, dk_acc, dv_acc, dqr_acc, dkr_acc) = refs
    else:
        dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = refs
    heads, block_q, _ = q_ref.shape
    kv_heads, block_k, _ = k_ref.shape
    # the tile's rows of the resident accumulators
    keys = pl.ds(pl.multiple_of(ik_ref[pl.program_id(1)] * block_k, block_k),
                 block_k)

    def open_row():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        if latent:
            dkr_acc[...] = jnp.zeros_like(dkr_acc)

    def init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        if latent:
            dqr_acc[...] = jnp.zeros_like(dqr_acc)

    def attend(visible):
        kv = _kv_of(k_ref, v_ref)
        bias = _bias(visible, (block_k, block_q), 1)
        for h in range(heads):
            q, do = q_ref[h], do_ref[h]                # [block_q, d]
            (k, v), at = kv(h), h % kv_heads
            p_t = jnp.exp(_scores(
                k, q, sm_scale, bias,
                (kr_ref[0], qr_ref[h]) if latent else None) - lse_ref[h])
            dv_acc[at, keys] += jnp.dot(p_t.astype(do.dtype), do,
                                        preferred_element_type=jnp.float32)
            dp_t = jax.lax.dot_general(
                v, do, _NT, preferred_element_type=jnp.float32)
            ds_t = (p_t * (dp_t - delta_ref[h])).astype(q.dtype)
            dk_acc[at, keys] += jnp.dot(ds_t, q,
                                        preferred_element_type=jnp.float32)
            dq_acc[h] += jax.lax.dot_general(
                ds_t, k, _TN, preferred_element_type=jnp.float32)
            if latent:
                dkr_acc[0, keys] += jnp.dot(
                    ds_t, qr_ref[h], preferred_element_type=jnp.float32)
                dqr_acc[h] += jax.lax.dot_general(
                    ds_t, kr_ref[0], _TN, preferred_element_type=jnp.float32)

    def finalize():
        dq_ref[...] = (dq_acc[...] * sm_scale).astype(dq_ref.dtype)
        if latent:
            dqr_ref[...] = (dqr_acc[...] * sm_scale).astype(dqr_ref.dtype)

    def close_row():
        dk_ref[...] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)
        if latent:
            dkr_ref[...] = (dkr_acc[...] * sm_scale).astype(dkr_ref.dtype)

    _visit(iq_ref, ik_ref, flags_ref, init, attend, finalize,
           open_row=open_row, close_row=close_row, **walk)


def _sum_shares(x, shares: int, dtype):
    """The float32 shares that ``shares`` neighbouring grid rows wrote of one
    gradient, added (one share: the gradient itself, in its own dtype)."""
    return x.reshape(-1, shares, *x.shape[1:]).sum(1).astype(dtype)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _flash_bwd_pallas(qt, kt, vt, do_t, lse, delta, qr=None, kr=None, *,
                      plan: _Plan, sm_scale, interpret):
    """The backward on head-major operands; ``lse`` as ``_flash_fwd_pallas``
    returns it and ``delta = rowsum(dO * O)`` like it, ``[B*H, Sq]`` float32.
    Returns ``(dq, dk, dv)`` head-major, dk and dv at the K/V head count:
    summed over the query heads of a visit inside the kernel (where a group
    takes several grid rows, each writes its float32 share and they are added
    here).  With ``qr`` and ``kr`` (see ``_flash_fwd_pallas``) also ``(dqr,
    dkr)``: the shared key's gradient is summed over a visit's heads in the
    kernel, and over a batch row's grid rows here.

    ONE kernel where the plan says that dk and dv fit VMEM over the whole key
    length (``plan.fused`` query heads a visit, the q-major walk); else the
    dk/dv pass over the k-major walk and the dq pass over the q-major one,
    ``plan.heads`` a visit."""
    bh, sq, d_p = qt.shape
    sk, dv_p, latent = kt.shape[1], vt.shape[2], kr is not None
    static = dict(plan.tile, sm_scale=sm_scale, latent=latent)
    block_q, block_k = static["block_q"], static["block_k"]
    sq_p, sk_p = plan.sq_p, plan.sk_p
    heads = plan.fused or plan.heads
    kv_heads = heads if plan.kv_heads > 1 else 1
    # a row that saw no key has lse = NEG_INF: lifted, so that exp(NEG_INF -
    # lse) is 0 there too.  Padded q rows have do = 0 and so add nothing.
    lse = jnp.maximum(lse, NEG_INF / 2)
    stats = [jnp.pad(x, ((0, 0), (0, sq_p - sq))) for x in (lse, delta)]
    rows = [x[:, None, :] for x in stats]                       # [bh, 1, sq_p]
    qt, do_t = _pad_seq(qt, sq_p), _pad_seq(do_t, sq_p)
    kt, vt = _pad_seq(kt, sk_p), _pad_seq(vt, sk_p)
    shared = (_pad_seq(qr, sq_p), _pad_seq(kr, sk_p)) if latent else ()
    parts, batch_rows = _visit_rows(heads, kv_heads, qt, kt, kr)
    grid_rows = bh // heads

    # index maps over a walk's table, whose row ``i`` holds the block index:
    # a query-side block of this grid row (or its own block of a K/V-side
    # gradient), a block of its statistics' rows, a block of the K/V head(s)
    # or of the shared key that ``rows`` grid rows meet
    q_of = lambda i: lambda r, v, *table: (r, table[i][v], 0)   # noqa: E731
    stat_of = lambda i: lambda r, v, *table: (r, 0, table[i][v])    # noqa: E731
    k_of = lambda i, rows: lambda r, v, *table: (               # noqa: E731
        r // rows, table[i][v], 0)
    q_block = (heads, block_q, d_p)
    if latent:
        qr_block = (heads, block_q, qr.shape[2])

    def operands(iq, ik, stat_block, stat_at):
        """The in_specs of q, dO, lse, delta, K, V (and qr, kr) over a table
        whose rows ``iq`` and ``ik`` are the q and the k block."""
        specs = [pl.BlockSpec(q_block, q_of(iq)),
                 pl.BlockSpec((heads, block_q, dv_p), q_of(iq)),
                 pl.BlockSpec(stat_block, stat_at),
                 pl.BlockSpec(stat_block, stat_at),
                 pl.BlockSpec((kv_heads, block_k, d_p), k_of(ik, parts)),
                 pl.BlockSpec((kv_heads, block_k, dv_p), k_of(ik, parts))]
        if latent:
            specs += [pl.BlockSpec(qr_block, q_of(iq)),
                      pl.BlockSpec((1, block_k, kr.shape[2]),
                                   k_of(ik, batch_rows))]
        return specs

    def k_side(length, at):
        """(block over ``length`` keys, index map, shape and dtype) of dk, dv
        (and dkr) as a grid row writes them: the gradient, or its float32
        share where several grid rows hold one."""
        sides = [(kv_heads, d_p, parts), (kv_heads, dv_p, parts)]
        if latent:
            sides.append((1, kr.shape[2], batch_rows))
        return [((n, length, width), at, jax.ShapeDtypeStruct(
            (grid_rows * n, sk_p, width),
            kt.dtype if shares == 1 else jnp.float32))
            for n, width, shares in sides]

    def call(kernel, table, limit, specs, outs):
        """``kernel`` over ``table``; ``outs`` are (block, index map, shape
        and dtype) of its results, each with a float32 accumulator."""
        return _walk_call(
            functools.partial(kernel, **static), table, grid_rows, limit,
            in_specs=specs,
            out_specs=[pl.BlockSpec(block, at) for block, at, _ in outs],
            out_shape=[shape for _, _, shape in outs],
            scratch_shapes=[pltpu.VMEM(block, jnp.float32)
                            for block, _, _ in outs],
            interpret=interpret)

    like = lambda x: jax.ShapeDtypeStruct(x.shape, qt.dtype)    # noqa: E731
    q_side = [(q_block, q_of(0), like(qt))]
    if latent:
        q_side.append((qr_block, q_of(0), like(shared[0])))
    row_block = (heads, 1, block_q)
    if plan.fused:
        # the q-major walk, rows (iq, ik, flags); the kernel's order: dq, dk,
        # dv (, dqr, dkr), the K/V side ONE block over all keys a grid row
        whole = k_side(sk_p, lambda r, v, *table: (r, 0, 0))
        dq, dk, dv, *more = call(
            _flash_bwd_kernel, plan.walk, plan.fused_limit,
            operands(0, 1, row_block, stat_of(0)),
            [q_side[0], *whole[:2], *q_side[1:], *whole[2:]])(
            qt, do_t, *rows, kt, vt, *shared)
        dqr, dkr = more[:1], more[1:]
    else:
        limit = _VMEM_LIMIT if heads > 1 else None
        # the k-major walk, rows (ik, iq, flags), then the q-major one
        dk, dv, *dkr = call(
            _flash_bwd_dkv_kernel, plan.walk_t, limit,
            operands(1, 0, row_block, stat_of(1)), k_side(block_k, q_of(0)))(
            qt, do_t, *rows, kt, vt, *shared)
        cols = [jnp.broadcast_to(x[:, :, None], (bh, sq_p, 128))
                for x in stats]
        dq, *dqr = call(
            _flash_bwd_dq_kernel, plan.walk, limit,
            operands(0, 1, (heads, block_q, 128), q_of(0)), q_side)(
            qt, do_t, *cols, kt, vt, *shared)
    dk, dv = (_sum_shares(x, parts, kt.dtype) for x in (dk, dv))
    dkr = [_sum_shares(x, batch_rows, kt.dtype) for x in dkr]
    return (dq[:, :sq], dk[:, :sk], dv[:, :sk],
            *(x[:, :sq] for x in dqr), *(x[:, :sk] for x in dkr))


def _scale(sm_scale, d: int) -> float:
    return sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)


def _split_shared(q, k, k_shared, interpret: bool):
    """The head-major operands of the shared product, or two Nones: the
    queries' columns past the keys' own width, and the shared key ``[B, S,
    D_r]`` as the one row a batch row has."""
    if k_shared is None:
        return None, None
    return (_head_major(q[..., k.shape[-1]:], interpret),
            _head_major(k_shared[:, :, None], interpret))


def _kind(window) -> str:
    """What a window's kernels add to their scopes' names (``flash_fwd_window``,
    ``flash_bwd_window``): a reader of device time by scope tells the band's
    kernels from the full mask's, which keep ``flash_fwd`` and ``flash_bwd``."""
    return "" if window is None else "_window"


# What the forward KERNEL alone can make of a layer's residuals, by
# ``checkpoint_name``: attention's output ``[B, S, H, D_v]`` and its
# log-sum-exp ``[B * H, S]`` float32 (``S * H * D_v * 2 B + S * H * 4 B`` a
# row in bf16).  A caller that recomputes the layer (``jax.checkpoint``) and
# saves these names runs the backward kernels on them and the forward kernel
# once; the head-major layouts of q, k and v it makes again from the
# projections.  Outside a checkpoint a name is an identity and lowers to
# nothing.
SAVED_NAMES = ("flash_out", "flash_lse")


def _flash_fwd_rule(q, k, v, k_shared, causal, sm_scale, kv_offset, block_q,
                    block_k, interpret, block_diffusion, window):
    # q, k, v stay head-major and lane-padded, as both backward kernels read
    # them (the backward lays out only the cotangent); the output stays as
    # the caller holds it anyway, and the kernel's log-sum-exp is kept.
    b = q.shape[0]
    # forward rules traced: each names SAVED_NAMES (Transformer reads it to
    # tell the rematerialised blocks whose policy keeps them)
    telemetry.counter("flash.fwd_calls").inc()
    with jax.named_scope("flash_fwd" + _kind(window)):
        qt, kt, vt = (_head_major(x, interpret)
                      for x in (q[..., :k.shape[-1]], k, v))
        qr, kr = _split_shared(q, k, k_shared, interpret)
        plan = _plan(qt, kt, vt, kr, causal=causal, kv_offset=kv_offset,
                     block_q=block_q, block_k=block_k,
                     block_diffusion=block_diffusion, window=window)
        _count(plan, kr is not None, plan.heads, plan.walk)
        ot, lse = _flash_fwd_pallas(qt, kt, vt, qr, kr, plan=plan,
                                    sm_scale=_scale(sm_scale, q.shape[-1]),
                                    interpret=interpret)
        out = checkpoint_name(_from_head_major(ot, b, v.shape[-1]),
                              "flash_out")
        lse = checkpoint_name(lse, "flash_lse")
        # the widths of the keys and of the shared key, as empty arrays: the
        # backward reads them off shapes (the operands are lane-padded)
        widths = (jnp.zeros((0, k.shape[-1])),
                  jnp.zeros((0, q.shape[-1] - k.shape[-1])))
        return out, (qt, kt, vt, qr, kr, out, lse, widths)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11))
def _flash_attention_tpu(q, k, v, k_shared, causal, sm_scale, kv_offset,
                         block_q, block_k, interpret, block_diffusion,
                         window):
    return _flash_fwd_rule(q, k, v, k_shared, causal, sm_scale, kv_offset,
                           block_q, block_k, interpret, block_diffusion,
                           window)[0]


def _flash_bwd_rule(causal, sm_scale, kv_offset, block_q, block_k, interpret,
                    block_diffusion, window, res, g):
    qt, kt, vt, qr, kr, out, lse, widths = res
    b, sq, h, d_v = g.shape
    d_k, d_r = (x.shape[1] for x in widths)
    with jax.named_scope("flash_bwd" + _kind(window)):
        delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1).transpose(0, 2, 1).reshape(b * h, sq)
        plan = _plan(qt, kt, vt, kr, causal=causal, kv_offset=kv_offset,
                     block_q=block_q, block_k=block_k,
                     block_diffusion=block_diffusion, window=window)
        # the share of backward calls traced that took the one-pass kernel
        telemetry.counter("flash.bwd_calls").inc()
        if plan.fused:
            telemetry.counter("flash.bwd_fused").inc()
            _count(plan, kr is not None, plan.fused, plan.walk)
        else:
            _count(plan, kr is not None, plan.heads, plan.walk_t, plan.walk)
        dq, dk, dv, *shared = _flash_bwd_pallas(
            qt, kt, vt, _head_major(g, interpret), lse, delta, qr, kr,
            plan=plan, sm_scale=_scale(sm_scale, d_k + d_r),
            interpret=interpret)
        dq, dk, dv = (_from_head_major(x, b, d)
                      for x, d in ((dq, d_k), (dk, d_k), (dv, d_v)))
        if kr is None:
            return dq, dk, dv, None
        dqr, dkr = (_from_head_major(x, b, d_r) for x in shared)
        return jnp.concatenate([dq, dqr], axis=-1), dk, dv, dkr[:, :, 0]


_flash_attention_tpu.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# ---------------------------------------------------------------------------
# Public entry.
# ---------------------------------------------------------------------------

Impl = Literal["pallas", "pallas_interpret", "xla"]


def flash_attention(q, k, v, *, causal: bool = True,
                    sm_scale: float | None = None, kv_offset: int = 0,
                    block_q: int = 512, block_k: int = 512,
                    impl: Impl | None = None,
                    block_diffusion: tuple[int, int] | None = None,
                    k_shared=None, window: int | None = None):
    """Attention, ``[B, S, H, D]`` queries in, ``[B, S, H, D_v]`` out; ``k``
    and ``v`` carry ``H`` heads or a divisor of it (grouped-query heads), and
    ``v`` may be of another width than ``q`` and ``k``.

    ``k_shared`` ``[B, S, D_r]`` is ONE further key head that every query
    head meets beside its own (latent attention's rotary key, DeepSeek-V2,
    arXiv:2405.04434): ``q`` is then ``D + D_r`` wide, ``k`` ``D``, and a
    score is ``q[:D] · k + q[D:] · k_shared``, scaled by ``(D + D_r)^-1/2``
    unless ``sm_scale`` says otherwise.  The kernels fetch it once a visit
    and sum its gradient over the heads inside the visit; no copy of it a
    head ever exists.

    ``block_diffusion=(length, block)`` replaces ``causal`` by the training
    mask of block diffusion over ``2 * length`` positions
    (``block_diffusion_visible``).

    ``window=W`` (static, with ``causal``): query ``i`` sees key ``j`` iff ``0
    <= i - j < W``, itself and the ``W - 1`` before it.  The kernels visit
    the band's tiles and no other, under the scopes ``flash_fwd_window`` and
    ``flash_bwd_window``; a window that reaches over the whole row is dropped
    here, and the call is plain causal's, table for table.  Not built, and
    refused by name: a window beside ``block_diffusion`` (another mask in
    causal's place) or beside ``k_shared`` (latent attention), and one
    without ``causal``.  Ring attention (``parallel/sp.py``) takes none.

    ``impl=None`` auto-selects: Pallas kernel on TPU, blockwise XLA scan
    elsewhere.  ``pallas_interpret`` runs the kernel in interpreter mode (CPU
    tests of the kernel itself).
    """
    if q.shape[2] % k.shape[2] or k.shape[2] != v.shape[2]:
        raise ValueError(f"{q.shape[2]} query heads over {k.shape[2]} key and "
                         f"{v.shape[2]} value heads")
    d_shared = 0 if k_shared is None else k_shared.shape[-1]
    if q.shape[-1] != k.shape[-1] + d_shared or (
            d_shared and k_shared.shape != k.shape[:2] + (d_shared,)):
        raise ValueError(
            f"queries {q.shape[-1]} wide over keys {k.shape[-1]} wide and a "
            f"shared key of shape {getattr(k_shared, 'shape', None)}")
    if block_diffusion:
        block_diffusion = tuple(int(x) for x in block_diffusion)
        length, block = block_diffusion
        if causal or kv_offset or length % block or not (
                q.shape[1] == k.shape[1] == 2 * length):
            raise ValueError(
                f"block_diffusion={block_diffusion} masks a noised and a "
                f"clean copy of {length} tokens in whole blocks, queries and "
                f"keys alike, in place of causal: got {q.shape[1]} queries, "
                f"{k.shape[1]} keys, causal={causal}, kv_offset={kv_offset}")
    if window is not None:
        window = int(window)
        if window < 1 or not causal or block_diffusion or k_shared is not None:
            raise NotImplementedError(
                f"window={window} narrows the causal mask of plain or "
                f"grouped-query attention: got causal={causal}, "
                f"block_diffusion={block_diffusion}, k_shared="
                f"{getattr(k_shared, 'shape', None)}")
        if window > q.shape[1] - 1 - kv_offset:     # no query looks that far
            window = None
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "xla":
        return blockwise_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                                   block_k=block_k, kv_offset=kv_offset,
                                   block_diffusion=block_diffusion,
                                   k_shared=k_shared, window=window)
    if impl in ("pallas", "pallas_interpret"):
        def kernel(q, k, v, k_shared=None):
            return _flash_attention_tpu(q, k, v, k_shared, causal, sm_scale,
                                        kv_offset, block_q, block_k,
                                        impl == "pallas_interpret",
                                        block_diffusion, window)

        shared = () if k_shared is None else (k_shared,)

        # GSPMD cannot partition a Mosaic kernel: on more than one device
        # jax refuses to lower a bare pallas_call ("Mosaic kernels cannot be
        # automatically partitioned").  Attention is independent per
        # (batch, head), so under an ambient mesh (``jax.set_mesh``) run the
        # kernel per shard over the axes the model already constrains q/k/v
        # to: batch over (dp, fsdp), heads over tp.  Sequence stays whole
        # here (splitting it is ring attention's job, parallel/sp.py).
        mesh = jax.sharding.get_abstract_mesh()
        auto = [] if mesh.empty else [a for a in mesh.axis_names
                                      if a not in mesh.manual_axes]
        if auto:
            batch_axes = tuple(a for a in ("dp", "fsdp") if a in auto)
            spec = P(batch_axes or None, None,
                     "tp" if "tp" in auto else None, None)
            # EVERY remaining mesh axis goes manual (the Mosaic lowering
            # accepts nothing less); the ones the spec does not name see
            # q/k/v replicated and compute the same shard redundantly
            kernel = jax.shard_map(
                kernel, in_specs=(spec, spec, spec) + (P(*spec[:2], None),)
                * len(shared), out_specs=spec,
                axis_names=frozenset(auto), check_vma=False)
        return kernel(q, k, v, *shared)
    raise ValueError(f"unknown attention impl {impl!r}")
