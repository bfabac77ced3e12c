"""Attention ops: Pallas TPU flash attention + blockwise-JAX fallback.

The reference framework has no attention anywhere (SURVEY.md §5.7 — its
models are CNNs/wide-and-deep), but long-context support is first-class in
this build, so the hot op gets a real TPU kernel:

- ``flash_attention`` — public entry.  On TPU it runs a Pallas online-softmax
  kernel (forward) with a memory-efficient recompute backward; elsewhere it
  lowers to ``blockwise_attention`` (a ``lax.scan`` over KV blocks with
  per-block rematerialisation, so memory stays O(S·block) instead of O(S²)).
- ``chunk_attention`` / ``merge_attention`` — the (output, logsumexp)
  chunk-compute and online-softmax merge primitives that
  ``parallel/sp.py``'s ring attention composes over ICI neighbours.

Array convention: ``[batch, seq, heads, head_dim]`` (flax-style).  All
softmax accumulation is float32 regardless of input dtype (bf16 inputs keep
the MXU fed; the VPU-side accumulators must not lose mass).
"""

from __future__ import annotations

import functools
import math
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

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

def mha_reference(q, k, v, *, causal: bool = True, sm_scale: float | None = None,
                  kv_offset: int = 0):
    """Dense O(S²) attention.  ``kv_offset`` is the global position of
    ``k[:, 0]`` relative to ``q[:, 0]`` (ring attention passes non-zero
    offsets so causal masks stay globally consistent across chunks)."""
    *_, d = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        qpos = jnp.arange(sq)[:, None]
        kpos = kv_offset + jnp.arange(sk)[None, :]
        logits = jnp.where(kpos <= qpos, logits, NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# Chunk + merge primitives (shared with ring attention in parallel/sp.py).
# ---------------------------------------------------------------------------

def chunk_attention(q, k, v, *, causal: bool = True, sm_scale: float | None = None,
                    kv_offset=0):
    """Attend q over one KV chunk; return ``(out, lse)``.

    ``out`` is the softmax-normalised output **for this chunk alone** and
    ``lse`` its log-sum-exp (``[B, Sq, H]``, float32).  Two chunk results
    combine exactly via ``merge_attention`` — the online-softmax identity
    ring attention is built on.  ``kv_offset`` may be a traced scalar.
    """
    *_, d = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    sq, sk = q.shape[1], k.shape[1]
    if causal:
        qpos = jnp.arange(sq)[:, None]
        kpos = kv_offset + jnp.arange(sk)[None, :]
        logits = jnp.where(kpos <= qpos, logits, NEG_INF)
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
                        kv_offset: int = 0):
    """Flash-style attention as a ``lax.scan`` over KV blocks.

    Differentiable, runs on every backend, and with the per-block
    ``jax.checkpoint`` memory is O(Sq·block_k) — this is both the CPU test
    path and the recompute backward for the Pallas kernel.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    block_k = min(block_k, sk)
    nblocks = -(-sk // block_k)
    pad = nblocks * block_k - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(b, nblocks, block_k, h, d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nblocks, block_k, h, d).transpose(1, 0, 2, 3, 4)

    qpos = jnp.arange(sq)[:, None]

    @jax.checkpoint
    def block(carry, inputs):
        o_acc, m_acc, l_acc = carry
        kc, vc, start = inputs
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, kc).astype(jnp.float32) * scale
        kpos = kv_offset + start + jnp.arange(block_k)[None, :]
        mask = kpos < kv_offset + sk  # padded tail
        if causal:
            mask = mask & (kpos <= qpos)
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

    o0 = match_vma(jnp.zeros((b, sq, h, d), jnp.float32), q)
    m0 = match_vma(jnp.full((b, h, sq), NEG_INF, jnp.float32), q)
    l0 = match_vma(jnp.zeros((b, h, sq), jnp.float32), q)
    starts = jnp.arange(nblocks) * block_k
    (o, m, l), _ = jax.lax.scan(block, (o0, m0, l0), (kb, vb, starts))
    o = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas TPU kernel (forward) — online softmax over a sequential k-block grid.
# ---------------------------------------------------------------------------

def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      acc_ref, m_ref, l_ref,
                      *, sm_scale: float, causal: bool, kv_offset: int,
                      block_q: int, block_k: int, sq: int, sk: int):
    # m/l scratch and the lse output are lane-replicated to 128 lanes (column
    # 0 is authoritative) — TPU tiling requires the last dim be 128-aligned.
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start = iq * block_q
    k_start = kv_offset + ik * block_k
    # Skip blocks that are entirely in the causal future or entirely padding.
    live = (k_start + 0) < kv_offset + sk
    if causal:
        live = jnp.logical_and(live, k_start <= q_start + block_q - 1)

    @pl.when(live)
    def _attend():
        qb = q_ref[0].astype(jnp.float32)              # [block_q, d]
        kb = k_ref[0].astype(jnp.float32)              # [block_k, d]
        logits = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        mask = kpos < kv_offset + sk
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        logits = jnp.where(mask, logits, NEG_INF)
        m_prev = m_ref[:]                               # [block_q, 128]
        m_blk = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        m_safe = jnp.maximum(m_new, NEG_INF / 2)
        p = jnp.exp(logits - m_safe[:, 0:1])
        p = jnp.where(logits <= NEG_INF / 2, 0.0, p)
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - m_safe))
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha[:, 0:1] + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = (acc_ref[:] / l[:, 0:1]).astype(o_ref.dtype)
        lse = m_ref[:] + jnp.log(l)
        lse_ref[0] = jnp.where(l_ref[:] > 0.0, lse, NEG_INF)


def _flash_fwd_pallas(q, k, v, *, causal, sm_scale, kv_offset,
                      block_q, block_k, interpret):
    """Run the Pallas forward; returns (out, lse).  Head-major internally."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)

    # Head-major [B*H, S, D]; pad S to block multiples and D to the 128 lane.
    def to_bh(x, s):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    qt, kt, vt = to_bh(q, sq), to_bh(k, sk), to_bh(v, sk)
    block_q = min(block_q, max(8, sq))
    block_k = min(block_k, max(8, sk))
    sq_p = -(-sq // block_q) * block_q
    sk_p = -(-sk // block_k) * block_k
    d_p = max(128, -(-d // 128) * 128) if not interpret else d
    qt = jnp.pad(qt, ((0, 0), (0, sq_p - sq), (0, d_p - d)))
    kt = jnp.pad(kt, ((0, 0), (0, sk_p - sk), (0, d_p - d)))
    vt = jnp.pad(vt, ((0, 0), (0, sk_p - sk), (0, d_p - d)))

    grid = (b * h, sq_p // block_q, sk_p // block_k)
    kernel = functools.partial(
        _flash_fwd_kernel, sm_scale=scale, causal=causal, kv_offset=kv_offset,
        block_q=block_q, block_k=block_k, sq=sq, sk=sk)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d_p), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, d_p), lambda bh, iq, ik: (bh, ik, 0)),
            pl.BlockSpec((1, block_k, d_p), lambda bh, iq, ik: (bh, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d_p), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_q, 128), lambda bh, iq, ik: (bh, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq_p, d_p), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq_p, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d_p), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    out = out[:, :sq, :d].reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    lse = lse[:, :sq, 0].reshape(b, h, sq).transpose(0, 2, 1)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_attention_tpu(q, k, v, causal, sm_scale, kv_offset,
                         block_q, block_k, interpret):
    with jax.named_scope("flash_fwd"):
        out, _ = _flash_fwd_pallas(q, k, v, causal=causal, sm_scale=sm_scale,
                                   kv_offset=kv_offset, block_q=block_q,
                                   block_k=block_k, interpret=interpret)
    return out


def _flash_fwd_rule(q, k, v, causal, sm_scale, kv_offset, block_q, block_k,
                    interpret):
    out = _flash_attention_tpu(q, k, v, causal, sm_scale, kv_offset,
                               block_q, block_k, interpret)
    return out, (q, k, v)


def _flash_bwd_rule(causal, sm_scale, kv_offset, block_q, block_k, interpret,
                    res, g):
    # Memory-efficient recompute backward: VJP through the blockwise scan
    # (each block is checkpointed, so peak memory stays O(S·block_k)).
    q, k, v = res
    with jax.named_scope("flash_bwd"):
        _, vjp = jax.vjp(
            lambda q_, k_, v_: blockwise_attention(
                q_, k_, v_, causal=causal, sm_scale=sm_scale,
                block_k=block_k, kv_offset=kv_offset),
            q, k, v)
        return vjp(g)


_flash_attention_tpu.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# ---------------------------------------------------------------------------
# Public entry.
# ---------------------------------------------------------------------------

Impl = Literal["pallas", "pallas_interpret", "xla", "reference"]


def flash_attention(q, k, v, *, causal: bool = True,
                    sm_scale: float | None = None, kv_offset: int = 0,
                    block_q: int = 512, block_k: int = 512,
                    impl: Impl | None = None):
    """Multi-head attention, ``[B, S, H, D]`` in and out.

    ``impl=None`` auto-selects: Pallas kernel on TPU, blockwise XLA scan
    elsewhere.  ``pallas_interpret`` runs the kernel in interpreter mode (CPU
    tests of the kernel itself).
    """
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "reference":
        return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale,
                             kv_offset=kv_offset)
    if impl == "xla":
        return blockwise_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                                   block_k=block_k, kv_offset=kv_offset)
    if impl in ("pallas", "pallas_interpret"):
        def kernel(q, k, v):
            return _flash_attention_tpu(q, k, v, causal, sm_scale, kv_offset,
                                        block_q, block_k,
                                        impl == "pallas_interpret")

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
                kernel, in_specs=(spec, spec, spec), out_specs=spec,
                axis_names=frozenset(auto), check_vma=False)
        return kernel(q, k, v)
    raise ValueError(f"unknown attention impl {impl!r}")
