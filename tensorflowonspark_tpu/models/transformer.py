"""Decoder-only transformer LM — the long-context / parallelism flagship.

The reference's model zoo stops at CNNs and wide-and-deep (its ``examples/``
tree; SURVEY.md §5.7 records that sequence length is never a sharded axis
there).  This family exists because long-context and model parallelism are
first-class in the TPU build:

- attention runs the Pallas flash kernel (``ops/attention.py``) on TPU, or
  ring/Ulysses sequence parallelism (``parallel/sp.py``) when a mesh with an
  ``sp`` axis is supplied;
- param layouts follow ``parallel/tp.TRANSFORMER_TP_RULES`` (Megatron
  column/row parallel over ``tp``, optionally composed with fsdp);
- the FFN can be a dense SwiGLU or an expert-parallel MoE
  (``parallel/ep.MoEMLP``) over ``ep``.

Pre-norm RMSNorm + RoPE, bf16 compute / f32 params — the standard
MXU-friendly recipe.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tensorflowonspark_tpu.models.registry import register
from tensorflowonspark_tpu.ops.attention import flash_attention
from tensorflowonspark_tpu.parallel.tp import constrain

BATCH = ("dp", "fsdp")


def apply_rope(x, positions, theta: float = 10000.0):
    """Rotary embedding, ``x: [B, S, H, D]``, ``positions: [S]``."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[:, None].astype(jnp.float32) * freqs[None, :]  # [S, half]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


class RMSNorm(nn.Module):
    epsilon: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        norm = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.epsilon)
        return (norm * scale).astype(x.dtype)


class Attention(nn.Module):
    n_heads: int
    d_head: int
    rope_theta: float = 10000.0
    attn_impl: str = "auto"       # auto | pallas | pallas_interpret | xla | ring
    mesh: Optional[Any] = None    # required for ring
    compute_dtype: Any = jnp.bfloat16
    decode: bool = False          # autoregressive single-token mode (KV cache)
    max_decode_len: int = 0
    # QK-norm (OLMoE, arXiv:2409.02060 §4.2): an RMSNorm with a learned
    # scale over the WHOLE n_heads·d_head projection of q and of k, before
    # the split into heads matters and before RoPE.
    qk_norm: bool = False
    norm_eps: float = 1e-6
    # Grouped-query heads: K and V are projected to ``n_kv_heads`` heads
    # (0: as many as the queries) and query head j reads head j // group.
    n_kv_heads: int = 0
    # Where ``qk_norm`` sits: over each head's ``d_head`` with ONE learned
    # scale of that width for all heads (Qwen3's placement) instead of over
    # the whole projection (OLMoE's).
    qk_norm_per_head: bool = False
    # Learned sparse attention (DeepSeek Sparse Attention, as Keye-VL-2.0
    # trains it): ``(index heads, index head dim, topk)``.  An indexer scores
    # the causal pairs and every query attends to its ``topk`` best keys
    # only (``_sparse_attention``, ``ops/sparse_attention.py``).
    sparse: Optional[tuple] = None
    # Latent attention (MLA, DeepSeek-V2, arXiv:2405.04434, without a query
    # latent): ``(kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
    # v_head_dim)``.  Keys and values are up-projections of one normed latent
    # a token, RoPE turns ``qk_rope_head_dim`` columns of a query and ONE key
    # head of that width that all query heads share (``_latent_attention``);
    # ``d_head``, ``n_kv_heads`` and QK-norm say nothing here.
    latent: Optional[tuple] = None
    # False: no rotation and no position input at all (``nemotron_h``'s
    # attention layers: the state-space layers around them carry the order).
    rope: bool = True

    @nn.compact
    def __call__(self, x, positions=None, block_diffusion=None):
        """``positions`` ``[S]`` are the tokens' RoPE positions (None: their
        places in the sequence); ``block_diffusion=(length, block)`` puts
        that mask in place of the causal one (``ops/attention.py``).  The
        caller hands in both: what a sequence holds is the loss's business
        (``make_block_diffusion_loss_fn``)."""
        if not self.rope and (self.latent or self.sparse or self.decode):
            raise NotImplementedError(
                "rope=False is the plain training path's: latent attention, "
                "the indexer and the cache path all turn their keys")
        if self.latent:
            return self._latent_attention(x, positions, block_diffusion)
        b, s, _ = x.shape
        h, dh = self.n_heads, self.d_head
        h_kv = self.n_kv_heads or h
        dense = lambda name, heads: nn.DenseGeneral(  # noqa: E731
            (heads, dh), axis=-1, use_bias=False, name=name,
            dtype=self.compute_dtype)
        q, k, v = (dense("q_proj", h)(x), dense("k_proj", h_kv)(x),
                   dense("v_proj", h_kv)(x))
        if self.qk_norm:
            # before the branch: the cache path computes the same model
            with jax.named_scope("qk_norm"):
                if self.qk_norm_per_head:
                    norm = lambda t, name: RMSNorm(  # noqa: E731
                        self.norm_eps, name=name)(t)
                else:
                    norm = lambda t, name: RMSNorm(  # noqa: E731
                        self.norm_eps, name=name)(
                            t.reshape(b, s, -1)).reshape(t.shape)
                q, k = norm(q, "q_norm"), norm(k, "k_norm")
        if self.decode:
            if (h_kv != h or positions is not None or block_diffusion
                    or self.sparse):
                raise NotImplementedError(
                    "the cache path holds one K/V head per query head, at "
                    "the tokens' own places, under the causal mask")
            return self._decode_step(x, q, k, v)
        if self.sparse:
            if block_diffusion or self.attn_impl == "ring":
                raise NotImplementedError(
                    "learned sparse attention selects among the causal keys "
                    "of one whole sequence on one chip")
            return self._sparse_attention(x, q, k, v, positions)
        if self.attn_impl == "ring" and (
                self.mesh is None or h_kv != h or block_diffusion):
            raise ValueError("ring attention needs mesh=, as many K/V heads "
                             "as query heads and the causal mask")
        # named scope: rope, layout and the kernel (both halves of its
        # VJP) carry "attention" in their op names, whatever XLA fuses
        with jax.named_scope("attention"):
            if self.rope:
                if positions is None:
                    positions = jnp.arange(s)
                q = apply_rope(q, positions, self.rope_theta)
                k = apply_rope(k, positions, self.rope_theta)
            q = constrain(q, P(BATCH, "sp", "tp", None))
            k = constrain(k, P(BATCH, "sp", "tp", None))
            v = constrain(v, P(BATCH, "sp", "tp", None))
            if self.attn_impl == "ring":
                from tensorflowonspark_tpu.parallel.sp import (
                    sequence_parallel_attention,
                )
                out = sequence_parallel_attention(
                    self.mesh, q, k, v, causal=True)
            else:
                impl = None if self.attn_impl == "auto" else self.attn_impl
                out = flash_attention(q, k, v, causal=not block_diffusion,
                                      impl=impl,
                                      block_diffusion=block_diffusion)
        out = nn.DenseGeneral(x.shape[-1], axis=(-2, -1), use_bias=False,
                              name="o_proj", dtype=self.compute_dtype)(out)
        return out

    def _latent_attention(self, x, positions, block_diffusion):
        """Latent attention on the training path.  From the layer's normed
        hidden state ``u``: ``q = u W_q`` = per head ``[q_nope | q_rope]``;
        ``[c | k_r] = u W_kva``, ``c`` RMS-normed (``kv_a_norm``), ``k_r`` ONE
        rotary key head for all query heads; ``[k_nope | v] = c W_kvb`` per
        head.  RoPE turns ``q_rope`` and ``k_r`` only (this file's half-split
        pairing: against the published interleaved one a fixed permutation
        of the rotary columns of ``W_q`` and ``W_kva``, which no score
        sees).  ``score = (q_nope · k_nope + q_rope · k_r) / sqrt(nope +
        rope)``: the kernels take ``k_r`` as their shared key, so no copy of
        it a head exists, forward or backward.  Autodiff keeps ``k_nope``
        and ``v`` whole for the backward (the kernels' residuals)."""
        if self.decode:
            raise NotImplementedError(
                "latent attention (Attention.latent) has no cache path: "
                "decode=True would have to cache the latent and the rotary "
                "key, which Attention._decode_step does not")
        if self.attn_impl == "ring" or self.sparse or self.qk_norm:
            raise NotImplementedError(
                "latent attention runs one whole sequence through "
                "flash_attention, without QK-norm and without an indexer")
        rank, nope, rope, dv = self.latent
        h, s = self.n_heads, x.shape[1]
        cdt = self.compute_dtype
        with jax.named_scope("mla/project"):
            q = nn.DenseGeneral((h, nope + rope), use_bias=False,
                                name="q_proj", dtype=cdt)(x)
            kv_a = nn.Dense(rank + rope, use_bias=False, name="kv_a_proj",
                            dtype=cdt)(x)
            c = RMSNorm(self.norm_eps, name="kv_a_norm")(kv_a[..., :rank])
            kv = nn.DenseGeneral((h, nope + dv), use_bias=False,
                                 name="kv_b_proj", dtype=cdt)(c)
            if positions is None:
                positions = jnp.arange(s)
            q = jnp.concatenate(
                [q[..., :nope],
                 apply_rope(q[..., nope:], positions, self.rope_theta)], -1)
            k_r = apply_rope(kv_a[:, :, None, rank:], positions,
                             self.rope_theta)[:, :, 0]
            q = constrain(q, P(BATCH, "sp", "tp", None))
            k = constrain(kv[..., :nope], P(BATCH, "sp", "tp", None))
            v = constrain(kv[..., nope:], P(BATCH, "sp", "tp", None))
        with jax.named_scope("attention"):
            out = flash_attention(
                q, k, v, k_shared=k_r, causal=not block_diffusion,
                impl=None if self.attn_impl == "auto" else self.attn_impl,
                block_diffusion=block_diffusion)
        with jax.named_scope("mla/project"):
            return nn.DenseGeneral(x.shape[-1], axis=(-2, -1), use_bias=False,
                                   name="o_proj", dtype=cdt)(out)

    def _decode_step(self, x, q, k, v):
        """``s`` tokens through a static-size KV cache (``cache`` collection).

        Handles BOTH serving phases with one code path and static shapes
        (the cache is ``[B, max_decode_len, H, D]``; masking does the rest):

        - **prefill** (``s == prompt_len``): the whole prompt runs in ONE
          forward, writing cache slots ``[cur, cur+s)`` — queries attend
          causally within the slab and to everything before it;
        - **decode** (``s == 1``): the classic single-token step.

        So a serving loop issues O(1) compiled calls for the prompt (one
        prefill shape + one decode shape) instead of O(prompt_len) — the
        standard prefill/decode split of TPU serving stacks.
        """
        if self.max_decode_len <= 0:
            raise ValueError("decode mode needs max_decode_len > 0")
        b, s, h, dh = q.shape
        L = self.max_decode_len
        ck = self.variable("cache", "k", jnp.zeros, (b, L, h, dh),
                           self.compute_dtype)
        cv = self.variable("cache", "v", jnp.zeros, (b, L, h, dh),
                           self.compute_dtype)
        idx = self.variable("cache", "index",
                            lambda: jnp.zeros((), jnp.int32))
        cur = idx.value
        pos = cur + jnp.arange(s)  # RoPE positions of this slab
        q = apply_rope(q, pos, self.rope_theta)
        k = apply_rope(k, pos, self.rope_theta)
        ck.value = jax.lax.dynamic_update_slice(ck.value, k, (0, cur, 0, 0))
        cv.value = jax.lax.dynamic_update_slice(cv.value, v, (0, cur, 0, 0))
        idx.value = cur + s
        logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                            ck.value.astype(jnp.float32))
        logits = logits / math.sqrt(dh)
        # query at slab offset i sees cache positions <= cur + i
        mask = (jnp.arange(L)[None, None, None, :]
                <= cur + jnp.arange(s)[None, None, :, None])
        logits = jnp.where(mask, logits, -1e30)
        weights = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", weights,
                         cv.value.astype(jnp.float32))
        out = out.astype(self.compute_dtype)
        return nn.DenseGeneral(x.shape[-1], axis=(-2, -1), use_bias=False,
                               name="o_proj", dtype=self.compute_dtype)(out)

    def _sparse_attention(self, x, q, k, v, positions):
        """Attention over the keys the indexer selects.  From the layer's
        normed hidden state ``x``, with the gradient STOPPED (cross-entropy
        never reaches the indexer): ``J`` index queries ``a`` and one index
        key ``b`` a token (LayerNorm, then RoPE over all its dims, as the
        queries), and the heads' weights ``c``, scaled by ``J^-1/2 ·
        dim^-1/2``.  ``I[t, s] = Σ_j c[t, j] · ReLU(a[t, j] · b[s])``; every
        query keeps its ``topk`` best causal keys, a constant of the step,
        and all heads attend to those.  The indexer's own loss, ``Σ_t KL(p_t
        ‖ softmax over the kept keys of I[t])`` over this layer's tokens with
        ``p`` the heads' mean attention probabilities (a constant), is sown
        as ``aux_loss/index_kl`` (mean over tokens): it reaches ``index_q``,
        ``index_k``, ``index_k_norm`` and ``index_w`` and nothing else
        (``make_sparse_loss_fn``).  Operands of the score and attention
        matmuls are in ``compute_dtype``; accumulation, ReLU, the weighted
        sum, the threshold and the softmaxes are float32."""
        from tensorflowonspark_tpu.ops import sparse_attention as dsa

        heads, dim, topk = self.sparse
        impl = None if self.attn_impl == "auto" else self.attn_impl
        b, s, _ = x.shape
        if positions is None:
            positions = jnp.arange(s)
        f32 = jnp.float32
        with jax.named_scope("dsa/index"):
            u = jax.lax.stop_gradient(x)
            a = nn.DenseGeneral((heads, dim), use_bias=False, name="index_q",
                                dtype=self.compute_dtype)(u)
            key = nn.Dense(dim, use_bias=False, name="index_k",
                           dtype=self.compute_dtype)(u)
            key = nn.LayerNorm(epsilon=self.norm_eps, name="index_k_norm",
                               dtype=f32)(key.astype(f32))
            c = nn.Dense(heads, use_bias=False, name="index_w", dtype=f32)(
                u.astype(f32)) * (heads * dim) ** -0.5
            a = apply_rope(a.astype(f32), positions, self.rope_theta).astype(
                self.compute_dtype)
            key = apply_rope(key[:, :, None], positions, self.rope_theta)[
                :, :, 0].astype(self.compute_dtype)
        with jax.named_scope("dsa/attend"):
            q = apply_rope(q, positions, self.rope_theta)
            k = apply_rope(k, positions, self.rope_theta)

        def row(a, key, c, q, k, v):
            mask, lse_i = dsa.lightning_select(a, key, c, topk, impl=impl)
            out, lse = dsa.sparse_attention(q, k, v, mask, impl=impl)
            kl = dsa.index_kl(a, key, c, q, k, lse, lse_i, mask, impl=impl)
            return (out, kl, dsa.selection_stats(mask),
                    mask if shown else None)

        # the selection itself is shown only to a caller that asks for the
        # intermediates (the benchmark's check): L x L bytes a layer
        shown = self.is_mutable_collection("intermediates")
        out, kl, (selected, live), mask = dsa.per_row(row, a, key, c, q, k, v)
        if shown:
            self.sow("intermediates", "dsa_mask", mask)
        self.sow("aux_loss", "index_kl", jnp.sum(kl) / (b * s))
        self.sow("dsa_stats", "selected_pairs", jnp.sum(selected) / b)
        self.sow("dsa_stats", "live_tiles", jnp.mean(live))
        return nn.DenseGeneral(x.shape[-1], axis=(-2, -1), use_bias=False,
                               name="o_proj", dtype=self.compute_dtype)(out)


class SwiGLU(nn.Module):
    d_ff: int
    compute_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, name=name, dtype=self.compute_dtype)
        with jax.named_scope("mlp"):
            gate = jax.nn.silu(dense(self.d_ff, "gate_proj")(x))
            up = dense(self.d_ff, "up_proj")(x)
            h = constrain(gate * up, P(BATCH, "sp", "tp"))
            return dense(x.shape[-1], "down_proj")(h)


class Relu2MLP(nn.Module):
    """``relu(x·U)²·V``: two matrices and no gate (``mlp_hidden_act``
    ``relu2``)."""

    d_ff: int
    compute_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, name=name, dtype=self.compute_dtype)
        with jax.named_scope("mlp"):
            h = jnp.square(jax.nn.relu(dense(self.d_ff, "up_proj")(x)))
            h = constrain(h, P(BATCH, "sp", "tp"))
            return dense(x.shape[-1], "down_proj")(h)


class Mamba2(nn.Module):
    """Mamba-2's mixer (Dao & Gu, arXiv:2405.21060; transformers'
    ``nemotron_h``), ``[B, L, D] -> [B, L, D]``, ``L`` a multiple of
    ``chunk``.  With ``H`` heads of ``P`` channels, ``G`` groups and a state
    of ``N`` columns: ``[z | xBC | dt] = u·W_in`` (``H·P`` | ``H·P + 2·G·N``
    | ``H``); ``xBC <- silu(conv1d(xBC) + b)``, depthwise, causal, ``conv``
    taps; ``x`` in heads of ``P``, ``B`` and ``C`` in groups of ``N`` (head
    ``j`` reads group ``j // (H / G)``); ``Δ = softplus(dt + dt_bias)``, ``a =
    -exp(A_log)``; the recurrence of ``ops/ssd.py``; the gated norm ``y <-
    RMSNorm_group(y · silu(z))`` over groups of ``H·P / G`` channels with one
    weight a channel; ``y·W_out``.  No bias but the conv's.

    ``Δ``, the decay, its running sums, the carried state and the gated norm
    are float32; the projections, the conv's operands and the scan's
    products ``compute_dtype``.  Seeded as Mamba-2 is: ``A`` uniform in [1,
    16], ``D`` = 1, ``dt_bias`` the inverse softplus of a log-uniform draw
    from ``dt_range`` = (min, max, floor)."""

    n_heads: int
    head_dim: int
    n_groups: int
    state_size: int
    conv: int = 4
    chunk: int = 128
    dt_range: tuple = (0.001, 0.1, 1e-4)
    norm_eps: float = 1e-5
    compute_dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32      # ``ops/ssd.py``: a check's control

    @nn.compact
    def __call__(self, u):
        from tensorflowonspark_tpu.ops.ssd import causal_conv1d, ssd_scan

        b, length, d = u.shape
        h, p, g, n = (self.n_heads, self.head_dim, self.n_groups,
                      self.state_size)
        inner, f32 = h * p, jnp.float32

        def dt_bias_init(key, shape):
            lo, hi, floor = self.dt_range
            dt = jnp.exp(jax.random.uniform(key, shape)
                         * (math.log(hi) - math.log(lo)) + math.log(lo))
            dt = jnp.maximum(dt, floor)
            return dt + jnp.log(-jnp.expm1(-dt))        # softplus^-1

        with jax.named_scope("ssm"):
            with jax.named_scope("ssm/in_proj"):
                zxbcdt = nn.Dense(2 * inner + 2 * g * n + h, use_bias=False,
                                  name="in_proj", dtype=self.compute_dtype)(u)
                z, xbc, dt = jnp.split(
                    zxbcdt, [inner, 2 * inner + 2 * g * n], axis=-1)
            with jax.named_scope("ssm/conv"):
                kernel = self.param(
                    "conv_kernel", lambda key, shape: jax.random.uniform(
                        key, shape, minval=-1.0, maxval=1.0)
                    / math.sqrt(self.conv), (self.conv, inner + 2 * g * n))
                bias = self.param("conv_bias", nn.initializers.zeros,
                                  (inner + 2 * g * n,))
                xbc = jax.nn.silu(causal_conv1d(xbc, kernel, bias))
                x, bm, cm = jnp.split(xbc, [inner, inner + g * n], axis=-1)
            with jax.named_scope("ssm/scan"):
                a_log = self.param(
                    "A_log", lambda key, shape: jnp.log(jax.random.uniform(
                        key, shape, minval=1.0, maxval=16.0)), (h,))
                skip = self.param("D", nn.initializers.ones, (h,))
                dt_bias = self.param("dt_bias", dt_bias_init, (h,))
                y = ssd_scan(
                    x.reshape(b, length, h, p),
                    jax.nn.softplus(dt.astype(f32) + dt_bias),
                    -jnp.exp(a_log.astype(f32)),
                    bm.reshape(b, length, g, n), cm.reshape(b, length, g, n),
                    skip, chunk=self.chunk, state_dtype=self.state_dtype)
            with jax.named_scope("ssm/gate_norm"):
                scale = self.param("norm_scale", nn.initializers.ones,
                                   (inner,))
                y = (y.reshape(b, length, inner).astype(f32)
                     * jax.nn.silu(z.astype(f32))).reshape(b, length, g, -1)
                y = y * jax.lax.rsqrt(
                    jnp.mean(y * y, axis=-1, keepdims=True) + self.norm_eps)
                y = (y.reshape(b, length, inner) * scale).astype(
                    self.compute_dtype)
            with jax.named_scope("ssm/out_proj"):
                return nn.Dense(d, use_bias=False, name="out_proj",
                                dtype=self.compute_dtype)(y)


class MixerBlock(nn.Module):
    """A layer of ONE pre-norm, ONE mixer and ONE residual add, ``h <- h +
    mixer(norm(h))``, the mixer by ``kind`` (``nemotron_h``'s
    ``hybrid_override_pattern``): ``"M"`` a ``Mamba2`` (``ssm``), ``"*"``
    grouped-query ``Attention`` (``attn``), ``"E"`` the experts of
    ``parallel/ep.MoEMLP`` (``moe``) beside the shared expert that every
    token passes (``shared``, in the experts' own form, under ``moe/shared``:
    every chip of an expert-parallel stage computes it whole, so under
    ``moe_held`` it is in the layer's output once).  The fields are
    ``Transformer``'s."""

    kind: str
    n_heads: int
    d_head: int
    d_ff: int
    n_kv_heads: int = 0
    rope: bool = True
    rope_theta: float = 10000.0
    attn_impl: str = "auto"
    mesh: Optional[Any] = None
    compute_dtype: Any = jnp.bfloat16
    norm_eps: float = 1e-6
    ssm: Optional[tuple] = None
    ssm_state_dtype: Any = jnp.float32
    n_experts: int = 0
    moe_top_k: int = 2
    moe_norm_topk_prob: bool = True
    moe_held: Optional[tuple] = None
    moe_router: Optional[tuple] = None
    moe_shared_d_ff: int = 0
    moe_expert_act: str = "swiglu"
    moe_latent: int = 0

    @nn.compact
    def __call__(self, x, positions=None, block_diffusion=None):
        u = RMSNorm(self.norm_eps, name="norm")(x)
        if self.kind == "*":
            y = Attention(self.n_heads, self.d_head, self.rope_theta,
                          self.attn_impl, self.mesh, self.compute_dtype,
                          norm_eps=self.norm_eps, n_kv_heads=self.n_kv_heads,
                          rope=self.rope, name="attn")(
                              u, positions, block_diffusion)
        elif block_diffusion:
            raise NotImplementedError(
                "a block-diffusion mask is attention's: a state-space or an "
                "expert layer of a per-layer-mixer model takes none")
        elif self.kind == "M":
            heads, head_dim, groups, state, conv, chunk, *dt_range = self.ssm
            y = Mamba2(heads, head_dim, groups, state, conv, chunk,
                       tuple(dt_range), self.norm_eps, self.compute_dtype,
                       self.ssm_state_dtype, name="ssm")(u)
        elif self.kind == "E":
            from tensorflowonspark_tpu.parallel.ep import MoEMLP

            scoring, bias, scale = self.moe_router or ("softmax", False, 1.0)
            y = MoEMLP(x.shape[-1], self.d_ff, self.n_experts, self.moe_top_k,
                       None, compute_dtype=self.compute_dtype,
                       norm_topk_prob=self.moe_norm_topk_prob,
                       held=self.moe_held, scoring=scoring,
                       selection_bias=bias, routed_scale=scale,
                       expert_act=self.moe_expert_act, latent=self.moe_latent,
                       name="moe")(u)
            if self.moe_shared_d_ff:
                shared = (SwiGLU if self.moe_expert_act == "swiglu"
                          else Relu2MLP)
                with jax.named_scope("moe/shared"):
                    y = y + shared(self.moe_shared_d_ff, self.compute_dtype,
                                   name="shared")(u)
        else:
            raise ValueError(f"layer kind {self.kind!r}: M, * or E")
        return constrain(x + y, P(BATCH, "sp", None))


class Block(nn.Module):
    n_heads: int
    d_head: int
    d_ff: int
    n_experts: int = 0
    moe_top_k: int = 2
    rope_theta: float = 10000.0
    attn_impl: str = "auto"
    mesh: Optional[Any] = None
    compute_dtype: Any = jnp.bfloat16
    decode: bool = False
    max_decode_len: int = 0
    norm_eps: float = 1e-6
    qk_norm: bool = False
    moe_capacity_factor: Optional[float] = 1.25   # None: dropless routing
    moe_norm_topk_prob: bool = True
    n_kv_heads: int = 0
    qk_norm_per_head: bool = False
    moe_held: Optional[tuple] = None
    sparse: Optional[tuple] = None
    latent: Optional[tuple] = None
    moe_router: Optional[tuple] = None      # see Transformer
    moe_shared_d_ff: int = 0

    @nn.compact
    def __call__(self, x, positions=None, block_diffusion=None):
        norm = lambda name: RMSNorm(self.norm_eps, name=name)  # noqa: E731
        x = x + Attention(self.n_heads, self.d_head, self.rope_theta,
                          self.attn_impl, self.mesh, self.compute_dtype,
                          self.decode, self.max_decode_len, self.qk_norm,
                          self.norm_eps, self.n_kv_heads,
                          self.qk_norm_per_head, self.sparse, self.latent,
                          name="attn")(
                              norm("attn_norm")(x), positions,
                              block_diffusion)
        x = constrain(x, P(BATCH, "sp", None))
        if self.n_experts:
            from tensorflowonspark_tpu.parallel.ep import MoEMLP

            scoring, bias, scale = self.moe_router or ("softmax", False, 1.0)
            ffn = MoEMLP(x.shape[-1], self.d_ff, self.n_experts,
                         self.moe_top_k, self.moe_capacity_factor,
                         compute_dtype=self.compute_dtype,
                         norm_topk_prob=self.moe_norm_topk_prob,
                         held=self.moe_held, scoring=scoring,
                         selection_bias=bias, routed_scale=scale, name="moe")
        else:
            ffn = SwiGLU(self.d_ff, self.compute_dtype, name="mlp")
        y = norm("mlp_norm")(x)
        x = x + ffn(y)
        if self.n_experts and self.moe_shared_d_ff:
            # the shared experts, as ONE SwiGLU of their summed width that
            # every token passes beside its routed experts: every chip of an
            # expert-parallel stage computes it whole, so under ``moe_held``
            # it is in the layer's output once, as it is without
            with jax.named_scope("moe/shared"):
                x = x + SwiGLU(self.moe_shared_d_ff, self.compute_dtype,
                               name="shared")(y)
        return constrain(x, P(BATCH, "sp", None))


class Transformer(nn.Module):
    """Decoder-only LM.  ``__call__(input_ids: [B, S]) -> logits [B, S, V]``."""

    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    d_head: int = 0          # 0 ⇒ d_model // n_heads
    d_ff: int = 0            # 0 ⇒ 4 * d_model
    n_experts: int = 0       # 0 ⇒ dense FFN
    moe_top_k: int = 2
    rope_theta: float = 10000.0
    attn_impl: str = "auto"
    mesh: Optional[Any] = None
    compute_dtype: Any = jnp.bfloat16
    decode: bool = False
    max_decode_len: int = 0
    # Return final_norm hidden states instead of logits (the lm_head matmul
    # is then fused into a blockwise loss — see ops/xent.py).  Init with the
    # default model so lm_head params exist; apply may skip them.
    return_hidden: bool = False
    # Rematerialize each block's activations in the backward pass
    # (jax.checkpoint): activation memory drops from O(n_layers) residuals
    # to O(1) per block at ~1/3 extra FLOPs — the standard long-context /
    # large-batch trade on HBM-bound TPUs.
    remat: bool = False
    # What a published config states beyond the sizes; the defaults are the
    # model this class built before it had the keys.
    norm_eps: float = 1e-6   # every RMSNorm
    qk_norm: bool = False    # see Attention
    # MoE routing rule: a capacity factor (Switch / GShard: overflow is
    # dropped) or None for dropless routing; whether the top-k weights are
    # renormalised to sum to 1 (HF ``norm_topk_prob``).
    moe_capacity_factor: Optional[float] = 1.25
    moe_norm_topk_prob: bool = True
    # Grouped-query heads and the placement of QK-norm (see Attention); the
    # range of each layer's ``n_experts`` experts that this chip holds (see
    # ``parallel/ep.py``; None: all of them).
    n_kv_heads: int = 0
    qk_norm_per_head: bool = False
    moe_held: Optional[tuple] = None
    # Learned sparse attention in every layer (see ``Attention.sparse``).
    sparse: Optional[tuple] = None
    # Latent attention in every layer (see ``Attention.latent``).
    latent: Optional[tuple] = None
    # The router of the expert layers beyond softmax -> top-k: ``(scoring,
    # selection_bias, routed_scale)`` and the width of the shared SwiGLU that
    # every token also passes (0: none); see ``parallel/ep.MoEMLP``.
    moe_router: Optional[tuple] = None
    moe_shared_d_ff: int = 0
    # Layers that differ inside one model: one entry a layer, the width of a
    # DENSE SwiGLU that the layer has in place of the model's own FFN, or 0
    # for that (the experts where ``n_experts``, else ``d_ff``).  None: every
    # layer the model's own.  DeepSeek-V3's ``first_k_dense_replace`` 1 over
    # 5 layers is ``(6144, 0, 0, 0, 0)``.
    layer_ffn: Optional[tuple] = None
    # A MIXER a layer (``nemotron_h``'s ``hybrid_override_pattern``): one
    # entry a layer, ``"M"`` a Mamba-2 mixer (``ssm`` = (heads, head dim,
    # groups, state size, conv taps, chunk, dt min, max, floor)), ``"*"``
    # attention, ``"E"`` the experts; such a layer is a ``MixerBlock`` (one
    # norm, one mixer, one add), not a ``Block`` (attention THEN an FFN).
    # None: every layer a ``Block``, and nothing below says anything.
    # ``rope`` False: attention turns nothing; ``moe_expert_act`` / ``moe_
    # latent``: the experts' form and the latent they live in
    # (``parallel/ep.MoEMLP``), the shared expert in the same form.  Dropless
    # routing, training path (no cache for the recurrent and conv state).
    layer_mixer: Optional[tuple] = None
    ssm: Optional[tuple] = None
    ssm_state_dtype: Any = jnp.float32      # ``ops/ssd.py``: a check's control
    rope: bool = True
    moe_expert_act: str = "swiglu"
    moe_latent: int = 0

    @nn.compact
    def __call__(self, input_ids, positions=None, block_diffusion=None):
        """``positions`` and ``block_diffusion`` go to every layer's
        attention as they are (see ``Attention``).

        Under ``remat`` a block is ``nn.remat(Block, static_argnums=(3,))``:
        of a block's arguments (the module is 0) ``x`` and ``positions`` are
        traced arrays and ``block_diffusion`` (3) is STATIC, a tuple of two
        sizes from which the flash kernels build their visit tables in numpy
        at trace time; traced, it could build none.  A mask that depends on
        the data (``sparse``: the indexer's selection) is no argument at
        all: it is a traced array born inside the block from ``x`` and the
        indexer's weights.  A recomputation would make it again, and the same
        (the selection draws nothing and breaks its ties by position); the
        block's policy saves it instead, with what else the sparse kernels
        give (``_remat_policy``)."""
        dh = self.d_head or self.d_model // self.n_heads
        dff = self.d_ff or 4 * self.d_model
        emb = nn.Embed(self.vocab_size, self.d_model, name="embed",
                       dtype=self.compute_dtype)
        x = emb(input_ids)
        x = constrain(x, P(BATCH, "sp", None))
        if self.layer_mixer:
            if len(self.layer_mixer) != self.n_layers or (
                    self.decode or self.sparse or self.latent
                    or self.layer_ffn or self.qk_norm
                    or self.moe_capacity_factor is not None):
                raise NotImplementedError(
                    f"layer_mixer={self.layer_mixer} over {self.n_layers} "
                    "layers: a mixer a layer is plain grouped-query "
                    "attention, Mamba-2 and dropless experts on the training "
                    "path (no cache for recurrent state, no latent or sparse "
                    "attention, no QK-norm, no layer_ffn)")
            block_cls = (nn.remat(MixerBlock, static_argnums=(3,),
                                  policy=self._remat_policy())
                         if self.remat else MixerBlock)
            for i, kind in enumerate(self.layer_mixer):
                x = block_cls(
                    kind, self.n_heads, dh, dff, n_kv_heads=self.n_kv_heads,
                    rope=self.rope, rope_theta=self.rope_theta,
                    attn_impl=self.attn_impl, mesh=self.mesh,
                    compute_dtype=self.compute_dtype, norm_eps=self.norm_eps,
                    ssm=self.ssm, ssm_state_dtype=self.ssm_state_dtype,
                    n_experts=self.n_experts,
                    moe_top_k=self.moe_top_k,
                    moe_norm_topk_prob=self.moe_norm_topk_prob,
                    moe_held=self.moe_held, moe_router=self.moe_router,
                    moe_shared_d_ff=self.moe_shared_d_ff,
                    moe_expert_act=self.moe_expert_act,
                    moe_latent=self.moe_latent, name=f"block_{i}")(
                        x, positions, block_diffusion)
        else:
            layer_ffn = self.layer_ffn or (0,) * self.n_layers
            if len(layer_ffn) != self.n_layers:
                raise ValueError(f"layer_ffn={self.layer_ffn} names "
                                 f"{len(layer_ffn)} of {self.n_layers} layers")
            # the mask is a tuple of sizes: static under remat
            block_cls = (nn.remat(Block, static_argnums=(3,),
                                  policy=self._remat_policy())
                         if self.remat else Block)
            for i, dense in enumerate(layer_ffn):
                x = block_cls(
                    self.n_heads, dh, dense or dff,
                    0 if dense else self.n_experts, self.moe_top_k,
                    self.rope_theta, self.attn_impl, self.mesh,
                    self.compute_dtype, self.decode, self.max_decode_len,
                    self.norm_eps, self.qk_norm, self.moe_capacity_factor,
                    self.moe_norm_topk_prob, self.n_kv_heads,
                    self.qk_norm_per_head, self.moe_held, self.sparse,
                    self.latent, self.moe_router, self.moe_shared_d_ff,
                    name=f"block_{i}")(x, positions, block_diffusion)
        x = RMSNorm(self.norm_eps, name="final_norm")(x)
        if self.return_hidden:
            return x
        with jax.named_scope("lm_head_loss"):   # the loss half: make_loss_fn
            logits = nn.Dense(self.vocab_size, use_bias=False, name="lm_head",
                              dtype=self.compute_dtype)(x)
            return constrain(logits.astype(jnp.float32), P(BATCH, "sp", None))

    def _remat_policy(self):
        """What a rematerialised block keeps besides its input: nothing, or,
        under ``sparse``, what ``ops/sparse_attention.py`` names (the
        selection, attention's output and the indexer's loss with its
        gradient: 0.4 GB a layer at 16k), so that the second forward runs
        the projections, norms and experts again and none of the sparse
        kernels."""
        if not self.sparse:
            return None
        from tensorflowonspark_tpu.ops.sparse_attention import SAVED_NAMES

        return jax.checkpoint_policies.save_only_these_names(*SAVED_NAMES)


@register("transformer")
def build_transformer(config: dict) -> Transformer:
    capacity = config.get("moe_capacity_factor", 1.25)
    held = config.get("moe_held")
    sparse = config.get("sparse_attention")
    latent = config.get("latent_attention")
    router = config.get("moe_router")
    layer_ffn = config.get("layer_ffn")
    layer_mixer = config.get("layer_mixer")
    ssm = config.get("ssm")
    if router is not None and int(router.get("n_group", 1)) > 1:
        raise NotImplementedError(
            f"group-limited routing (n_group {router['n_group']}): "
            "parallel/ep.py chooses among all of a layer's experts")
    return Transformer(
        vocab_size=int(config.get("vocab_size", 32000)),
        d_model=int(config.get("d_model", 512)),
        n_layers=int(config.get("n_layers", 4)),
        n_heads=int(config.get("n_heads", 8)),
        d_head=int(config.get("d_head", 0)),
        d_ff=int(config.get("d_ff", 0)),
        n_experts=int(config.get("n_experts", 0)),
        moe_top_k=int(config.get("moe_top_k", 2)),
        rope_theta=float(config.get("rope_theta", 10000.0)),
        attn_impl=config.get("attn_impl", "auto"),
        compute_dtype=jnp.bfloat16 if config.get("bf16", True) else jnp.float32,
        remat=bool(config.get("remat", False)),
        norm_eps=float(config.get("norm_eps", 1e-6)),
        qk_norm=bool(config.get("qk_norm", False)),
        # null / None in the config: dropless
        moe_capacity_factor=None if capacity is None else float(capacity),
        moe_norm_topk_prob=bool(config.get("moe_norm_topk_prob", True)),
        n_kv_heads=int(config.get("n_kv_heads", 0)),
        qk_norm_per_head=bool(config.get("qk_norm_per_head", False)),
        moe_held=None if held is None else tuple(int(x) for x in held),
        sparse=None if sparse is None else tuple(
            int(sparse[key]) for key in ("index_heads", "index_head_dim",
                                         "topk")),
        latent=None if latent is None else tuple(
            int(latent[key]) for key in (
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim")),
        moe_router=None if router is None else (
            str(router.get("scoring", "softmax")),
            bool(router.get("selection_bias", False)),
            float(router.get("routed_scale", 1.0))),
        moe_shared_d_ff=int(config.get("moe_shared_d_ff", 0)),
        layer_ffn=None if layer_ffn is None else tuple(
            int(width) for width in layer_ffn),
        layer_mixer=None if layer_mixer is None else tuple(layer_mixer),
        ssm=None if ssm is None else (
            *(int(ssm[key]) for key in (
                "n_heads", "head_dim", "n_groups", "state_size",
                "conv_kernel", "chunk_size")),
            *(float(ssm[key]) for key in ("dt_min", "dt_max", "dt_floor"))),
        ssm_state_dtype=jnp.dtype(config.get("ssm_state_dtype", "float32")),
        rope=bool(config.get("rope", True)),
        moe_expert_act=str(config.get("moe_expert_act", "swiglu")),
        moe_latent=int(config.get("moe_latent", 0)),
    )


def pad_batch(token_lists, seq_len: int, pad_id: int = 0):
    """Ragged token lists → ``{"input_ids": [B,S], "loss_mask": [B,S]}``.

    The mask marks REAL tokens; ``make_loss_fn`` averages the next-token
    loss over real target positions only, so padding contributes nothing to
    the LM loss (causal attention keeps real positions blind to right-pads).
    Sequences longer than ``seq_len`` are truncated.

    MoE caveat: the expert router (``parallel/ep.py``) runs over ALL
    positions — pad tokens still occupy capacity slots and enter the
    load-balance aux statistics.  For ``n_experts > 0`` training prefer
    packing sequences back-to-back over padding ragged ones.
    """
    import numpy as np

    b = len(token_lists)
    ids = np.full((b, seq_len), pad_id, np.int32)
    mask = np.zeros((b, seq_len), np.float32)
    for i, toks in enumerate(token_lists):
        n = min(len(toks), seq_len)
        ids[i, :n] = np.asarray(toks[:n], np.int32)
        mask[i, :n] = 1.0
    return {"input_ids": ids, "loss_mask": mask}


def pack_batch(token_lists, seq_len: int, eos_id: int, pad_id: int = 0,
               n_rows: int | None = None):
    """Greedy sequence packing — the padding-free alternative to ``pad_batch``.

    Documents are laid back-to-back (each terminated by ``eos_id``) into
    fixed ``seq_len`` rows, first-fit: a document goes into the first row
    with room, else opens a new row; documents longer than ``seq_len``-1 are
    split across rows (GPT-style chunking).  Returns ``{"input_ids": [B,S],
    "loss_mask": [B,S]}`` where the mask marks real tokens (EOS included —
    predicting document ends is part of the LM task; only tail padding is
    masked out).

    The natural row count is CONTENT-DEPENDENT — under a jitted train loop
    a varying ``B`` means a recompile per new shape, and ``B`` must divide
    the batch mesh axes.  Pass ``n_rows`` to fix the batch dimension: short
    packs are padded with all-masked rows, and a pack that needs more than
    ``n_rows`` rows raises (size your budget from the token count:
    ``n_rows >= ceil(sum(len(d)+1) / seq_len)`` plus fragmentation slack).

    Semantics note: this is standard dense packing WITHOUT attention
    resetting — tokens may attend across document boundaries within a row
    (the usual GPT pretraining trade; the EOS token is the separator signal).
    For MoE models this is the recommended input shape: pad tokens occupy
    expert capacity, packed tokens don't (see ``pad_batch``'s caveat).
    """
    import numpy as np

    rows: list[list[int]] = []
    for toks in token_lists:
        doc = list(toks) + [eos_id]
        placed = False
        for row in rows:
            if len(row) + len(doc) <= seq_len:
                row.extend(doc)
                placed = True
                break
        if not placed:
            while len(doc) > seq_len:
                rows.append(doc[:seq_len])
                doc = doc[seq_len:]
            if doc:
                rows.append(doc)
    if n_rows is not None:
        if len(rows) > n_rows:
            raise ValueError(
                f"pack needs {len(rows)} rows of {seq_len} but n_rows={n_rows}; "
                "raise n_rows or feed fewer tokens per pack")
        rows.extend([] for _ in range(n_rows - len(rows)))
    b = len(rows)
    ids = np.full((b, seq_len), pad_id, np.int32)
    mask = np.zeros((b, seq_len), np.float32)
    for i, row in enumerate(rows):
        ids[i, : len(row)] = np.asarray(row, np.int32)
        mask[i, : len(row)] = 1.0
    return {"input_ids": ids, "loss_mask": mask}


def greedy_generate(model: Transformer, params, prompt_ids, max_new_tokens: int,
                    max_decode_len: int = 0, temperature: float = 0.0,
                    top_k: int = 0, seed: int = 0,
                    eos_id: int | None = None, pad_id: int = 0):
    """Autoregressive decoding through the static KV cache.

    ``prompt_ids: [B, S] int32`` → ``[B, S + max_new_tokens]``.  Serving
    runs in the standard two phases against a static ``[B, L, H, D]`` cache
    (``Attention._decode_step``): one chunked PREFILL forward over the whole
    prompt, then ONE-token decode steps — two compiled programs total,
    regardless of prompt length.  No reference counterpart (its models are
    CNNs); this exists because the LM family is first-class here.

    ``temperature == 0`` (default) is greedy argmax; ``> 0`` samples from
    ``softmax(logits / temperature)``, optionally truncated to the
    ``top_k`` most likely tokens.  Sampling is deterministic under ``seed``.

    ``eos_id`` enables early stopping: a row that emits it keeps its EOS and
    produces ``pad_id`` from then on, and the loop exits once EVERY row has
    finished (possibly before ``max_new_tokens``, so the returned width
    varies).  The per-row masking happens host-side between steps — the
    compiled decode step itself stays batch-static, so no recompiles.
    """
    import numpy as np

    b, s = prompt_ids.shape
    L = max_decode_len or (s + max_new_tokens)
    if L < s + max_new_tokens:
        raise ValueError(f"max_decode_len {L} < prompt {s} + new {max_new_tokens}")
    dmodel = model.clone(decode=True, max_decode_len=L, return_hidden=False)
    # flax init RUNS the decode step, so the returned cache already holds the
    # dummy token with index=1 — zero it to get a genuinely empty cache.
    cache = jax.tree.map(jnp.zeros_like, dmodel.init(
        jax.random.PRNGKey(0), jnp.zeros((b, 1), jnp.int32))["cache"])

    @jax.jit
    def step(params, cache, tok):
        # params is an ARGUMENT, not a closure capture: captured arrays
        # would be baked into the executable as constants (a second copy
        # of the weights in HBM for the serving loop).
        logits, mutated = dmodel.apply({"params": params, "cache": cache},
                                       tok, mutable=["cache"])
        return mutated["cache"], logits[:, -1]

    @jax.jit
    def pick(logits, key):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = logits.astype(jnp.float32) / temperature
        if top_k:
            kth = jax.lax.top_k(scaled, top_k)[0][:, -1:]
            scaled = jnp.where(scaled >= kth, scaled, -jnp.inf)
        return jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)

    key = jax.random.PRNGKey(seed)
    tokens = [np.asarray(prompt_ids[:, i]) for i in range(s)]
    # Chunked prefill: ONE forward over the whole prompt populates the KV
    # cache and yields the last position's logits — O(1) compiled calls
    # (one [B,S] prefill program + one [B,1] decode program) instead of the
    # O(S) sequential single-token steps of the naive loop.
    cache, logits = step(params, cache, jnp.asarray(prompt_ids, jnp.int32))
    finished = np.zeros((b,), bool)
    for _ in range(max_new_tokens):
        key, sub = jax.random.split(key)
        nxt = np.asarray(pick(logits, sub))
        if eos_id is not None:
            nxt = np.where(finished, pad_id, nxt)
        tokens.append(nxt)
        if eos_id is not None:
            finished |= nxt == eos_id
            if finished.all():
                break
        cache, logits = step(params, cache, jnp.asarray(nxt[:, None]))
    return np.stack(tokens, axis=1)


def _sown_collections(model: Transformer) -> list:
    return ["aux_loss", "moe_stats"] if model.n_experts else ["aux_loss"]


def _with_sown_terms(loss, updates, aux_loss_coef: float,
                     router_z_coef: float):
    """``(total, metrics)``: the LM loss plus the weighted auxiliary terms
    the layers sowed (``aux_loss``), and their ``moe_stats`` averaged over
    layers (see ``make_loss_fn``)."""
    aux = jnp.asarray(0.0)
    z = jnp.asarray(0.0)
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            updates.get("aux_loss", {}))[0]:
        if any("router_z" in str(p) for p in path):
            z = z + leaf
        else:
            aux = aux + leaf
    total = loss + aux_loss_coef * aux + router_z_coef * z
    metrics = {"lm_loss": loss, "aux_loss": aux, "router_z_loss": z}
    stats: dict[str, list] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            updates.get("moe_stats", {}))[0]:
        name = [p.key for p in path if hasattr(p, "key")][-1]
        stats.setdefault(f"moe_{name}", []).append(leaf)
    metrics.update({k: jnp.mean(jnp.stack(v)) for k, v in stats.items()})
    return total, metrics


def make_loss_fn(model: Transformer, aux_loss_coef: float = 0.01,
                 vocab_chunk: int = 0, router_z_coef: float = 1e-3):
    """Next-token LM loss.  Batch: ``{"input_ids": [B, S] int32}`` (targets
    are inputs shifted left; final position predicts a discarded token).
    MoE auxiliary losses are collected from the ``aux_loss`` sow:
    ``load_balance`` leaves weighted by ``aux_loss_coef`` and ``router_z``
    leaves (ST-MoE z-loss) by ``router_z_coef``.  For a model with experts
    the metrics also carry ``moe_max_load`` and ``moe_min_load``: pairs at
    the fullest and at the emptiest expert over the mean, averaged over
    layers (the ``moe_stats`` sow) — a collapsing router shows there first —
    and, under dropless routing, ``moe_executed_rows``: the rows the grouped
    matmul's tiles compute over the routed ones (``parallel/ep.py``).

    ``vocab_chunk > 0`` fuses the lm_head matmul into a blockwise
    cross-entropy (``ops/xent.py``): the ``[B, S, V]`` logits are never
    materialized — the HBM-dominant op at large vocab.  Not for
    tensor-parallel vocab-sharded heads (use the dense path there).

    A model that keeps BUFFERS (the collection ``buffers``: a router's
    selection bias, ``parallel/ep.MoEMLP``) is handed them as a third
    argument, ``loss_fn(params, batch, buffers)``: what
    ``parallel/dp.make_train_step`` calls where the train state carries
    them.  The loss is not differentiated by them."""

    sown = _sown_collections(model)

    def _variables(params, buffers):
        return ({"params": params} if buffers is None
                else {"params": params, "buffers": buffers})

    def _reduce(nll, batch, updates):
        mask = batch.get("loss_mask")
        if mask is not None:
            mask = mask[:, 1:].astype(jnp.float32)
            loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        else:
            loss = jnp.mean(nll)
        return _with_sown_terms(loss, updates, aux_loss_coef, router_z_coef)

    if vocab_chunk:
        from tensorflowonspark_tpu.ops.xent import blockwise_cross_entropy

        hidden_model = model.clone(return_hidden=True)

        def fused_loss_fn(params, batch, buffers=None):
            ids = batch["input_ids"]
            h, updates = hidden_model.apply(_variables(params, buffers), ids,
                                            mutable=sown)
            b, s, d = h.shape
            h = h[:, :-1].reshape(b * (s - 1), d)
            targets = ids[:, 1:].reshape(-1)
            with jax.named_scope("lm_head_loss"):
                nll = blockwise_cross_entropy(
                    h, params["lm_head"]["kernel"].astype(h.dtype), targets,
                    chunk=vocab_chunk)
            return _reduce(nll.reshape(b, s - 1), batch, updates)

        return fused_loss_fn

    def loss_fn(params, batch, buffers=None):
        ids = batch["input_ids"]
        logits, updates = model.apply(_variables(params, buffers), ids,
                                      mutable=sown)
        with jax.named_scope("lm_head_loss"):
            logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
            targets = ids[:, 1:]
            nll = -jnp.take_along_axis(logp, targets[..., None],
                                       axis=-1)[..., 0]
        return _reduce(nll, batch, updates)

    return loss_fn


def corrupt_blocks(ids, noise_seed, block: int, mask_id: int,
                   t_min: float = 1e-3):
    """Block diffusion's forward process on rows of token ids ``[rows, L]``
    (BD3-LM, arXiv:2503.09573, linear schedule): every block of ``block``
    tokens draws a noise level ``t ~ U[t_min, 1]`` and each of its tokens is
    replaced by ``mask_id`` with probability ``t``, independently.  The draw
    is a function of ``noise_seed`` ``[rows]`` (an integer a row, which the
    feed makes: the step's signature holds no key).  Returns ``(noised ids,
    masked [rows, L] bool, t [rows, L] float32, each token's level)``."""
    length = ids.shape[1]

    def row(seed):
        k_level, k_token = jax.random.split(
            jax.random.fold_in(jax.random.key(0), seed))
        t = jnp.repeat(jax.random.uniform(
            k_level, (length // block,), minval=t_min, maxval=1.0), block)
        return jax.random.uniform(k_token, (length,)) < t, t

    masked, t = jax.vmap(row)(noise_seed)
    return jnp.where(masked, mask_id, ids), masked, t


def make_block_diffusion_loss_fn(model: Transformer, block: int, mask_id: int,
                                 aux_loss_coef: float = 0.01,
                                 vocab_chunk: int = 4096,
                                 router_z_coef: float = 0.0,
                                 t_min: float = 1e-3):
    """The training loss of block diffusion (BD3-LM; SDAR, arXiv:2510.06303).
    Batch: ``{"input_ids": [rows, L] int32, "noise_seed": [rows] uint32}``.

    A row ``x_0`` is corrupted (``corrupt_blocks``) into ``x_t``; the stack
    runs ONCE over the 2L positions ``[x_t ‖ x_0]``, both copies at positions
    ``0 .. L-1``, under the block-diffusion mask (``ops/attention.py``): a
    noised block sees itself and the clean blocks before it.  The logits of
    the noised copy at position i predict ``x_0[i]`` itself (no shift), and
    ``loss = Σ_i masked_i · CE_i / t_i / (rows · L)`` plus the sown auxiliary
    terms (``make_loss_fn``).  The head and the blockwise cross-entropy
    (``ops/xent.py``) run over the L noised positions only.  Metrics also
    carry ``masked_share``: masked tokens over all."""
    from tensorflowonspark_tpu.ops.xent import blockwise_cross_entropy

    sown = _sown_collections(model)
    hidden_model = model.clone(return_hidden=True)

    def loss_fn(params, batch):
        ids = batch["input_ids"]
        rows, length = ids.shape
        if length % block:
            raise ValueError(f"rows of {length} tokens in blocks of {block}")
        # one scope around the whole loss: a transform names itself around
        # the OUTERMOST scope it meets ("jvp(block_diffusion)/diffusion/
        # corrupt/..."), and the readers look for whole components
        with jax.named_scope("block_diffusion"):
            with jax.named_scope("diffusion/corrupt"):
                noised, masked, t = corrupt_blocks(
                    ids, batch["noise_seed"], block, mask_id, t_min)
                both = jnp.concatenate([noised, ids], axis=1)
                positions = jnp.tile(jnp.arange(length), 2)
                weight = (masked / t).reshape(-1)
                # materialised here: fused into the embedding's gather and
                # the loss's sum, the draw would show under their scopes
                both, weight = jax.lax.optimization_barrier((both, weight))
            h, updates = hidden_model.apply(
                {"params": params}, both, positions, (length, block),
                mutable=sown)
            h = h[:, :length].reshape(rows * length, h.shape[-1])
            with jax.named_scope("lm_head_loss"):
                nll = blockwise_cross_entropy(
                    h, params["lm_head"]["kernel"].astype(h.dtype),
                    ids.reshape(-1), chunk=vocab_chunk)
                loss = jnp.sum(nll * weight) / (rows * length)
            total, metrics = _with_sown_terms(loss, updates, aux_loss_coef,
                                              router_z_coef)
        return total, {**metrics, "masked_share": jnp.mean(masked)}

    return loss_fn


def make_sparse_loss_fn(model: Transformer, aux_loss_coef: float = 0.01,
                        vocab_chunk: int = 4096, router_z_coef: float = 0.0,
                        index_loss_coef: float = 1.0):
    """The training loss of a model with learned sparse attention
    (``Transformer.sparse``): ``make_loss_fn``'s next-token cross-entropy,
    fused with the head, and sown auxiliary terms, plus ``index_loss_coef``
    times the indexers' term ``L_I``: the KL terms the layers sowed
    (``aux_loss/index_kl``, each a mean over tokens), averaged over layers.
    Batch: ``{"input_ids": [B, S] int32}`` (no ``loss_mask``).

    One ``value_and_grad`` of the sum gives two gradients that never mix:
    the indexers read the hidden state with the gradient stopped and their
    selection and their target are constants, so cross-entropy and the
    routers' terms reach every parameter BUT the indexers', and ``L_I``
    reaches those and nothing else (DeepSeek-V3.2-Exp's sparse training
    stage).  Metrics also carry ``index_loss``, ``dsa_selected_pairs`` (kept
    pairs a row) and ``dsa_live_tiles`` (tiles with a kept pair over causal
    tiles), means over layers."""
    from flax import traverse_util

    from tensorflowonspark_tpu.ops.xent import blockwise_cross_entropy

    sown = _sown_collections(model) + ["dsa_stats"]
    hidden_model = model.clone(return_hidden=True)

    def loss_fn(params, batch):
        ids = batch["input_ids"]
        # one scope around the whole loss, as block diffusion's has: the
        # readers look for whole components of the dsa/* scopes
        with jax.named_scope("sparse_lm"):
            h, updates = hidden_model.apply({"params": params}, ids,
                                            mutable=sown)
            b, s, d = h.shape
            with jax.named_scope("lm_head_loss"):
                nll = blockwise_cross_entropy(
                    h[:, :-1].reshape(b * (s - 1), d),
                    params["lm_head"]["kernel"].astype(h.dtype),
                    ids[:, 1:].reshape(-1), chunk=vocab_chunk)
                loss = jnp.mean(nll)
            aux = traverse_util.flatten_dict(dict(updates.get("aux_loss", {})))
            index = [v for k, v in aux.items() if "index_kl" in k]
            rest = {k: v for k, v in aux.items() if "index_kl" not in k}
            total, metrics = _with_sown_terms(
                loss, {**updates,
                       "aux_loss": traverse_util.unflatten_dict(rest)},
                aux_loss_coef, router_z_coef)
            index_loss = jnp.mean(jnp.stack(jax.tree.leaves(index)))
            stats = traverse_util.flatten_dict(
                dict(updates.get("dsa_stats", {})))
            for name in ("selected_pairs", "live_tiles"):
                metrics[f"dsa_{name}"] = jnp.mean(jnp.stack(jax.tree.leaves(
                    [v for k, v in stats.items() if name in k])))
        return (total + index_loss_coef * index_loss,
                {**metrics, "index_loss": index_loss})

    return loss_fn
