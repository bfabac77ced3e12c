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

import contextlib
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tensorflowonspark_tpu import telemetry
from tensorflowonspark_tpu.models.registry import register
from tensorflowonspark_tpu.ops.attention import flash_attention
from tensorflowonspark_tpu.ops.qk_prep import engages, qk_prep
from tensorflowonspark_tpu.parallel.tp import constrain

BATCH = ("dp", "fsdp")


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention factor ``0.1 · mscale · ln(factor) + 1`` (1 where
    nothing is stretched), as transformers' ``yarn_get_mscale``."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_scaling_from_config(scaling: Optional[dict]) -> Optional[tuple]:
    """A published ``rope_scaling`` group, by its own keys, as the tuple
    ``rope_frequencies`` takes (a module's attribute has to hash)."""
    if scaling is None:
        return None
    return (str(scaling.get("type", scaling.get("rope_type"))),
            float(scaling["factor"]),
            int(scaling["original_max_position_embeddings"]),
            float(scaling.get("beta_fast", 32)),
            float(scaling.get("beta_slow", 1)),
            float(scaling.get("mscale", 0)),
            float(scaling.get("mscale_all_dim", 0)))


def _yarn_only(rope_scaling: Optional[tuple]) -> None:
    if rope_scaling is not None and rope_scaling[0] != "yarn":
        raise NotImplementedError(
            f"rope_scaling type {rope_scaling[0]!r}: rope_frequencies "
            "computes 'yarn' and no other stretch of the rotary frequencies")


def rope_frequencies(theta: float, rope_scaling: Optional[tuple], width: int):
    """``(inverse frequencies [width // 2] float32, factor on cos and sin)``
    of a rotation over ``width`` columns: the ONE place that says how fast a
    pair turns.  ``rope_scaling`` None: ``theta^(-i / half)`` and 1.

    ``("yarn", factor, original positions, beta_fast, beta_slow, mscale,
    mscale_all_dim)`` is YaRN (arXiv:2309.00071) as transformers'
    ``_compute_yarn_parameters`` computes it: pairs that turn more than
    ``beta_fast`` times over the original context keep their frequency, pairs
    that turn less than ``beta_slow`` times have it divided by ``factor``,
    and a linear ramp over the pair index lies between (the correction range,
    floored and ceiled).  All of it is arithmetic on Python floats and numpy
    at trace time: the program holds constants."""
    half = width // 2
    if rope_scaling is None:
        return jnp.exp(-math.log(theta)
                       * jnp.arange(half, dtype=jnp.float32) / half), 1.0
    import numpy as np

    _yarn_only(rope_scaling)
    _kind, factor, original, beta_fast, beta_slow, mscale, all_dim = \
        rope_scaling

    def correction_dim(rotations):     # the pair that turns so many times
        return (width * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), width - 1)
    if low == high:
        high += 0.001
    inv = theta ** (-np.arange(0, width, 2, dtype=np.float64) / width)
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    inv = inv / factor * ramp + inv * (1.0 - ramp)
    on_cos_sin = (yarn_mscale(factor, mscale) / yarn_mscale(factor, all_dim)
                  if mscale and all_dim else yarn_mscale(factor))
    return jnp.asarray(inv, jnp.float32), float(on_cos_sin)


def apply_rope(x, positions, theta: float = 10000.0,
               rope_scaling: Optional[tuple] = None):
    """Rotary embedding, ``x: [B, S, H, D]``, ``positions: [S]``; the
    frequencies are ``rope_frequencies``'."""
    half = x.shape[-1] // 2
    freqs, factor = rope_frequencies(theta, rope_scaling, x.shape[-1])
    angles = positions[:, None].astype(jnp.float32) * freqs[None, :]  # [S, half]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
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


def _token_major_dot(lhs, rhs, dimension_numbers, precision=None,
                     preferred_element_type=None):
    """``DenseGeneral``'s product ``[B, S, M] x [M, H, D]`` as the 2-D product
    ``[B * S, M] x [M, H * D]``: its result is token-major as it is written,
    which is how ``ops/qk_prep.py`` reads it, and its two backward products
    read that op's cotangent as it was written (a product over ``[.., H, D]``
    lays its result out head by head, and each way costs a copy)."""
    if dimension_numbers != (((lhs.ndim - 1,), (0,)), ((), ())):
        raise NotImplementedError(f"a projection's product: got "
                                  f"{dimension_numbers}")
    out = jax.lax.dot_general(
        lhs.reshape(-1, lhs.shape[-1]), rhs.reshape(rhs.shape[0], -1),
        (((1,), (0,)), ((), ())), precision=precision,
        preferred_element_type=preferred_element_type)
    return out.reshape(lhs.shape[:-1] + rhs.shape[1:])


class _NormScale(nn.Module):
    """``RMSNorm``'s learned scale, under the name and the shape ``RMSNorm``
    gives it, for a caller whose norm runs elsewhere (``ops/qk_prep.py``)."""

    @nn.compact
    def __call__(self, width: int):
        return self.param("scale", nn.initializers.ones, (width,))


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
    # the whole projection (OLMoE's).  On the kernels' path this norm and
    # RoPE are ONE op (``_prepare``, ``ops/qk_prep.py``); ``RMSNorm`` is the
    # cache path's, a 96-wide head's and every other backend's.
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
    # Latent attention takes it too (``mla_use_nope``: the ``qk_rope_head_dim``
    # columns keep their place in the shapes and are never turned).  True:
    # ``_prepare`` turns q and k, by ``ops/qk_prep.py`` on the kernels' path
    # and by ``apply_rope`` elsewhere; latent attention turns its rotary
    # columns with ``apply_rope``, as the indexer and the cache path do theirs.
    rope: bool = True
    # A query latent beside ``latent`` (DeepSeek-V3's ``q_lora_rank``): ``q =
    # RMSNorm(u W_qa) W_qb`` through a latent of this width (0: ``u W_q``).
    q_lora_rank: int = 0
    # How the rotary frequencies are stretched (``rope_frequencies``; None:
    # not at all).  Under latent attention YaRN also scales the SOFTMAX, by
    # ``yarn_mscale(factor, mscale_all_dim)²`` (``_latent_attention``).
    rope_scaling: Optional[tuple] = None
    # A window on the causal mask: a query sees itself and the ``window - 1``
    # keys before it (0: every key before it).  A field, so static under
    # ``remat`` by construction: the flash kernels build the band's visit
    # tables from it at trace time (``ops/attention.py``).
    window: int = 0

    @nn.compact
    def __call__(self, x, positions=None, block_diffusion=None):
        """``positions`` ``[S]`` are the tokens' RoPE positions (None: their
        places in the sequence); ``block_diffusion=(length, block)`` puts
        that mask in place of the causal one (``ops/attention.py``).  The
        caller hands in both: what a sequence holds is the loss's business
        (``make_block_diffusion_loss_fn``)."""
        if not self.rope and (self.sparse or self.decode):
            raise NotImplementedError(
                "rope=False is the training path's, plain or latent: the "
                "indexer and the cache path turn their keys")
        if self.window and (self.latent or self.sparse or self.decode
                            or self.attn_impl == "ring"):
            raise NotImplementedError(
                f"window={self.window} is the plain training path's, one "
                "whole sequence through flash_attention: latent and sparse "
                "attention mask otherwise, the cache path holds every key, "
                "and ring attention's chunks take no window")
        if self.latent:
            return self._latent_attention(x, positions, block_diffusion)
        if self.q_lora_rank:
            raise NotImplementedError(
                f"q_lora_rank={self.q_lora_rank} without latent=: the query "
                "latent is latent attention's (Attention._latent_attention)")
        b, s, _ = x.shape
        h, dh = self.n_heads, self.d_head
        h_kv = self.n_kv_heads or h
        per_head = self.qk_norm and self.qk_norm_per_head
        # q and k take the fused pass where the kernels run and it can: the
        # share of the sites traced that did is attn.qk_prep / attn.qk_sites
        fused = (self._impl() != "xla" and not self.decode
                 and self.attn_impl != "ring"
                 and engages(dh, per_head, self.rope))
        dense = lambda name, heads, dot=None: nn.DenseGeneral(  # noqa: E731
            (heads, dh), axis=-1, use_bias=False, name=name,
            dtype=self.compute_dtype, dot_general=dot)
        dot = _token_major_dot if fused else None
        q, k, v = (dense("q_proj", h, dot)(x), dense("k_proj", h_kv, dot)(x),
                   dense("v_proj", h_kv)(x))
        telemetry.counter("attn.qk_sites").inc()
        if fused:
            telemetry.counter("attn.qk_prep").inc()
        scales = (None, None)
        if fused and per_head:
            scales = (_NormScale(name="q_norm")(dh),
                      _NormScale(name="k_norm")(dh))
        elif self.qk_norm:
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
        if self.rope and positions is None:
            positions = jnp.arange(s)
        if self.sparse:
            if block_diffusion or self.attn_impl == "ring":
                raise NotImplementedError(
                    "learned sparse attention selects among the causal keys "
                    "of one whole sequence on one chip")
            q, k = self._prepare(q, k, positions, scales, fused,
                                 "dsa/attend")
            return self._sparse_attention(x, q, k, v, positions)
        if self.attn_impl == "ring" and (
                self.mesh is None or h_kv != h or block_diffusion):
            raise ValueError("ring attention needs mesh=, as many K/V heads "
                             "as query heads and the causal mask")
        q, k = self._prepare(q, k, positions, scales, fused, "attention")
        # named scope: layout and the kernel (both halves of its VJP) carry
        # "attention" in their op names, whatever XLA fuses
        with jax.named_scope("attention"):
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
                out = flash_attention(q, k, v, causal=not block_diffusion,
                                      impl=self._impl(),
                                      block_diffusion=block_diffusion,
                                      window=self.window or None)
        out = nn.DenseGeneral(x.shape[-1], axis=(-2, -1), use_bias=False,
                              name="o_proj", dtype=self.compute_dtype)(out)
        return out

    @nn.nowrap
    def _impl(self) -> str:
        """Which kernels this module's attention runs: ``attn_impl``, and
        under ``auto`` Pallas on the TPU and XLA's elsewhere."""
        if self.attn_impl == "auto":
            return "pallas" if jax.default_backend() == "tpu" else "xla"
        return self.attn_impl

    @nn.nowrap
    def _prepare(self, q, k, positions, scales, fused: bool, scope: str):
        """q and k from the projections' ``[B, S, H, D]`` to what the
        kernels take.  ``fused``: per-head QK-norm (``scales``, where the
        model has it) and RoPE in one pass each way, under the scope
        ``qk_prep`` (``ops/qk_prep.py``); else q and k arrive normed and
        ``apply_rope`` turns them under ``scope``, as the kernels' own
        layout work is."""
        if fused:
            freqs, factor = (rope_frequencies(
                self.rope_theta, self.rope_scaling, self.d_head)
                if self.rope else (None, 1.0))
            return tuple(
                qk_prep(t, scale, positions, freqs, factor=factor,
                        eps=self.norm_eps,
                        interpret=self._impl() == "pallas_interpret")
                for t, scale in zip((q, k), scales))
        if not self.rope:
            return q, k
        with jax.named_scope(scope):
            return tuple(apply_rope(t, positions, self.rope_theta,
                                    self.rope_scaling) for t in (q, k))

    def _latent_attention(self, x, positions, block_diffusion):
        """Latent attention on the training path.  From the layer's normed
        hidden state ``u``: ``q = u W_q`` (under ``q_lora_rank``: ``RMSNorm(u
        W_qa) W_qb``, a query latent) = per head ``[q_nope | q_rope]``;
        ``[c | k_r] = u W_kva``, ``c`` RMS-normed (``kv_a_norm``), ``k_r`` ONE
        rotary key head for all query heads; ``[k_nope | v] = c W_kvb`` per
        head.  Under ``rope`` ``apply_rope`` turns ``q_rope`` and ``k_r`` only
        (this file's half-split pairing: against the published interleaved
        one a fixed permutation of the rotary columns of ``W_q`` and
        ``W_kva``, which no score sees); under ``rope=False`` nothing is
        turned and ``positions`` is not read: the columns are a second,
        shared part of the key (Kimi-Linear's ``mla_use_nope``).  ``score = (q_nope · k_nope + q_rope · k_r) / sqrt(nope +
        rope)``: the kernels take ``k_r`` as their shared key, so no copy of
        it a head exists, forward or backward.  Autodiff keeps ``k_nope``
        and ``v`` whole for the backward (the kernels' residuals).  Under
        YaRN (``rope_scaling``) the frequencies are ``rope_frequencies``' and
        the softmax scale is ``(nope + rope)^-1/2 · m²``, ``m = yarn_mscale(
        factor, mscale_all_dim)`` (transformers' ``deepseek_v3``), a constant
        that reaches the kernels as their static ``sm_scale``; without it the
        argument stays None and the kernels are compiled as they were."""
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
        sm_scale = None
        if self.rope_scaling is not None and self.rope_scaling[6]:
            _kind, factor, *_rest, all_dim = self.rope_scaling
            sm_scale = (nope + rope) ** -0.5 * yarn_mscale(factor, all_dim) ** 2
        with jax.named_scope("mla/project"):
            if self.q_lora_rank:
                q_a = nn.Dense(self.q_lora_rank, use_bias=False,
                               name="q_a_proj", dtype=cdt)(x)
                q = nn.DenseGeneral((h, nope + rope), use_bias=False,
                                    name="q_b_proj", dtype=cdt)(
                    RMSNorm(self.norm_eps, name="q_a_norm")(q_a))
            else:
                q = nn.DenseGeneral((h, nope + rope), use_bias=False,
                                    name="q_proj", dtype=cdt)(x)
            kv_a = nn.Dense(rank + rope, use_bias=False, name="kv_a_proj",
                            dtype=cdt)(x)
            c = RMSNorm(self.norm_eps, name="kv_a_norm")(kv_a[..., :rank])
            kv = nn.DenseGeneral((h, nope + dv), use_bias=False,
                                 name="kv_b_proj", dtype=cdt)(c)
            k_r = kv_a[..., rank:]
            if self.rope:
                if positions is None:
                    positions = jnp.arange(s)
                q = jnp.concatenate(
                    [q[..., :nope],
                     apply_rope(q[..., nope:], positions, self.rope_theta,
                                self.rope_scaling)], -1)
                k_r = apply_rope(k_r[:, :, None], positions, self.rope_theta,
                                 self.rope_scaling)[:, :, 0]
            q = constrain(q, P(BATCH, "sp", "tp", None))
            k = constrain(kv[..., :nope], P(BATCH, "sp", "tp", None))
            v = constrain(kv[..., nope:], P(BATCH, "sp", "tp", None))
        with jax.named_scope("attention"):
            out = flash_attention(
                q, k, v, k_shared=k_r, causal=not block_diffusion,
                sm_scale=sm_scale,
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
        q = apply_rope(q, pos, self.rope_theta, self.rope_scaling)
        k = apply_rope(k, pos, self.rope_theta, self.rope_scaling)
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
            a = apply_rope(a.astype(f32), positions, self.rope_theta,
                           self.rope_scaling).astype(self.compute_dtype)
            key = apply_rope(key[:, :, None], positions, self.rope_theta,
                             self.rope_scaling)[:, :, 0].astype(
                                 self.compute_dtype)

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


def _dt_bias_init(dt_range: tuple):
    """The initialiser of a ``dt_bias``: the inverse softplus of a
    log-uniform draw from ``dt_range`` = (min, max, floor) (Mamba-2's, and
    Kimi Delta Attention's)."""
    lo, hi, floor = dt_range

    def init(key, shape):
        dt = jnp.exp(jax.random.uniform(key, shape)
                     * (math.log(hi) - math.log(lo)) + math.log(lo))
        dt = jnp.maximum(dt, floor)
        return dt + jnp.log(-jnp.expm1(-dt))            # softplus^-1

    return init


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
                dt_bias = self.param("dt_bias", _dt_bias_init(self.dt_range),
                                     (h,))
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


class KimiDeltaAttention(nn.Module):
    """Kimi Delta Attention's mixer (Kimi Linear, arXiv:2510.26692 §3-4;
    the public ``KimiDeltaAttention``), ``[B, L, D] -> [B, L, D]``, ``L`` a
    multiple of ``chunk``.  From the layer's normed state ``u``, per head
    ``h`` of ``H`` with ``d = head_dim`` key and value channels:

        q = L2Norm(SiLU(Conv(u W_q)))_h · d^-1/2,  k = L2Norm(SiLU(Conv(u
        W_k)))_h,  v = SiLU(Conv(u W_v))_h
        g = -exp(A_log_h) · softplus((u W_f↓ W_f↑)_h + dt_bias_h)   [d]
        β = sigmoid(u W_β)_h
        S_t = (I - β_t k_t k_tᵀ) Diag(exp g_t) S_{t-1} + β_t k_t v_tᵀ,
        o_t = S_tᵀ q_t                                   (``ops/kda.py``)
        y = (RMSNorm_d(o) ⊙ sigmoid((u W_g↓ W_g↑)_h)) W_o

    ``Conv``: a depthwise causal conv of ``conv`` taps over each of the
    three ``H·d``-wide projections, no bias (``ops/ssd.causal_conv1d``); the
    L2 norm over a head's ``d`` channels, ``x / sqrt(Σ x² + 1e-6)``; the
    decay a vector over the head's ``d`` KEY channels through a low-rank
    pair ``D -> d -> H·d`` (``f_a_proj``, ``f_b_proj``), ``A_log`` one value
    a head, ``dt_bias`` one a channel; ``β`` one value a head; the output
    norm one learned weight of width ``d`` (``o_norm``) for all heads, gated
    through a second low-rank pair (``g_a_proj``, ``g_b_proj``).  No bias
    anywhere.

    ``g``, ``β``, the L2 norm, the op's running sums, decays and carried
    state, the output norm's mean and the gate's sigmoid are float32; the
    projections, the conv's operands, the op's products and the gate's
    multiply ``compute_dtype``.  The L2 norm and the decay run under
    ``jax.checkpoint``: a layer keeps the maps they are made from, not
    float32 copies of ``[B, L, H·d]``.  Seeded as the public layer is: ``A``
    uniform in [1, 16], ``dt_bias`` the inverse softplus of a log-uniform draw
    from ``dt_range`` (min, max, floor).  ``attn_impl``: the op's ``impl``."""

    n_heads: int
    head_dim: int
    conv: int = 4
    chunk: int = 64
    norm_eps: float = 1e-5
    compute_dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32      # ``ops/kda.py``: a check's control
    dt_range: tuple = (0.001, 0.1, 1e-4)
    attn_impl: str = "auto"         # auto | pallas | pallas_interpret | xla

    @nn.compact
    def __call__(self, u):
        from tensorflowonspark_tpu.ops.kda import kda_scan
        from tensorflowonspark_tpu.ops.ssd import causal_conv1d

        b, length, d_model = u.shape
        h, d = self.n_heads, self.head_dim
        inner, f32, cdt = h * d, jnp.float32, self.compute_dtype
        telemetry.counter("kda.layers").inc()
        telemetry.counter("kda.chunks").inc(length // self.chunk)

        heads = lambda name: nn.DenseGeneral(           # noqa: E731
            (h, d), use_bias=False, name=name, dtype=cdt)
        pair = lambda name, x: nn.Dense(                # noqa: E731
            inner, use_bias=False, name=f"{name}_b_proj", dtype=cdt)(
                nn.Dense(d, use_bias=False, name=f"{name}_a_proj",
                         dtype=cdt)(x))
        with jax.named_scope("kda"):
            q, k, v = (heads(name)(u).reshape(b, length, inner)
                       for name in ("q_proj", "k_proj", "v_proj"))
            with jax.named_scope("kda/conv"):
                def short_conv(name, x):
                    kernel = self.param(
                        name, lambda key, shape: jax.random.uniform(
                            key, shape, minval=-1.0, maxval=1.0)
                        / math.sqrt(self.conv), (self.conv, inner))
                    x = jax.nn.silu(causal_conv1d(
                        x, kernel, jnp.zeros((inner,), f32)))
                    return x.reshape(b, length, h, d)

                # the L2 norm (and below the decay) under ``jax.checkpoint``:
                # what a layer keeps of them are the bf16 maps they are made
                # from, not float32 copies (0.25 GB each at 16k rows)
                @jax.checkpoint
                def unit(x, scale):
                    x32 = x.astype(f32)
                    return (x32 * (scale * jax.lax.rsqrt(jnp.sum(
                        x32 * x32, axis=-1, keepdims=True) + 1e-6))
                            ).astype(cdt)

                q = unit(short_conv("q_conv", q), d ** -0.5)
                k = unit(short_conv("k_conv", k), 1.0)
                v = short_conv("v_conv", v)
            with jax.named_scope("kda/gates"):
                a_log = self.param(
                    "A_log", lambda key, shape: jnp.log(jax.random.uniform(
                        key, shape, minval=1.0, maxval=16.0)), (h,))
                dt_bias = self.param("dt_bias", _dt_bias_init(self.dt_range),
                                     (inner,))

                @jax.checkpoint
                def decay(f, a_log, dt_bias):
                    return (-jnp.exp(a_log.astype(f32))[:, None]
                            * jax.nn.softplus(f.astype(f32) + dt_bias)
                            .reshape(b, length, h, d))

                g = decay(pair("f", u), a_log, dt_bias)
                beta = jax.nn.sigmoid(nn.Dense(
                    h, use_bias=False, name="b_proj", dtype=cdt)(u)
                    .astype(f32))
                gate = pair("g", u)
            with jax.named_scope("kda/scan"):
                o = kda_scan(q, k, v, g, beta, chunk=self.chunk,
                             state_dtype=self.state_dtype, impl=self.attn_impl)
            with jax.named_scope("kda/gate_norm"):
                scale = self.param("o_norm", nn.initializers.ones, (d,))
                o = o.astype(f32)
                o = o * jax.lax.rsqrt(
                    jnp.mean(o * o, axis=-1, keepdims=True) + self.norm_eps)
                o = ((o * scale).astype(cdt) * jax.nn.sigmoid(
                    gate.astype(f32)).astype(cdt).reshape(b, length, h, d))
            return nn.DenseGeneral(d_model, axis=(-2, -1), use_bias=False,
                                   name="o_proj", dtype=cdt)(o)


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
        with jax.named_scope("residual"):
            x = x + y
        return _constrained(x)


class HyperConnection(nn.Module):
    """The maps of one manifold-constrained hyper-connection (mHC,
    arXiv:2512.24880; hyper-connections, arXiv:2409.19606) from the ``n``
    residual streams of a token, which the carry holds side by side on the
    feature axis (``x``: ``[B, S, n·C]``, stream ``i`` the columns ``i·C ..
    (i+1)·C``: whole 128-lane tiles, where a ``[.., n, C]`` array would pad
    its rows of ``n`` to a tile).  With ``x̃ = RMSNorm(x)`` over all ``n·C``
    values (its own weight, ``norm_scale``) and ``phi`` ``[n·C, n + n + n²]``:

        H_pre  = σ(α_pre · x̃ φ_pre + b_pre)                  [B, S, n]
        H_post = 2 σ(α_post · x̃ φ_post + b_post)             [B, S, n]
        H_res  = SK(exp(clip(α_res · mat(x̃ φ_res) + b_res)))  [B, S, n, n]

    ``SK``: ``iters`` rounds of (rows over their sum + ``eps``, columns over
    theirs), so ``H_res`` is doubly stochastic to within what the rounds
    leave (sown: ``hc_stats/res_row_err``, ``res_col_err``, the largest
    deviation of a row's and of a column's sum from 1, and ``pre_mean``).  A
    sub-layer ``F`` then reads ``hc_read(x, H_pre) = H_pre x`` and the
    streams become ``hc_write(x, F(..), H_post, H_res) = H_res x + H_postᵀ
    F(..)``.  Returned ``(H_pre [n, B, S], H_post [n, B, S], H_res [n, n, B,
    S])``: tokens on the minor axes, so that the rounds are elementwise work
    on whole vectors of tokens and no reduction over a padded axis of 4.

    Everything here is ``dtype`` (float32; a check's control sets bf16) but
    the operands of the ONE product ``x φ``, which are the streams as they
    are and ``φ`` scaled by the norm's weight in the streams' dtype, summed
    in float32: ``x̃ φ = (x (w ⊙ φ)) / rms(x)``, so the normed streams are
    never written."""

    n: int
    iters: int = 20
    eps: float = 1e-6
    clamp: tuple = (-30.0, 30.0)
    norm_eps: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        n, dt = self.n, self.dtype
        width = x.shape[-1]
        maps = 2 * n + n * n
        # small maps at the start (x̃ φ ~ N(0, 1) a map, α 0.01): near their
        # static part σ(b), which a checkpoint then moves
        phi = self.param("phi", nn.initializers.lecun_normal(), (width, maps))
        bias = self.param("bias", nn.initializers.zeros, (maps,))
        alpha = self.param("alpha", nn.initializers.constant(0.01), (3,))
        scale = self.param("norm_scale", nn.initializers.ones, (width,))
        with jax.named_scope("hc/maps"):
            x32 = x.astype(dt)
            rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1)
                                + self.norm_eps)                   # [B, S]
            z = jnp.einsum("bsk,kj->jbs", x,
                           (scale[:, None] * phi).astype(x.dtype),
                           preferred_element_type=dt)
            # α_pre over the first n maps, α_post the next n, α_res the n²
            of = [0] * n + [1] * n + [2] * (n * n)
            z = (z * rms[None] * alpha.astype(dt)[jnp.array(of)][:, None, None]
                 + bias.astype(dt)[:, None, None])
            h_pre = jax.nn.sigmoid(z[:n])
            h_post = 2.0 * jax.nn.sigmoid(z[n:2 * n])
            h_res = jnp.exp(jnp.clip(z[2 * n:], *self.clamp)).reshape(
                (n, n) + z.shape[1:])               # [row j, column i, B, S]
            for _ in range(self.iters):
                h_res = h_res / (jnp.sum(h_res, axis=1, keepdims=True)
                                 + self.eps)
                h_res = h_res / (jnp.sum(h_res, axis=0, keepdims=True)
                                 + self.eps)
            f32 = jnp.float32
            self.sow("hc_stats", "res_row_err", jnp.max(jnp.abs(
                jnp.sum(h_res.astype(f32), axis=1) - 1.0)))
            self.sow("hc_stats", "res_col_err", jnp.max(jnp.abs(
                jnp.sum(h_res.astype(f32), axis=0) - 1.0)))
            self.sow("hc_stats", "pre_mean", jnp.mean(h_pre.astype(f32)))
        return h_pre, h_post, h_res


def _constrained(x):
    """The residual carry ``[B, S, ·]`` under its sharding constraint, named
    as the carry's own work (``residual``: the layers' adds and this)."""
    with jax.named_scope("residual"):
        return constrain(x, P(BATCH, "sp", None))


def _streams(x, n: int):
    """The ``n`` streams of the carry ``[B, S, n·C]``, each ``[B, S, C]``."""
    width = x.shape[-1] // n
    return [x[..., i * width:(i + 1) * width] for i in range(n)]


def hc_read(x, h_pre):
    """``H_pre x``: the ONE mixed stream a sub-layer reads, ``[B, S, C]`` in
    the carry's dtype, summed in the maps' dtype."""
    with jax.named_scope("hc/pre"):
        parts = _streams(x, h_pre.shape[0])
        return sum(h_pre[i][..., None] * part.astype(h_pre.dtype)
                   for i, part in enumerate(parts)).astype(x.dtype)


def hc_write(x, y, h_post, h_res):
    """``H_res x + H_postᵀ y``: every stream ``j`` becomes the ``H_res[j]``
    mix of the streams plus ``H_post[j]`` times the sub-layer's output ``y``
    ``[B, S, C]``; the carry's dtype, summed in the maps' dtype."""
    with jax.named_scope("hc/post"):
        n, dt = h_post.shape[0], h_post.dtype
        parts = [part.astype(dt) for part in _streams(x, n)]
        y = y.astype(dt)
        return jnp.concatenate(
            [(sum(h_res[j, i][..., None] * parts[i] for i in range(n))
              + h_post[j][..., None] * y).astype(x.dtype)
             for j in range(n)], axis=-1)


def _to_streams(x, n: int):
    """The embedding ``[B, S, C]`` copied into the ``n`` streams (one stream:
    as it is, and no op)."""
    if n == 1:
        return x
    with jax.named_scope("hc/ends"):
        return jnp.tile(x, (1, 1, n))


def _from_streams(x, n: int):
    """The SUM of the ``n`` streams (hyper-connections, arXiv:2409.19606),
    summed in float32 (one stream: as it is, and no op)."""
    if n == 1:
        return x
    with jax.named_scope("hc/ends"):
        return sum(part.astype(jnp.float32)
                   for part in _streams(x, n)).astype(x.dtype)


def _attention_entry(entry: tuple) -> tuple:
    """``(window, rope, kind)`` of a ``Transformer.layer_attention`` entry,
    which may leave the kind out (``"own"``)."""
    return (*entry, "own")[:3]


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
    q_lora_rank: int = 0
    rope_scaling: Optional[tuple] = None
    hyper: Optional[tuple] = None
    hyper_dtype: Any = jnp.float32
    # ``(window, rope[, kind])``: see Transformer.layer_attention
    attention: tuple = (0, True)
    moe_expert_act: str = "swiglu"
    moe_router_input: str = "ffn"
    kda: Optional[tuple] = None             # see Transformer
    kda_state_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, positions=None, block_diffusion=None):
        norm = lambda name: RMSNorm(self.norm_eps, name=name)  # noqa: E731
        window, rope, kind = _attention_entry(self.attention)
        if kind == "kda":
            if block_diffusion:
                raise NotImplementedError(
                    f"block_diffusion={block_diffusion} beside a KDA layer: "
                    "a block-diffusion mask is attention's, the delta rule "
                    "reads every position before its own")
            mixer = KimiDeltaAttention(     # heads, head_dim, conv, chunk
                *self.kda, self.norm_eps, self.compute_dtype,
                self.kda_state_dtype, attn_impl=self.attn_impl,
                name="attn")
            attn = lambda u, _positions, _mask: mixer(u)    # noqa: E731
        else:
            attn = Attention(self.n_heads, self.d_head, self.rope_theta,
                             self.attn_impl, self.mesh, self.compute_dtype,
                             self.decode, self.max_decode_len, self.qk_norm,
                             self.norm_eps, self.n_kv_heads,
                             self.qk_norm_per_head, self.sparse, self.latent,
                             rope=rope, q_lora_rank=self.q_lora_rank,
                             rope_scaling=self.rope_scaling, window=window,
                             name="attn")
        if self.hyper:
            return self._hyper_connected(x, attn, positions, block_diffusion)
        layer_input = x
        y = attn(norm("attn_norm")(x), positions, block_diffusion)
        with jax.named_scope("residual"):
            x = x + y
        x = _constrained(x)
        ffn, shared = self._ffn(x.shape[-1])
        y = norm("mlp_norm")(x)
        # the router may read the residual stream as the layer found it
        routed = (ffn(y, router_input=layer_input)
                  if self.n_experts and self.moe_router_input == "layer"
                  else ffn(y))
        with jax.named_scope("residual"):
            x = x + routed
        if shared is not None:
            with jax.named_scope("moe/shared"):
                x = x + shared(y)
        return _constrained(x)

    def _ffn(self, d_model: int):
        """``(the layer's FFN, the shared experts or None)``: the experts of
        ``parallel/ep.MoEMLP`` or a dense SwiGLU; the shared experts are ONE
        SwiGLU of their summed width that every token passes beside its
        routed experts: every chip of an expert-parallel stage computes it
        whole, so under ``moe_held`` it is in the layer's output once, as it
        is without."""
        if not self.n_experts:
            return SwiGLU(self.d_ff, self.compute_dtype, name="mlp"), None
        from tensorflowonspark_tpu.parallel.ep import MoEMLP

        scoring, bias, scale = self.moe_router or ("softmax", False, 1.0)
        ffn = MoEMLP(d_model, self.d_ff, self.n_experts,
                     self.moe_top_k, self.moe_capacity_factor,
                     compute_dtype=self.compute_dtype,
                     norm_topk_prob=self.moe_norm_topk_prob,
                     held=self.moe_held, scoring=scoring,
                     selection_bias=bias, routed_scale=scale,
                     expert_act=self.moe_expert_act, name="moe")
        shared = (SwiGLU(self.moe_shared_d_ff, self.compute_dtype,
                         name="shared") if self.moe_shared_d_ff else None)
        return ffn, shared

    def _hyper_connected(self, x, attn, positions, block_diffusion):
        """The layer over ``n`` residual streams (``hyper`` = ``(n, Sinkhorn
        rounds, eps, clamp min, clamp max)``; ``x``: ``[B, S, n·C]``): each
        of the two sub-layers, attention and the FFN (the experts AND the
        shared expert: one sub-layer), with the pre-norm it has, reads ONE
        mix of the streams and writes to all of them, ``x <- H_res x +
        H_postᵀ F(H_pre x)``, through maps of its own (``hc_attn``,
        ``hc_mlp``: ``HyperConnection``).  Same modules under the same names
        as the plain layer's, so the two parameter trees differ by the two
        ``hc_*`` entries alone."""
        n, iters, eps, *clamp = self.hyper
        norm = lambda name: RMSNorm(self.norm_eps, name=name)  # noqa: E731
        maps = lambda name: HyperConnection(  # noqa: E731
            int(n), int(iters), eps, tuple(clamp), self.norm_eps,
            self.hyper_dtype, name=name)
        h_pre, h_post, h_res = maps("hc_attn")(x)
        y = attn(norm("attn_norm")(hc_read(x, h_pre)), positions,
                 block_diffusion)
        x = _constrained(hc_write(x, y, h_post, h_res))
        h_pre, h_post, h_res = maps("hc_mlp")(x)
        u = norm("mlp_norm")(hc_read(x, h_pre))
        ffn, shared = self._ffn(u.shape[-1])
        y = ffn(u)
        if shared is not None:
            with jax.named_scope("moe/shared"):
                y = y + shared(u)
        return _constrained(hc_write(x, y, h_post, h_res))


def _run(remat: bool, block, *args):
    """``block(*args)``, counted for the run report where it is
    rematerialised (trace time, as ``flash.*`` is): ``remat.blocks``, and of
    them ``remat.flash_kept``, the blocks whose trace ran the flash forward
    rule, and ``remat.kda_kept``, the blocks whose trace ran the KDA op:
    each names what ``Transformer._remat_policy`` keeps.  No method of the
    model: flax would put its name into every scope below."""
    if not remat:
        return block(*args)
    named = {"remat.flash_kept": telemetry.counter("flash.fwd_calls"),
             "remat.kda_kept": telemetry.counter("kda.layers")}
    before = {kept: counter.value() for kept, counter in named.items()}
    out = block(*args)
    telemetry.counter("remat.blocks").inc()
    for kept, counter in named.items():
        telemetry.counter(kept).inc(int(counter.value() > before[kept]))
    return out


class Transformer(nn.Module):
    """Decoder-only LM.  ``__call__(input_ids: [B, S]) -> logits [B, S, V]``.

    Layers may differ inside one model, each by ONE field with an entry a
    layer: ``layer_ffn`` (a dense FFN in place of the experts),
    ``layer_mixer`` (the layer's one mixer) and ``layer_attention`` (what
    a ``Block``'s attention slot holds: the width of the window on its
    causal mask, whether it rotates, and its kind: the model's attention,
    plain or latent, or a Kimi Delta Attention mixer).
    What the experts' router reads is ``moe_router_input``, their form
    ``moe_expert_act``.  The fields below say the rest."""

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
    # large-batch trade on HBM-bound TPUs.  Kept beside a block's input:
    # what its attention kernels name (``_remat_policy``).
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
    # What each layer's ATTENTION slot holds, one entry a layer beside
    # ``layer_ffn``: ``(window, rope)`` or ``(window, rope, kind)``.  The
    # width of the window on the layer's causal mask (0: the full causal
    # mask), whether it turns its queries and keys, and its KIND: ``"own"``
    # (as without the third entry) the model's attention, plain or
    # grouped-query, or latent where ``latent`` is set; ``"latent"`` the
    # same, said by name (it needs ``latent``); ``"kda"`` a Kimi Delta
    # Attention mixer (``KimiDeltaAttention``, sizes in ``kda``), which has
    # neither window nor rotation and is written ``(0, False, "kda")``.
    # None: every layer ``(0, True, "own")``.  SmallThinker's ``sliding_
    # window_layout`` = ``rope_layout`` = 0, 1, 1, 1 with ``sliding_window_
    # size`` 4096 is ``((0, False), (4096, True), (4096, True), (4096,
    # True))``: a global layer without rotation, then three that rotate
    # inside a band.  Kimi-Linear's ``kda_layers`` 1, 2, 3 and ``full_attn_
    # layers`` 4 with ``mla_use_nope`` are three ``(0, False, "kda")`` and a
    # ``(0, False, "latent")``: latent attention that turns nothing.  A
    # window is plain attention's; all of it a ``Block``'s on the training
    # path (``__post_init__``).
    layer_attention: Optional[tuple] = None
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
    # A ``Block``'s experts take ``moe_expert_act`` too (``"swiglu"`` or
    # ``"reglu"``), and ``moe_router_input`` says what their ROUTER reads:
    # ``"ffn"`` the normed state the experts read, or ``"layer"`` the layer's
    # input as it comes, un-normed, before attention (SmallThinker: the
    # experts are known while attention runs; ``MoEMLP``'s ``router_input``).
    layer_mixer: Optional[tuple] = None
    ssm: Optional[tuple] = None
    ssm_state_dtype: Any = jnp.float32      # ``ops/ssd.py``: a check's control
    rope: bool = True
    moe_expert_act: str = "swiglu"
    moe_latent: int = 0
    moe_router_input: str = "ffn"
    # The sizes of the layers whose ``layer_attention`` kind is ``"kda"``:
    # ``(heads, head dim, conv taps, chunk)`` (see ``KimiDeltaAttention``).
    kda: Optional[tuple] = None
    kda_state_dtype: Any = jnp.float32      # ``ops/kda.py``: a check's control
    # Latent attention's query latent and the stretch of the rotary
    # frequencies (see ``Attention``; ``rope_scaling`` as ``rope_frequencies``
    # takes it).
    q_lora_rank: int = 0
    rope_scaling: Optional[tuple] = None
    # A residual path of ``n`` streams mixed by doubly-stochastic maps (mHC):
    # ``(n, Sinkhorn rounds, eps, clamp min, clamp max)``, see
    # ``Block._hyper_connected``.  The embedding is copied into the ``n``
    # streams and the final norm reads their SUM.  None: ``x + F(norm(x))``.
    # ``hyper_dtype``: what the maps are computed in (a check's control).
    hyper: Optional[tuple] = None
    hyper_dtype: Any = jnp.float32
    # Multi-token prediction (DeepSeek-V3, arXiv:2412.19437 §2.2) at depth
    # ``mtp_layers`` (0 or 1): a module ``mtp`` after the trunk, of ONE more
    # layer of the model's last kind between a projection and a norm of its
    # own, that predicts the token after next from the trunk's hidden state
    # and the SHARED embedding of the next token; the model then returns
    # ``(main, mtp)`` and ``make_loss_fn`` adds the second loss through the
    # shared head.  Training path only.
    mtp_layers: int = 0

    def __post_init__(self):
        super().__post_init__()
        # what the code cannot compute says so where the model is BUILT
        if self.hyper and (self.decode or self.layer_mixer or self.sparse
                           or self.attn_impl == "ring"):
            raise NotImplementedError(
                f"hyper={self.hyper} with decode={self.decode}, layer_mixer="
                f"{self.layer_mixer}, sparse={self.sparse}, attn_impl="
                f"{self.attn_impl!r}: the residual streams run through "
                "Block on the training path (no cache of n streams, no "
                "MixerBlock, no indexer, no ring attention)")
        if self.layer_attention is not None and (
                self.decode or self.sparse or self.hyper
                or self.layer_mixer or self.mtp_layers
                or self.attn_impl == "ring"
                or len(self.layer_attention) != self.n_layers):
            raise NotImplementedError(
                f"layer_attention={self.layer_attention} over {self.n_layers} "
                f"layers with decode={self.decode}, sparse={self.sparse}, "
                f"hyper={self.hyper}, layer_mixer={self.layer_mixer}, "
                f"mtp_layers={self.mtp_layers}, attn_impl="
                f"{self.attn_impl!r}: an entry a layer is the attention "
                "slot of a Block on the training path (no cache in which "
                "window layers hold a window or a KDA layer its state and "
                "its convs' last positions, no indexer, no residual "
                "streams, no MTP module and no ring attention's chunks "
                "beside a window or a KDA layer)")
        for entry in self.layer_attention or ():
            window, rope, kind = _attention_entry(entry)
            if kind not in ("own", "latent", "kda") or (
                    kind == "kda" and (self.kda is None or window or rope)
                    ) or (kind == "latent" and self.latent is None
                          ) or (window and kind != "kda" and self.latent):
                raise NotImplementedError(
                    f"layer_attention entry {tuple(entry)} with latent="
                    f"{self.latent}, kda={self.kda}: a kind is 'own', "
                    "'latent' (beside latent=) or 'kda' (beside kda=, "
                    "written (0, False, 'kda'): the delta rule has neither "
                    "window nor rotation), and latent attention takes no "
                    "window")
        if self.kda is not None and not any(
                _attention_entry(entry)[2] == "kda"
                for entry in self.layer_attention or ()):
            raise NotImplementedError(
                f"kda={self.kda} without a 'kda' entry in layer_attention="
                f"{self.layer_attention}: the sizes are those of the layers "
                "that layer_attention names")
        if self.moe_router_input not in ("ffn", "layer") or (
                self.moe_router_input == "layer"
                and (self.hyper or self.layer_mixer
                     or self.moe_capacity_factor is not None)):
            raise NotImplementedError(
                f"moe_router_input={self.moe_router_input!r} with hyper="
                f"{self.hyper}, layer_mixer={self.layer_mixer}, "
                f"moe_capacity_factor={self.moe_capacity_factor}: the router "
                "reads a Block's input ('layer') or what its experts read "
                "('ffn'), one residual stream, dropless routing")
        _yarn_only(self.rope_scaling)
        if self.q_lora_rank and not self.latent:
            raise NotImplementedError(
                f"q_lora_rank={self.q_lora_rank} without latent=: the query "
                "latent is latent attention's (Attention._latent_attention)")
        if self.mtp_layers and (self.mtp_layers > 1 or self.decode
                                or self.layer_mixer or self.sparse):
            raise NotImplementedError(
                f"mtp_layers={self.mtp_layers} with decode={self.decode}, "
                f"layer_mixer={self.layer_mixer}, sparse={self.sparse}: ONE "
                "multi-token-prediction module of a Block, in the training "
                "loss of make_loss_fn (no drafting on the cache path)")

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
        give (``_remat_policy``).  What a recomputation makes again is the
        rest of the block: the norms, ``q/k/v_proj``, RoPE, the head-major
        layouts, ``o_proj``, the router and the experts.  What an attention
        KERNEL alone can make it does not: the policy keeps the flash
        forward's output and log-sum-exp too (``S * H * D_v * 2 B + S * H * 4
        B`` a layer beside the block's input), so the second forward of a
        layer runs no kernel of either family.  A layer's WINDOW
        (``layer_attention``) is static in another way: it is a field of the
        layer's ``Block``, and a rematerialised module's fields are part of
        the module, never traced, so the band's tables are built from it at
        trace time with no ``static_argnums`` of their own."""
        dh = self.d_head or self.d_model // self.n_heads
        dff = self.d_ff or 4 * self.d_model
        if block_diffusion and (self.hyper or self.mtp_layers):
            raise NotImplementedError(
                f"block_diffusion={block_diffusion} with hyper={self.hyper}, "
                f"mtp_layers={self.mtp_layers}: the block-diffusion loss runs "
                "one residual stream and one head pass")
        streams = int(self.hyper[0]) if self.hyper else 1
        emb = nn.Embed(self.vocab_size, self.d_model, name="embed",
                       dtype=self.compute_dtype)
        x = _to_streams(_constrained(emb(input_ids)), streams)
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
                x = _run(self.remat, block_cls(
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
                    moe_latent=self.moe_latent, name=f"block_{i}"),
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
            layer_attention = self.layer_attention or (
                (0, True),) * self.n_layers

            def block(dense, name, attention=(0, True)):
                return block_cls(
                    self.n_heads, dh, dense or dff,
                    0 if dense else self.n_experts, self.moe_top_k,
                    self.rope_theta, self.attn_impl, self.mesh,
                    self.compute_dtype, self.decode, self.max_decode_len,
                    self.norm_eps, self.qk_norm, self.moe_capacity_factor,
                    self.moe_norm_topk_prob, self.n_kv_heads,
                    self.qk_norm_per_head, self.moe_held, self.sparse,
                    self.latent, self.moe_router, self.moe_shared_d_ff,
                    self.q_lora_rank, self.rope_scaling, self.hyper,
                    self.hyper_dtype, attention, self.moe_expert_act,
                    self.moe_router_input, self.kda, self.kda_state_dtype,
                    name=name)

            for i, dense in enumerate(layer_ffn):
                x = _run(self.remat,
                         block(dense, f"block_{i}", layer_attention[i]),
                         x, positions, block_diffusion)
        x = _from_streams(x, streams)
        head = nn.Dense(self.vocab_size, use_bias=False, name="lm_head",
                        dtype=self.compute_dtype)

        def out(hidden):
            if self.return_hidden:
                return hidden
            with jax.named_scope("lm_head_loss"):
                logits = head(hidden)
                return constrain(logits.astype(jnp.float32),
                                 P(BATCH, "sp", None))

        main = out(RMSNorm(self.norm_eps, name="final_norm")(x))
        if not self.mtp_layers:
            return main
        # position i reads the trunk's state h_i (before the final norm) and
        # the embedding of token i + 1 and predicts token i + 2.  The row is
        # rolled, not cut, so the module runs the trunk's shapes: its last
        # position reads token 0 and has no target, and under the causal mask
        # no other position sees it.
        with jax.named_scope("mtp"):
            nxt = emb(jnp.roll(input_ids, -1, axis=1))
            both = jnp.concatenate(
                [RMSNorm(self.norm_eps, name="mtp_hnorm")(x),
                 RMSNorm(self.norm_eps, name="mtp_enorm")(nxt)], axis=-1)
            y = nn.Dense(self.d_model, use_bias=False, name="mtp_eh_proj",
                         dtype=self.compute_dtype)(both)
            y = _to_streams(_constrained(y), streams)
            y = _run(self.remat, block(layer_ffn[-1], "mtp_block"), y,
                     positions, None)
            return main, out(RMSNorm(self.norm_eps, name="mtp_norm")(
                _from_streams(y, streams)))

    def _remat_policy(self):
        """What a rematerialised block keeps besides its input: what its
        attention KERNELS name, of either family, and what the KDA op names.
        A sparse layer emits ``ops/sparse_attention.py``'s names (the
        selection, attention's output and the indexer's loss with its
        gradient: 0.4 GB a layer at 16k), a flash layer ``ops/
        attention.py``'s (the output and its log-sum-exp: ``S * H * D_v * 2 B
        + S * H * 4 B``, 0.12 GB a layer at 16k rows of 28 heads of 128), a
        KDA layer ``ops/kda.py``'s (the op's output, ``S * H * D_v * 2 B``:
        0.13 GB a layer at 16k rows of 32 heads of 128, and from the kernels
        the state every chunk starts from, 0.5 GB; the backward kernel makes
        a chunk's local part again from q, k, v, g and β, which the second
        forward makes), the state-space scan none, and then nothing is kept.
        The second forward runs the projections, convs, norms and experts
        again and none of those kernels, nor the KDA op at all."""
        from tensorflowonspark_tpu.ops import attention, kda, sparse_attention

        return jax.checkpoint_policies.save_only_these_names(
            *sparse_attention.SAVED_NAMES, *attention.SAVED_NAMES,
            *kda.SAVED_NAMES)


@register("transformer")
def build_transformer(config: dict) -> Transformer:
    capacity = config.get("moe_capacity_factor", 1.25)
    held = config.get("moe_held")
    sparse = config.get("sparse_attention")
    latent = config.get("latent_attention")
    router = config.get("moe_router")
    layer_ffn = config.get("layer_ffn")
    layer_mixer = config.get("layer_mixer")
    layer_attention = config.get("layer_attention")
    ssm = config.get("ssm")
    scaling = config.get("rope_scaling")
    hyper = config.get("hyper_connections")
    kda = config.get("kda")
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
        layer_attention=None if layer_attention is None else tuple(
            (int(entry[0]), bool(entry[1]), *(str(k) for k in entry[2:]))
            for entry in layer_attention),
        kda=None if kda is None else tuple(int(kda[key]) for key in (
            "n_heads", "head_dim", "conv_kernel", "chunk_size")),
        kda_state_dtype=jnp.dtype(config.get("kda_state_dtype", "float32")),
        ssm=None if ssm is None else (
            *(int(ssm[key]) for key in (
                "n_heads", "head_dim", "n_groups", "state_size",
                "conv_kernel", "chunk_size")),
            *(float(ssm[key]) for key in ("dt_min", "dt_max", "dt_floor"))),
        ssm_state_dtype=jnp.dtype(config.get("ssm_state_dtype", "float32")),
        rope=bool(config.get("rope", True)),
        moe_expert_act=str(config.get("moe_expert_act", "swiglu")),
        moe_latent=int(config.get("moe_latent", 0)),
        moe_router_input=str(config.get("moe_router_input", "ffn")),
        q_lora_rank=int(config.get("q_lora_rank") or 0),
        rope_scaling=rope_scaling_from_config(scaling),
        hyper=None if hyper is None else (
            int(hyper["hc_mult"]), int(hyper["hc_sinkhorn_iters"]),
            float(hyper["hc_eps"]), float(hyper["mhc_h_res_clamp_min"]),
            float(hyper["mhc_h_res_clamp_max"])),
        hyper_dtype=jnp.dtype(config.get("hyper_dtype", "float32")),
        mtp_layers=int(config.get("num_nextn_predict_layers", 0)),
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
    return (["aux_loss"] + (["moe_stats"] if model.n_experts else [])
            + (["hc_stats"] if model.hyper else []))


@jax.named_scope("loss_terms")
def _with_sown_terms(loss, updates, aux_loss_coef: float,
                     router_z_coef: float):
    """``(total, metrics)``: the LM loss plus the weighted auxiliary terms
    the layers sowed (``aux_loss``), and their ``moe_stats`` averaged over
    layers (see ``make_loss_fn``).  Under the scope ``loss_terms``, which the
    caller keeps a whole component (``_loss_scope``)."""
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
    # a collection's leaves by name over the layers: an error is the worst
    # of them (``hc_stats/res_*_err``), anything else their mean
    for collection, prefix in (("moe_stats", "moe_"), ("hc_stats", "hc_")):
        stats: dict[str, list] = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                updates.get(collection, {}))[0]:
            name = [p.key for p in path if hasattr(p, "key")][-1]
            stats.setdefault(prefix + name, []).append(leaf)
        metrics.update({k: (jnp.max if k.endswith("_err") else jnp.mean)(
            jnp.stack(v)) for k, v in stats.items()})
    return total, metrics


@contextlib.contextmanager
def _loss_scope(outer: str, *scopes: str):
    """``scopes`` as WHOLE components of the op names inside a
    differentiated function, forward and backward.  A transform names itself
    around the OUTERMOST scope it meets (``jvp(lm_loss)/lm_head_loss/...``,
    ``transpose(jvp(mtp_loss))/mtp/lm_head_loss/...``), and a reader of
    device time looks for whole components: so every scope a loss opens has
    ``outer`` around it, which no reader reads."""
    with contextlib.ExitStack() as stack:
        for name in (outer, *scopes):
            stack.enter_context(jax.named_scope(name))
        yield


def make_loss_fn(model: Transformer, aux_loss_coef: float = 0.01,
                 vocab_chunk: int = 0, router_z_coef: float = 1e-3,
                 mtp_coef: float = 0.1):
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
    them.  The loss is not differentiated by them.

    A model with a multi-token-prediction module (``Transformer.mtp_layers``)
    returns a second hidden state (or logits) a position: the SAME head and
    cross-entropy a second time, over targets shifted by TWO (position i
    predicts token i + 2; the row's last two positions have none), under the
    scope ``mtp``, and ``total = main + mtp_coef · mtp_loss`` (DeepSeek-V3
    §2.2, one depth: its λ).  The gradients of the two uses of the embedding
    and of the head are summed by the one ``value_and_grad``.  Metrics carry
    ``mtp_loss``."""

    sown = _sown_collections(model)
    mtp = bool(model.mtp_layers)

    def _variables(params, buffers):
        return ({"params": params} if buffers is None
                else {"params": params, "buffers": buffers})

    def _mean(nll, batch, shift: int):
        """The mean of ``nll`` ``[B, S - shift]`` over the real targets."""
        mask = batch.get("loss_mask")
        if mask is None:
            return jnp.mean(nll)
        mask = mask[:, shift:].astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)

    def _with_mtp(total, metrics, loss):
        """The second loss: the mean over targets 2 .. S - 1."""
        with _loss_scope("mtp_loss", "loss_terms"):
            total = total + mtp_coef * loss
        return total, {**metrics, "mtp_loss": loss}

    if vocab_chunk:
        from tensorflowonspark_tpu.ops.xent import blockwise_cross_entropy

        hidden_model = model.clone(return_hidden=True)

        def _fused_mean(params, h, batch, shift: int):
            """``_mean`` of the cross-entropy of ``h``'s position i against
            token i + shift, the head fused in (``ops/xent.py``)."""
            b, s, d = h.shape
            ids, mask = batch["input_ids"], batch.get("loss_mask")
            if mask is not None:
                mask = mask[:, shift:].reshape(-1).astype(jnp.float32)
            total = blockwise_cross_entropy(
                h[:, :-shift].reshape(b * (s - shift), d),
                params["lm_head"]["kernel"].astype(h.dtype),
                ids[:, shift:].reshape(-1), mask, chunk=vocab_chunk)
            if mask is None:
                return total / (b * (s - shift))
            return total / jnp.maximum(jnp.sum(mask), 1.0)

        def fused_loss_fn(params, batch, buffers=None):
            h, updates = hidden_model.apply(_variables(params, buffers),
                                            batch["input_ids"], mutable=sown)
            if mtp:
                h, h_mtp = h
            with _loss_scope("lm_loss", "lm_head_loss"):
                loss = _fused_mean(params, h, batch, 1)
            with _loss_scope("lm_loss"):
                out = _with_sown_terms(loss, updates, aux_loss_coef,
                                       router_z_coef)
            if not mtp:
                return out
            with _loss_scope("mtp_loss", "mtp", "lm_head_loss"):
                loss = _fused_mean(params, h_mtp, batch, 2)
            return _with_mtp(*out, loss)

        return fused_loss_fn

    def loss_fn(params, batch, buffers=None):
        ids = batch["input_ids"]
        logits, updates = model.apply(_variables(params, buffers), ids,
                                      mutable=sown)
        if mtp:
            logits, logits_mtp = logits
        with _loss_scope("lm_loss", "lm_head_loss"):
            logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
            targets = ids[:, 1:]
            nll = -jnp.take_along_axis(logp, targets[..., None],
                                       axis=-1)[..., 0]
            loss = _mean(nll, batch, 1)
        with _loss_scope("lm_loss"):
            out = _with_sown_terms(loss, updates, aux_loss_coef,
                                   router_z_coef)
        if not mtp:
            return out
        with _loss_scope("mtp_loss", "mtp", "lm_head_loss"):
            logp = jax.nn.log_softmax(logits_mtp[:, :-2].astype(jnp.float32))
            nll = -jnp.take_along_axis(logp, ids[:, 2:, None], axis=-1)[..., 0]
            loss = _mean(nll, batch, 2)
        return _with_mtp(*out, loss)

    return loss_fn


def _one_stream_one_head(model: Transformer, loss: str) -> None:
    if model.hyper or model.mtp_layers:
        raise NotImplementedError(
            f"{loss} with hyper={model.hyper}, mtp_layers={model.mtp_layers}: "
            "the block-diffusion and the sparse losses run one residual "
            "stream and one pass of the head (make_loss_fn has both)")


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

    _one_stream_one_head(model, "make_block_diffusion_loss_fn")
    sown = _sown_collections(model)
    hidden_model = model.clone(return_hidden=True)

    def loss_fn(params, batch):
        ids = batch["input_ids"]
        rows, length = ids.shape
        if length % block:
            raise ValueError(f"rows of {length} tokens in blocks of {block}")
        # one scope around the whole loss: _loss_scope's rule
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
                loss = blockwise_cross_entropy(
                    h, params["lm_head"]["kernel"].astype(h.dtype),
                    ids.reshape(-1), weight,
                    chunk=vocab_chunk) / (rows * length)
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

    _one_stream_one_head(model, "make_sparse_loss_fn")
    sown = _sown_collections(model) + ["dsa_stats"]
    hidden_model = model.clone(return_hidden=True)

    def loss_fn(params, batch):
        ids = batch["input_ids"]
        # one scope around the whole loss: _loss_scope's rule
        with jax.named_scope("sparse_lm"):
            h, updates = hidden_model.apply({"params": params}, ids,
                                            mutable=sown)
            b, s, d = h.shape
            with jax.named_scope("lm_head_loss"):
                loss = blockwise_cross_entropy(
                    h[:, :-1].reshape(b * (s - 1), d),
                    params["lm_head"]["kernel"].astype(h.dtype),
                    ids[:, 1:].reshape(-1),
                    chunk=vocab_chunk) / (b * (s - 1))
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
