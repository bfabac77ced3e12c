"""Mamba-2's selective state-space scan in its chunked (state-space-duality)
form, and the depthwise causal conv that feeds it.

The recurrence (Dao & Gu, arXiv:2405.21060), per head ``h`` with ``P``
channels and a state of ``N`` columns, ``a = -exp(A_log)`` a head:

    S_t = exp(Δ_t a) · S_{t-1} + Δ_t · x_t ⊗ B_t          S: [P, N]
    y_t = S_t · C_t + D · x_t

``B`` and ``C`` are shared by the heads of a group (head ``j`` reads group
``j // (H / G)``).  ``ssd_scan`` computes it a chunk of ``chunk`` positions
at a time, as four products on the MXU instead of ``L`` sequential updates.
With ``l_t = Δ_t a`` and ``cum`` its running sum inside a chunk:

- inside a chunk, ``y_t += Σ_{s<=t} exp(cum_t - cum_s) (C_t · B_s) Δ_s x_s``:
  the masked, decay-weighted ``C·Bᵀ`` (one ``[chunk, chunk]`` matrix a
  GROUP, weighted a head) times ``x``;
- the chunk's own state, ``Σ_s exp(cum_end - cum_s) Δ_s x_s ⊗ B_s``;
- the ``L / chunk`` chunk states carried by a short recurrence,
  ``S_c = exp(cum_end) S_{c-1} + state_c`` (``lax.scan`` over chunks: 64
  steps at 8,192 positions, none over positions);
- what the state before the chunk adds, ``exp(cum_t) C_t · S_{c-1}``.

Precision: the products take operands in ``x``'s dtype (bf16 in the models)
and accumulate in float32; ``Δ``, ``l``, the running sums, every ``exp`` and
the carried state are float32.  ``state_dtype`` is there for the checks'
control only: bf16 rounds the running sums, the decays and the carried state
as variables of that type would hold them, which a check has to catch (a
running sum of 50 holds a quarter in bf16, and the decay ``exp`` of it is
then off by a quarter of itself).

Backward: plain autodiff of the chunked form under ``jax.checkpoint``: the
residuals are the INPUTS (x, Δ, B, C), the backward runs the forward's four
products again and keeps the ``L / chunk`` chunk states, never ``L`` states
and none of the ``[chunks, heads, chunk, chunk]`` float32 decay matrices
across layers.  The scan is plain XLA (why no Pallas kernel: PERF.md §6).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def causal_conv1d(x, kernel, bias):
    """Depthwise causal conv over the sequence: ``x`` ``[B, L, C]``,
    ``kernel`` ``[K, C]`` (tap ``K - 1`` meets the position itself, tap 0
    the one ``K - 1`` before it), ``bias`` ``[C]``: ``y_t = Σ_k kernel[k] ·
    x_{t - (K-1) + k} + bias``, zeros before the sequence.  ``K`` shifted
    multiply-adds that XLA fuses into one pass; float32 accumulation."""
    taps, length = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    out = bias.astype(jnp.float32)
    for k in range(taps):
        out = out + (padded[:, k:k + length].astype(jnp.float32)
                     * kernel[k].astype(jnp.float32))
    return out.astype(x.dtype)


def ssd_scan(x, dt, a, b, c, d, *, chunk: int = 128,
             state_dtype=jnp.float32):
    """``y`` ``[B, L, H, P]`` of the recurrence above.

    ``x`` ``[B, L, H, P]``; ``dt`` ``[B, L, H]`` = Δ, already positive
    (softplus applied); ``a`` ``[H]`` negative; ``b``, ``c`` ``[B, L, G, N]``
    with ``G`` dividing ``H``; ``d`` ``[H]``.  ``L`` has to be a multiple of
    ``chunk``: a ragged tail would be a second program shape."""
    length, heads, groups = x.shape[1], x.shape[2], b.shape[2]
    if length % chunk:
        raise ValueError(
            f"ssd_scan: a sequence of {length} positions is no multiple of "
            f"chunk_size {chunk}")
    if heads % groups or dt.shape != x.shape[:3] or b.shape != c.shape:
        raise ValueError(
            f"ssd_scan: x {x.shape}, dt {dt.shape}, B {b.shape}, C {c.shape}:"
            f" {heads} heads over {groups} groups")
    return _ssd_chunked(x, dt, a, b, c, d, chunk, state_dtype)


def _held_in(x, dtype):
    """Float32 ``x`` as a variable of ``dtype`` would hold it (float32: as
    it is).  ``reduce_precision``, not a cast there and back, which XLA
    removes on the chip (PERF.md §6, PR 33)."""
    if jnp.dtype(dtype) == jnp.float32:
        return x
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


@functools.partial(jax.checkpoint, static_argnums=(6, 7))
def _ssd_chunked(x, dt, a, b, c, d, chunk, state_dtype):
    bsz, length, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    per = heads // groups                       # heads a group
    nc, cdt, f32 = length // chunk, x.dtype, jnp.float32
    # head-major, a head as (group, head of the group): the trailing two
    # dimensions of every operand are a chunk's [positions, channels]
    xs = x.reshape(bsz, nc, chunk, groups, per, p).transpose(0, 1, 3, 4, 2, 5)
    bs = b.reshape(bsz, nc, chunk, groups, n).transpose(0, 1, 3, 2, 4)
    cs = c.reshape(bsz, nc, chunk, groups, n).transpose(0, 1, 3, 2, 4)
    dts = (dt.astype(f32).reshape(bsz, nc, chunk, groups, per)
           .transpose(0, 1, 3, 4, 2))                       # [B,nc,G,K,Q]
    held = functools.partial(_held_in, dtype=state_dtype)
    with jax.named_scope("ssd/decay"):
        log_decay = held(dts * a.astype(f32).reshape(groups, per, 1))
        cum = held(jnp.cumsum(log_decay, axis=-1))
        total = cum[..., -1]                                # [B, nc, G, K]
        # exp(cum_t - cum_s) for s <= t, 0 above the diagonal (masked
        # BEFORE the exp: above it the difference is positive)
        diff = cum[..., :, None] - cum[..., None, :]        # [B,nc,G,K,Q,S]
        lower = jnp.tril(jnp.ones((chunk, chunk), bool))
        within = held(jnp.exp(jnp.where(lower, diff, -jnp.inf)))
        to_end = held(jnp.exp(total[..., None] - cum))      # [B,nc,G,K,Q]
        from_start = held(jnp.exp(cum))
    with jax.named_scope("ssd/intra"):
        cb = jnp.einsum("bcgqn,bcgsn->bcgqs", cs, bs,
                        preferred_element_type=f32)
        weight = cb[:, :, :, None] * within * dts[..., None, :]
        y = jnp.einsum("bcgkqs,bcgksp->bcgkqp", weight.astype(cdt), xs,
                       preferred_element_type=f32)
    with jax.named_scope("ssd/state"):
        x_decayed = xs.astype(f32) * (to_end * dts)[..., None]
        states = jnp.einsum("bcgksp,bcgsn->bcgkpn", x_decayed.astype(cdt),
                            bs, preferred_element_type=f32)

        def carry(s_prev, inputs):
            state, decay = inputs
            s = held(decay[..., None, None] * s_prev + state)
            return s, s_prev                    # the state BEFORE the chunk

        _, before = jax.lax.scan(
            carry, jnp.zeros((bsz, groups, per, p, n), f32),
            (states.swapaxes(0, 1), held(jnp.exp(total)).swapaxes(0, 1)))
        before = before.swapaxes(0, 1)                      # [B,nc,G,K,P,N]
    with jax.named_scope("ssd/inter"):
        y = y + (jnp.einsum("bcgqn,bcgkpn->bcgkqp", cs, before.astype(cdt),
                            preferred_element_type=f32)
                 * from_start[..., None])
    y = y + xs.astype(f32) * d.astype(f32).reshape(groups, per, 1, 1)
    return (y.transpose(0, 1, 4, 2, 3, 5).reshape(bsz, length, heads, p)
            .astype(cdt))
