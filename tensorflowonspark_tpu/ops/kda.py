"""Kimi Delta Attention's recurrence in its chunked form: a gated delta rule
whose decay is a VECTOR over the key channels (Kimi Linear,
arXiv:2510.26692 §3-4).

The recurrence, per head with ``d_k`` key and ``d_v`` value channels, a
state ``S`` ``[d_k, d_v]`` that is zero before the row, ``α_t = exp(g_t)`` in
``(0, 1]^d_k`` and ``β_t`` in ``[0, 1]``:

    S_t = (I - β_t k_t k_tᵀ) Diag(α_t) S_{t-1} + β_t k_t v_tᵀ
    o_t = S_tᵀ q_t

(the state decays a channel at a time, the delta rule then erases what the
state holds under ``k_t`` and writes ``v_t`` there).  ``ops/ssd.py``'s
chunked form rests on ONE decay a head and position; here a chunk's scores
are products only after the decay is folded into the operands.  ``kda_scan``
computes a chunk of ``chunk`` positions at a time.  With ``G_t = Σ_{s<=t in
the chunk} g_s`` (a vector) and a chunk that starts from the state ``S``:

    A  = strict_lower[ β_t Σ_c k_tc k_sc exp(G_tc - G_sc) ]      [chunk, chunk]
    T  = (I + A)^-1 Diag(β)
    W  = T (K ⊙ exp(G)),  U = T V,  Ũ = U - W S
    O  = (Q ⊙ exp(G)) S + lower[ Σ_c q_tc k_sc exp(G_tc - G_sc) ] Ũ
    S' = Diag(exp(G_end)) S + (K ⊙ exp(G_end - G))ᵀ Ũ

(the WY / UT transform of the delta rule: ``Ũ`` are the chunk's values as
the erasures before them leave them, ``(I + A) Ũ = Diag(β)(V - (K ⊙ exp(G))
S)``).  Everything but ``Ũ``, ``O`` and ``S'`` is independent of ``S`` and is
computed for many chunks at once; a ``lax.scan`` over the ``L / chunk`` chunks
carries ``S`` (256 steps at 16,384 positions, none over positions).

Every exponent above is <= 0, and so is every one the op takes.  ``exp(G_t -
G_s)`` inside a product is never split through ``exp(-G)``, which overflows
float32 after four positions of a channel that decays by ``e^-20`` a step.
A chunk's lower triangle of scores is HALVED again and again instead
(``_chunk_scores``): a block of ``2w`` positions gives its lower-left ``w x
w`` square as the product of the later half's ``x_t ⊙ exp(G_t - R)`` and the
earlier half's ``k_s ⊙ exp(R - G_s)``, ``R`` the running sum at the later
half's first position, so ``G_t <= R <= G_s`` and both exponents are <= 0;
``w`` runs from ``chunk / 2`` down to 1 (six levels at 64, each position an
operand once a level) and the diagonal is ``x_t · k_t``.  ``(I + A)^-1``: the
``sub``-wide diagonal blocks as the finite product ``(I + N)(I + N²)(I +
N⁴)..`` of ``N = -A`` (nilpotent: ``N^sub = 0``), then merged two by two,
``[[P1, 0], [-P2 A21 P1, P2]]``; a product over the whole chunk would sum
binomials of 63 with alternating signs.

Precision: ``g``, ``G``, every ``exp``, the scores' diagonal, the inverse
and the carried state are float32; the other products take operands
in ``q``'s dtype (bf16 in the models) and accumulate in float32 (``ops/
ssd.py``'s rule).  ``state_dtype`` is for the checks' control only: bf16
rounds the running sums, the decays and the carried state as variables of
that type would hold them.

Backward: plain autodiff of the chunked form under ``jax.checkpoint``: the
residuals are the INPUTS (q, k, v, g, β); the backward runs the forward
again and keeps the chunk states and ``Ũ`` while it runs, a group of 8 heads
at a time, nothing across layers.  What does not depend on the state (the
scores, the inverse, ``W``, ``U``) is made for 32 chunks at a time under a
``jax.checkpoint`` of its own (``lax.map``): its float32 intermediates are
never held for a whole row, and the backward makes a group's again.  The
output carries the name ``SAVED_NAMES`` for a rematerialised layer's policy
(``Transformer._remat_policy``).  Plain XLA, no Pallas kernel (why: PERF.md
§6, PR 52).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from tensorflowonspark_tpu.ops.ssd import _held_in

# What a rematerialised layer may keep of the op: its output ``[B, L, H,
# d_v]`` (``Transformer._remat_policy``), so the layer's second forward runs
# the chunked form once (inside the op's own backward) and not twice.
SAVED_NAMES = ("kda_out",)

# Chunks whose scores, inverse, W and U are in memory at once (32 heads of
# 32 chunks of 64 x 128: 34 MB an operand in float32).
_GROUP = 32
# Heads whose recurrence over chunks runs, and is differentiated, together.
_HEADS = 8


def kda_scan(q, k, v, g, beta, *, chunk: int = 64, sub: int | None = None,
             state_dtype=jnp.float32):
    """``o`` ``[B, L, H, d_v]`` of the recurrence above.

    ``q``, ``k`` ``[B, L, H, d_k]`` as the recurrence takes them (the L2
    norm and ``q``'s scale are the caller's); ``v`` ``[B, L, H, d_v]``; ``g``
    ``[B, L, H, d_k]`` the log decay, <= 0; ``beta`` ``[B, L, H]``.  ``L``
    has to be a multiple of ``chunk`` (a ragged tail would be a second
    program shape) and ``chunk`` of ``sub``, the width of the inverse's
    diagonal blocks (None: 16, or the chunk where that is narrower)."""
    length = q.shape[1]
    sub = min(16, chunk) if sub is None else sub
    if length % chunk:
        raise ValueError(
            f"kda_scan: a sequence of {length} positions is no multiple of "
            f"chunk {chunk}")
    if chunk % sub or sub & (sub - 1) or chunk & (chunk - 1):
        raise ValueError(
            f"kda_scan: chunk {chunk} in sub-blocks of {sub}: both have to "
            "be powers of two and sub divide the chunk")
    if (k.shape != q.shape or g.shape != q.shape or v.shape[:3] != q.shape[:3]
            or beta.shape != q.shape[:3]):
        raise ValueError(
            f"kda_scan: q {q.shape}, k {k.shape}, v {v.shape}, g {g.shape}, "
            f"beta {beta.shape}")
    # a group of heads at a time, each under its own ``jax.checkpoint``: a
    # group's backward holds its own chunk states and ``Ũ``, not all heads'
    heads = q.shape[2]
    group = _largest_divisor(heads, _HEADS)
    out = [_kda_chunked(*(t[:, :, at:at + group] for t in (q, k, v, g, beta)),
                        chunk, sub, state_dtype)
           for at in range(0, heads, group)]
    return checkpoint_name(
        out[0] if len(out) == 1 else jnp.concatenate(out, axis=2),
        SAVED_NAMES[0])


def _largest_divisor(n: int, at_most: int) -> int:
    return max(d for d in range(1, min(n, at_most) + 1) if n % d == 0)


def _dot(a, b, spec: str):
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def _dot32(a, b):
    """``a @ b`` over the trailing two axes in float32 proper (the MXU's
    default rounds float32 operands to bf16)."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _inverse_unit_lower(a, sub: int):
    """``(I + a)^-1`` of a strictly lower triangular ``a`` ``[.., n, n]``,
    float32: blocks of ``sub`` by the nilpotent product, merged in halves."""
    n = a.shape[-1]
    if n == sub:
        neg = -a
        inv = jnp.eye(n, dtype=a.dtype) + neg
        for _ in range(max(sub.bit_length() - 2, 0)):   # N², N⁴, .. N^(sub/2)
            neg = _dot32(neg, neg)
            inv = inv + _dot32(inv, neg)
        return inv
    half = n // 2
    first = _inverse_unit_lower(a[..., :half, :half], sub)
    second = _inverse_unit_lower(a[..., half:, half:], sub)
    low = -_dot32(_dot32(second, a[..., half:, :half]), first)
    return jnp.concatenate([
        jnp.concatenate([first, jnp.zeros_like(low).swapaxes(-1, -2)], -1),
        jnp.concatenate([low, second], -1)], -2)


def _chunk_scores(q, k, cum, held):
    """``Σ_c x_tc k_sc exp(G_tc - G_sc)`` for ``x`` = q and k, ``s <= t``
    inside a chunk, 0 above the diagonal: ``[2, .., C, C]`` float32 from
    ``q``, ``k`` ``[.., C, d_k]`` and the running sums ``cum``.

    The lower triangle is halved again and again: at the level of half-width
    ``w`` a block of ``2w`` positions gives its lower-left ``w x w`` square,
    the later half's ``x_t ⊙ exp(G_t - R)`` times the earlier half's ``k_s ⊙
    exp(R - G_s)`` with ``R`` the running sum at the later half's first
    position (``G_t <= R <= G_s``: both exponents <= 0); ``w`` = C/2 .. 1,
    and the diagonal itself is ``x_t · k_t``."""
    lead, chunk, dk = q.shape[:-2], q.shape[-2], q.shape[-1]
    f32, cdt = jnp.float32, q.dtype
    x = jnp.stack([q, k])                                   # [2, .., C, dk]
    total = (jnp.sum(x.astype(f32) * k.astype(f32), axis=-1)[..., None]
             * jnp.eye(chunk, dtype=f32))
    width = chunk // 2
    while width:
        nb = chunk // (2 * width)
        halves = lambda t: t.reshape(                       # noqa: E731
            t.shape[:-2] + (nb, 2, width, dk))
        cum_h, x_h, k_h = halves(cum), halves(x), halves(k)
        ref = cum_h[..., 1, :1, :]                          # [.., nb, 1, dk]
        later = (x_h[..., 1, :, :].astype(f32)
                 * held(jnp.exp(cum_h[..., 1, :, :] - ref))).astype(cdt)
        earlier = (k_h[..., 0, :, :].astype(f32)
                   * held(jnp.exp(ref - cum_h[..., 0, :, :]))).astype(cdt)
        square = _dot(later, jnp.broadcast_to(earlier, later.shape),
                      "...td,...sd->...ts")                 # [2,..,nb,w,w]
        # to its place: rows of the later half, columns of the earlier one,
        # of the same block
        placed = (square[..., :, None, :, None, None, :]
                  * jnp.eye(nb, dtype=f32)[:, None, None, :, None, None])
        pad = [(0, 0)] * placed.ndim
        pad[-5], pad[-2] = (1, 0), (0, 1)
        total = total + jnp.pad(placed, pad).reshape(total.shape)
        width //= 2
    return total


def _before_the_carry(q, k, v, g, beta, sub, state_dtype):
    """What a group of chunks ``[n, B, H, C, ·]`` hands the recurrence over
    chunks, none of it a function of the carried state: ``(W above Q ⊙
    exp(G), U, K ⊙ exp(G_end - G), the lower scores of q and k,
    exp(G_end))``."""
    cdt, f32 = q.dtype, jnp.float32
    chunk = q.shape[-2]
    held = functools.partial(_held_in, dtype=state_dtype)
    with jax.named_scope("kda_op/decay"):
        cum = held(jnp.cumsum(g, axis=-2))
        total = cum[..., -1:, :]                            # [n,B,H,1,dk]
        from_start = held(jnp.exp(cum))
        to_end = held(jnp.exp(total - cum))
        end = held(jnp.exp(total[..., 0, :]))
    with jax.named_scope("kda_op/intra"):
        scores = _chunk_scores(q, k, cum, held)
        strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
        a = jnp.where(strict, scores[1], 0.0) * beta[..., None]
        qk = scores[0].astype(cdt)          # lower, its diagonal included
    with jax.named_scope("kda_op/solve"):
        t = (_inverse_unit_lower(a, sub) * beta[..., None, :]).astype(cdt)
        w = _dot(t, (k.astype(f32) * from_start).astype(cdt),
                 "...ts,...sd->...td").astype(cdt)
        u = _dot(t, v, "...ts,...sd->...td")                # float32
    q_in = (q.astype(f32) * from_start).astype(cdt)
    k_out = (k.astype(f32) * to_end).astype(cdt)
    # W above Q ⊙ exp(G): the state meets both in ONE product a chunk
    return jnp.concatenate([w, q_in], axis=-2), u, k_out, qk, end


@functools.partial(jax.checkpoint, static_argnums=(5, 6, 7))
def _kda_chunked(q, k, v, g, beta, chunk, sub, state_dtype):
    b, length, h, dk = q.shape
    dv = v.shape[-1]
    nc, cdt, f32 = length // chunk, q.dtype, jnp.float32
    held = functools.partial(_held_in, dtype=state_dtype)
    # groups of chunks, chunk-major, then head: the trailing two dimensions
    # of every operand are a chunk's [positions, channels]
    group = _largest_divisor(nc, _GROUP)
    chunks = lambda t: t.reshape(               # noqa: E731
        (b, nc // group, group, chunk, h) + t.shape[3:]).transpose(
            (1, 2, 0, 4, 3) + tuple(range(5, t.ndim + 2)))
    # a group at a time, its intermediates made again in the backward: what
    # is held for all chunks at once is what the recurrence reads
    before = jax.lax.map(
        jax.checkpoint(lambda xs: _before_the_carry(*xs, sub, state_dtype)),
        (chunks(q), chunks(k), chunks(v), chunks(g.astype(f32)),
         chunks(beta.astype(f32))))
    before = jax.tree.map(lambda t: t.reshape((nc,) + t.shape[2:]), before)
    with jax.named_scope("kda_op/inter"):
        def carry(state, inputs):           # state [B, H, dk, dv] float32
            wq_c, u_c, k_c, qk_c, end_c = inputs
            from_state = _dot(wq_c, state.astype(cdt), "...tk,...kv->...tv")
            u_left = (u_c - from_state[..., :chunk, :]).astype(cdt)     # Ũ
            out = (from_state[..., chunk:, :]
                   + _dot(qk_c, u_left, "...ts,...sv->...tv"))
            state = held(end_c[..., None] * state
                         + _dot(k_c, u_left, "...sk,...sv->...kv"))
            return state, out.astype(cdt)

        _, out = jax.lax.scan(carry, jnp.zeros((b, h, dk, dv), f32), before)
    return out.transpose(1, 0, 3, 2, 4).reshape(b, length, h, dv)
