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
computed for many chunks at once; the ``L / chunk`` chunks are walked in
order carrying ``S`` (256 steps at 16,384 positions, none over positions).

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

Two implementations of the same mathematics, chosen by ``impl`` (a model's
``attn_impl``):

- **Pallas kernels** (``"pallas"``; on a TPU what ``"auto"`` takes): a grid
  over ``(batch, head, 256 positions)``, the positions in order, reads q, k,
  v and g as column blocks of ``[B, L, H·d]`` as the mixer holds them, β as
  ``[B, H, L]``, and carries the state ``[d_v, d_k]`` (transposed: a key
  channel's decay scales a lane) in VMEM.  A grid step makes the local parts
  of its four chunks SIDE BY SIDE (their products do not wait for each
  other), the 64-wide squares of two chunks in one vreg row; none of it
  leaves VMEM.  Where the XLA form halves the triangle down to 1, the
  kernels halve it to blocks of 8 (three products a chunk) and exponentiate
  the diagonal blocks pair by pair, ``exp(G_t - G_s)`` for one column of
  every block at a time, in float32: in VMEM that costs a vreg a block.
  The inverse is the same nilpotent product and two-by-two merge, its
  float32 products as three bf16 passes in one product three times as deep
  (head·head + tail·head + head·tail, error 2^-16).  ``kda_fwd`` writes the
  output and the state every chunk starts from (float32, ``L / chunk * d_k
  * d_v``: 0.5 GB a layer at 16k rows of 32 heads; the choice against a
  state-only pass in the backward, which costs about a forward kernel,
  PERF.md §6, PR 54); ``kda_bwd`` walks the row backwards carrying the
  state's cotangent, makes a chunk's local part again ONCE from q, k, v, g,
  β, takes ``Ũ`` from the kept state, and writes dq, dk, dv, dg, dβ in the
  operands' own layout: the gradient through the inverse is ``-Mᵀ dM Mᵀ``
  on float32 tiles; every decay is ``exp(G_t - ·)`` or ``exp(· - G_s)``, so
  ``dG = q ⊙ dq + k ⊙ (dk₊ - dk₋)`` with the chunk-end term on the last
  row, and ``dg`` its reverse running sum inside the chunk.  Both are named
  for a rematerialised layer's policy (``SAVED_NAMES``): the layer's second
  forward runs neither kernel.  ``"pallas_interpret"`` runs them in
  interpreter mode (the CPU tests).  Shapes they do not tile (a chunk under
  16 positions; on the chip a head that is no multiple of 128 lanes) take
  the XLA form.
- **The chunked XLA form** (``"xla"``; off a TPU what ``"auto"`` takes; the
  kernels' oracle): plain autodiff under ``jax.checkpoint``: the residuals
  are the INPUTS (q, k, v, g, β); the backward runs the forward again and
  keeps the chunk states and ``Ũ`` while it runs, a group of 8 heads at a
  time, nothing across layers.  What does not depend on the state (the
  scores, the inverse, ``W``, ``U``) is made for 32 chunks at a time under a
  ``jax.checkpoint`` of its own (``lax.map``): its float32 intermediates are
  never held for a whole row, and the backward makes a group's again.  It
  names its output alone.
"""

from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensorflowonspark_tpu import telemetry
from tensorflowonspark_tpu.ops.ssd import _held_in

# What a rematerialised layer may keep of the op
# (``Transformer._remat_policy``): its output ``[B, L, H, d_v]`` and, from the
# kernels, the state every chunk starts from ``[B, H, L / chunk, d_v, d_k]``
# float32, so the layer's second forward runs no kernel (the XLA form: its
# chunked form once, inside the op's own backward, and not twice).
SAVED_NAMES = ("kda_out", "kda_states")

# Chunks whose scores, inverse, W and U are in memory at once (32 heads of
# 32 chunks of 64 x 128: 34 MB an operand in float32).
_GROUP = 32
# Heads whose recurrence over chunks runs, and is differentiated, together.
_HEADS = 8


def kda_scan(q, k, v, g, beta, *, chunk: int = 64, sub: int | None = None,
             state_dtype=jnp.float32, impl: str | None = None):
    """``o`` ``[B, L, H, d_v]`` of the recurrence above.

    ``q``, ``k`` ``[B, L, H, d_k]`` as the recurrence takes them (the L2
    norm and ``q``'s scale are the caller's); ``v`` ``[B, L, H, d_v]``; ``g``
    ``[B, L, H, d_k]`` the log decay, <= 0; ``beta`` ``[B, L, H]``.  ``L``
    has to be a multiple of ``chunk`` (a ragged tail would be a second
    program shape) and ``chunk`` of ``sub``, the width of the XLA form's
    inverse's diagonal blocks (None: 16, or the chunk where that is
    narrower).  ``impl``: ``"pallas"`` the kernels, ``"pallas_interpret"``
    the kernels in interpreter mode (CPU tests), ``"xla"`` the chunked XLA
    form; None or ``"auto"`` the kernels on a TPU, the XLA form elsewhere.  A
    shape the kernels do not tile (``_kernels_tile``) takes the XLA form."""
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
    if impl in (None, "auto"):
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in ("pallas", "pallas_interpret", "xla"):
        raise ValueError(f"kda_scan: unknown impl {impl!r}")
    if impl != "xla" and _kernels_tile(q, v, chunk, impl == "pallas"):
        # layers traced whose op took the kernels (``kda.layers`` counts all)
        telemetry.counter("kda.kernel_layers").inc()
        return _kda_kernels(q, k, v, g.astype(jnp.float32),
                            beta.astype(jnp.float32), chunk,
                            jnp.dtype(state_dtype), impl == "pallas_interpret")
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


# ---------------------------------------------------------------------------
# The kernels: a (batch, head) at a time, chunks in order, everything a chunk
# makes in VMEM.
# ---------------------------------------------------------------------------

# Positions a grid step (four chunks of 64): the DMAs' size, and how many
# chunks' local parts are made side by side (their products are
# independent: what one waits for, another computes).
_BLOCK = 256
# Width of the scores' diagonal blocks, whose decays are exponentiated pair
# by pair (one float32 vreg of 8 x 128 a block and column), and of the
# inverse's diagonal blocks.
_DIAG = 8
_VMEM_LIMIT = 64 * 1024 * 1024

# products over a leading axis: a @ b, a @ b.T, a.T @ b
_NN = (((2,), (1,)), ((0,), (0,)))
_NT = (((2,), (2,)), ((0,), (0,)))
_TN = (((1,), (1,)), ((0,), (0,)))
# and of one chunk
_NN2 = (((1,), (0,)), ((), ()))
_NT2 = (((1,), (1,)), ((), ()))
_TN2 = (((0,), (0,)), ((), ()))


def _kernels_tile(q, v, chunk: int, on_chip: bool) -> bool:
    """Whether the kernels take these shapes: a chunk of whole bf16 tiles
    (16 rows) and, compiled for the chip, heads of whole lanes."""
    if chunk < 2 * _DIAG:
        return False
    return not on_chip or (q.shape[-1] % 128 == 0 and v.shape[-1] % 128 == 0)


def _positions_a_step(length: int, chunk: int) -> int:
    chunks = length // chunk
    return chunk * _largest_divisor(chunks, max(_BLOCK // chunk, 1))


def _mm(a, b, dims=_NN):
    """A product on the MXU: operands as they are, float32 out."""
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _rounder(state_dtype):
    """``_held_in`` inside a kernel (Mosaic has no ``reduce_precision``; it
    keeps a cast there and back)."""
    if jnp.dtype(state_dtype) == jnp.float32:
        return lambda x: x
    return lambda x: x.astype(state_dtype).astype(jnp.float32)


# A chunk's square matrices (the scores, ``A``, its inverse) are 64 wide,
# half a vreg's lanes: the kernels hold those of TWO neighbouring chunks side
# by side, ``[pairs, C, 2C]``, the first chunk's in the left half.  What is
# as wide as a head (``[chunks, C, d]``) stays a chunk at a time.

def _masks(chunk: int, dk: int):
    """The index maps the kernels compare, made once a grid step: of a pair's
    ``[C, 2C]`` (``col`` the column inside its own chunk, ``second`` the
    second chunk's half) and of a chunk's rows ``[C, d_k]`` (``at``)."""
    i32 = jnp.int32
    row = jax.lax.broadcasted_iota(i32, (chunk, 2 * chunk), 0)
    lane = jax.lax.broadcasted_iota(i32, (chunk, 2 * chunk), 1)
    col = lane & (chunk - 1)
    return types.SimpleNamespace(
        row=row, col=col, second=lane >= chunk, lower=col <= row,
        strict=col < row, eye=col == row,
        apart=row ^ col,                # < w: inside one block of w
        # the first column of a row's diagonal block
        first=row - (row & (_DIAG - 1)),
        at=jax.lax.broadcasted_iota(i32, (chunk, dk), 0))


def _pair(x):
    """``[2p, ..]`` a chunk as each pair's ``(first, second)`` ``[p, ..]``."""
    x = x.reshape((x.shape[0] // 2, 2) + x.shape[1:])
    return x[:, 0], x[:, 1]


def _unpair(first, second):
    return jnp.stack([first, second], axis=1).reshape(
        (-1,) + first.shape[1:])


def _stacked(x):
    """``[2p, C, d]`` as ``[p, 2C, d]``: a pair's rows, first chunk above."""
    return x.reshape(x.shape[0] // 2, 2 * x.shape[1], x.shape[2])


def _beside(m, first, second):
    """``[p, C, 2C]`` from a result that is right in its left half and one
    that is right in its right half (or from a column each)."""
    return jnp.where(m.second, second, first)


def _halved(m, x):
    """A pair's ``[p, C, 2C]`` with the other chunk's half zeroed: ``(first
    chunk's kept, second chunk's kept)``."""
    return jnp.where(m.second, 0, x), jnp.where(m.second, x, 0)


def _a_chunk(m, x, c: int):
    """Chunk ``c``'s square out of the pairs', the other half zeroed: a left
    operand whose right one holds the chunk's rows in both halves."""
    return _halved(m, x[c // 2])[c % 2]


def _twice(x):
    return jnp.concatenate([x, x], axis=0)


def _down(m, row):
    """``[p, 1, 2C]``, a value a position along the lanes, as a column a
    chunk ``[2p, C, 1]`` (and ``_along`` back): through the diagonal."""
    first, second = _halved(m, jnp.where(m.eye, row, 0.0))
    return _unpair(jnp.sum(first, axis=2, keepdims=True),
                   jnp.sum(second, axis=2, keepdims=True))


def _along(m, column):
    return jnp.sum(jnp.where(m.eye, _beside(m, *_pair(column)), 0.0),
                   axis=1, keepdims=True)


def _mm32(m, a, b, form: str):
    """A chunk's product of two float32 squares, pairs side by side, in
    float32: each operand as a bf16 head and tail, the three products that
    matter (the tails' own is below 2^-16 of the result) as ONE product
    three times as deep, accumulated in float32.  ``form``:
    ``"nn"`` a @ b, ``"nt"`` a @ b.T, ``"tn"`` a.T @ b."""
    def head_tail(x):
        head = x.astype(jnp.bfloat16)
        return head, (x - head.astype(jnp.float32)).astype(jnp.bfloat16)

    (a_head, a_tail), (b_head, b_tail) = head_tail(a), head_tail(b)
    if form == "tn":    # over the rows: the pair's own blocks of [2C, 2C]
        out = _mm(jnp.concatenate([a_head, a_tail, a_head], axis=1),
                  jnp.concatenate([b_head, b_head, b_tail], axis=1), _TN)
        chunk = a.shape[1]
        return _beside(m, out[:, :chunk], out[:, chunk:])
    # over the lanes: b block-diagonal, [[b1, 0], [0, b2]]
    b_head, b_tail = (jnp.concatenate(_halved(m, y), axis=1)
                      for y in (b_head, b_tail))
    return _mm(jnp.concatenate([a_head, a_tail, a_head], axis=2),
               jnp.concatenate([b_head, b_head, b_tail],
                               axis=1 if form == "nn" else 2),
               _NN if form == "nn" else _NT)


def _summed(ones, x):
    """``ones @ x`` a chunk for a 0/1 matrix ``[C, C]`` and float32 ``x``
    ``[n, C, d]``, exact: ``x`` as three bf16 parts whose sum it is (a 0/1
    matrix times each is exact) in ONE product three times as deep: ``x``'s
    running sum in float32."""
    parts = []
    for _ in range(3):
        parts.append(x.astype(jnp.bfloat16))
        x = x - parts[-1].astype(jnp.float32)
    ones = ones.astype(jnp.bfloat16)
    return _mm(jnp.broadcast_to(jnp.concatenate([ones] * 3, axis=1),
                                x.shape[:1] + (ones.shape[0],
                                               3 * ones.shape[1])),
               jnp.concatenate(parts, axis=1))


def _halves(m, g_sum, q32, k32, width, held, cdt):
    """One level of the halved triangle: in every block of ``2 width``
    positions the later half's ``x_t ⊙ exp(G_t - R)`` (q above k) and the
    earlier half's ``k_s ⊙ exp(R - G_s)``, ``R`` the running sum at the later
    half's first position; rows of the other half are 0."""
    n, chunk, dk = g_sum.shape
    blocks = chunk // (2 * width)
    ref = jnp.broadcast_to(
        g_sum.reshape(n, blocks, 2 * width, dk)[:, :, width:width + 1],
        (n, blocks, 2 * width, dk)).reshape(n, chunk, dk)
    later = (m.at & width) != 0
    e = held(jnp.exp(jnp.where(later, g_sum - ref, ref - g_sum)))
    ke = k32 * e
    left = jnp.concatenate([jnp.where(later, q32 * e, 0.0).astype(cdt),
                            jnp.where(later, ke, 0.0).astype(cdt)], axis=1)
    right = jnp.where(later, 0.0, ke).astype(cdt)
    return later, e, left, right, width


def _same(m, chunk: int, width: int, x):
    """``x`` inside the blocks of ``2 width`` positions, 0 elsewhere."""
    return x if 2 * width == chunk else jnp.where(m.apart < 2 * width, x, 0.0)


def _pairs(g3, k3, jj, held):
    """Column ``jj`` of every diagonal block: ``exp(G_t - G_s)`` for ``s``
    the block's ``jj``-th position, pair by pair (1 above the diagonal,
    where the scores are masked), and ``k_s`` times it."""
    e = held(jnp.exp(jnp.minimum(g3 - g3[:, jj:jj + 1], 0.0)))
    return e, k3[:, jj:jj + 1] * e


def _chunk_local(m, q, k, v, g, beta, held):
    """What the chunks of a grid step make that is no function of the state
    they start from (the module's docstring), side by side: the running sums
    and decays, the two triangles of scores, ``(I + A)^-1``, ``W``, ``U`` and
    the operands of the state's products, and beside them what only the
    backward reads of the way there.  ``q``, ``k``, ``v``, ``g`` ``[2p, C,
    d]``, ``beta`` ``[p, 1, 2C]``."""
    f32, cdt = jnp.float32, q.dtype
    n, chunk, dk = q.shape
    q32, k32, v32 = q.astype(f32), k.astype(f32), v.astype(f32)
    g_sum = held(_summed(m.lower[:, :chunk], g))        # G, float32
    g_end = g_sum[:, chunk - 1:chunk]
    from_start = held(jnp.exp(g_sum))
    to_end = held(jnp.exp(g_end - g_sum))
    end = held(jnp.exp(g_end))                          # [n, 1, dk]
    # the scores: the triangle halved down to blocks of _DIAG ...
    qk = jnp.zeros((n // 2, chunk, 2 * chunk), f32)
    kk = jnp.zeros((n // 2, chunk, 2 * chunk), f32)
    levels, width = [], chunk // 2
    while width >= _DIAG:
        level = _halves(m, g_sum, q32, k32, width, held, cdt)
        left, rights = _pair(level[2]), _stacked(level[3])
        first, second = (_mm(x, rights, _NT) for x in left)     # [p, 2C, 2C]
        qk = qk + _same(m, chunk, width, _beside(
            m, first[:, :chunk], second[:, :chunk]))
        kk = kk + _same(m, chunk, width, _beside(
            m, first[:, chunk:], second[:, chunk:]))
        levels.append(level)
        width //= 2
    # ... and the diagonal blocks pair by pair, a column of each at a time
    g3, k3 = (t.reshape(n * chunk // _DIAG, _DIAG, dk) for t in (g_sum, k32))
    for jj in range(_DIAG):
        ke = _pairs(g3, k3, jj, held)[1].reshape(n, chunk, dk)
        here = m.col == m.first + jj
        qk = jnp.where(here & m.lower, _beside(m, *_pair(
            jnp.sum(q32 * ke, axis=2, keepdims=True))), qk)
        kk = jnp.where(here & m.strict, _beside(m, *_pair(
            jnp.sum(k32 * ke, axis=2, keepdims=True))), kk)
    beta = _down(m, beta)                               # [n, C, 1]
    beta_wide = _beside(m, *_pair(beta))
    inverse = _inverse(m, beta_wide * kk)               # (I + A)^-1, float32
    k_in = k32 * from_start
    bk, bv = (beta * k_in).astype(cdt), (beta * v32).astype(cdt)
    bkv = _stacked(jnp.concatenate([bk, bv], axis=2))   # [p, 2C, dk + dv]
    wu = _unpair(*(_mm(x, bkv) for x in _halved(m, inverse.astype(cdt))))
    return types.SimpleNamespace(
        w=wu[:, :, :dk].astype(cdt), u=wu[:, :, dk:], qk=qk.astype(cdt),
        q_in=(q32 * from_start).astype(cdt),
        k_out=(k32 * to_end).astype(cdt), end=end,
        q32=q32, k32=k32, v32=v32, g3=g3, k3=k3, levels=levels, kk=kk,
        beta=beta, beta_wide=beta_wide, inverse=inverse, k_in=k_in, bkv=bkv,
        from_start=from_start, to_end=to_end)


def _inverse(m, a):
    """``(I + a)^-1`` of strictly lower triangular ``a`` (pairs side by side)
    in float32: the ``_DIAG``-wide diagonal blocks by the nilpotent product
    (all of them in one block-diagonal matrix), merged two by two as
    ``_inverse_unit_lower`` merges them."""
    chunk = a.shape[1]
    neg = jnp.where(m.apart < _DIAG, -a, 0.0)
    inv = jnp.where(m.eye, 1.0, neg)
    for _ in range(max(_DIAG.bit_length() - 2, 0)):     # N², N⁴
        neg = _mm32(m, neg, neg, "nn")
        inv = inv + _mm32(m, inv, neg, "nn")
    width = _DIAG
    while width < chunk:
        below = jnp.where((m.apart >= width) & (m.apart < 2 * width), a, 0.0)
        inv = inv - _mm32(m, _mm32(m, inv, below, "nn"), inv, "nn")
        width *= 2
    return inv


def _scores_backward(m, local, dqk, dkk, held, cdt):
    """The cotangents of q, of k where it decays with its own position's
    running sum (``k_t ⊙ exp(G_t - ·)``) and of k where it decays against it
    (``k_s ⊙ exp(· - G_s)``), from the two triangles' cotangents (masked,
    pairs side by side): ``(dq, dk_plus, dk_minus)``, each ``[n, C, d_k]``
    float32."""
    n, chunk, dk = local.q32.shape
    f32 = jnp.float32
    dq, dkp, dkm = (jnp.zeros((n, chunk, dk), f32) for _ in range(3))
    for later, e, left, right, width in local.levels:
        p = [_same(m, chunk, width, x) for x in (dqk, dkk)]
        rights = _stacked(right)
        d_left = _unpair(*(_mm(jnp.concatenate(half, axis=1).astype(cdt),
                               rights)                  # [p, 2C, dk] each
                           for half in zip(*(_halved(m, x) for x in p))))
        both = _mm(jnp.concatenate(p, axis=1).astype(cdt),
                   jnp.concatenate(_pair(left), axis=2), _TN)   # [p,2C,2dk]
        d_right = _unpair(both[:, :chunk, :dk], both[:, chunk:, dk:])
        dq = dq + jnp.where(later, d_left[:, :chunk] * e, 0.0)
        dkp = dkp + jnp.where(later, d_left[:, chunk:] * e, 0.0)
        dkm = dkm + jnp.where(later, 0.0, d_right * e)
    blocks = n * chunk // _DIAG
    for jj in range(_DIAG):
        e, ke = _pairs(local.g3, local.k3, jj, held)
        e, ke = e.reshape(n, chunk, dk), ke.reshape(n, chunk, dk)
        here = m.col == m.first + jj
        pq, pk = (_unpair(*(jnp.sum(half, axis=2, keepdims=True)
                            for half in _halved(m, jnp.where(here, x, 0.0))))
                  for x in (dqk, dkk))                  # [n, C, 1]
        dq = dq + pq * ke
        dkp = dkp + pk * ke
        column = ((pq * local.q32 + pk * local.k32) * e).reshape(
            blocks, _DIAG, dk).sum(axis=1, keepdims=True)
        column = jnp.broadcast_to(column, (blocks, _DIAG, dk)).reshape(
            n, chunk, dk)
        dkm = dkm + jnp.where((m.at & (_DIAG - 1)) == jj, column, 0.0)
    return dq, dkp, dkm


def _chunks(ref, n: int):
    """A grid step's rows ``[n·C, d]`` as its chunks ``[n, C, d]``, with a
    chunk of zeros behind an odd number (the pairs' last)."""
    x = ref[...].reshape(n, ref.shape[0] // n, ref.shape[1])
    return x if n % 2 == 0 else jnp.concatenate([x, jnp.zeros_like(x[:1])])


def _rows_of(x, ref):
    """``_chunks`` back: the chunks' rows as the block ``ref`` holds."""
    n = ref.shape[0] // x.shape[1]
    return x[:n].reshape(ref.shape).astype(ref.dtype)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, states_ref,
                state_ref, *, state_dtype):
    """A grid step: its chunks' local parts side by side, then the chunks in
    order through the state.  ``state_ref`` ``[d_v, d_k]`` float32 is the
    state TRANSPOSED (the decay of a key channel scales a lane); the state
    every chunk starts from goes to ``states_ref`` for the backward."""
    held = _rounder(state_dtype)
    cdt = q_ref.dtype
    n = states_ref.shape[0]
    chunk = q_ref.shape[0] // n
    m = _masks(chunk, q_ref.shape[-1])

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = _chunk_local(m, _chunks(q_ref, n), _chunks(k_ref, n),
                     _chunks(v_ref, n), _chunks(g_ref, n), beta_ref[...], held)
    state = state_ref[...]
    for c in range(n):
        states_ref[c] = state
        from_state = _mm(jnp.concatenate([x.w[c], x.q_in[c]], axis=0),
                         state.astype(cdt), _NT2)           # [2C, dv]
        u_left = (x.u[c] - from_state[:chunk]).astype(cdt)          # Ũ
        o_ref[c * chunk:(c + 1) * chunk, :] = (
            from_state[chunk:] + _mm(_a_chunk(m, x.qk, c), _twice(u_left),
                                     _NN2)).astype(o_ref.dtype)
        state = held(state * x.end[c] + _mm(u_left, x.k_out[c], _TN2))
    state_ref[...] = state


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate_ref, *,
                state_dtype):
    """A grid step of the row walked BACKWARDS: its chunks' local parts made
    again once, side by side, and their ``Ũ`` from the states they started
    from; the chunks in reverse through the state's cotangent
    (``dstate_ref`` ``[d_v, d_k]`` float32, transposed as the state is);
    then every other cotangent, side by side again."""
    held = _rounder(state_dtype)
    cdt = q_ref.dtype
    n, dk = states_ref.shape[0], q_ref.shape[-1]
    chunk = q_ref.shape[0] // n
    m = _masks(chunk, dk)

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    x = _chunk_local(m, _chunks(q_ref, n), _chunks(k_ref, n),
                     _chunks(v_ref, n), _chunks(g_ref, n), beta_ref[...], held)
    states = states_ref[...]                                # [n, dv, dk]
    if n % 2:
        states = jnp.concatenate([states, jnp.zeros_like(states[:1])])
    sb = states.astype(cdt)
    u_left = (x.u - _mm(x.w, sb, _NT)).astype(cdt)          # Ũ
    do = _chunks(do_ref, n)
    dv = do.shape[-1]
    # O = (Q ⊙ exp G) S + QK Ũ,  S' = Diag(end) S + (K ⊙ exp(G_end - G))ᵀ Ũ:
    # what of dŨ and dS does not wait for the cotangent of S' ...
    own = _mm(x.qk, jnp.concatenate(_pair(do), axis=2), _TN)    # [p,2C,2dv]
    du_own = _unpair(own[:, :chunk, :dv], own[:, chunk:, dv:])
    ds_own = _mm(do, x.q_in, _TN)                           # [n, dv, dk]
    # ... and the chunks in reverse through it (the chunk of zeros behind
    # an odd number keeps what it starts with: nothing reads its cotangents)
    dstate = dstate_ref[...]
    du_left, dstates = list(du_own), [dstate] * len(do)
    for c in reversed(range(n)):
        dstates[c] = dstate
        du_left[c] = du_own[c] + _mm(x.k_out[c], dstate.astype(cdt), _NT2)
        dstate = (dstate * x.end[c] + ds_own[c]
                  - _mm(du_left[c].astype(cdt), x.w[c], _TN2))
    dstate_ref[...] = dstate
    du_left, dstate = jnp.stack(du_left), jnp.stack(dstates)
    dub, dsb = du_left.astype(cdt), dstate.astype(cdt)
    u_lefts = _stacked(u_left)
    dqk = jnp.where(m.lower, _beside(m, *(
        _mm(d, u_lefts, _NT) for d in _pair(do))), 0.0)     # [p, C, 2C]
    both = _mm(jnp.concatenate([do, dub], axis=1), sb)      # [n, 2C, dk]
    dq_in, dw = both[:, :chunk], -both[:, chunk:]
    dk_out = _mm(u_left, dsb)                               # [n, C, dk]
    d_end = jnp.sum(states * dstate, axis=1, keepdims=True)         # [n,1,dk]
    # W = M (β K ⊙ exp G),  U = M (β V),  M = (I + A)^-1
    dwu = _pair(jnp.concatenate([dw, du_left], axis=2).astype(cdt))
    d_inverse = _beside(m, *(_mm(d, x.bkv, _NT) for d in dwu))
    wide = _mm(x.inverse.astype(cdt), jnp.concatenate(dwu, axis=2), _TN)
    dbkv = _unpair(wide[:, :chunk, :dk + dv], wide[:, chunk:, dk + dv:])
    dbk, dbv = dbkv[:, :, :dk], dbkv[:, :, dk:]
    dv_ref[...] = _rows_of(x.beta * dbv, dv_ref)
    # A = Diag(β) KK: dA = -Mᵀ dM Mᵀ below the diagonal
    da = jnp.where(m.strict, -_mm32(
        m, x.inverse, _mm32(m, d_inverse, x.inverse, "nt"), "tn"), 0.0)
    dbeta = (jnp.sum(dbk * x.k_in, axis=2, keepdims=True)
             + jnp.sum(dbv * x.v32, axis=2, keepdims=True)
             + _unpair(*(jnp.sum(half, axis=2, keepdims=True)
                         for half in _halved(m, da * x.kk))))
    dbeta_ref[...] = _along(m, dbeta)
    dq, dkp, dkm = _scores_backward(m, x, dqk, x.beta_wide * da, held, cdt)
    dq = dq + dq_in * x.from_start
    dkp = dkp + x.beta * dbk * x.from_start
    dkm = dkm + dk_out * x.to_end
    dq_ref[...] = _rows_of(dq, dq_ref)
    dk_ref[...] = _rows_of(dkp + dkm, dk_ref)
    # every decay is exp(G_t - ·) or exp(· - G_s): dG is read off the
    # operands' cotangents; G_end is the last row's
    dg_sum = x.q32 * dq + x.k32 * (dkp - dkm)
    dg_end = (jnp.sum(dk_out * x.k32 * x.to_end, axis=1, keepdims=True)
              + d_end * x.end)
    dg_sum = dg_sum + jnp.where(m.at == chunk - 1, dg_end, 0.0)
    # g_s reaches every running sum from its own position on
    dg_ref[...] = _rows_of(_summed((m.col >= m.row)[:, :chunk], dg_sum),
                           dg_ref)


def _plan(q, v, chunk: int, reverse: bool):
    """Grid and block specs over ``(batch, head, grid step)``: a head is a
    column block of ``[B, L, H·d]``, a grid step a row block, the arrays read
    as they lie; ``reverse`` walks the row backwards.  β and its cotangent
    are ``[B, H, grid steps, pairs of chunks a step, 1, 2 chunk]``, a pair's
    positions along the lanes; the chunk states ``[B, H, grid steps, chunks
    a step, d_v, d_k]``."""
    b, length, h, dk = q.shape
    dv = v.shape[-1]
    step = _positions_a_step(length, chunk)
    steps, n = length // step, step // chunk
    at = (lambda i: steps - 1 - i) if reverse else (lambda i: i)
    rows = lambda d: pl.BlockSpec(                      # noqa: E731
        (None, step, d), lambda b, h, i: (b, at(i), h))
    a_head = lambda *tail: pl.BlockSpec(                # noqa: E731
        (None, None, None) + tail,
        lambda b, h, i: (b, h, at(i)) + (0,) * len(tail))
    pairs = -(-n // 2)
    return types.SimpleNamespace(
        grid=(b, h, steps), n=n, q=rows(dk), v=rows(dv),
        states=a_head(n, dv, dk), beta=a_head(pairs, 1, 2 * chunk),
        states_shape=jax.ShapeDtypeStruct((b, h, steps, n, dv, dk),
                                          jnp.float32),
        beta_shape=jax.ShapeDtypeStruct((b, h, steps, pairs, 1, 2 * chunk),
                                        jnp.float32))


def _call(kernel, plan, state_dtype, interpret, **kwargs):
    dv, dk = plan.states_shape.shape[-2:]
    return pl.pallas_call(
        functools.partial(kernel, state_dtype=state_dtype), grid=plan.grid,
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, **kwargs)


def _flat(x):
    """``[B, L, H, d]`` as ``[B, L, H·d]``."""
    return x.reshape(x.shape[:2] + (-1,))


def _beta_blocks(beta, plan):
    """``[B, L, H]`` as the plan's blocks (zeros behind an odd number of
    chunks a step): one small pass."""
    b, _, h = beta.shape
    beta = beta.transpose(0, 2, 1).reshape(b, h, plan.grid[2], plan.n, -1)
    beta = jnp.pad(beta, ((0, 0),) * 3 + ((0, plan.n % 2), (0, 0)))
    return beta.reshape(plan.beta_shape.shape)


def _beta_back(dbeta, plan):
    """``_beta_blocks`` back, for β's cotangent."""
    b, h, steps, pairs, _, width = dbeta.shape
    dbeta = dbeta.reshape(b, h, steps, 2 * pairs, width // 2)[:, :, :, :plan.n]
    return dbeta.reshape(b, h, -1).transpose(0, 2, 1)


def _kda_forward(q, k, v, g, beta, chunk, state_dtype, interpret):
    plan = _plan(q, v, chunk, False)
    out, states = _call(
        _fwd_kernel, plan, state_dtype, interpret, name="kda_fwd",
        in_specs=[plan.q, plan.q, plan.v, plan.q, plan.beta],
        out_specs=[plan.v, plan.states],
        out_shape=[jax.ShapeDtypeStruct(_flat(v).shape, q.dtype),
                   plan.states_shape],
    )(_flat(q), _flat(k), _flat(v), _flat(g), _beta_blocks(beta, plan))
    return out.reshape(v.shape), states


def _kda_backward(q, k, v, g, beta, states, do, chunk, state_dtype,
                  interpret):
    plan = _plan(q, v, chunk, True)
    like = lambda x: jax.ShapeDtypeStruct(_flat(x).shape, x.dtype)  # noqa: E731
    dq, dk, dv, dg, dbeta = _call(
        _bwd_kernel, plan, state_dtype, interpret, name="kda_bwd",
        in_specs=[plan.q, plan.q, plan.v, plan.q, plan.beta, plan.states,
                  plan.v],
        out_specs=[plan.q, plan.q, plan.v, plan.q, plan.beta],
        out_shape=[like(q), like(k), like(v), like(g), plan.beta_shape],
    )(_flat(q), _flat(k), _flat(v), _flat(g), _beta_blocks(beta, plan),
      states, _flat(do))
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dg.reshape(g.shape), _beta_back(dbeta, plan))


def _kda_fwd_rule(q, k, v, g, beta, chunk, state_dtype, interpret):
    with jax.named_scope("kda_op/fwd"):
        out, states = _kda_forward(q, k, v, g, beta, chunk, state_dtype,
                                   interpret)
    out = checkpoint_name(out, SAVED_NAMES[0])
    states = checkpoint_name(states, SAVED_NAMES[1])
    return out, (q, k, v, g, beta, states)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _kda_kernels(q, k, v, g, beta, chunk, state_dtype, interpret):
    return _kda_fwd_rule(q, k, v, g, beta, chunk, state_dtype, interpret)[0]


def _kda_bwd_rule(chunk, state_dtype, interpret, residuals, do):
    q, k, v, g, beta, states = residuals
    with jax.named_scope("kda_op/bwd"):
        return _kda_backward(q, k, v, g, beta, states, do.astype(q.dtype),
                             chunk, state_dtype, interpret)


_kda_kernels.defvjp(_kda_fwd_rule, _kda_bwd_rule)
