"""q and k from their projection to the attention kernels: per-head RMSNorm
and RoPE as ONE op, one pass over the array each way.

``qk_prep(x, scale, positions, freqs)`` takes ``[B, S, H, D]`` from ``q_proj``
or ``k_proj`` and returns ``[B, S, H, D]`` for ``flash_attention`` and
``sparse_attention``: what ``models/transformer.py``'s ``RMSNorm``
(over each head's ``D``, one learned ``scale`` ``[D]`` for all heads) and
``apply_rope`` compute, either of them or both.  Those two stay the
specification (``tests/test_qk_prep.py`` holds the op to them) and the path of
every call this op does not take: a head that is not whole lane tiles wide,
the cache path, latent and ring attention, every backend but the TPU
(``Attention`` decides; the op itself is the two Pallas kernels and nothing
else).

Why an op of its own: written as ``jnp`` the backward of this chain
(RoPE's transposed slices and the sum of their two halves, the norm's
reductions, float32 to bf16) is folded by XLA into the OPERAND of the
projection's two backward products, and a product that computes its operand
runs at a quarter of the forward's speed (PERF.md section 6, PR 51).  A
``pallas_call`` is opaque to that: its backward WRITES the projection's
cotangent once, and the products are plain.

Layout: the projection's side is token-major, ``[B, S, H * D]`` (a reshape
of what the projection wrote, and what its two backward products read); the
kernels' side is head by head, ``[B, H, S, D]``, which is what the attention
kernels' own ``_head_major`` makes of ``[B, S, H, D]``: the forward WRITES it
and the backward READS the cotangent in it, the op hands it over as the
transpose that ``_head_major`` undoes, and XLA cancels the pair, so the
kernels' layout work is a reshape and no copy.  No kernel transposes: the
grid is (batch, sequence tile, block of heads), the heads innermost; a
token-major block is ``(block_s, heads * D)`` at lane offset ``head * D``
(``D`` is whole 128-lane tiles, so a head is a lane-aligned slice), a
head-by-head block ``(heads, block_s, D)``, and a head's slab goes from one
to the other as it is.  The norm reduces along lanes.  The rotation is ``x *
C + roll(x, D / 2) * S`` with ``C = [cos | cos]`` and ``S = [-sin | sin]``
(``[S, D]`` float32, one block for all heads of a sequence tile): the two
64-lane halves never exist as arrays.  Its transpose is the rotation by the
negated angle, ``g * C - roll(g, D / 2) * S``: no slices and no sum of
halves.

Rounding points, forward: float32 inside; bf16 after the norm (kept where
``RMSNorm`` has it, so the forward is that chain's) and bf16 after the
rotation.  Backward: float32 from the kernel's cotangent to the projection's,
rounded ONCE as it is written (the ``jnp`` chain rounds the normed q's
cotangent in between; this does not).  The scale's cotangent is summed in
float32, a partial sum a (batch, sequence tile), added up outside.

Residuals: the projection's output (which the norm's VJP reads; the row's
``rsqrt`` is recomputed from it), ``scale``, ``positions`` and ``freqs``.  The
normed q is not kept and cos and sin are computed again from ``positions``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

_LANES = 128
_BLOCK_LANES = 512      # heads a block: as many as fit this many lanes


def engages(d_head: int, norm: bool, rope: bool) -> bool:
    """Whether there is something to prepare and the kernels can: a head of
    whole lane tiles (a 96-wide head would be sliced inside a tile)."""
    return (norm or rope) and d_head % _LANES == 0


class _Plan(NamedTuple):
    """What is static of a call: the kernels are traced once a plan."""
    block_s: int        # positions a block
    heads: int          # heads a block
    d: int
    rows: int           # positions of the sequence (the last block may overhang)
    norm: bool
    rope: bool
    eps: float


def _plan(shape, norm: bool, rope: bool, eps: float, block_s: int) -> _Plan:
    _b, s, h, d = shape
    heads = max(n for n in range(1, h + 1)
                if h % n == 0 and n * d <= max(_BLOCK_LANES, d))
    return _Plan(s if s <= block_s else block_s, heads, d, s, norm, rope,
                 float(eps))


def _tables(positions, freqs, factor: float):
    """``C = [cos | cos]`` and ``S = [-sin | sin]``, ``[S, D]`` float32, as
    ``apply_rope`` computes cos and sin (YaRN's factor on both)."""
    angles = positions[:, None].astype(jnp.float32) * freqs[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    return (jnp.concatenate([cos, cos], axis=-1),
            jnp.concatenate([-sin, sin], axis=-1))


def _roll_half(x, interpret: bool):
    """``[x1 | x2] -> [x2 | x1]``: a lane rotation by half the head."""
    half = x.shape[-1] // 2
    return jnp.roll(x, half, axis=-1) if interpret else pltpu.roll(x, half, 1)


def _refs(refs, plan: _Plan):
    """A kernel's refs after its first: ``(scale, cos, sin, the outputs)``,
    None where the plan has no norm or no rotation."""
    refs = list(refs)
    w = refs.pop(0) if plan.norm else None
    c, s = (refs.pop(0), refs.pop(0)) if plan.rope else (None, None)
    return w, c, s, refs


def _fwd_kernel(x_ref, *refs, plan: _Plan, interpret: bool):
    w_ref, c_ref, s_ref, (o_ref,) = _refs(refs, plan)
    f32, d = jnp.float32, plan.d
    for h in range(plan.heads):
        at = slice(h * d, (h + 1) * d)
        x = x_ref[:, at].astype(f32)
        if plan.norm:
            x = x * lax.rsqrt(
                jnp.mean(x * x, axis=-1, keepdims=True) + plan.eps)
            x = (x * w_ref[...]).astype(o_ref.dtype).astype(f32)
        if plan.rope:
            x = x * c_ref[...] + _roll_half(x, interpret) * s_ref[...]
        o_ref[h] = x.astype(o_ref.dtype)


def _bwd_kernel(g_ref, *refs, plan: _Plan, interpret: bool):
    x_ref = refs[0] if plan.norm else None      # the norm's VJP alone reads x
    w_ref, c_ref, s_ref, (dx_ref, *dw_ref) = _refs(refs[plan.norm:], plan)
    f32, d = jnp.float32, plan.d
    dw = jnp.zeros((1, d), f32)
    if plan.rows % plan.block_s:        # the last block overhangs the rows
        valid = (pl.program_id(1) * plan.block_s + lax.broadcasted_iota(
            jnp.int32, (plan.block_s, 1), 0)) < plan.rows
    else:
        valid = None
    for h in range(plan.heads):
        at = slice(h * d, (h + 1) * d)
        g = g_ref[h].astype(f32)
        if plan.rope:
            g = g * c_ref[...] - _roll_half(g, interpret) * s_ref[...]
        if plan.norm:
            x = x_ref[:, at].astype(f32)
            r = lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + plan.eps)
            xh = x * r
            gx = g * xh
            if valid is not None:
                gx = jnp.where(valid, gx, 0.0)
            dw = dw + jnp.sum(gx, axis=0, keepdims=True)
            u = g * w_ref[...]
            g = r * (u - xh * jnp.mean(u * xh, axis=-1, keepdims=True))
        dx_ref[:, at] = g.astype(dx_ref.dtype)
    if plan.norm:
        (dw_ref,) = dw_ref

        @pl.when(pl.program_id(2) == 0)
        def _():
            dw_ref[...] = jnp.zeros_like(dw_ref)

        dw_ref[...] += dw


def _call(kernel, name: str, plan: _Plan, interpret: bool, shape, by_head,
          by_token, scale, tables):
    """One pass over ``shape = (B, S, H, D)``: the arrays ``by_head`` ``[B,
    H, S, D]`` (the backward's cotangent) and ``by_token`` ``[B, S, H * D]``,
    the scale whole and the tables by sequence tile.  The output crosses
    over: head by head from the forward, which reads none so, token-major
    from the backward, which with a norm also returns the scale's partial
    sums."""
    b, s, h, d = shape
    backward = bool(by_head)
    grid = (b, pl.cdiv(s, plan.block_s), h // plan.heads)
    head_tile = pl.BlockSpec((None, plan.heads, plan.block_s, d),
                             lambda i, j, k: (i, k, j, 0))
    token_tile = pl.BlockSpec((None, plan.block_s, plan.heads * d),
                              lambda i, j, k: (i, j, k))
    operands = [*by_head, *by_token]
    specs = [head_tile] * len(by_head) + [token_tile] * len(by_token)
    if plan.norm:
        operands.append(scale.astype(jnp.float32)[None])
        specs.append(pl.BlockSpec((1, d), lambda i, j, k: (0, 0)))
    if plan.rope:
        operands += list(tables)
        specs += [pl.BlockSpec((plan.block_s, d), lambda i, j, k: (j, 0))] * 2
    out_shape = [jax.ShapeDtypeStruct(
        (b, s, h * d) if backward else (b, h, s, d), operands[0].dtype)]
    out_specs = [token_tile if backward else head_tile]
    if plan.norm and backward:      # a partial sum a (batch, sequence tile)
        out_shape.append(jax.ShapeDtypeStruct((b, grid[1], 1, d),
                                              jnp.float32))
        out_specs.append(pl.BlockSpec((None, None, 1, d),
                                      lambda i, j, k: (i, j, 0, 0)))
    return pl.pallas_call(
        functools.partial(kernel, plan=plan, interpret=interpret),
        grid=grid, in_specs=specs, out_specs=out_specs, out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name=name)(*operands)


# Jitted on what is static of a call, as the flash kernels' wrappers are: a
# program's layers, and a process's programs, share one trace of each pass.
# The transposes around the calls are the inverse of the attention kernels'
# own ``_head_major`` / ``_from_head_major``: XLA cancels each pair, and what
# is left of the kernels' layout work is a reshape.
@functools.partial(jax.jit, static_argnames=("plan", "factor", "interpret"))
def _fwd_pallas(x, scale, positions, freqs, *, plan: _Plan, factor: float,
                interpret: bool):
    b, s, h, d = x.shape
    tables = _tables(positions, freqs, factor) if plan.rope else None
    (out,) = _call(_fwd_kernel, "qk_prep_fwd", plan, interpret, x.shape, [],
                   [x.reshape(b, s, h * d)], scale, tables)
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("plan", "factor", "interpret"))
def _bwd_pallas(g, x, scale, positions, freqs, *, plan: _Plan, factor: float,
                interpret: bool):
    b, s, h, d = g.shape
    tables = _tables(positions, freqs, factor) if plan.rope else None
    dx, *dw = _call(_bwd_kernel, "qk_prep_bwd", plan, interpret, g.shape,
                    [g.transpose(0, 2, 1, 3)],
                    [x.reshape(b, s, h * d)] if plan.norm else [], scale,
                    tables)
    return dx.reshape(g.shape), (jnp.sum(dw[0], axis=(0, 1, 2))
                                 if plan.norm else None)


def _per_shard(fn, arrays, rest, sums: bool):
    """``fn(*arrays, *rest)`` under an ambient mesh: GSPMD cannot partition a
    Mosaic kernel, and the op is independent a (batch, head), so it runs a
    shard over the axes the model constrains q and k to, as
    ``flash_attention`` does; ``rest`` is replicated.  With ``sums`` ``fn``
    returns ``(array, partial sum or None)`` and the sum is added up over
    the shards."""
    mesh = jax.sharding.get_abstract_mesh()
    auto = [] if mesh.empty else [a for a in mesh.axis_names
                                  if a not in mesh.manual_axes]
    if not auto:
        return fn(*arrays, *rest)
    batch_axes = tuple(a for a in ("dp", "fsdp") if a in auto)
    tp = ("tp",) if "tp" in auto else ()
    spec = P(batch_axes or None, None, tp or None, None)
    whole = jax.tree.map(lambda _: P(), tuple(rest))

    def shard(*args):
        out = fn(*args)
        if sums and out[1] is not None and batch_axes + tp:
            out = (out[0], lax.psum(out[1], batch_axes + tp))
        return out

    return jax.shard_map(
        shard, in_specs=(spec,) * len(arrays) + whole,
        out_specs=(spec, P()) if sums else spec,
        axis_names=frozenset(auto), check_vma=False)(*arrays, *rest)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _qk_prep(x, scale, positions, freqs, factor, eps, block_s, interpret):
    return _qk_prep_fwd(x, scale, positions, freqs, factor, eps, block_s,
                        interpret)[0]


def _qk_prep_fwd(x, scale, positions, freqs, factor, eps, block_s, interpret):
    def run(x, scale, positions, freqs):        # the plan is a shard's own
        plan = _plan(x.shape, scale is not None, freqs is not None, eps,
                     block_s)
        return _fwd_pallas(x, scale, positions, freqs, plan=plan,
                           factor=factor, interpret=interpret)

    with jax.named_scope("qk_prep"):
        out = _per_shard(run, (x,), (scale, positions, freqs), False)
    # a rotation's backward reads its cotangent alone: x is no residual
    return out, (x if scale is not None else None, scale, positions, freqs)


def _qk_prep_bwd(factor, eps, block_s, interpret, res, g):
    kept, scale, positions, freqs = res

    def run(g, *rest):          # rest: x where it was kept, then the rest
        x, scale, positions, freqs = (None,) * (kept is None) + rest
        plan = _plan(g.shape, scale is not None, freqs is not None, eps,
                     block_s)
        return _bwd_pallas(g, x, scale, positions, freqs, plan=plan,
                           factor=factor, interpret=interpret)

    with jax.named_scope("qk_prep"):
        dx, dw = _per_shard(run, (g,) if kept is None else (g, kept),
                            (scale, positions, freqs), True)
    return dx, dw, None, None


_qk_prep.defvjp(_qk_prep_fwd, _qk_prep_bwd)


def qk_prep(x, scale=None, positions=None, freqs=None, *, factor: float = 1.0,
            eps: float = 1e-6, block_s: int = 512, interpret: bool = False):
    """``x`` ``[B, S, H, D]`` normed over each head's ``D`` with the learned
    ``scale`` ``[D]`` (None: no norm) and turned by RoPE at ``positions``
    ``[S]`` with the inverse frequencies ``freqs`` ``[D / 2]`` and ``factor``
    on cos and sin (``rope_frequencies``'; ``freqs`` None: no rotation).
    Differentiable in ``x`` and ``scale``.  ``interpret`` runs the kernels in
    interpreter mode (CPU tests)."""
    if not engages(x.shape[-1], scale is not None, freqs is not None):
        raise ValueError(
            f"qk_prep of heads {x.shape[-1]} wide with scale "
            f"{getattr(scale, 'shape', None)} and freqs "
            f"{getattr(freqs, 'shape', None)}: nothing to prepare, or a head "
            "that is not whole 128-lane tiles (the caller's jnp path)")
    if freqs is not None and positions is None:
        positions = jnp.arange(x.shape[1])
    return _qk_prep(x, scale, positions, freqs, float(factor), float(eps),
                    int(block_s), bool(interpret))
