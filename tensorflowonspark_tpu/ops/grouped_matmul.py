"""Grouped matmul: rows sorted by group, each group against its own weights.

``grouped_matmul(rows, w, sizes)`` is ``rows[start_g:end_g] @ w[g]`` for
every group ``g``, where ``sizes[g]`` consecutive rows belong to it: the
expert layer of a dropless mixture of experts (``parallel/ep.py``), whose
(token, expert) pairs are sorted by expert.  Nothing is padded and no shape
depends on the sizes.  The sizes may add up to fewer rows than there are (a
chip that holds some of a layer's experts sorts their pairs to the front):
the rows past the last group belong to none, no tile of them is visited,
and their rows of the result are not written.

On TPU all three products are Pallas kernels over tiles of ``tm`` rows, the
MegaBlocks way (Gale et al., arXiv:2211.15841).  ``_visits`` lists the (row
tile, group) pairs to compute: a tile that holds rows of several groups is
visited once for each, masked.  The forward walks them against
``w[group]``; the rows' cotangent is the same walk against the transposed
weights; the weights' cotangent accumulates ``rows_gᵀ · dy_g`` in float32
over a group's visits and writes it once.  Operands keep their dtype (bf16
in the models), accumulation is float32.  Elsewhere it is
``jax.lax.ragged_dot``.

``jax.experimental.pallas.ops.tpu.megablox`` is the same design and ran as
fast on a v5e, but traces its group metadata anew inside each of its calls:
2.4 s more set-up in the cell that runs this, where these kernels add 0.5
(PERF.md §6, PR 29).
"""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _row_tile(m: int) -> int:
    """Rows per tile.  A weight tile is reused over the rows of one tile, so
    on a v5e (240 FLOP per byte at the ridge) under 256 rows a tile waits
    for its weights; every group boundary costs one more visit of a tile, so
    a larger one computes more masked rows (PERF.md §6, PR 29: 256 was the
    fastest of 128, 256 and 512 at OLMoE's widths)."""
    return min(256, -(-m // 128) * 128)


def _contraction_tile(k: int) -> int:
    if k <= 2048:
        return k
    for tk in (2048, 1024, 512, 256, 128):
        if k % tk == 0:
            return tk
    raise ValueError(f"grouped matmul over a contraction of {k}: above 2048 "
                     "it has to be a multiple of 128")


def _lanes(n: int) -> int:
    """A tile's last dimension, unless it is the array's: whole lanes."""
    return max(128, n // 128 * 128)


def _tiles(m: int, k: int, n: int, itemsize: int) -> tuple[int, int, int]:
    """``(tm, tk, tn)`` of ``[m, k] @ [g, k, n]``: a group's whole
    ``[k, n]`` matrix as one tile where 4 MiB hold it, so that consecutive
    row tiles of one group find it in VMEM and each row is read once."""
    tk = _contraction_tile(k)
    return _row_tile(m), tk, min(n, 2048, _lanes((4 << 20) // itemsize // tk))


def _transposed_tiles(m: int, k: int, n: int,
                      itemsize: int) -> tuple[int, int, int]:
    """``(tm, tk, tn)`` of ``[k, m] @ [m, n]`` per group: the ``[tk, tn]``
    float32 accumulator stays in VMEM over the group's rows, beside two
    buffers of the output tile."""
    tk = min(k, 1024)
    return _row_tile(m), tk, min(n, 1024, _lanes((2 << 20) // itemsize // tk))


@functools.partial(jax.jit, static_argnames=("m",))
def _visits(sizes, m: int):
    """The kernels' walk over ``m`` rows in groups of ``sizes``, in tiles of
    ``tm = _row_tile(m)``: ``(offsets, group, tile, count)``.  Visit
    ``v < count`` computes the rows of row tile ``tile[v]`` that lie in
    ``offsets[g]:offsets[g + 1]`` for ``g = group[v]``.  Groups come in
    order and tiles never go back, so one group's visits are consecutive and
    so are one tile's; an empty group gets one visit (of no row: its weight
    cotangent is written as zeros).  ``tiles + groups - 1`` visits hold any
    sizes."""
    tm = _row_tile(m)
    groups, tiles = sizes.shape[0], -(-m // tm)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    per_group = jnp.where(sizes > 0, (ends + tm - 1) // tm - starts // tm, 1)
    visit_ends = jnp.cumsum(per_group)
    v = jnp.arange(tiles + groups - 1, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.sum(visit_ends[None, :] <= v[:, None], axis=1), groups - 1)
    tile = v + (starts // tm - (visit_ends - per_group))[group]
    offsets = jnp.concatenate([jnp.zeros((1,), sizes.dtype), ends])
    return offsets, group, jnp.clip(tile, 0, tiles - 1), visit_ends[-1]


def executed_rows(sizes, m: int):
    """Rows the kernels compute for ``sizes``: visits times the row tile
    (``m`` rounded up to the tile when every group ends on a boundary)."""
    return _visits(sizes, m)[3] * _row_tile(m)


def _in_group(offsets_ref, group_ref, tile_ref, v, shape):
    """Which rows of visit ``v``'s tile (``shape[0]`` rows) are its group's."""
    row = tile_ref[v] * shape[0] + lax.broadcasted_iota(jnp.int32, shape, 0)
    return ((row >= offsets_ref[group_ref[v]])
            & (row < offsets_ref[group_ref[v] + 1]))


@functools.partial(jax.jit, static_argnames=("transpose_w", "interpret"))
def _rows_times_weights(rows, w, visits, transpose_w: bool, interpret: bool):
    """``out[r] = rows[r] @ w[group of r]`` (``w[g]ᵀ`` if ``transpose_w``),
    ``[m, k] -> [m, n]``."""
    offsets, group, tile, count = visits
    m, k = rows.shape
    n = w.shape[1] if transpose_w else w.shape[2]
    tm, tk, tn = _tiles(m, k, n, rows.dtype.itemsize)
    k_tiles = k // tk
    dims = (((1,), (1 if transpose_w else 0,)), ((), ()))

    def kernel(offsets_ref, group_ref, tile_ref, rows_ref, w_ref, out_ref,
               acc_ref):
        v, ki = pl.program_id(1), pl.program_id(2)

        @pl.when(ki == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += lax.dot_general(rows_ref[...], w_ref[...], dims,
                                        preferred_element_type=jnp.float32)

        @pl.when(ki == k_tiles - 1)
        def _():    # the tile's other rows are other visits'
            mine = _in_group(offsets_ref, group_ref, tile_ref, v, (tm, tn))
            out_ref[...] = jnp.where(
                mine, acc_ref[...],
                out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)

    if transpose_w:
        w_spec = pl.BlockSpec((None, tn, tk),
                              lambda ni, v, ki, o, g, t: (g[v], ni, ki))
    else:
        w_spec = pl.BlockSpec((None, tk, tn),
                              lambda ni, v, ki, o, g, t: (g[v], ki, ni))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(n, tn), count, k_tiles),
            in_specs=[pl.BlockSpec((tm, tk),
                                   lambda ni, v, ki, o, g, t: (t[v], ki)),
                      w_spec],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda ni, v, ki, o, g, t: (t[v], ni)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((m, n), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(offsets, group, tile, rows, w)


@functools.partial(jax.jit, static_argnames=("groups", "interpret"))
def _rows_transposed_times_rows(rows, dy, visits, groups: int,
                                interpret: bool):
    """``out[g] = rows_gᵀ @ dy_g``, ``[m, k], [m, n] -> [groups, k, n]``."""
    offsets, group, tile, count = visits
    (m, k), n = rows.shape, dy.shape[1]
    tm, tk, tn = _transposed_tiles(m, k, n, rows.dtype.itemsize)

    def kernel(offsets_ref, group_ref, tile_ref, rows_ref, dy_ref, out_ref,
               acc_ref):
        v, last = pl.program_id(2), pl.num_programs(2) - 1
        g = group_ref[v]

        @pl.when((v == 0) | (group_ref[jnp.maximum(v - 1, 0)] != g))
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        def mine(ref):      # selected in float32: no bf16 VPU on a v5e
            keep = _in_group(offsets_ref, group_ref, tile_ref, v, ref.shape)
            return jnp.where(keep, ref[...].astype(jnp.float32),
                             0.0).astype(ref.dtype)

        acc_ref[...] += lax.dot_general(
            mine(rows_ref), mine(dy_ref), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when((v == last) | (group_ref[jnp.minimum(v + 1, last)] != g))
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(n, tn), pl.cdiv(k, tk), count),
            in_specs=[pl.BlockSpec((tm, tk),
                                   lambda ni, ki, v, o, g, t: (t[v], ki)),
                      pl.BlockSpec((tm, tn),
                                   lambda ni, ki, v, o, g, t: (t[v], ni))],
            out_specs=pl.BlockSpec((None, tk, tn),
                                   lambda ni, ki, v, o, g, t: (g[v], ki, ni)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(offsets, group, tile, rows, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped_matmul_tpu(rows, w, visits, interpret):
    return _rows_times_weights(rows, w, visits, False, interpret)


def _fwd(rows, w, visits, interpret):
    return (_grouped_matmul_tpu(rows, w, visits, interpret),
            (rows, w, visits))


def _bwd(interpret, res, dy):
    rows, w, visits = res
    d_rows = _rows_times_weights(dy, w, visits, True, interpret)
    d_w = _rows_transposed_times_rows(rows, dy, visits, w.shape[0],
                                      interpret)
    return d_rows, d_w, None


_grouped_matmul_tpu.defvjp(_fwd, _bwd)

Impl = Literal["pallas", "pallas_interpret", "xla"]


def grouped_matmul(rows, w, sizes, *, impl: Impl | None = None):
    """``[m, k]`` rows sorted by group, ``[g, k, n]`` weights, ``[g]`` int32
    sizes that sum to ``m`` or less -> ``[m, n]`` in the rows' dtype (rows
    past the last group: not initialised by the kernels, zeros off the TPU).

    ``impl=None`` auto-selects as ``flash_attention`` does: the kernels on
    TPU, ``jax.lax.ragged_dot`` elsewhere; ``pallas_interpret`` runs the
    kernels in interpreter mode (CPU tests of the kernels themselves).
    Under a mesh the caller shard_maps it (``parallel/ep.py``)."""
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "xla":
        return jax.lax.ragged_dot(rows, w, sizes)
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(f"unknown grouped matmul impl {impl!r}")
    m = rows.shape[0]
    pad = -m % _row_tile(m)     # rows past the last group belong to none
    out = _grouped_matmul_tpu(
        jnp.pad(rows, ((0, pad), (0, 0))), w, _visits(sizes, m),
        impl == "pallas_interpret")
    return out[:m]
