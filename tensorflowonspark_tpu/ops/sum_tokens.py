"""A token's sum over its rows, for a chip's share of the experts.

``sum_tokens(rows, pos, end, gates)``: ``rows`` ``[m, d]`` lie in the grouped
matmul's order (sorted by expert).  In TOKEN order, position ``s`` of them is
row ``pos[s]``, and token ``t`` owns the positions ``end[t - 1] .. end[t] -
1``: runs that follow one another from position 0, empty for a token that
has no row; the positions from ``end[-1]`` on are nobody's.  The result ``[n, d]`` is each token's rows,
weighted by ``gates[s]`` where given, summed in float32 and cast once:
``parallel/ep.py``'s way back from the held experts to the tokens, in both
directions (the combine's forward with the routing weights, the dispatch's
backward without).

Both forms take the rows to token order by ONE XLA gather (nobody's
positions, whose rows were never written, read an owned row instead).  On TPU
the sum is then one Pallas kernel over that array: the walk is the grouped
matmul's (``_visits``) with a block of 128 tokens as a group, whose rows are
the contiguous positions ``end[t0 - 1] .. end[t0 + 127] - 1``; a visit is one
(block, tile of 256 positions) pair that holds a row, plus one for each block
that holds none (its zeros are written); tiles past the owned positions are
no visit and are never read.  A visit multiplies a ``[positions, tokens]``
weight matrix (the gate where the token owns the position, else 0) with the
tile on the MXU, accumulates in float32 over a block's visits and writes the
block once.  Elsewhere the specification runs: ``sum_runs``, shifted adds
over the whole array and a gather of each token's last row.

Why the kernel does not fetch the rows from the expert order itself, a row
DMA each: Mosaic refuses a slice of one row of an array tiled ``(8, 128)``
in HBM (a bf16 row is half of the words of a sublane pair); from a layout
whose rows are contiguous, which is a pass over the whole array to make, a
row DMA takes 32 ns on a v5e whether the row is 4 KB or 2 KB, where XLA's
gather of the same rows takes 6.4 and 3.9 (PERF.md section 6, PR 43).
"""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensorflowonspark_tpu import telemetry
from tensorflowonspark_tpu.ops.grouped_matmul import _row_tile, _visits

_TOKENS = 128       # tokens a block: the weight matrix's lanes


def sum_runs(z, tok, last, has, run: int):
    """``[r, d]`` rows in token order (row s is token ``tok[s]``'s: a token's
    rows lie next to each other, at most ``run`` of them) -> ``[n, d]``,
    each token's rows summed: ``last[t]`` is the row its run ends at and
    ``has[t]`` whether it has one."""
    rows = z.shape[0]
    # one pass: the run's earlier rows are shifted views of one padded copy
    z_pad = jnp.pad(z, ((run - 1, 0), (0, 0)))
    tok_pad = jnp.pad(tok, (run - 1, 0), constant_values=-1)
    total = z.astype(jnp.float32)
    for i in range(1, run):
        at = slice(run - 1 - i, run - 1 - i + rows)
        total = total + jnp.where((tok_pad[at] == tok)[:, None],
                                  z_pad[at].astype(jnp.float32), 0.0)
    return jnp.where(has[:, None], total.astype(z.dtype)[last], 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sum_tokens_pallas(z, end, gates, *, interpret: bool):
    """``z`` ``[m, d]`` in token order; finite wherever a tile that holds an
    owned position lies (a weight of 0 does not silence a NaN)."""
    (m, d), n = z.shape, end.shape[0]
    block = min(_TOKENS, -(-n // 8) * 8)
    blocks = -(-n // block)
    tile = _row_tile(m)
    gated = gates is not None
    # a token past the last owns nothing
    end = jnp.pad(end, (0, blocks * block - n), mode="edge")
    first = jnp.concatenate([jnp.zeros((1,), end.dtype), end[:-1]])
    visits = _visits(end[block - 1::block] - first[::block], m)
    exact = lax.Precision.HIGHEST if z.dtype == jnp.float32 else None

    def kernel(offsets_ref, group_ref, tile_ref, first_ref, end_ref, *refs):
        *gates_ref, z_ref, out_ref, acc = refs
        v, last = pl.program_id(0), pl.num_programs(0) - 1
        g = group_ref[v]

        @pl.when((v == 0) | (group_ref[jnp.maximum(v - 1, 0)] != g))
        def _():
            acc[...] = jnp.zeros_like(acc)

        @pl.when(offsets_ref[g + 1] > offsets_ref[g])
        def _():    # a block that owns nothing visits a tile it must not read
            at = tile_ref[v] * tile + lax.broadcasted_iota(
                jnp.int32, (tile, 1), 0)
            owns = (at >= first_ref[...]) & (at < end_ref[...])
            weight = jnp.where(owns, gates_ref[0][...] if gated else 1.0, 0.0)
            acc[...] += lax.dot_general(
                weight.astype(z_ref.dtype), z_ref[...],
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=exact)

        @pl.when((v == last) | (group_ref[jnp.minimum(v + 1, last)] != g))
        def _():
            out_ref[...] = acc[...].astype(out_ref.dtype)

    def token_block(v, offsets, group, tiles):
        return group[v], 0, 0

    def row_tile(v, offsets, group, tiles):
        return tiles[v], 0

    pad = -m % tile      # a tile's rows past the array would not be finite
    columns = [gates.astype(jnp.float32)[:, None]] if gated else []
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(visits[3],),
            in_specs=[pl.BlockSpec((None, 1, block), token_block),
                      pl.BlockSpec((None, 1, block), token_block),
                      *[pl.BlockSpec((tile, 1), row_tile) for _ in columns],
                      pl.BlockSpec((tile, d), row_tile)],
            out_specs=pl.BlockSpec(
                (block, d), lambda v, offsets, group, tiles: (group[v], 0)),
            scratch_shapes=[pltpu.VMEM((block, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((n, d), z.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="sum_tokens",
    )(*visits[:3], first.reshape(blocks, 1, block),
      end.reshape(blocks, 1, block),
      *[jnp.pad(c, ((0, pad), (0, 0))) for c in columns],
      jnp.pad(z, ((0, pad), (0, 0))))


Impl = Literal["pallas", "pallas_interpret", "xla"]


def _resolve(impl: Impl | None) -> Impl:
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in ("pallas", "pallas_interpret", "xla"):
        raise ValueError(f"unknown sum_tokens impl {impl!r}")
    return impl


def sum_tokens(rows, pos, end, gates=None, *, run: int,
               impl: Impl | None = None):
    """``[m, d]`` rows, ``[m]`` int32 ``pos`` (the row that holds position
    ``s`` of the token order), ``[n]`` int32 ``end`` (token ``t`` owns the
    positions ``end[t - 1] .. end[t] - 1``, from 0: at most ``run`` of them;
    positions from ``end[-1]`` on are nobody's and their rows are never
    read), ``[m]`` ``gates`` in token order or None -> ``[n, d]`` in the
    rows' dtype.

    ``impl=None`` auto-selects as ``grouped_matmul`` does: the kernel on
    TPU, the specification elsewhere; ``pallas_interpret`` runs the kernel
    in interpreter mode.  Under a mesh the caller shard_maps it
    (``parallel/ep.py``)."""
    impl = _resolve(impl)
    m = rows.shape[0]
    # nobody's rows were never written and may hold anything: their positions
    # read position 0's row instead (an owned one wherever one is), which
    # costs no pass of its own as a select after the gather would
    owned = jnp.arange(m, dtype=end.dtype) < end[-1]
    z = rows[jnp.where(owned, pos, pos[0])]
    if impl == "xla":
        z = jnp.where(owned[:, None], z, 0)
        if gates is not None:
            z = z * gates[:, None].astype(z.dtype)
        first = jnp.concatenate([jnp.zeros((1,), end.dtype), end[:-1]])
        tok = jnp.searchsorted(end, jnp.arange(m, dtype=end.dtype),
                               side="right")
        return sum_runs(z, tok, jnp.clip(end - 1, 0, m - 1), end > first,
                        run)
    telemetry.counter("moe.kernels.sum_tokens").inc()
    return _sum_tokens_pallas(z, end, gates,
                              interpret=impl == "pallas_interpret")


def moved_rows(m: int, end, *, impl: Impl | None = None):
    """Rows of the ``[m, d]`` array in token order that one ``sum_tokens``
    reads to sum them: under the kernel the tiles that hold an owned
    position, in the specification all of them (the gather that makes the
    array writes all ``m`` either way)."""
    if _resolve(impl) == "xla":
        return m
    tile = _row_tile(m)
    return jnp.minimum(-(-end[-1] // tile) * tile, m)
