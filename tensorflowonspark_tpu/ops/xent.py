"""Blockwise softmax cross-entropy fused with a large-vocabulary LM head.

The dense LM loss materializes ``[N, V]`` float32 logits and their
cotangent.  This op walks CHUNKS OF ROWS over the whole vocabulary, one
after the other: a chunk's logits ``[rows, V]`` are whole, so its log-sum-exp,
its targets' logits and its softmax all come from ONE head product, and
the gradient is taken where the logits are.  The differentiated call
(``_fwd``) makes three products a chunk — the logits, ``dh_chunk = dlogits ·
Wᵀ`` (a whole block: nothing accumulates over chunks) and ``dw += h_chunkᵀ ·
dlogits`` (the one carry, float32) — and keeps ``dh``, ``dw`` and the rows'
``nll`` for the backward rule, which only scales them by its scalar
cotangent.  No logits are kept and none are computed twice; the head is used
as ``[D, V]`` as it stands.

That needs each row's loss weight in the forward, so the op is the WEIGHTED
SUM ``Σ w·nll`` (a mean's denominator is the caller's, outside).  The
undifferentiated call (an evaluation) makes the one logits product a chunk
and no gradient.

The row chunk follows from the shapes: ``chunk`` is the width of the
``[N, chunk]`` float32 logits block the op may hold, spent as ``[rows, V]``,
but no fewer than 2,048 rows: every chunk reads and writes the carried ``dw``
once.  Rows are padded with weight 0 up to whole chunks.

No counterpart exists in the reference (its models are CNNs/wide-and-deep;
losses are delegated to TF) — this exists because the LM family is
first-class here.  XLA-level implementation (an unrolled loop of dot_general
with f32 accumulation), so it runs on TPU and CPU alike.  Under a data-parallel
mesh the carried ``dw`` is summed over the devices once a chunk, and the
row walk would fight a tensor-parallel sharding of the head's vocabulary:
use the dense path there.

Trace-time counters (``logs/run_report.json``): ``xent.calls`` a traced call
of the op, ``xent.grad_in_forward`` a traced ``_fwd``; a differentiated call
counts in both, a forward-only program in the first alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from tensorflowonspark_tpu import telemetry

_ROW_MULTIPLE = 8  # a float32 tile's sublanes
# A chunk reads and writes the carried ``dw`` once for ``rows / 4`` FLOP a
# byte of it: 2,048 rows are twice the v5e's ridge (197 TFLOP/s over 819
# GB/s = 240).  Below it the carry shows: 1.3 ms a chunk at OLMoE's shapes.
_MIN_ROWS = 2048


def _row_chunk(n: int, vocab: int, chunk: int) -> int:
    """Rows a chunk: as many chunks as ``chunk`` columns go into the
    vocabulary, so that ``[rows, vocab]`` is the block ``[n, chunk]`` was,
    and no fewer rows than pay for the carry."""
    n_chunks = -(-vocab // min(chunk, vocab))
    rows = max(-(-n // n_chunks), min(_MIN_ROWS, n))
    return -(-rows // _ROW_MULTIPLE) * _ROW_MULTIPLE


def blockwise_cross_entropy(hidden: jax.Array, kernel: jax.Array,
                            targets: jax.Array,
                            weights: jax.Array | None = None,
                            chunk: int = 4096) -> jax.Array:
    """``Σ_i weights_i · -log softmax(hidden_i @ kernel)[targets_i]`` without
    the ``[N, V]`` materialization.

    Args:
      hidden: ``[N, D]`` final hidden states (any float dtype; matmuls
        accumulate in f32).
      kernel: ``[D, V]`` LM-head kernel.
      targets: ``[N]`` int32 target ids in ``[0, V)``.
      weights: ``[N]`` loss weight a row (default: ones).  Its cotangent is
        the rows' negative log-likelihoods.
      chunk: width of the ``[N, chunk]`` float32 logits block the op may
        hold; it holds ``[max(N · chunk / V, 2048), V]``.

    Returns: the float32 scalar weighted sum.
    """
    n = hidden.shape[0]
    if weights is None:
        weights = jnp.ones((n,), jnp.float32)
    return _weighted_xent(hidden, kernel, targets,
                          weights.astype(jnp.float32),
                          _row_chunk(n, kernel.shape[1], chunk))


def _walk(body, carry, rows: int, *per_row: jax.Array):
    """``lax.scan`` of ``body(carry, chunk) -> (carry, outs)`` over chunks of
    ``rows`` rows of the ``[N, ...]`` arrays (zero-padded to whole chunks),
    the outputs joined back to ``N`` rows.  Unrolled, with each chunk held
    behind the carry of the one before by an optimization barrier, so one
    chunk's logits are alive at a time: as a ``while`` loop whose carried
    ``dw`` is a temporary, the step's program holds a further logits block
    by the compiler's count (PERF.md §6, PR 46)."""
    n = per_row[0].shape[0]
    pad = -n % rows
    chunks = [jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
              .reshape(-1, rows, *x.shape[1:]) for x in per_row]
    outs = []
    for chunk in zip(*chunks):
        chunk, carry = jax.lax.optimization_barrier((chunk, carry))
        carry, out = body(carry, chunk)
        outs.append(out)
    return carry, jax.tree.map(lambda *xs: jnp.concatenate(xs)[:n], *outs)


def _chunk_nll(h_c, kernel, t_c):
    """One chunk's f32 logits ``[rows, V]``, log-sum-exp and nll ``[rows]``."""
    logits = jax.lax.dot_general(
        h_c, kernel, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m = jnp.max(logits, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1))
    picked = jnp.take_along_axis(logits, t_c[:, None], axis=-1)[:, 0]
    return logits, lse, lse - picked


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _weighted_xent(hidden, kernel, targets, weights, rows):
    telemetry.counter("xent.calls").inc()

    def body(total, chunk):
        h_c, t_c, w_c = chunk
        nll = _chunk_nll(h_c, kernel, t_c)[2]
        return total + jnp.sum(nll * w_c), ()

    return _walk(body, jnp.zeros((), jnp.float32), rows,
                 hidden, targets, weights)[0]


def _fwd(hidden, kernel, targets, weights, rows):
    telemetry.counter("xent.calls").inc()
    telemetry.counter("xent.grad_in_forward").inc()

    def body(carry, chunk):
        total, dw = carry
        h_c, t_c, w_c = chunk
        logits, lse, nll = _chunk_nll(h_c, kernel, t_c)
        # d nll / d logits = softmax - onehot(target), times the row's weight
        p = jnp.exp(logits - lse[:, None])
        onehot = ((t_c[:, None] == jnp.arange(kernel.shape[1])[None, :])
                  .astype(jnp.float32))
        dlogits = (p - onehot) * w_c[:, None]
        dh_c = jax.lax.dot_general(
            dlogits, kernel, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dw_c = jax.lax.dot_general(
            h_c, dlogits, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dw = dw_c if dw is None else dw + dw_c     # no zeros to write and read
        return (total + jnp.sum(nll * w_c), dw), (dh_c, nll)

    (total, dw), (dh, nll) = _walk(body, (jnp.zeros((), jnp.float32), None),
                                   rows, hidden, targets, weights)
    # the empty slices carry the operands' dtypes to the backward rule
    return total, (dh, dw, nll, targets, hidden[:0], kernel[:0])


def _bwd(rows, residuals, g):
    del rows
    dh, dw, nll, targets, hidden, kernel = residuals
    # dh and dw are float32 up to here: each rounds once, after the scale
    dtargets = np.zeros(targets.shape, jax.dtypes.float0)  # int arg: float0
    return ((g * dh).astype(hidden.dtype), (g * dw).astype(kernel.dtype),
            dtargets, g * nll)


_weighted_xent.defvjp(_fwd, _bwd)
