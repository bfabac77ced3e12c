"""Sequence/context parallelism: ring attention over the ``sp`` axis.

The reference has nothing here (SURVEY.md §5.7 — it predates long-context
work), but long sequences are first-class in this build.  Built on the
chunk/merge online-softmax primitives from ``ops/attention.py``:

**Ring attention** (``ring_attention``): Q stays put, KV shards rotate around
the ``sp`` ring via ``jax.lax.ppermute`` over ICI neighbours; each hop's
partial result merges via the online-softmax identity.  Memory per chip is
O(S_local²-ish blockwise); the sequence can exceed any single chip's HBM.

``ring_attention`` is meant to run *inside* ``jax.shard_map``;
``sequence_parallel_attention`` shard_maps it over a standard mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tensorflowonspark_tpu.ops.attention import (
    chunk_attention,
    match_vma,
    merge_attention,
)


def ring_attention(q, k, v, *, axis_name: str = "sp", causal: bool = True,
                   sm_scale: float | None = None):
    """Ring attention over a named axis; call inside ``shard_map``.

    ``q``/``k``/``v`` are local sequence shards ``[B, S_local, H, D]`` with
    the global sequence laid out contiguously across the axis (shard i holds
    positions ``[i*S_local, (i+1)*S_local)``).  Each step attends the local Q
    against the currently-held KV chunk (with its *global* offset, so causal
    masks stay exact), merges online-softmax style, then rotates KV to the
    next ring neighbour with ``ppermute`` — XLA overlaps the permute with the
    next chunk's compute over ICI.
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    s_local = q.shape[1]
    perm = [(i, (i + 1) % n) for i in range(n)]

    def attend_held(o, lse, k_cur, v_cur, hop):
        # KV currently held originated on ring neighbour (idx - hop) mod n.
        src = jax.lax.rem(idx - hop + n, n)
        kv_off = (src - idx) * s_local  # kv global start relative to q's
        o_c, lse_c = chunk_attention(q, k_cur, v_cur, causal=causal,
                                     sm_scale=sm_scale, kv_offset=kv_off)
        return merge_attention(o, lse, o_c, lse_c)

    def step(carry, hop):
        o, lse, k_cur, v_cur = carry
        k_nxt, v_nxt = jax.lax.ppermute((k_cur, v_cur), axis_name, perm)
        o, lse = attend_held(o, lse, k_cur, v_cur, hop)
        return (o, lse, k_nxt, v_nxt), None

    b, s, h, d = q.shape
    # The accumulator stays float32 through every merge (merge_attention
    # preserves o1's dtype): a bf16 carry would round after each hop and
    # precision would degrade with ring size relative to the f32
    # accumulation used everywhere else in ops/attention.py.
    o0 = match_vma(jnp.zeros((b, s, h, d), jnp.float32), q)
    lse0 = match_vma(jnp.full((b, s, h), -jnp.inf, jnp.float32), q)
    # n-1 hops rotate KV while attending; the final held chunk is attended
    # outside the scan so its rotation (whose result nobody reads) is never
    # issued on the ICI.
    (o, lse, k_last, v_last), _ = jax.lax.scan(
        step, (o0, lse0, k, v), jnp.arange(n - 1, dtype=jnp.int32))
    o, lse = attend_held(o, lse, k_last, v_last, jnp.int32(n - 1))
    return o.astype(q.dtype)


def sequence_parallel_attention(mesh, q, k, v, *, causal: bool = True,
                                sm_scale: float | None = None):
    """Shard_map wrapper: ring self-attention with sequence sharded over ``sp``.

    Global arrays ``[B, S, H, D]``: batch over ``(dp, fsdp)``, sequence over
    ``sp``, heads over ``tp``.  Returns the same layout.
    """
    pspec = P(("dp", "fsdp"), "sp", "tp", None)
    ring = functools.partial(ring_attention, axis_name="sp", causal=causal,
                             sm_scale=sm_scale)
    return jax.shard_map(ring, mesh=mesh, in_specs=(pspec, pspec, pspec),
                         out_specs=pspec)(q, k, v)
