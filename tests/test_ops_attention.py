"""Attention kernels vs the dense reference (CPU; Pallas via interpret mode)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.ops import attention as att


def make_qkv(b=2, s=64, h=4, d=16, sk=None, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    sk = s if sk is None else sk
    q = jnp.asarray(rng.randn(b, s, h, d), dtype)
    k = jnp.asarray(rng.randn(b, sk, h, d), dtype)
    v = jnp.asarray(rng.randn(b, sk, h, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("block_k", [16, 24, 64])
def test_blockwise_matches_reference(causal, block_k):
    q, k, v = make_qkv()
    ref = att.mha_reference(q, k, v, causal=causal)
    out = att.blockwise_attention(q, k, v, causal=causal, block_k=block_k)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_blockwise_grads_match_reference():
    q, k, v = make_qkv(b=1, s=32, h=2, d=8)

    def loss_ref(q, k, v):
        return jnp.sum(att.mha_reference(q, k, v, causal=True) ** 2)

    def loss_blk(q, k, v):
        return jnp.sum(att.blockwise_attention(q, k, v, causal=True, block_k=8) ** 2)

    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    g_blk = jax.jit(jax.grad(loss_blk, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ref, g_blk):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_pallas_kernel_matches_reference(causal):
    q, k, v = make_qkv(b=1, s=48, h=2, d=16)
    ref = att.mha_reference(q, k, v, causal=causal)
    out = att.flash_attention(q, k, v, causal=causal, block_q=16, block_k=16,
                              impl="pallas_interpret")
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_pallas_kernel_cross_attention_lengths():
    # sq != sk and non-divisible by blocks exercises padding/masking.
    q, k, v = make_qkv(b=1, s=20, h=2, d=8, sk=52)
    ref = att.mha_reference(q, k, v, causal=False)
    out = att.flash_attention(q, k, v, causal=False, block_q=16, block_k=16,
                              impl="pallas_interpret")
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def _grads(fn, q, k, v, w):
    def loss(q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32) * w)
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)


# The backward is one kernel where the plan finds room for dk and dv over the
# whole key length (every shape of this file), else the dk/dv pass and the dq
# pass: with no room beside the tile body's, the plan takes the two.
_PASSES = [pytest.param(1, id="one-pass"), pytest.param(2, id="two-passes")]


def _backward_kernels(passes, monkeypatch):
    if passes == 2:
        monkeypatch.setattr(att, "_VMEM_BODY", att._VMEM_LIMIT)


def _fused_calls():
    from tensorflowonspark_tpu import telemetry

    counters = telemetry.snapshot()["counters"]
    return (counters.get("flash.bwd_fused", 0),
            counters.get("flash.bwd_calls", 0))


@pytest.mark.parametrize("passes", _PASSES)
def test_pallas_backward_matches_reference_under_a_squared_loss(passes,
                                                                monkeypatch):
    _backward_kernels(passes, monkeypatch)
    q, k, v = make_qkv(b=1, s=32, h=2, d=8)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    g_pal = jax.jit(jax.grad(loss(lambda q, k, v: att.flash_attention(
        q, k, v, block_q=16, block_k=16, impl="pallas_interpret")),
        argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(loss(lambda q, k, v: att.mha_reference(q, k, v)),
                    argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_pal, g_ref):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("case", [
    # sq, sk, kv_offset, causal, block_q, block_k, dtype, tolerance
    pytest.param((32, 32, 0, True, 16, 16, jnp.float32, 1e-5), id="causal"),
    pytest.param((32, 32, 0, False, 16, 16, jnp.float32, 1e-5),
                 id="not-causal"),
    pytest.param((40, 72, -24, True, 16, 16, jnp.float32, 1e-5),
                 id="sq-ne-sk-kv-offset"),
    pytest.param((20, 52, 0, False, 16, 16, jnp.float32, 1e-5),
                 id="cross-lengths-not-causal"),
    pytest.param((50, 50, 0, True, 16, 16, jnp.float32, 1e-5),
                 id="length-not-a-multiple-of-the-block"),
    pytest.param((64, 64, 0, True, 16, 32, jnp.float32, 1e-5),
                 id="four-q-two-kv-blocks"),
    pytest.param((64, 64, 0, True, 32, 16, jnp.float32, 1e-5),
                 id="two-q-four-kv-blocks"),
    pytest.param((12, 12, 0, True, 16, 16, jnp.float32, 1e-5),
                 id="shorter-than-a-block"),
    pytest.param((48, 48, 0, True, 16, 16, jnp.bfloat16, 2e-2), id="bf16"),
    pytest.param((40, 72, -24, True, 16, 16, jnp.bfloat16, 2e-2),
                 id="bf16-sq-ne-sk-kv-offset"),
])
@pytest.mark.parametrize("passes", _PASSES)
def test_pallas_backward_kernels_match_reference(case, passes, monkeypatch):
    """The one-pass kernel, and the dk/dv and dq kernels that run where its
    accumulators do not fit (interpret mode), against ``jax.grad`` of the
    dense reference in float32, under a random cotangent: skipped, interior
    and masked tiles, one tile a row and several, padded tails on both sides,
    an offset KV chunk.  Errors are relative to the largest entry of the
    reference gradient."""
    sq, sk, kv_offset, causal, block_q, block_k, dtype, tol = case
    _backward_kernels(passes, monkeypatch)
    q, k, v = make_qkv(b=2, s=sq, h=2, d=8, sk=sk, dtype=dtype)
    w = jnp.asarray(np.random.RandomState(1).randn(*q.shape), jnp.float32)
    fused, calls = _fused_calls()
    got = _grads(lambda q, k, v: att.flash_attention(
        q, k, v, causal=causal, kv_offset=kv_offset, block_q=block_q,
        block_k=block_k, impl="pallas_interpret"), q, k, v, w)
    # the path is the plan's choice, counted where the backward is traced
    assert _fused_calls() == (fused + (passes == 1), calls + 1)
    want = _grads(lambda q, k, v: att.mha_reference(
        q, k, v, causal=causal, kv_offset=kv_offset),
        *(x.astype(jnp.float32) for x in (q, k, v)), w)
    for name, a, b in zip("qkv", got, want):
        assert a.dtype == dtype and a.shape == b.shape
        err = jnp.max(jnp.abs(a.astype(jnp.float32) - b)) / jnp.max(jnp.abs(b))
        assert float(err) < tol, (name, float(err))


@pytest.mark.parametrize("passes", _PASSES)
def test_pallas_backward_rows_that_see_no_key_get_and_give_no_gradient(
        passes, monkeypatch):
    # a KV chunk from the future of the first rows (ring attention's
    # offsets): those rows' output is exactly 0, their lse NEG_INF, and the
    # backward must recompute p = 0 there, not exp(NEG_INF - NEG_INF) = 1
    _backward_kernels(passes, monkeypatch)
    q, k, v = make_qkv(b=1, s=32, h=2, d=8, sk=16)
    w = jnp.asarray(np.random.RandomState(1).randn(*q.shape), jnp.float32)
    off = 8
    got = _grads(lambda q, k, v: att.flash_attention(
        q, k, v, causal=True, kv_offset=off, block_q=16, block_k=16,
        impl="pallas_interpret"), q, k, v, w)
    want = _grads(lambda q, k, v: att.chunk_attention(
        q, k, v, causal=True, kv_offset=off)[0], q, k, v, w)
    np.testing.assert_array_equal(got[0][:, :off], 0.0)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("passes", _PASSES)
def test_pallas_backward_holds_no_scan(passes, monkeypatch):
    """Neither rule of the kernel's VJP goes through ``blockwise_attention``:
    the gradient program is the forward's ``pallas_call``, the backward's one
    or two, and layout, no loop."""
    _backward_kernels(passes, monkeypatch)
    q, k, v = make_qkv(b=1, s=32, h=2, d=8)
    jaxpr = str(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
        att.flash_attention(q, k, v, block_q=16, block_k=16,
                            impl="pallas_interpret")), argnums=(0, 1, 2)))(
                                q, k, v))
    assert jaxpr.count("pallas_call") == 1 + passes
    assert "scan" not in jaxpr and "while" not in jaxpr


# ---------------------------------------------------------------------------
# The walk: the kernels visit the live tiles of their mask and nothing else
# (ISSUE 32).
# ---------------------------------------------------------------------------

def _brute_force_tiles(sq, sk, tile, causal, kv_offset, block_diffusion):
    """Per tile of the padded score matrix, pair by pair from the mask's own
    elementwise rule: whether SOME pair is visible, whether EVERY pair is.
    Padded keys are never visible; padded query rows count as rows."""
    block_q, block_k, sq_p, sk_p = att._blocks(sq, sk, tile, tile)
    qpos, kidx = np.arange(sq_p)[:, None], np.arange(sk_p)[None, :]
    kpos = kv_offset + kidx
    visible = np.broadcast_to(kidx < sk, (sq_p, sk_p)).copy()
    if causal:
        visible &= kpos <= qpos
    if block_diffusion:
        visible &= np.asarray(att.block_diffusion_visible(
            qpos, kpos, *block_diffusion))
    tiles = visible.reshape(sq_p // block_q, block_q, sk_p // block_k,
                            block_k)
    static = dict(causal=causal, kv_offset=kv_offset, block_q=block_q,
                  block_k=block_k, sk=sk, block_diffusion=block_diffusion)
    return tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3)), static


_WALKS = [pytest.param((sq, sk, True, off, None),
                       id=f"causal-{shape}-kv-offset-{name}")
          for shape, sq, sk in [("square", 64, 64), ("cross", 32, 80),
                                ("padded", 40, 52)]
          for name, off in [("0", 0), ("positive", 24), ("negative", -24),
                            ("wholly-in-the-future", 96)]]
_WALKS += [pytest.param((20, 52, False, 0, None), id="not-causal-padded"),
           pytest.param((12, 12, True, 0, None), id="shorter-than-a-block")]
_WALKS += [pytest.param((2 * length, 2 * length, False, 0, (length, block)),
                        id=f"block-diffusion-{length}-{block}")
           for length, block in [(24, 4), (64, 32), (20, 4)]]


def _live(flags):
    return (flags & (att._MASKED | att._INTERIOR)) != 0


def _assert_flags_bracket_each_block(block, flags, blocks):
    """Every output block is one run of consecutive visits, in order; its
    first visit and no other carries _FIRST, its last and no other _LAST."""
    assert list(dict.fromkeys(block.tolist())) == list(range(blocks))
    first = np.r_[True, block[1:] != block[:-1]]
    last = np.r_[block[1:] != block[:-1], True]
    np.testing.assert_array_equal((flags & att._FIRST) != 0, first)
    np.testing.assert_array_equal((flags & att._LAST) != 0, last)


@pytest.mark.parametrize("case", _WALKS)
def test_walk_visits_the_live_tiles_and_nothing_else(case):
    """The forward's and the dq pass's table against the brute-force set of
    tiles in which the mask shows any pair: the same tiles, row-major with k
    ascending; interior exactly where every pair shows; a q block with no
    live tile visited once, attending nothing."""
    sq, sk, causal, kv_offset, block_diffusion = case
    some, every, static = _brute_force_tiles(sq, sk, 16, causal, kv_offset,
                                             block_diffusion)
    kinds = att._tile_kinds(*some.shape, **static)
    iq, ik, flags = att._walk(kinds)
    live = _live(flags)
    assert list(zip(iq[live], ik[live])) == list(zip(*np.nonzero(some)))
    np.testing.assert_array_equal((flags[live] & att._INTERIOR) != 0,
                                  every[iq[live], ik[live]])
    both = att._MASKED | att._INTERIOR
    assert not np.any((flags & both) == both)
    np.testing.assert_array_equal(iq[~live],
                                  np.flatnonzero(~some.any(axis=1)))
    _assert_flags_bracket_each_block(iq, flags, some.shape[0])
    assert flags.dtype == np.int32


def _pallas_calls(jaxpr):
    """``(grid, block shapes of the operands and results, VMEM limit)`` of
    every ``pallas_call`` of a jaxpr, in order."""
    from jax._src import core

    calls = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            mapping = eqn.params["grid_mapping"]
            calls.append((tuple(mapping.grid), [
                tuple(getattr(dim, "block_size", dim)
                      for dim in block.block_shape)
                for block in mapping.block_mappings],
                eqn.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes))
        for sub in core.jaxprs_in_params(eqn.params):
            calls += _pallas_calls(sub)
    return calls


def _pallas_grids(jaxpr):
    return [grid for grid, _, _ in _pallas_calls(jaxpr)]


def _flash_calls(q, k, **kwargs):
    """The ``pallas_call``s of one gradient program: the forward and the
    one-pass backward, or the forward, the dk/dv pass and the dq pass."""
    jaxpr = jax.make_jaxpr(jax.value_and_grad(lambda q, k, v: jnp.sum(
        att.flash_attention(q, k, v, impl="pallas_interpret", **kwargs)),
        argnums=(0, 1, 2)))(q, k, k)
    assert "scan" not in str(jaxpr) and "while" not in str(jaxpr)
    return _pallas_calls(jaxpr.jaxpr)


@pytest.mark.parametrize("group", [1, 8])
@pytest.mark.parametrize("case", _WALKS)
def test_dkv_walk_visits_each_head_of_each_live_tile_once(case, group,
                                                          monkeypatch):
    """The dk/dv pass's table: per k block, in order, every live tile ONCE
    with q ascending, whatever the group; a k block that no query sees
    visited once.  The group's query heads are served INSIDE the visit: the
    pass's grid is (K/V heads, visits) and its query-side blocks hold the
    ``group`` heads, so every (head, live tile) is met exactly once."""
    sq, sk, causal, kv_offset, block_diffusion = case
    _backward_kernels(2, monkeypatch)
    some, every, static = _brute_force_tiles(sq, sk, 16, causal, kv_offset,
                                             block_diffusion)
    kinds = att._tile_kinds(*some.shape, **static)
    ik, iq, flags = att._walk(kinds.T)
    live = _live(flags)
    for k in range(some.shape[1]):
        here = live & (ik == k)
        assert list(iq[here]) == list(np.flatnonzero(some[:, k]))
    np.testing.assert_array_equal((flags[live] & att._INTERIOR) != 0,
                                  every[iq[live], ik[live]])
    np.testing.assert_array_equal(ik[~live],
                                  np.flatnonzero(~some.any(axis=0)))
    _assert_flags_bracket_each_block(ik, flags, some.shape[1])

    kv_heads, d = 2, 8
    _, (grid, blocks, _), _ = _flash_calls(
        jnp.zeros((1, sq, kv_heads * group, d)),
        jnp.zeros((1, sk, kv_heads, d)), causal=causal, kv_offset=kv_offset,
        block_q=16, block_k=16, block_diffusion=block_diffusion)
    assert grid == (kv_heads, ik.size)
    block_q, block_k = static["block_q"], static["block_k"]
    assert blocks == (
        [(group, block_q, d)] * 2 + [(group, 1, block_q)] * 2   # q dO lse delta
        + [(1, block_k, d)] * 4)                                # k v dk dv


@pytest.mark.parametrize("group", [1, 8])
@pytest.mark.parametrize("case", _WALKS)
def test_one_pass_backward_walks_the_forward_s_table(case, group):
    """The one-pass backward: grid (K/V heads, the q-major table's length),
    the query-side blocks of the ``group`` heads and dq a q block, K and V a
    k block, and dk and dv ONE block over the whole padded key length a grid
    row, whatever the visit: resident from the row's first visit to its
    last, which carry the table's ``_OPEN`` and ``_CLOSE`` and no other."""
    sq, sk, causal, kv_offset, block_diffusion = case
    some, _, static = _brute_force_tiles(sq, sk, 16, causal, kv_offset,
                                         block_diffusion)
    iq, _, flags = att._walk(att._tile_kinds(*some.shape, **static))
    np.testing.assert_array_equal((flags & att._OPEN) != 0,
                                  np.arange(iq.size) == 0)
    np.testing.assert_array_equal((flags & att._CLOSE) != 0,
                                  np.arange(iq.size) == iq.size - 1)
    kv_heads, d = 2, 8
    calls = _flash_calls(
        jnp.zeros((1, sq, kv_heads * group, d)),
        jnp.zeros((1, sk, kv_heads, d)), causal=causal, kv_offset=kv_offset,
        block_q=16, block_k=16, block_diffusion=block_diffusion)
    assert len(calls) == 2
    grid, blocks, _ = calls[1]
    assert grid == (kv_heads, iq.size)
    block_q, block_k = static["block_q"], static["block_k"]
    sk_p = some.shape[1] * block_k
    assert blocks == (
        [(group, block_q, d)] * 2 + [(group, 1, block_q)] * 2   # q dO lse delta
        + [(1, block_k, d)] * 2                                 # k v
        + [(group, block_q, d)] + [(1, sk_p, d)] * 2)           # dq dk dv


@pytest.mark.parametrize("case", [
    # query rows (batch x heads), K/V rows, positions, shared key?, dtype
    # size -> heads a visit forward, in the one pass, its VMEM limit raised?
    pytest.param((32, 4, 8192, False, 2, 8, 8, True), id="sdar-8k-32-over-4"),
    pytest.param((128, 128, 2048, False, 2, 1, 1, False),
                 id="dense-lm-2k-default-scope"),
    pytest.param((32, 32, 4096, False, 2, 1, 1, False),
                 id="olmoe-4k-default-scope"),
    pytest.param((32, 32, 8192, False, 2, 1, 1, True), id="one-head-8k"),
    # every head K and V of its own: 76 MiB of them at the forward's 4 heads
    pytest.param((32, 32, 8192, True, 2, 4, 4, True), id="kanana-8k-latent"),
    pytest.param((32, 32, 16384, True, 2, 4, 1, True), id="latent-16k"),
    # float32 shares of dk and dv where a group takes four grid rows
    pytest.param((32, 1, 2048, False, 2, 8, 8, True), id="32-over-1"),
    pytest.param((32, 4, 32768, False, 2, 8, 8, True), id="32k-32-over-4"),
    pytest.param((32, 4, 65536, False, 2, 8, 0, False), id="64k-32-over-4"),
    pytest.param((8, 1, 131072, False, 2, 8, 0, False), id="128k-row"),
    pytest.param((8, 8, 16384, False, 4, 1, 1, True), id="float32-16k"),
])
def test_plan_takes_one_pass_where_dk_and_dv_fit(case):
    """From shapes and bytes alone, at the default tile: one pass where a
    visit's blocks and the float32 accumulators and two output buffers of a
    grid row's dk and dv leave the tile body its room in the VMEM limit, at
    the forward's heads a visit (fewer where every head has K and V of its
    own); the two passes for a row too long.  A one-head visit keeps
    Mosaic's default scope while the same sum leaves that room there."""
    rows, kv_rows, length, latent, size, heads, fused, raised = case
    dtype = {2: jnp.bfloat16, 4: jnp.float32}[size]
    shape = lambda n, width=128: jax.ShapeDtypeStruct(  # noqa: E731
        (n, length, width), dtype)
    plan = att._plan(shape(rows), shape(kv_rows), shape(kv_rows),
                     shape(1) if latent else None, causal=True, kv_offset=0,
                     block_q=512, block_k=512, block_diffusion=None)
    assert (plan.heads, plan.fused) == (heads, fused)
    assert plan.fused_limit == (att._VMEM_LIMIT if raised else None)
    assert att._VMEM_DEFAULT < att._VMEM_BLOCKS + att._VMEM_BODY \
        < att._VMEM_LIMIT


@pytest.mark.parametrize("passes", _PASSES)
@pytest.mark.parametrize("case", [
    # mask, heads, K/V heads, tiles of the dense grid a head, live tiles
    pytest.param((dict(causal=True), 2, 2, 256, 136), id="causal"),
    pytest.param((dict(causal=False, block_diffusion=(128, 4)), 8, 1, 256,
                  80), id="block-diffusion-at-sdar-s-tile-count"),
])
def test_pallas_grids_have_the_length_of_the_walk(case, passes, monkeypatch):
    """Forward and backward are two ``pallas_call``s (three where the
    backward takes its two passes) whose grid is (K/V heads, the table's
    length), not the dense (q tile, k tile) grid of every query head: 80 of
    256 under SDAR's mask (16 x 16 tiles, block 4, scaled down), in the
    backward too, which serves a group's query heads inside the visit.  The
    counters read the share of the dense grid that is walked, the heads a
    visit serves and the share of backward calls that took one pass: a
    one-pass backward is ONE kernel over one table."""
    from tensorflowonspark_tpu import telemetry

    mask, heads, kv_heads, dense, walked = case
    group = heads // kv_heads
    kernels = 1 + passes
    _backward_kernels(passes, monkeypatch)
    q = jnp.zeros((1, 256, heads, 8))
    k = jnp.zeros((1, 256, kv_heads, 8))
    before = telemetry.snapshot()["counters"]
    calls = _flash_calls(q, k, block_q=16, block_k=16, **mask)
    after = telemetry.snapshot()["counters"]
    assert [grid for grid, _, _ in calls] == [(kv_heads, walked)] * kernels
    # a visit of one head keeps Mosaic's default VMEM scope
    assert [limit for _, _, limit in calls] == [
        att._VMEM_LIMIT if group > 1 else None] * kernels
    counted = {name: after.get(name, 0) - before.get(name, 0)
               for name in ("flash.kernels", "flash.visit_heads",
                            "flash.tiles", "flash.tiles_walked",
                            "flash.bwd_calls", "flash.bwd_fused")}
    assert counted == {"flash.kernels": kernels,
                       "flash.visit_heads": kernels * group,
                       "flash.tiles": kernels * dense,
                       "flash.tiles_walked": kernels * walked,
                       "flash.bwd_calls": 1, "flash.bwd_fused": 2 - passes}


def test_layers_and_programs_share_one_trace_of_each_kernel(monkeypatch):
    """The kernels' wrappers are jitted on what is static of a call: the
    second layer of a program, and the next program of the process, trace no
    kernel anew (a visit's head loop is 8 tile bodies: SDAR's set-up paid
    13 s for tracing them per layer and program).  The counters still count
    every kernel a program holds."""
    from tensorflowonspark_tpu import telemetry

    visits = []
    real = att._visit
    monkeypatch.setattr(att, "_visit", lambda *args, **kwargs: (
        visits.append(1), real(*args, **kwargs))[1])
    q, k = jnp.zeros((1, 32, 8, 8)), jnp.zeros((1, 32, 2, 8))
    attend = functools.partial(     # a scale of its own: a signature no
        att.flash_attention, sm_scale=0.1357, block_q=16, block_k=16,
        impl="pallas_interpret")    # other test has traced

    def two_layers(q, k, v):
        return jnp.sum(attend(attend(q, k, v), k, v))

    before = telemetry.snapshot()["counters"]
    first = _pallas_calls(jax.make_jaxpr(jax.grad(two_layers))(q, k, k).jaxpr)
    assert len(first) == 4 and len(visits) == 2     # forward, backward
    again = _pallas_calls(jax.make_jaxpr(jax.value_and_grad(
        lambda q, k, v: 2 * two_layers(q, k, v)))(q, k, k).jaxpr)
    assert again == first and len(visits) == 2
    after = telemetry.snapshot()["counters"]
    # two programs of two layers of two kernels, 4 heads a visit
    assert after["flash.kernels"] - before.get("flash.kernels", 0) == 8
    assert after["flash.visit_heads"] - before.get(
        "flash.visit_heads", 0) == 8 * 4


@pytest.mark.parametrize("passes", _PASSES)
def test_rows_of_one_tile_run_straight_through(passes, monkeypatch):
    """Where the table says that every visit is its block's first and last
    and builds the mask (one tile a row: 512-id rows at the default tile),
    no kernel holds a branch (the one visit also opens and closes the row of
    the one-pass backward); where rows have several tiles, they do."""
    _backward_kernels(passes, monkeypatch)

    def jaxpr(s):
        q = jnp.zeros((1, s, 2, 8))
        return str(jax.make_jaxpr(jax.value_and_grad(lambda q, k, v: jnp.sum(
            att.flash_attention(q, k, v, block_q=16, block_k=16,
                                impl="pallas_interpret")),
            argnums=(0, 1, 2)))(q, q, q))

    assert "cond" not in jaxpr(16)
    assert "cond" in jaxpr(32)


def test_chunk_merge_equals_full_attention():
    # Split KV into 4 chunks with global offsets, merge — must equal dense.
    q, k, v = make_qkv(b=2, s=64, h=2, d=16)
    nchunks, cs = 4, 16
    ref = att.mha_reference(q, k, v, causal=True)
    o, lse = att.chunk_attention(q, k[:, :cs], v[:, :cs], causal=True, kv_offset=0)
    for i in range(1, nchunks):
        oc, lc = att.chunk_attention(q, k[:, i * cs:(i + 1) * cs],
                                     v[:, i * cs:(i + 1) * cs],
                                     causal=True, kv_offset=i * cs)
        o, lse = att.merge_attention(o, lse, oc, lc)
    np.testing.assert_allclose(o, ref, atol=1e-5, rtol=1e-5)


def test_fully_masked_chunk_is_identity_under_merge():
    # A pure-future chunk contributes nothing (ring attention relies on this).
    q, k, v = make_qkv(b=1, s=8, h=1, d=4)
    o1, l1 = att.chunk_attention(q, k, v, causal=True, kv_offset=0)
    o2, l2 = att.chunk_attention(q, k, v, causal=True, kv_offset=1000)  # all future
    assert np.all(np.asarray(l2) == att.NEG_INF)
    om, lm = att.merge_attention(o1, l1, o2, l2)
    np.testing.assert_allclose(om, o1, atol=1e-6)
    np.testing.assert_allclose(lm, l1, atol=1e-6)


def test_kv_offset_matches_sliced_dense():
    # chunk_attention with offset == dense attention restricted to that chunk.
    q, k, v = make_qkv(b=1, s=16, h=2, d=8)
    off = 4
    ref = att.mha_reference(q, k[:, :8], v[:, :8], causal=True, kv_offset=off)
    out, _ = att.chunk_attention(q, k[:, :8], v[:, :8], causal=True, kv_offset=off)
    # q rows < off are fully masked: chunk_attention yields exact zeros there
    # (the dense reference's softmax degenerates to uniform garbage instead).
    np.testing.assert_allclose(out[:, off:], ref[:, off:], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(out[:, :off], 0.0, atol=1e-6)


def test_bf16_inputs():
    q, k, v = make_qkv(dtype=jnp.bfloat16)
    ref = att.mha_reference(q, k, v, causal=True)
    out = att.blockwise_attention(q, k, v, causal=True, block_k=32)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2, rtol=3e-2)
    assert out.dtype == jnp.bfloat16


def test_pallas_kernel_runs_per_shard_under_an_ambient_mesh():
    """Under ``jax.set_mesh`` the kernel is shard_mapped over the batch
    (dp, fsdp) and head (tp) axes with every other mesh axis manual too —
    the only form jax lowers a Mosaic kernel in on more than one device
    (chip_smoke.py checks the real lowering on four chips).  Forward and
    gradients must still match the dense reference, also when the mesh
    has an axis (sp) the kernel's specs do not name."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tensorflowonspark_tpu.parallel import mesh as meshlib

    mesh = meshlib.make_mesh(dp=2, sp=2, tp=2)
    q, k, v = make_qkv(b=4, s=32, h=4, d=8)
    placed = [jax.device_put(x, NamedSharding(
        mesh, P(("dp", "fsdp"), "sp", "tp", None))) for x in (q, k, v)]

    def loss(attend):
        def f(q, k, v):
            out = attend(q, k, v, causal=True)
            return jnp.sum(out ** 2), out
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

    with jax.set_mesh(mesh):
        fn = jax.jit(loss(functools.partial(
            att.flash_attention, impl="pallas_interpret",
            block_q=16, block_k=16)))
        # shardy spells a shard_map region "sdy.manual_computation"
        assert "manual_computation" in fn.lower(*placed).as_text()
        (_, out), grads = fn(*placed)
    assert out.sharding.spec == P("dp", None, "tp")
    (_, ref), ref_grads = loss(att.mha_reference)(q, k, v)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)
    for g, r in zip(grads, ref_grads):
        np.testing.assert_allclose(g, r, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# Grouped-query heads and the block-diffusion mask (ISSUE 31).
# ---------------------------------------------------------------------------

def _brute_force_block_diffusion(length: int, block: int) -> np.ndarray:
    """``M[q, k]`` over ``[x_t ‖ x_0]`` from the three sentences that define
    it (ISSUE 31), pair by pair."""
    m = np.zeros((2 * length, 2 * length), bool)
    for q in range(2 * length):
        for k in range(2 * length):
            q_noised, k_noised = q < length, k < length
            bq, bk = (q % length) // block, (k % length) // block
            m[q, k] = ((q_noised and k_noised and bq == bk)
                       or (q_noised and not k_noised and bk < bq)
                       or (not q_noised and not k_noised and bk <= bq))
    return m


@pytest.mark.parametrize("length,block", [(24, 4), (64, 32), (20, 4)])
def test_block_diffusion_mask_is_the_brute_force_one(length, block):
    want = _brute_force_block_diffusion(length, block)
    idx = jnp.arange(2 * length)
    got = att.block_diffusion_visible(idx[:, None], idx[None, :], length,
                                      block)
    np.testing.assert_array_equal(np.asarray(got), want)
    # clean queries never see a noised key; every query sees some key
    assert not want[length:, :length].any() and want.any(axis=1).all()


@pytest.mark.parametrize("length,block,tile", [(24, 4, 16), (64, 32, 16),
                                               (20, 4, 16), (32, 4, 8)])
def test_block_diffusion_tile_bounds_agree_with_the_mask(length, block, tile):
    """What the kernels decide per tile, for every tile of every tiling the
    tests below use (tiles that straddle the two copies included): live iff
    some pair of the tile is visible, interior iff every pair is."""
    mask = _brute_force_block_diffusion(length, block)
    s = 2 * length
    args = dict(causal=False, kv_offset=0, block_q=tile, block_k=tile, sk=s,
                block_diffusion=(length, block))
    for q0 in range(0, s, tile):
        for k0 in range(0, s, tile):
            sub = mask[q0:q0 + tile, k0:k0 + tile]
            whole = sub.shape == (tile, tile)
            live = bool(att._tile_live(q0, k0, **args))
            interior = bool(att._tile_interior(q0, k0, **args))
            # rows past the end of the queries are padding: they may keep a
            # tile alive, never make it interior when a real pair is masked
            if q0 + tile <= s:
                assert live == bool(sub.any()), (q0, k0)
            else:
                assert live or not sub.any(), (q0, k0)
            if interior:
                assert whole and sub.all(), (q0, k0)
            elif whole:
                assert not sub.all(), (q0, k0)


@pytest.mark.parametrize("group", [1, 8])
@pytest.mark.parametrize("impl", ["pallas_interpret", "xla"])
def test_grouped_query_heads_match_the_reference_on_repeated_heads(group,
                                                                   impl):
    """Query head j reads K/V head j // group, forward and all three
    gradients; dk and dv come back at the K/V head count (the sum over the
    group's query heads)."""
    rng = np.random.RandomState(3)
    b, s, h, d = 2, 40, 8, 8
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h // group, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h // group, d), jnp.float32)
    w = jnp.asarray(rng.randn(*q.shape), jnp.float32)
    fn = lambda q, k, v: att.flash_attention(  # noqa: E731
        q, k, v, causal=True, block_q=16, block_k=16, impl=impl)
    ref = lambda q, k, v: att.mha_reference(  # noqa: E731
        q, jnp.repeat(k, group, 2), jnp.repeat(v, group, 2), causal=True)
    np.testing.assert_allclose(fn(q, k, v), ref(q, k, v), atol=1e-5,
                               rtol=1e-5)
    for a, r in zip(_grads(fn, q, k, v, w), _grads(ref, q, k, v, w)):
        assert a.shape == r.shape
        np.testing.assert_allclose(a, r, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("impl", ["pallas_interpret", "xla"])
@pytest.mark.parametrize("length,block,tile,group", [
    (24, 4, 16, 1),      # 48 positions in tiles of 16: tiles straddle L
    (64, 32, 16, 1),
    (20, 4, 16, 4),      # 40 positions: a padded tail, grouped heads
    (32, 4, 8, 2),
])
def test_block_diffusion_attention_and_both_backward_passes(impl, length,
                                                            block, tile,
                                                            group):
    """Forward, dq and dk/dv under the mask against the dense reference on
    the brute-force mask's own definition: skipped tiles, interior tiles
    and masked ones, for L not a multiple of the tile."""
    rng = np.random.RandomState(5)
    b, h, d, s = 1, 4, 8, 2 * length
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h // group, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h // group, d), jnp.float32)
    w = jnp.asarray(rng.randn(*q.shape), jnp.float32)
    mask = jnp.asarray(_brute_force_block_diffusion(length, block))

    def ref(q, k, v):
        k, v = jnp.repeat(k, group, 2), jnp.repeat(v, group, 2)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
        p = jax.nn.softmax(jnp.where(mask, logits, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    fn = lambda q, k, v: att.flash_attention(  # noqa: E731
        q, k, v, causal=False, block_q=tile, block_k=tile, impl=impl,
        block_diffusion=(length, block))
    np.testing.assert_allclose(fn(q, k, v), ref(q, k, v), atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(
        att.mha_reference(q, k, v, causal=False,
                          block_diffusion=(length, block)),
        ref(q, k, v), atol=1e-5, rtol=1e-5)
    for a, r in zip(_grads(fn, q, k, v, w), _grads(ref, q, k, v, w)):
        np.testing.assert_allclose(a, r, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# A visit serves the query heads of a K/V group together (ISSUE 36).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shapes,heads", [
    # SDAR's and Keye's: 8 heads of 512 x 128 in bf16 fill a visit
    pytest.param((8, 512, 128, 2), 8, id="sdar-8-over-1-group"),
    pytest.param((1, 512, 128, 2), 1, id="group-1"),
    pytest.param((1, 512, 128, 4), 1, id="group-1-float32"),
    # 32 over 1 does not fit: a proper divisor, several visits a tile
    pytest.param((32, 512, 128, 2), 8, id="32-over-1-in-four-visits"),
    pytest.param((8, 512, 128, 4), 8, id="8-heads-float32"),
    pytest.param((12, 512, 128, 4), 6, id="12-heads-the-largest-divisor"),
    pytest.param((7, 512, 128, 4), 7, id="a-prime-group-that-fits"),
    pytest.param((11, 512, 128, 4), 1, id="a-prime-group-that-does-not"),
    pytest.param((32, 16, 8, 4), 32, id="interpret-mode-tiles-all-fit"),
])
def test_heads_a_visit_is_the_largest_divisor_that_fits(shapes, heads):
    """Read from the shapes at trace time: the whole group where its blocks
    and scratch fit the budget, else its largest divisor that does; one head
    always (a single head's blocks are what the kernels held before)."""
    group = shapes[0]
    got = att._visit_heads(*shapes)
    assert got == heads and group % got == 0
    assert att._VMEM_BLOCKS < att._VMEM_LIMIT <= 100 << 20


@pytest.mark.parametrize("case", [
    # batch, sq, sk, heads, K/V heads, d, mask, dtype, heads a visit or None
    pytest.param((2, 40, 40, 8, 1, 8, dict(causal=True), jnp.float32, None),
                 id="8-over-1-batch-2-length-not-whole-blocks"),
    pytest.param((2, 40, 72, 8, 2, 8, dict(causal=True, kv_offset=-24),
                  jnp.float32, None), id="8-over-2-batch-2-causal-kv-offset"),
    pytest.param((1, 48, 48, 2, 2, 96, dict(causal=True), jnp.float32, None),
                 id="group-1-head-dim-96"),
    pytest.param((2, 40, 40, 8, 2, 8,
                  dict(causal=False, block_diffusion=(20, 4)), jnp.float32,
                  None), id="8-over-2-batch-2-block-diffusion-padded"),
    pytest.param((2, 48, 48, 8, 2, 8, dict(causal=False), jnp.float32, None),
                 id="8-over-2-batch-2-not-causal"),
    pytest.param((2, 48, 48, 8, 2, 8, dict(causal=True), jnp.bfloat16, None),
                 id="bf16-8-over-2-batch-2"),
    pytest.param((1, 48, 48, 8, 1, 8,
                  dict(causal=False, block_diffusion=(24, 4)), jnp.bfloat16,
                  None), id="bf16-8-over-1-block-diffusion"),
    pytest.param((1, 40, 72, 2, 2, 96, dict(causal=True, kv_offset=-24),
                  jnp.bfloat16, None), id="bf16-group-1-head-dim-96-offset"),
    pytest.param((2, 40, 40, 8, 1, 8, dict(causal=True), jnp.float32, 2),
                 id="8-over-1-batch-2-in-four-visits-a-tile"),
    pytest.param((2, 40, 40, 8, 2, 8,
                  dict(causal=False, block_diffusion=(20, 4)), jnp.bfloat16,
                  2), id="bf16-8-over-2-block-diffusion-in-two-visits"),
])
@pytest.mark.parametrize("passes", _PASSES)
def test_a_visit_serves_the_query_heads_of_its_group(case, passes,
                                                     monkeypatch):
    """Values and all three gradients of the kernels (interpret mode) against
    the dense float32 reference where a grid row holds a K/V head's whole
    group (a group's rows are neighbours, across the batch too), and where
    the budget splits a group over several grid rows (their dk/dv shares
    are added outside the kernel; in the one-pass backward each share is
    resident over the whole key length).  Errors relative to the reference's
    largest entry: float32 inputs round nothing, bf16 ones at the tolerance
    of ``test_pallas_backward_kernels_match_reference``."""
    b, sq, sk, h, kv_heads, d, mask, dtype, visit_heads = case
    group = h // kv_heads
    _backward_kernels(passes, monkeypatch)
    if visit_heads:
        a_head = 6 * 16 * d * jnp.dtype(dtype).itemsize + 4 * 16 * 128 * 4 \
            + 16 * d * 4
        monkeypatch.setattr(att, "_VMEM_BLOCKS", visit_heads * a_head)
    assert att._visit_heads(group, 16, d, jnp.dtype(dtype).itemsize) == (
        visit_heads or group)
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(b, sq, h, d), dtype)
    k = jnp.asarray(rng.randn(b, sk, kv_heads, d), dtype)
    v = jnp.asarray(rng.randn(b, sk, kv_heads, d), dtype)
    w = jnp.asarray(rng.randn(*q.shape), jnp.float32)
    fn = lambda q, k, v: att.flash_attention(  # noqa: E731
        q, k, v, block_q=16, block_k=16, impl="pallas_interpret", **mask)
    ref = lambda q, k, v: att.mha_reference(q, k, v, **mask)  # noqa: E731
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    grid, _, _ = _flash_calls(q, k, block_q=16, block_k=16, **mask)[1]
    assert grid[0] == b * h // (visit_heads or group)
    got = [fn(q, k, v), *_grads(fn, q, k, v, w)]
    want = [ref(*f32), *_grads(ref, *f32, w)]
    for name, a, r in zip(["out", "dq", "dk", "dv"], got, want):
        assert a.dtype == dtype and a.shape == r.shape, name
        err = jnp.max(jnp.abs(a.astype(jnp.float32) - r)) / jnp.max(jnp.abs(r))
        assert float(err) < tol, (name, float(err))


def test_block_diffusion_refuses_what_it_does_not_mask():
    q, k, v = make_qkv(b=1, s=32, h=2, d=8)
    for kwargs in (dict(causal=True, block_diffusion=(16, 4)),
                   dict(causal=False, block_diffusion=(12, 4)),
                   dict(causal=False, block_diffusion=(16, 5))):
        with pytest.raises(ValueError, match="block_diffusion"):
            att.flash_attention(q, k, v, impl="xla", **kwargs)
    with pytest.raises(ValueError, match="query heads"):
        att.flash_attention(q, k[:, :, :1], v, impl="xla")
