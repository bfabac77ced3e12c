"""A window on the causal mask through ``ops/attention.py`` (ISSUE 48): the
dense reference, the scan off the TPU, the chunk primitive, and all three
Pallas kernels in interpret mode; the tables hold the band's tiles and no
other, and a window that reaches over the row is plain causal's program."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu import telemetry
from tensorflowonspark_tpu.ops import attention as att

_PASSES = [pytest.param(1, id="one-pass"), pytest.param(2, id="two-passes")]


def _qkv(sq, heads, kv_heads, d=8, sk=None, b=1, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    q = rng.randn(b, sq, heads, d)
    k, v = (rng.randn(b, sk or sq, kv_heads, d) for _ in range(2))
    return tuple(jnp.asarray(x, dtype) for x in (q, k, v))


def _brute_force(sq, sk, window, kv_offset=0):
    qpos = np.arange(sq)[:, None]
    kpos = kv_offset + np.arange(sk)[None, :]
    return (kpos <= qpos) & (qpos - kpos < window)


def _value_and_grads(fn, q, k, v, w):
    def loss(q, k, v):
        out = fn(q, k, v)
        return jnp.sum(out.astype(jnp.float32) * w), out
    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    return (out, *grads)


@pytest.mark.parametrize("window", [1, 5, 16, 40])
def test_the_reference_s_window_is_the_brute_force_band(window):
    """``mha_reference(window=)`` against a softmax over the pairs ``0 <= i -
    j < W`` written out: the query itself and the ``W - 1`` before it."""
    q, k, v = _qkv(40, 2, 2)
    mask = _brute_force(40, 40, window)
    assert mask.sum(1).max() == min(window, 40) and mask[7, 7]
    logits = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    logits = np.where(mask, logits, -np.inf)
    weights = np.exp(logits - logits.max(-1, keepdims=True))
    weights /= weights.sum(-1, keepdims=True)
    want = np.einsum("bhqk,bkhd->bqhd", weights, v)
    got = att.mha_reference(q, k, v, window=window)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


_CASES = [
    # length, window, query heads, K/V heads, kv_offset (tiles of 16)
    pytest.param((64, 24, 7, 1, 0), id="no-multiple-of-the-tile-group-7"),
    pytest.param((64, 5, 2, 2, 0), id="smaller-than-a-tile-group-1"),
    pytest.param((64, 16, 2, 1, 0), id="one-tile"),
    pytest.param((50, 33, 14, 2, 0), id="padded-row-two-groups-of-7"),
    pytest.param((48, 48, 2, 2, 0), id="equal-to-the-row"),
    pytest.param((48, 100, 7, 1, 0), id="larger-than-the-row"),
    pytest.param((40, 20, 2, 2, -24), id="kv-offset"),
]


@pytest.mark.parametrize("case", _CASES)
@pytest.mark.parametrize("passes", _PASSES)
def test_the_kernels_under_a_window_match_the_reference(case, passes,
                                                        monkeypatch):
    """Forward and all three gradients of ``flash_attention(window=)`` in
    interpret mode, through the one-pass backward and through the two
    passes, against ``jax.grad`` of ``mha_reference(window=)`` under a random
    cotangent.  Errors relative to the largest entry of the reference."""
    length, window, heads, kv_heads, kv_offset = case
    if passes == 2:
        monkeypatch.setattr(att, "_VMEM_BODY", att._VMEM_LIMIT)
    sk = length - kv_offset
    q, k, v = _qkv(length, heads, kv_heads, sk=sk)
    w = jnp.asarray(np.random.RandomState(1).randn(*q.shape), jnp.float32)
    got = _value_and_grads(lambda q, k, v: att.flash_attention(
        q, k, v, window=window, kv_offset=kv_offset, block_q=16, block_k=16,
        impl="pallas_interpret"), q, k, v, w)
    want = _value_and_grads(lambda q, k, v: att.mha_reference(
        q, k, v, window=window, kv_offset=kv_offset), q, k, v, w)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.shape == b.shape
        err = jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))
        assert float(err) < 1e-5, (name, float(err))


@pytest.mark.parametrize("fn", ["xla", "chunk"])
@pytest.mark.parametrize("case", _CASES)
def test_the_paths_off_the_kernels_take_the_window(case, fn):
    length, window, heads, kv_heads, kv_offset = case
    q, k, v = _qkv(length, heads, kv_heads, sk=length - kv_offset)
    want = att.mha_reference(q, k, v, window=window, kv_offset=kv_offset)
    if fn == "xla":
        got = att.flash_attention(q, k, v, window=window, kv_offset=kv_offset,
                                  block_k=16, impl="xla")
    else:
        got, _lse = att.chunk_attention(
            q, *att._repeat_kv(q, k, v), window=window, kv_offset=kv_offset)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def _tables(sq, sk, window, kv_offset=0, tile=16):
    block_q, block_k, sq_p, sk_p = att._blocks(sq, sk, tile, tile)
    return att._tile_kinds(
        sq_p // block_q, sk_p // block_k, causal=True, kv_offset=kv_offset,
        block_q=block_q, block_k=block_k, sk=sk, block_diffusion=None,
        window=window), (block_q, block_k, sq_p, sk_p)


@pytest.mark.parametrize("case", [
    # queries, keys, window, kv_offset
    (64, 64, 24, 0), (64, 64, 5, 0), (64, 64, 16, 0), (64, 64, 17, 0),
    (50, 50, 33, 0), (40, 64, 20, -24), (64, 64, 1, 0), (32, 80, 30, 24)])
def test_the_walk_under_a_window_visits_exactly_the_band(case):
    """``_tile_kinds`` against the band's tiles counted pair by pair: live
    where SOME pair of the tile is visible, interior where EVERY pair is,
    masked tiles on both edges of a query block's run."""
    sq, sk, window, kv_offset = case
    kinds, (block_q, block_k, sq_p, sk_p) = _tables(sq, sk, window, kv_offset)
    visible = _brute_force(sq_p, sk_p, window, kv_offset)
    visible &= np.arange(sk_p)[None, :] < sk
    tiles = visible.reshape(sq_p // block_q, block_q, sk_p // block_k,
                            block_k)
    some, every = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
    np.testing.assert_array_equal(kinds != 0, some)
    np.testing.assert_array_equal(kinds == att._INTERIOR, every)
    iq, ik, flags = att._walk(kinds)
    live = (flags & (att._MASKED | att._INTERIOR)) != 0
    assert list(zip(iq[live], ik[live])) == list(zip(*np.nonzero(some)))
    if window > block_k and not kv_offset:
        # a run that left the first tiles behind starts masked, ends masked
        row = kinds[-1][np.flatnonzero(kinds[-1])]
        assert row[0] == att._MASKED and row[-1] == att._MASKED


@pytest.mark.parametrize("window", [48, 49, 1000])
def test_a_window_over_the_whole_row_is_causal_table_for_table(window):
    """``W >=`` the row: the tables are causal's, and ``flash_attention``
    drops the window before it plans, so the program is causal's too (one
    plan, one trace, the scopes and counters of the full mask)."""
    kinds, _ = _tables(48, 48, window)
    np.testing.assert_array_equal(kinds, _tables(48, 48, None)[0])
    q, k, v = _qkv(48, 2, 1)

    def program(**mask):
        return str(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
            att.flash_attention(q, k, v, block_q=16, block_k=16,
                                impl="pallas_interpret", **mask)),
            argnums=(0, 1, 2)))(q, k, v))

    before = telemetry.snapshot()["counters"].get("flash.window.visits", 0)
    assert program(window=window) == program()
    assert "flash_fwd_window" not in program(window=window)
    assert telemetry.snapshot()["counters"].get(
        "flash.window.visits", 0) == before


def _digest(tables) -> str:
    return hashlib.sha256(np.asarray(tables, np.int32).tobytes()).hexdigest()[:16]


# The visit tables of the kernels at the shapes of the benchmark's cells that
# run them, as the PARENT commit (b8ec99a, PR 47) builds them: sha256 of
# (forward / dq walk, dk/dv walk) from ``_plan`` there.  A mask argument of
# None must leave every one of them, and so every lowered program, as it was.
_PARENT_TABLES = {
    # positions, query heads, K/V heads, mask
    (512, 32, 32, None): ("d5e39e8c8a2878e0", "d5e39e8c8a2878e0"),
    (2048, 32, 32, None): ("d834d55f414a8f2f", "fc418e3ae3a0e9b5"),
    (4096, 16, 16, None): ("e09683e096b48573", "1caa2927e3d00607"),
    (8192, 32, 4, (4096, 4)): ("52cc32d374a9c8a7", "6b569ff1723f8154"),
    (8192, 32, 32, None): ("d7162c69f0393d34", "19285d553caae5da"),
    (16384, 28, 4, None): ("3707b60af613b986", "4cc0edc97c92a4b5"),
}


def _plan_of(length, heads, kv_heads, block_diffusion, window=None):
    q = jax.ShapeDtypeStruct((heads, length, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((kv_heads, length, 128), jnp.bfloat16)
    return att._plan(q, k, k, causal=not block_diffusion, kv_offset=0,
                     block_q=512, block_k=512,
                     block_diffusion=block_diffusion, window=window)


@pytest.mark.parametrize("shape", sorted(_PARENT_TABLES, key=str))
def test_without_a_window_the_visit_tables_are_the_parent_s(shape):
    plan = _plan_of(*shape)
    assert (_digest(plan.walk), _digest(plan.walk_t)) == _PARENT_TABLES[shape]
    assert dict(plan.tile)["window"] is None


def test_the_published_band_at_16k_and_what_the_counters_say():
    """SmallThinker's window layer at the cell's size: 28 query heads over 4
    K/V heads (a group of 7, all in one visit), 16,384 positions, window
    4,096 in tiles of 512: 9 live tiles a query block once the band has left
    the row's start (8 whole windows' worth and the two cut edges), 252 of
    causal's 528 visits; the one-pass backward holds dk and dv of the whole
    row (``_plan``)."""
    plan = _plan_of(16384, 28, 4, None, window=4096)
    assert (plan.heads, plan.fused) == (7, 7)
    iq, ik, flags = np.asarray(plan.walk)
    assert len(iq) == 252 and len(_plan_of(16384, 28, 4, None).walk[0]) == 528
    last = flags[iq == 31]
    assert len(last) == 9 and (last[0] & att._MASKED) and (
        last[-1] & att._MASKED) and all(f & att._INTERIOR for f in last[1:-1])
    before = telemetry.snapshot()["counters"]
    att._count(plan, False, plan.heads, plan.walk)
    after = telemetry.snapshot()["counters"]
    moved = {k: after[k] - before.get(k, 0) for k in after
             if k.startswith("flash.window.")}
    assert moved == {"flash.window.visits": 252,
                     "flash.window.causal_visits": 528,
                     "flash.window.masked_tiles": 32 + 24}


def test_the_window_s_kernels_carry_scopes_of_their_own():
    q, k, v = _qkv(64, 2, 1)
    hlo = jax.jit(jax.grad(lambda q, k, v: jnp.sum(att.flash_attention(
        q, k, v, window=24, block_q=16, block_k=16,
        impl="pallas_interpret")), argnums=(0, 1, 2))).lower(
            q, k, v).as_text(debug_info=True)
    assert "flash_fwd_window" in hlo and "flash_bwd_window" in hlo
    assert "flash_fwd/" not in hlo and "flash_bwd/" not in hlo


@pytest.mark.parametrize("mask,error", [
    (dict(causal=False), "causal=False"),
    (dict(causal=False, block_diffusion=(32, 4)), "block_diffusion=(32, 4)"),
    (dict(window=0), "window=0"),
])
def test_a_window_refuses_what_it_does_not_narrow(mask, error):
    q, k, v = _qkv(64, 2, 2)
    with pytest.raises(NotImplementedError, match="window=") as e:
        att.flash_attention(q, k, v, **{"window": 8, **mask})
    assert error in str(e.value)
    shared = jnp.zeros((1, 64, 4))
    with pytest.raises(NotImplementedError, match="k_shared"):
        att.flash_attention(jnp.zeros((1, 64, 2, 12)), k, v, k_shared=shared,
                            window=8)
