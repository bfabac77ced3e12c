"""Learned sparse attention (``ops/sparse_attention.py``): the dense path and
the kernels in interpret mode against a dense masked softmax written here, at
grouped-query heads 4 over 2 (attention itself: 4 over 4, 2 and 1 too, a
visit of its two kernels serving 1, 2 or 4 query heads)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu import telemetry
from tensorflowonspark_tpu.ops import attention as att
from tensorflowonspark_tpu.ops import sparse_attention as dsa
from tests.test_ops_attention import _pallas_grids

IMPLS = ["xla", "pallas_interpret"]
L, H, HKV, D, J, DI = 64, 4, 2, 16, 3, 8
GROUPS = [1, 2, 4]          # query heads a K/V head: H over 4, 2 and 1


def make_row(seed=0, length=L, dtype=jnp.float32, kv_heads=HKV):
    rng = np.random.RandomState(seed)
    draw = lambda *shape: jnp.asarray(rng.randn(*shape), dtype)  # noqa: E731
    return {"q": draw(length, H, D), "k": draw(length, kv_heads, D),
            "v": draw(length, kv_heads, D), "a": draw(length, J, DI),
            "b": draw(length, DI), "c": draw(length, J)}


def sorted_selection(scores, topk):
    """The selection by a STABLE sort of each row's causal scores, largest
    first: equal scores keep the lower position."""
    scores = np.asarray(scores, np.float32)
    mask = np.zeros(scores.shape, np.int8)
    for t in range(scores.shape[0]):
        order = np.argsort(-scores[t, :t + 1], kind="stable")
        mask[t, order[:topk]] = 1
    return mask


def dense_attention(q, k, v, mask):
    """float32 softmax with -inf outside the mask; (out, lse [H, L], p)."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    logits = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(q.shape[-1])
    logits = jnp.where(jnp.asarray(mask)[None] != 0, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    return (jnp.einsum("hqk,khd->qhd", p, v),
            jax.nn.logsumexp(logits, axis=-1), p)


def dense_scores(a, b, c):
    return jnp.einsum("tj,tjs->ts", c,
                      jnp.maximum(jnp.einsum("tjd,sd->tjs", a, b), 0.0))


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("topk", [1, 5, 24, 64, 100])
def test_the_selection_is_the_stable_sort_s(impl, topk):
    row = make_row(seed=topk)
    scores = dsa.index_scores(row["a"], row["b"], row["c"], impl=impl)
    want_scores = dense_scores(row["a"], row["b"], row["c"])
    causal = np.tril(np.ones((L, L), bool))
    np.testing.assert_allclose(np.where(causal, scores, 0.0),
                               np.where(causal, want_scores, 0.0),
                               atol=1e-5, rtol=1e-5)
    mask, lse = dsa.select_topk(jnp.asarray(want_scores), topk, impl=impl)
    want = sorted_selection(want_scores, topk)
    np.testing.assert_array_equal(np.asarray(mask), want)
    np.testing.assert_allclose(
        lse, jax.nn.logsumexp(jnp.where(want != 0, want_scores, -jnp.inf), 1),
        atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("impl", IMPLS)
def test_tied_scores_take_the_lower_position(impl):
    # scores from a handful of values (zeros of both signs among them):
    # every row has ties at its threshold
    rng = np.random.RandomState(3)
    scores = jnp.asarray(rng.choice(
        np.array([-1.5, -0.0, 0.0, 0.25, 2.0], np.float32), (L, L)))
    for topk in (1, 7, 30):
        mask, _lse = dsa.select_topk(scores, topk, impl=impl)
        np.testing.assert_array_equal(np.asarray(mask),
                                      sorted_selection(scores, topk))


@pytest.mark.parametrize("impl", IMPLS)
def test_the_selection_goes_chunk_by_chunk_of_queries(impl):
    row = make_row(seed=5)
    whole, lse = dsa.lightning_select(row["a"], row["b"], row["c"], 9,
                                      impl=impl)
    chunked, lse_c = dsa.lightning_select(row["a"], row["b"], row["c"], 9,
                                          impl=impl, chunk=16)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(chunked))
    np.testing.assert_allclose(lse, lse_c, atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(whole),
        sorted_selection(dense_scores(row["a"], row["b"], row["c"]), 9))


@pytest.fixture
def tiles_of_16(monkeypatch):
    monkeypatch.setattr(dsa, "_tile",
                        lambda length, tile=16: min(tile, length))


def _select(row, topk, impl):
    return dsa.lightning_select(row["a"], row["b"], row["c"], topk,
                                impl=impl)


def assert_attention_and_its_three_gradients(row, mask, impl, seed):
    """``sparse_attention`` under ``mask`` against the dense softmax: the
    output, the log-sum-exp and the gradients in q, k and v."""
    w = jnp.asarray(np.random.RandomState(seed).randn(*row["q"].shape),
                    jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v)[0] * w)

    qkv = (row["q"], row["k"], row["v"])
    out, lse = dsa.sparse_attention(*qkv, mask, impl=impl)
    want_out, want_lse, _p = dense_attention(*qkv, mask)
    np.testing.assert_allclose(out, want_out, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(lse, want_lse, atol=2e-5, rtol=2e-5)
    got = jax.grad(loss(lambda q, k, v: dsa.sparse_attention(
        q, k, v, mask, impl=impl)), argnums=(0, 1, 2))(*qkv)
    want = jax.grad(loss(lambda q, k, v: dense_attention(q, k, v, mask)),
                    argnums=(0, 1, 2))(*qkv)
    for g, g_want in zip(got, want):
        assert g.shape == g_want.shape
        np.testing.assert_allclose(g, g_want, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("group", GROUPS)
def test_attention_over_the_kept_pairs_and_its_three_gradients(group, impl):
    row = make_row(seed=1, kv_heads=H // group)
    mask, _ = _select(row, 12, "xla")
    # queries with fewer causal keys than topk keep them all
    np.testing.assert_array_equal(np.asarray(mask)[:12],
                                  np.tril(np.ones((L, L), np.int8))[:12])
    assert_attention_and_its_three_gradients(row, mask, impl, seed=2)


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("group", GROUPS)
def test_rows_that_keep_nothing_inside_a_visited_tile(group, impl,
                                                      tiles_of_16):
    """Tiles of 16.  Every query keeps itself, the even ones key 0 and every
    fourth one key 20 as well: in the tiles ``(i, 0)`` and ``(i, 1)`` below
    the diagonal most rows keep nothing.  An odd row comes to its diagonal
    tile with no maximum yet (``m_prev = NEG_INF``: its visits so far must
    have added exactly nothing), a row ``2 mod 4`` meets a tile with nothing
    for it AFTER it has a maximum."""
    t = np.arange(L)
    mask = np.eye(L, dtype=np.int8)
    mask[t % 2 == 0, 0] = 1
    mask[(t % 4 == 0) & (t >= 32), 20] = 1
    live = np.asarray(dsa.live_tiles(jnp.asarray(mask), 16))
    assert live[2].tolist() == [True, True, True, False]
    assert not mask[33, :32].any() and not mask[34, 16:32].any()
    assert_attention_and_its_three_gradients(
        make_row(seed=12, kv_heads=H // group), jnp.asarray(mask), impl,
        seed=13)


@pytest.mark.parametrize("group", GROUPS)
def test_dk_and_dv_of_a_tile_one_visit_touches_and_of_one_all_visit(
        group, tiles_of_16):
    """Tiles of 16, four a side.  Every query keeps itself and key 3, so the
    first column of tiles is visited by every query block and the K/V tiles
    1, 2 and 3 by their diagonal visit alone: K/V tile 0 takes its first
    share on the walk's opening visit and more on visits of every block
    after, K/V tile 3 takes its only share on the walk's closing visit."""
    mask = np.eye(L, dtype=np.int8)
    mask[3:, 3] = 1
    live = np.asarray(dsa.live_tiles(jnp.asarray(mask), 16))
    assert live.tolist() == [[True, False, False, False],
                             [True, True, False, False],
                             [True, False, True, False],
                             [True, False, False, True]]
    _block, tile, flags, count = dsa._visit_table(jnp.asarray(live))
    assert np.asarray(tile)[:int(count)].tolist() == [0, 0, 1, 0, 2, 0, 3]
    assert np.flatnonzero(np.asarray(flags) & 4).tolist() == [0]
    assert np.flatnonzero(np.asarray(flags) & 8).tolist() == [6]
    assert_attention_and_its_three_gradients(
        make_row(seed=15, kv_heads=H // group), jnp.asarray(mask),
        "pallas_interpret", seed=16)


@pytest.mark.parametrize("tiles", [1, 2, 3])
def test_a_walk_whose_first_visit_is_a_k_v_heads_last(tiles, tiles_of_16):
    """Rows of 1, 2 and 3 tiles a side whose queries keep themselves alone:
    only diagonal tiles are live.  In the row of one tile visit 0 opens and
    closes the walk; in the longer ones K/V tile 0 is complete after visit 0
    and must still be there, with every other tile's share, when the last
    visit writes dk and dv."""
    length = 16 * tiles
    row = {name: x[:length] for name, x in make_row(seed=17).items()}
    mask = jnp.eye(length, dtype=jnp.int8)
    *_, flags, count = dsa._visit_table(dsa.live_tiles(mask, 16))
    assert int(count) == tiles
    assert (int(flags[0]) & 8 != 0) == (tiles == 1)
    assert_attention_and_its_three_gradients(row, mask, "pallas_interpret",
                                             seed=18)


@pytest.mark.parametrize("length,fits", [
    (16384, True), (32768, True), (40960, True), (41472, False),
    (65536, False)])
def test_a_row_too_long_for_resident_dk_and_dv_is_refused_at_trace_time(
        length, fits):
    """Keye's widths in bf16 (32 x 128 query heads over 4 K/V heads): the
    backward keeps dk and dv of a K/V head's whole row in VMEM.  Rows up to
    80 tiles trace; a longer one raises with the byte count (nothing is run
    or allocated: shapes only)."""
    shape = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        dims, dtype)
    q, k = shape(length, 32, 128), shape(length, 4, 128)
    grad = jax.grad(lambda q, k, v, mask: jnp.sum(dsa.sparse_attention(
        q, k, v, mask, impl="pallas_interpret")[0].astype(jnp.float32)),
        argnums=(0, 1, 2))
    trace = lambda: jax.eval_shape(  # noqa: E731
        grad, q, k, k, shape(length, length, dtype=jnp.int8))
    if fits:
        assert [g.shape for g in trace()] == [q.shape, k.shape, k.shape]
    else:
        held = dsa._bwd_bytes(8, 512, length, 128, 2)
        with pytest.raises(ValueError, match=f"{length} positions.*{held:,}"):
            trace()


@pytest.mark.parametrize("group", GROUPS)
def test_a_visit_serves_the_whole_group_of_query_heads(group):
    """The two attention kernels (the forward and the one-pass backward) have
    the grid ``(K/V heads, visits)`` and count the query heads a visit
    serves: ``dsa.visit_heads`` over the attention kernels built reads the
    group.  The two passes' counters are gone."""
    row = make_row(seed=14, kv_heads=H // group)
    mask, _ = _select(row, 12, "xla")
    before = telemetry.snapshot()["counters"]
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda q, k, v: jnp.sum(dsa.sparse_attention(
            q, k, v, mask, impl="pallas_interpret")[0]),
        argnums=(0, 1, 2)))(row["q"], row["k"], row["v"])
    after = telemetry.snapshot()["counters"]
    counted = {name.removeprefix("dsa."): after[name] - before.get(name, 0)
               for name in after if name.startswith("dsa.")}
    assert counted["kernels.attend_fwd"] == counted["kernels.attend_bwd"] == 1
    assert counted["kernels"] == 2
    assert not {"kernels.attend_dkv", "kernels.attend_dq"} & set(counted)
    assert counted["visit_heads"] == group * 2
    assert [grid[0] for grid in _pallas_grids(jaxpr.jaxpr)] == [H // group] * 2


@pytest.mark.parametrize("impl", IMPLS)
def test_topk_of_the_whole_row_is_causal_flash_attention(impl):
    row = make_row(seed=4)
    mask, _ = _select(row, L + 5, impl)
    np.testing.assert_array_equal(np.asarray(mask),
                                  np.tril(np.ones((L, L), np.int8)))
    out, _lse = dsa.sparse_attention(row["q"], row["k"], row["v"], mask,
                                     impl=impl)
    flash = att.flash_attention(row["q"][None], row["k"][None],
                                row["v"][None], causal=True, block_q=16,
                                block_k=16, impl="pallas_interpret")[0]
    np.testing.assert_allclose(out, flash, atol=2e-5, rtol=2e-5)


def test_head_mean_probs_is_the_mean_of_the_dense_softmax():
    row = make_row(seed=6)
    mask, _ = _select(row, 10, "xla")
    _out, lse, p = dense_attention(row["q"], row["k"], row["v"], mask)
    got = dsa.head_mean_probs(row["q"], row["k"], lse, mask, 1 / np.sqrt(D))
    np.testing.assert_allclose(got, jnp.mean(p, axis=0), atol=1e-6)
    np.testing.assert_allclose(jnp.sum(got, axis=1), 1.0, atol=1e-5)


@pytest.mark.parametrize("impl", IMPLS)
def test_the_indexers_loss_and_its_gradient(impl):
    """KL(p ‖ r) against the dense formula; its gradient reaches a, b and c
    and nothing else (p is a constant: q and k get none)."""
    row = make_row(seed=7)
    topk = 10
    mask, lse_i = _select(row, topk, impl)
    _out, lse, p = dense_attention(row["q"], row["k"], row["v"], mask)
    p = jnp.mean(p, axis=0)

    def want(a, b, c):
        scores = jnp.where(mask != 0, dense_scores(a, b, c), -jnp.inf)
        log_r = jax.nn.log_softmax(scores, axis=1)
        return jnp.sum(jnp.where(p > 0, p * (jnp.log(p) - log_r), 0.0))

    def got(a, b, c, q, k):
        return dsa.index_kl(a, b, c, q, k, lse, lse_i, mask, impl=impl)

    abc = (row["a"], row["b"], row["c"])
    np.testing.assert_allclose(got(*abc, row["q"], row["k"]), want(*abc),
                               rtol=1e-5)
    grads = jax.grad(got, argnums=(0, 1, 2, 3, 4))(*abc, row["q"], row["k"])
    for g, g_want in zip(grads[:3], jax.grad(want, argnums=(0, 1, 2))(*abc)):
        assert float(jnp.max(jnp.abs(g_want))) > 1e-3
        np.testing.assert_allclose(g, g_want, atol=2e-5, rtol=1e-4)
    for g in grads[3:]:
        assert not np.asarray(g).any()


@pytest.mark.parametrize("impl", IMPLS)
def test_a_selection_that_leaves_tiles_empty_walks_the_live_ones(impl,
                                                               tiles_of_16):
    """Tiles of 16: index scores that favour the first keys leave most of
    the causal tiles without a kept pair; the visit table lists the live
    ones (and each block's diagonal), and the results are the dense ones."""
    row = make_row(seed=8)
    scores = jnp.broadcast_to(-jnp.arange(L, dtype=jnp.float32), (L, L))
    mask, lse_i = dsa.select_topk(scores, 8, impl=impl)
    live = dsa.live_tiles(mask, 16)
    assert int(live.sum()) == 4         # the first column of tiles
    # no head column: a visit serves every query head of its group
    block, tile, flags, count = dsa._visit_table(live)
    assert int(count) == 4 + 3          # and the three other diagonal tiles
    visits = list(zip(*(np.asarray(x)[:int(count)]
                        for x in (block, tile, flags))))
    # first / last of a block: 1 / 2; of the whole walk: 4 / 8
    assert visits == [(0, 0, 3 + 4), (1, 0, 1), (1, 1, 2), (2, 0, 1),
                      (2, 2, 2), (3, 0, 1), (3, 3, 2 + 8)]
    qkv = (row["q"], row["k"], row["v"])
    w = jnp.asarray(np.random.RandomState(9).randn(L, H, D), jnp.float32)
    got = jax.value_and_grad(lambda q, k, v: jnp.sum(dsa.sparse_attention(
        q, k, v, mask, impl=impl)[0] * w), argnums=(0, 1, 2))(*qkv)
    want = jax.value_and_grad(lambda q, k, v: jnp.sum(dense_attention(
        q, k, v, mask)[0] * w), argnums=(0, 1, 2))(*qkv)
    for g, g_want in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, g_want, atol=1e-4, rtol=1e-4)


def test_per_row_maps_a_batch_row_by_row():
    rows = [make_row(seed=s) for s in (10, 11)]
    stack = {key: jnp.stack([r[key] for r in rows]) for key in rows[0]}

    def one(a, b, c, q, k, v):
        mask, _ = dsa.lightning_select(a, b, c, 6, impl="xla")
        return dsa.sparse_attention(q, k, v, mask, impl="xla")[0]

    names = ("a", "b", "c", "q", "k", "v")
    got = dsa.per_row(one, *(stack[n] for n in names))
    for i, r in enumerate(rows):
        np.testing.assert_allclose(got[i], one(*(r[n] for n in names)),
                                   atol=1e-6)
    single = dsa.per_row(one, *(stack[n][:1] for n in names))
    np.testing.assert_allclose(single[0], got[0], atol=1e-6)
