"""A chip's share of a layer's experts (``MoEMLP.held``, ISSUE 31): the
shares add up to the uncut layer, nothing is dropped among the held pairs
whatever the routing, and no shape depends on it.  Small, float32, CPU."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.parallel import ep as eplib

E, K, D, F = 32, 4, 16, 8          # experts, choices a token, widths


def _layer(held=None, e=E, k=K):
    return eplib.MoEMLP(D, F, e, k, None, norm_topk_prob=True, held=held)


def _whole(seed=0, n=48, e=E, k=K):
    """The uncut layer's parameters and an input ``[1, n, D]``."""
    x = jnp.asarray(np.random.default_rng(seed).standard_normal((1, n, D)),
                    jnp.float32)
    params = _layer(e=e, k=k).init(jax.random.PRNGKey(seed), x)["params"]
    return params, x


def _share(params, first, end):
    """What a chip that holds experts ``first .. end-1`` keeps: the whole
    router, its slice of every expert-stacked weight."""
    return {"router": params["router"],
            **{name: params[name][first:end] for name in
               ("experts_gate", "experts_up", "experts_down")}}


def _dense_reference(params, x, first=0, end=E, k=K):
    """Every expert in ``first .. end-1`` on every token, weighted by the
    token's renormalised routing weight for it (0 where not chosen)."""
    xf = x.reshape(-1, D)
    probs = jax.nn.softmax(xf @ params["router"]["kernel"], axis=-1)
    top_p, top_idx = jax.lax.top_k(probs, k)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    weight = jnp.einsum("nke,nk->ne", jax.nn.one_hot(top_idx, probs.shape[1]),
                        top_p)
    out = jnp.zeros_like(xf)
    for i in range(first, end):
        h = (jax.nn.silu(xf @ params["experts_gate"][i])
             * (xf @ params["experts_up"][i]))
        out = out + weight[:, i:i + 1] * (h @ params["experts_down"][i])
    return out.reshape(x.shape)


def test_the_shares_add_up_to_the_uncut_layer_and_to_the_reference():
    """Eight layers holding experts 0-3 ... 28-31 of one seeded layer: their
    outputs, and their gradients to the input, sum to the uncut layer's and
    to the dense reference's for all 32; each share alone is the reference
    restricted to its experts."""
    params, x = _whole()
    w = jnp.asarray(np.random.default_rng(1).standard_normal(x.shape),
                    jnp.float32)

    def run(layer, p):
        f = lambda x: layer.apply({"params": p}, x)  # noqa: E731
        y, vjp = jax.vjp(f, x)
        return y, vjp(w)[0]

    whole_y, whole_dx = run(_layer(), params)
    ref_y, ref_vjp = jax.vjp(lambda x: _dense_reference(params, x), x)
    np.testing.assert_allclose(whole_y, ref_y, atol=2e-6)
    np.testing.assert_allclose(whole_dx, ref_vjp(w)[0], atol=2e-6)
    sum_y, sum_dx = jnp.zeros_like(x), jnp.zeros_like(x)
    for first in range(0, E, 4):
        y, dx = run(_layer((first, first + 4)),
                    _share(params, first, first + 4))
        np.testing.assert_allclose(
            y, _dense_reference(params, x, first, first + 4), atol=2e-6)
        sum_y, sum_dx = sum_y + y, sum_dx + dx
    np.testing.assert_allclose(sum_y, whole_y, atol=5e-6)
    np.testing.assert_allclose(sum_dx, whole_dx, atol=5e-6)


def test_every_gradient_of_a_share_is_the_reference_s():
    params, x = _whole(seed=2)
    first, end = 8, 12
    share = _share(params, first, end)
    w = jnp.asarray(np.random.default_rng(3).standard_normal(x.shape),
                    jnp.float32)

    def system(p, x):
        return jnp.sum(_layer((first, end)).apply({"params": p}, x) * w)

    def reference(p, x):
        full = {**params, **{k: params[k].at[first:end].set(p[k])
                             for k in p if k != "router"},
                "router": p["router"]}
        return jnp.sum(_dense_reference(full, x, first, end) * w)

    got = jax.grad(system, argnums=(0, 1))(share, x)
    want = jax.grad(reference, argnums=(0, 1))(share, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=5e-6)


def _forced(params, favoured, strength=50.0):
    """The router pushed so that every token's first choices are
    ``favoured`` (in that order of preference)."""
    kernel = jnp.zeros_like(params["router"]["kernel"])
    for rank, e in enumerate(favoured):
        kernel = kernel.at[:, e].set(strength - rank)
    return {**params, "router": {"kernel": kernel}}


@pytest.mark.parametrize("routing", ["all_on_one_held", "every_pair_held",
                                     "none_held"])
def test_nothing_is_dropped_among_held_pairs_whatever_the_routing(
        routing, monkeypatch):
    """512 tokens, a piece of 256 rows.  With one choice a token and every
    token on ONE held expert all 512 pairs land here (two pieces); with four
    choices all on the four held experts 2048 pairs do (eight pieces); with
    every choice on absent experts none does (no piece): the output is the
    reference's every time, and so is the input's gradient."""
    n = 512
    first, end = 4, 8
    k = 1 if routing == "all_on_one_held" else K
    params, x = _whole(seed=4, n=n, k=k)
    x = jnp.abs(x)      # one sign: the forced router's order holds for all
    favoured = {"all_on_one_held": [5], "every_pair_held": [4, 5, 6, 7],
                "none_held": [0, 1, 2, 3]}[routing]
    params = _forced(params, favoured)
    monkeypatch.setattr(eplib, "_piece_rows", lambda pairs, share: 256)
    layer = _layer((first, end), k=k)
    share = _share(params, first, end)
    w = jnp.asarray(np.random.default_rng(5).standard_normal(x.shape),
                    jnp.float32)
    (y, sown), vjp = jax.vjp(
        lambda x: layer.apply({"params": share}, x, mutable=["moe_stats"]),
        x, has_aux=False)
    dx = vjp((w, jax.tree.map(jnp.zeros_like, sown)))[0]
    held_share = float(sown["moe_stats"]["held_pairs"][0])
    assert held_share == {"none_held": 0.0}.get(routing, 1.0)
    ref_y, ref_vjp = jax.vjp(
        lambda x: _dense_reference(params, x, first, end, k), x)
    np.testing.assert_allclose(y, ref_y, atol=5e-6)
    np.testing.assert_allclose(dx, ref_vjp(w)[0], atol=5e-5)
    if routing == "none_held":
        assert not np.asarray(y).any()


def test_routing_changes_neither_shapes_nor_the_program():
    """Two routings as far apart as they get (every pair held, none held)
    trace to one jaxpr: no shape, and no operation, depends on it.  1024
    pairs in pieces of 256: the pieces after the first are a loop."""
    params, x = _whole(seed=6, n=256)
    layer = _layer((4, 8))

    def program(p):
        return str(jax.make_jaxpr(jax.value_and_grad(
            lambda x: jnp.sum(layer.apply({"params": p}, x))))(x))

    a = program(_share(_forced(params, [4, 5, 6, 7]), 4, 8))
    b = program(_share(_forced(params, [0, 1, 2, 3]), 4, 8))
    assert a == b and "while" in a


def test_a_share_needs_dropless_routing_and_a_range_of_the_layer():
    _, x = _whole()
    for kwargs in (dict(capacity_factor=1.25, held=(0, 4)),
                   dict(capacity_factor=None, held=(4, 4)),
                   dict(capacity_factor=None, held=(30, 34))):
        layer = eplib.MoEMLP(D, F, E, K, **kwargs)
        with pytest.raises(ValueError, match="held="):
            layer.init(jax.random.PRNGKey(0), x)


def test_the_kernels_serve_a_piece_as_they_serve_all_the_rows(monkeypatch):
    """The held path through the Pallas grouped matmul (interpret mode): rows
    past the piece's held pairs belong to no group and are never written;
    what they hold must not reach the output."""
    from tensorflowonspark_tpu.ops import grouped_matmul as gm

    params, x = _whole(seed=7, n=96)
    monkeypatch.setattr(eplib, "grouped_matmul", functools.partial(
        gm.grouped_matmul, impl="pallas_interpret"))
    y = _layer((0, 4)).apply({"params": _share(params, 0, 4)}, x)
    np.testing.assert_allclose(y, _dense_reference(params, x, 0, 4),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# ISSUE 43: one index plan a piece; a token's sum by the ``sum_tokens`` kernel.
# ---------------------------------------------------------------------------

def _kernel_form(monkeypatch, impl="pallas_interpret"):
    """``sum_tokens`` as the chip runs it (here in interpreter mode)."""
    from tensorflowonspark_tpu.ops import sum_tokens as st

    monkeypatch.setattr(eplib, "sum_tokens",
                        functools.partial(st.sum_tokens, impl=impl))
    monkeypatch.setattr(eplib, "moved_rows",
                        functools.partial(st.moved_rows, impl=impl))


# routing, experts held, choices a token, tokens, rows a piece, width, latent
_SUM_CASES = {
    # one choice a token, all on ONE held expert: two pieces, runs of 1 (= k)
    "all_on_one_held": ([5], (4, 8), 1, 512, 256, D, 0),
    # four choices, all held: eight pieces, runs of 4 (= k = held experts)
    "every_pair_held": ([4, 5, 6, 7], (4, 8), K, 512, 256, D, 0),
    # no pair held: no piece holds a row, every block of tokens reads zeros
    "none_held": ([0, 1, 2, 3], (4, 8), K, 512, 256, D, 0),
    # a piece of 250 rows cuts a token's run of 4 in two (and is no whole
    # tile: the kernel's last tile is padded)
    "run_at_a_piece_s_edge": ([4, 5, 6, 7], (4, 8), K, 128, 250, D, 0),
    # seeded routing over 1/8 of the experts in pieces of half the even
    # share: a second and a third piece, partly filled
    "a_second_piece": (None, (8, 12), K, 512, 128, D, 0),
    # rows as wide as lanes come (2,048 = 16 x 128; here 2 x 128)
    "lane_wide_rows": (None, (8, 12), K, 256, 256, 256, 0),
    # six choices over four held experts: runs of 4 (= held experts < k),
    # relu2 experts in a latent of 128
    "latent_run_of_the_held": (None, (0, 4), 6, 256, 256, 32, 128),
}


@pytest.mark.parametrize("case", list(_SUM_CASES))
def test_the_sum_tokens_kernel_is_the_run_sum_whatever_the_routing(
        case, monkeypatch):
    """ISSUE 43.  The held layer with ``sum_tokens`` as the Pallas kernel
    (interpreter mode) against the same layer with the specification
    (``sum_runs``: shifted adds over the piece, a gather of each token's last
    row): the output, and the gradient to the input, to the router (which is
    the routing weights': the choice is no function of it) and to every
    expert matrix, within this file's limits."""
    favoured, held, k, n, piece, d, latent = _SUM_CASES[case]
    kwargs = dict(norm_topk_prob=True, held=held)
    if latent:
        kwargs.update(expert_act="relu2", latent=latent)
    layer = eplib.MoEMLP(d, F, E, k, None, **kwargs)
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((1, n, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal(x.shape), jnp.float32)
    params = layer.init(jax.random.PRNGKey(11), x)["params"]
    if favoured:
        x, params = jnp.abs(x), _forced(params, favoured)
    monkeypatch.setattr(eplib, "_piece_rows", lambda pairs, share: piece)

    def run():
        def loss(p, x):
            y, sown = layer.apply({"params": p}, x, mutable=["moe_stats"])
            return jnp.sum(y * w), (y, sown["moe_stats"])

        (_, (y, stats)), grads = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(params, x)
        return y, grads, stats

    want_y, want_grads, want_stats = run()
    _kernel_form(monkeypatch)
    from tensorflowonspark_tpu import telemetry

    built = telemetry.counter("moe.kernels.sum_tokens").value()
    got_y, got_grads, got_stats = run()
    # the first piece's combine forward and dispatch backward; the loop of
    # the further pieces: its forward's combine, and both of its backward,
    # which runs the piece again
    assert telemetry.counter(
        "moe.kernels.sum_tokens").value() - built == 2 + 3 * (piece < n * k)
    np.testing.assert_allclose(got_y, want_y, atol=5e-6)
    flat = lambda t: jax.tree_util.tree_leaves_with_path(t)  # noqa: E731
    for (path, a), (_, b) in zip(flat(got_grads), flat(want_grads)):
        np.testing.assert_allclose(a, b, atol=5e-5, err_msg=str(path))
    assert float(want_stats["moved_rows"][0]) == 1.0    # the whole piece
    held_pairs = float(got_stats["held_pairs"][0]) * n * k
    tile = min(256, -(-piece // 128) * 128)
    assert float(got_stats["moved_rows"][0]) == pytest.approx(
        min(piece, -(-min(held_pairs, piece) // tile) * tile) / piece)
    if case == "none_held":
        assert not np.asarray(got_y).any()


def test_the_kernel_reads_the_tiles_that_hold_a_pair_and_no_other(
        monkeypatch):
    """On seeded routing a piece (twice the even share) is about half full:
    ``moe_stats/moved_rows`` reads the share of its rows that the sum reads,
    whole tiles of 256, well under 1; what lies past the held pairs in the
    experts' output (never written on the chip: here NaN) reaches nothing."""
    from tensorflowonspark_tpu.ops import grouped_matmul as gm

    n = 2048
    params, x = _whole(seed=9, n=n)
    _kernel_form(monkeypatch)

    def unwritten(rows, w, sizes):
        out = gm.grouped_matmul(rows, w, sizes, impl="xla")
        past = jnp.arange(rows.shape[0]) >= jnp.sum(sizes)
        return jnp.where(past[:, None], jnp.nan, out)

    monkeypatch.setattr(eplib, "grouped_matmul", unwritten)
    layer = _layer((8, 12))
    share = _share(params, 8, 12)
    (y, sown), vjp = jax.vjp(
        lambda x: layer.apply({"params": share}, x, mutable=["moe_stats"]),
        x)
    dx = vjp((jnp.ones_like(y), jax.tree.map(jnp.zeros_like, sown)))[0]
    assert np.isfinite(np.asarray(y)).all()
    assert np.isfinite(np.asarray(dx)).all()
    np.testing.assert_allclose(y, _dense_reference(params, x, 8, 12),
                               atol=5e-6)
    piece = eplib._piece_rows(n * K, 4 / E)
    held = float(sown["moe_stats"]["held_pairs"][0]) * n * K
    moved = float(sown["moe_stats"]["moved_rows"][0])
    assert piece == 2048 and moved == -(-held // 256) * 256 / piece
    assert 0.25 < moved <= 0.75
