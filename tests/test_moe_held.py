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
