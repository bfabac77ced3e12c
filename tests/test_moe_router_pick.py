"""The router's pick of its routing weights (ISSUE 47): ``ep._pick`` takes the
chosen scores by one one-hot of the choice, a compare, a select and a sum,
where the router gathered them (``take_along_axis`` under a selection bias,
``lax.top_k``'s own values without one).  ONE parametrised test over the
cells' (k, e, scoring): the pick and its cotangent are the gather's and the
scatter-add's to the bit, ``MoEMLP`` with the old lines put back gives the
same bits everywhere, and the differentiated router holds no gather and no
scatter-add over ``[n, e]``.  Small, float32, CPU."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu import telemetry
from tensorflowonspark_tpu.parallel import ep as eplib

N, D, F = 64, 16, 8          # tokens, widths
# (k, e, scoring, capacity_factor): Nemotron-3, Kanana-2, Xing4.0 (sigmoid and
# a seeded bias), SDAR and Keye, OLMoE (softmax), and the capacity path
CELLS = [(22, 512, "sigmoid", None), (6, 128, "sigmoid", None),
         (4, 64, "sigmoid", None), (8, 128, "softmax", None),
         (8, 64, "softmax", None), (2, 8, "softmax", 1.25)]


def _old_pick(biased, k):
    """The router's two lines as they were: a gather of the unbiased scores
    under a bias, ``lax.top_k``'s values (and their JVP) without one; the
    one-hot of the indices as ``pairs`` made it."""
    def pick(probs, top_idx):
        top_p = (jnp.take_along_axis(probs, top_idx, axis=-1) if biased
                 else jax.lax.top_k(probs, k)[0])
        return top_p, jax.nn.one_hot(top_idx, probs.shape[-1],
                                     dtype=jnp.int32)
    return pick


def _layer(k, e, scoring, capacity):
    sigmoid = scoring == "sigmoid"
    return eplib.MoEMLP(D, F, e, k, capacity, norm_topk_prob=True,
                        scoring=scoring, selection_bias=sigmoid,
                        routed_scale=2.5 if sigmoid else 1.0)


def _inputs(k, e, scoring, capacity, seed=47):
    x = jnp.asarray(np.random.default_rng(seed).standard_normal((1, N, D)),
                    jnp.float32)
    variables = _layer(k, e, scoring, capacity).init(
        jax.random.PRNGKey(seed), x)
    if scoring == "sigmoid":
        variables = {**variables, "buffers": {
            "e_score_correction_bias": 0.05 * jax.random.normal(
                jax.random.PRNGKey(seed + 1), (e,))}}
    return variables, x


def _value_and_grad(k, e, scoring, capacity):
    """``(variables, x) -> ((loss, everything sown), gradients)``: the
    loss holds the output and the auxiliary terms."""
    layer = _layer(k, e, scoring, capacity)

    def loss(params, x, rest):
        y, sown = layer.apply(
            {"params": params, **rest}, x,
            mutable=["aux_loss", "moe_stats", "intermediates"])
        aux = sum(jnp.sum(v) for v in jax.tree.leaves(sown.get("aux_loss")))
        return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape))) \
            + 0.1 * aux, (y, sown)

    def run(variables, x):
        rest = {c: v for c, v in variables.items() if c != "params"}
        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
            variables["params"], x, rest)
    return run


def _router_eqns(jaxpr, outer=""):
    """``(primitive, shapes of its operands and results)`` of every equation
    under the scope ``moe/router``, the jaxprs that equations hold included
    (an inner jaxpr's name stacks start at its equation's)."""
    for eqn in jaxpr.eqns:
        stack = f"{outer}/{eqn.source_info.name_stack}"
        if "moe/router" in stack:
            yield eqn.primitive.name, [
                getattr(v.aval, "shape", None)
                for v in list(eqn.invars) + list(eqn.outvars)]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _router_eqns(sub, stack)


def _anew(run):
    """``run`` as a function that no trace cache has seen (none is keyed on
    ``eplib._pick``, which the test swaps)."""
    return lambda *args: run(*args)


def _element_moves(run, variables, x, e):
    """The router's primitives, and those of them that move single elements
    of an ``[n, e]`` array."""
    eqns = list(_router_eqns(
        jax.make_jaxpr(_anew(run))(variables, x).jaxpr))
    return {name for name, _ in eqns}, sorted(
        name for name, shapes in eqns if (N, e) in shapes
        and name in ("gather", "scatter", "scatter-add"))


def _same(a, b):
    jax.tree.map(np.testing.assert_array_equal, a, b)


@pytest.mark.parametrize("part", ["pick", "layer", "jaxpr"])
@pytest.mark.parametrize("k,e,scoring,capacity", CELLS, ids=[
    f"{k}_of_{e}_{s}{'' if c is None else '_capacity'}"
    for k, e, s, c in CELLS])
def test_the_pick_is_the_gather_to_the_bit(monkeypatch, k, e, scoring,
                                           capacity, part):
    biased = scoring == "sigmoid"
    if part == "pick":
        # (a) the values and the cotangent, alone: eager and jitted
        logits = jax.random.normal(jax.random.PRNGKey(k * e), (N, e))
        probs = (jax.nn.sigmoid(logits) if biased
                 else jax.nn.softmax(logits, -1))
        bias = 0.05 * jax.random.normal(jax.random.PRNGKey(e), (e,))
        _, top_idx = jax.lax.top_k(probs + bias if biased else probs, k)
        g = jax.random.normal(jax.random.PRNGKey(1), (N, k))
        for wrap in (lambda f: f, jax.jit):
            want, want_vjp = jax.vjp(wrap(
                lambda p: jnp.take_along_axis(p, top_idx, axis=-1)), probs)
            got, got_vjp = jax.vjp(wrap(
                lambda p: eplib._pick(p, top_idx)[0]), probs)
            _same(got, want)
            _same(got_vjp(g), want_vjp(g))     # the scatter-add into [n, e]
        if not biased:
            _same(got, jax.lax.top_k(probs, k)[0])
        hit = eplib._pick(probs, top_idx)[1]
        assert hit.shape == (N, k, e)
        _same(hit, jax.nn.one_hot(top_idx, e, dtype=bool))
        return
    variables, x = _inputs(k, e, scoring, capacity)
    run = _value_and_grad(k, e, scoring, capacity)
    if part == "layer":
        # (b) the layer beside itself with the old two lines put back
        picks = telemetry.counter("moe.router.picks").value()
        new = [run(variables, x), jax.jit(_anew(run))(variables, x)]
        assert telemetry.counter(      # a router built a trace, n·k each
            "moe.router.picks").value() - picks == 2 * N * k
        (_, (_, sown)), grads = new[0]
        assert set(sown["moe_stats"]) >= {"max_load", "min_load"}
        assert ("bias_moved" in sown["moe_stats"]) == biased
        assert ("aux_loss" in sown) == (not biased)
        assert float(jnp.abs(grads[0]["router"]["kernel"]).max()) > 0
        monkeypatch.setattr(eplib, "_pick", _old_pick(biased, k))
        old = [run(variables, x), jax.jit(_anew(run))(variables, x)]
        # eager and compiled: the loss, the output, the auxiliary terms,
        # moe_stats and the routing, every parameter's gradient, the input's
        _same(new, old)
        return
    # (c) the differentiated router: nothing moves an element at a time
    names, moves = _element_moves(run, variables, x, e)
    assert {"top_k", "dot_general", "select_n", "reduce_sum"} <= names
    assert not moves, moves
    # ... and with the old lines the same search finds what they held
    monkeypatch.setattr(eplib, "_pick", _old_pick(biased, k))
    _, old_moves = _element_moves(run, variables, x, e)
    assert "scatter-add" in old_moves and ("gather" in old_moves) == biased
