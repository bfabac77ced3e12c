"""``ops/kda.py``: the chunked gated delta rule with a decay a key channel
against the recurrence itself, a ``lax.scan`` over positions, in float32:
forward and every gradient, at decays from none to ``e^-20`` a step; the XLA
form first, then the Pallas kernels in interpreter mode."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.ops import kda


def recurrence(q, k, v, g, beta):
    """``S_t = (I - β_t k_t k_tᵀ) Diag(exp g_t) S_{t-1} + β_t k_t v_tᵀ``,
    ``o_t = S_tᵀ q_t``, one position at a time."""
    b, _length, h, dk = q.shape

    def position(state, inputs):                    # state [B, H, dk, dv]
        q_t, k_t, v_t, g_t, beta_t = inputs
        state = jnp.exp(g_t)[..., None] * state
        held = jnp.einsum("bhk,bhkv->bhv", k_t, state)
        state = state + (beta_t[..., None, None] * k_t[..., None]
                         * (v_t - held)[..., None, :])
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    _, out = jax.lax.scan(
        position, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
        tuple(t.swapaxes(0, 1) for t in (q, k, v, g, beta)))
    return out.swapaxes(0, 1)


def _inputs(seed: int, length: int, decay: str, h: int = 2, dk: int = 16,
            dv: int = 8):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    def unit(t):
        return t / jnp.linalg.norm(t, axis=-1, keepdims=True)

    q = unit(jax.random.normal(keys[0], (2, length, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(keys[1], (2, length, h, dk)))
    v = jax.random.normal(keys[2], (2, length, h, dv))
    beta = jax.nn.sigmoid(jax.random.normal(keys[3], (2, length, h)))
    if decay == "none":             # the pure delta rule
        g = jnp.zeros((2, length, h, dk))
    elif decay == "strong":         # some channels lose e^-20 a step
        g = -jnp.exp(jax.random.uniform(keys[4], (2, length, h, dk),
                                        minval=np.log(1e-3),
                                        maxval=np.log(30.0)))
        g = g.at[..., ::4].set(-20.0)
    else:                           # decades, as a log-uniform dt_bias draws
        g = -jnp.exp(jax.random.uniform(keys[4], (2, length, h, dk),
                                        minval=np.log(1e-3),
                                        maxval=np.log(1.0)))
    if decay == "beta0":            # nothing is written: the state decays
        beta = jnp.zeros_like(beta)
    return q, k, v, g, beta


CASES = [(32, 16, 4, "spread"), (64, 16, 8, "spread"), (64, 16, 4, "strong"),
         (32, 16, 4, "none"), (32, 16, 4, "beta0"), (32, 8, 8, "spread"),
         (128, 64, 16, "strong")]


@pytest.mark.parametrize("length,chunk,sub,decay", CASES)
def test_forward_matches_the_recurrence(length, chunk, sub, decay):
    args = _inputs(0, length, decay)
    got = kda.kda_scan(*args, chunk=chunk, sub=sub)
    want = recurrence(*args)
    assert float(jnp.abs(want).max()) > 0 or decay == "beta0"
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("length,chunk,sub,decay", CASES)
def test_every_gradient_matches_the_recurrence(length, chunk, sub, decay):
    args = _inputs(1, length, decay)
    weight = jax.random.normal(jax.random.PRNGKey(7),
                               args[2].shape)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * weight)

    got = jax.grad(loss(lambda *a: kda.kda_scan(*a, chunk=chunk, sub=sub)),
                   argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(loss(recurrence), argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        scale = float(jnp.abs(b).max()) + 1e-30
        np.testing.assert_allclose(a / scale, b / scale, atol=5e-5,
                                   err_msg=name)


def test_a_state_held_in_bf16_reads_differently():
    """The control ``state_dtype`` exists for: running sums, decays and the
    carried state rounded as bf16 variables would hold them move the output
    by far more than float32's rounding."""
    args = _inputs(2, 128, "spread")
    want = recurrence(*args)
    exact = kda.kda_scan(*args, chunk=32, sub=8)
    rounded = kda.kda_scan(*args, chunk=32, sub=8, state_dtype=jnp.bfloat16)
    err = lambda t: float(jnp.linalg.norm(t - want)     # noqa: E731
                          / jnp.linalg.norm(want))
    assert err(exact) < 1e-5
    assert err(rounded) > 1e-3


def test_bf16_operands_stay_near_the_float32_recurrence():
    args = _inputs(3, 128, "spread")
    want = recurrence(*args)
    q, k, v, g, beta = args
    got = kda.kda_scan(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                       v.astype(jnp.bfloat16), g, beta, chunk=64, sub=16)
    assert got.dtype == jnp.bfloat16
    err = float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                / jnp.linalg.norm(want))
    assert err < 2e-2, err


def test_the_output_is_named_for_a_remat_policy():
    args = _inputs(4, 32, "spread")
    jaxpr = str(jax.make_jaxpr(
        lambda *a: kda.kda_scan(*a, chunk=16, sub=4))(*args))
    assert "name=kda_out" in jaxpr


@pytest.mark.parametrize("length,chunk,sub,named", [
    (40, 16, 4, "no multiple of chunk 16"),
    (32, 16, 3, "powers of two"),
    (48, 24, 8, "powers of two"),
])
def test_what_the_chunked_form_cannot_take_is_refused_by_name(length, chunk,
                                                              sub, named):
    args = _inputs(5, length, "spread")
    with pytest.raises(ValueError, match="kda_scan") as e:
        kda.kda_scan(*args, chunk=chunk, sub=sub)
    assert named in str(e.value)


def test_shapes_that_disagree_are_refused():
    q, k, v, g, beta = _inputs(6, 32, "spread")
    with pytest.raises(ValueError, match="kda_scan"):
        kda.kda_scan(q, k, v, g[..., :4], beta, chunk=16, sub=4)


# ---------------------------------------------------------------------------
# The kernels (``impl="pallas_interpret"``): the same recurrence, the same
# regimes.  Small ``_BLOCK``s make a row several grid steps, so the state and
# its cotangent cross from one step to the next, forwards and backwards.
# ---------------------------------------------------------------------------

KERNEL_CASES = [
    # length, chunk, positions a grid step, decay
    (32, 16, 256, "spread"), (128, 64, 256, "spread"),
    (64, 16, 32, "strong"), (128, 64, 64, "strong"),
    (32, 16, 16, "none"), (128, 32, 64, "none"),
    (64, 16, 32, "beta0"), (128, 64, 256, "beta0")]


def _kernels(chunk, **kwargs):
    return lambda *a: kda.kda_scan(*a, chunk=chunk, impl="pallas_interpret",
                                   **kwargs)


@pytest.mark.parametrize("length,chunk,block,decay", KERNEL_CASES)
def test_the_kernels_forward_matches_the_recurrence(length, chunk, block,
                                                    decay, monkeypatch):
    monkeypatch.setattr(kda, "_BLOCK", block)
    args = _inputs(0, length, decay)
    got = _kernels(chunk)(*args)
    want = recurrence(*args)
    assert float(jnp.abs(want).max()) > 0 or decay == "beta0"
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("length,chunk,block,decay", KERNEL_CASES)
def test_the_kernels_every_gradient_matches_the_recurrence(
        length, chunk, block, decay, monkeypatch):
    """The backward written by hand: dq, dk, dv, dg (the reverse running sum
    of the decays' cotangents with the chunk-end term) and dβ."""
    monkeypatch.setattr(kda, "_BLOCK", block)
    args = _inputs(1, length, decay)
    weight = jax.random.normal(jax.random.PRNGKey(7), args[2].shape)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * weight)

    got = jax.grad(loss(_kernels(chunk)), argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(loss(recurrence), argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        scale = float(jnp.abs(b).max()) + 1e-30
        np.testing.assert_allclose(a / scale, b / scale, atol=5e-5,
                                   err_msg=name)


def test_the_kernels_and_the_xla_form_agree_at_bf16_operands():
    """Both take bf16 operands into float32 accumulators: each stays near
    the float32 recurrence, and they are nearer each other than either is
    to it, forward and in every gradient."""
    q, k, v, g, beta = _inputs(3, 128, "spread", dk=32, dv=32)
    low = (q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
           v.astype(jnp.bfloat16), g, beta)
    weight = jax.random.normal(jax.random.PRNGKey(8), v.shape)

    def both(fn, args):
        return jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * weight),
            argnums=(0, 1, 2, 3, 4))(*args)[1], fn(*args)

    def err(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    want, out = both(recurrence, (q, k, v, g, beta))
    kernels, out_k = both(_kernels(64), low)
    xla, out_x = both(lambda *a: kda.kda_scan(*a, chunk=64, sub=16,
                                              impl="xla"), low)
    assert out_k.dtype == jnp.bfloat16
    assert err(out_k, out) < 2e-2 and err(out_x, out) < 2e-2
    assert err(out_k, out_x) < 1e-2
    for name, a, b, c in zip("q k v g beta".split(), kernels, xla, want):
        assert err(a, c) < 3e-2 and err(b, c) < 3e-2, name
        assert err(a, b) < 2e-2, name


def test_a_state_held_in_bf16_reads_differently_in_the_kernels_too():
    args = _inputs(2, 128, "spread")
    want = recurrence(*args)
    exact = _kernels(32)(*args)
    rounded = _kernels(32, state_dtype=jnp.bfloat16)(*args)
    err = lambda t: float(jnp.linalg.norm(t - want)     # noqa: E731
                          / jnp.linalg.norm(want))
    assert err(exact) < 1e-5
    assert err(rounded) > 1e-3
    # and it still has every gradient, finite
    grads = jax.grad(lambda *a: jnp.sum(_kernels(
        32, state_dtype=jnp.bfloat16)(*a)), argnums=(0, 1, 2, 3, 4))(*args)
    assert all(bool(jnp.isfinite(t).all()) for t in grads)


def test_the_kernels_name_the_output_and_the_chunk_states():
    """What ``Transformer._remat_policy`` keeps of the kernel path: the
    output and the state every chunk starts from, both by name."""
    args = _inputs(4, 32, "spread")
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(_kernels(16)(*a))))(*args))
    for name in kda.SAVED_NAMES:
        assert f"name={name}" in jaxpr, name
    assert "kda_fwd" in jaxpr and "kda_bwd" in jaxpr


@pytest.mark.parametrize("length,chunk,sub,named", [
    (40, 16, 4, "no multiple of chunk 16"),
    (32, 16, 3, "powers of two"),
    (48, 24, 8, "powers of two"),
])
def test_what_is_refused_by_name_stays_refused_on_the_kernel_path(
        length, chunk, sub, named):
    args = _inputs(5, length, "spread")
    with pytest.raises(ValueError, match="kda_scan") as e:
        kda.kda_scan(*args, chunk=chunk, sub=sub, impl="pallas_interpret")
    assert named in str(e.value)


def test_shapes_that_disagree_and_an_unknown_impl_are_refused():
    q, k, v, g, beta = _inputs(6, 32, "spread")
    with pytest.raises(ValueError, match="kda_scan"):
        kda.kda_scan(q, k, v, g[..., :4], beta, chunk=16,
                     impl="pallas_interpret")
    with pytest.raises(ValueError, match="unknown impl 'mosaic'"):
        kda.kda_scan(q, k, v, g, beta, chunk=16, impl="mosaic")


@pytest.mark.parametrize("chunk,impl,kernels", [
    (16, "pallas_interpret", 1), (8, "pallas_interpret", 0), (16, "xla", 0),
    (16, None, 0), (16, "auto", 0)])
def test_which_path_ran_is_counted_and_a_chunk_below_a_tile_takes_xla(
        chunk, impl, kernels):
    """``kda.kernel_layers``: calls traced that took the kernels.  A chunk
    of 8 is half a bf16 tile: the XLA form takes it by its shape; off the
    chip ``auto`` is the XLA form."""
    from tensorflowonspark_tpu import telemetry

    args = _inputs(7, 32, "spread")
    counter = telemetry.counter("kda.kernel_layers")
    before = counter.value()
    got = kda.kda_scan(*args, chunk=chunk, impl=impl)
    assert counter.value() - before == kernels
    np.testing.assert_allclose(got, recurrence(*args), atol=2e-5, rtol=2e-5)
