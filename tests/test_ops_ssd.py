"""``ops/ssd.py`` (ISSUE 41): the chunked state-space scan against the
recurrence it computes, written here position by position, values and the
gradients of every input, for one and several B/C groups and several chunks;
the depthwise causal conv against its definition; a length that is no
multiple of the chunk raises by name; the check's control (decay and state
held in bf16) is another result.  Float32 on the CPU."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.ops.ssd import causal_conv1d, ssd_scan

# the chunked form sums in another order than the recurrence: 1e-6 to 2e-5 on
# these sizes, relative to the largest entry
TOL = 2e-4


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def _inputs(seed, batch, length, heads, dim, groups, state):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    return {"x": f(batch, length, heads, dim),
            # Δ after its softplus, as a trained layer's: 0.001 .. 0.5
            "dt": jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.5),
                                                 (batch, length, heads))),
                              jnp.float32),
            "a_log": jnp.asarray(np.log(rng.uniform(1, 16, heads)),
                                 jnp.float32),
            "b": f(batch, length, groups, state),
            "c": f(batch, length, groups, state),
            "d": f(heads)}


def _recurrence(x, dt, a_log, b, c, d):
    """``S_t = exp(Δ_t a) S_{t-1} + Δ_t x_t ⊗ B_t``, ``y_t = S_t C_t + D
    x_t``, one position after the other."""
    heads, groups = x.shape[2], b.shape[2]
    a = -jnp.exp(a_log)
    b, c = (jnp.repeat(t, heads // groups, axis=2) for t in (b, c))

    def step(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    zero = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:], jnp.float32)
    _, y = jax.lax.scan(step, zero, tuple(
        t.swapaxes(0, 1) for t in (x, dt, b, c)))
    return y.swapaxes(0, 1) + d[:, None] * x


def _chunked(chunk):
    def f(x, dt, a_log, b, c, d):
        return ssd_scan(x, dt, -jnp.exp(a_log), b, c, d, chunk=chunk)
    return f


@pytest.mark.parametrize("heads,groups,length,chunk", [
    (4, 1, 32, 8),          # one group, four chunks: the cell's shape in small
    (8, 4, 48, 16),         # several groups: head j reads group j // 2
    (2, 2, 16, 16),         # one chunk: no carried state
    (4, 2, 128, 128),       # the published chunk
])
def test_chunked_scan_is_the_recurrence(heads, groups, length, chunk):
    inputs = _inputs(0, 2, length, heads, 8, groups, 16)
    names = list(inputs)
    want = _recurrence(**inputs)
    got = _chunked(chunk)(**inputs)
    assert got.shape == want.shape == inputs["x"].shape
    assert _rel(got, want) < TOL
    # gradients of x, Δ, A_log, B, C and D under a cotangent that is no
    # constant (a sum would hide a transposed axis)
    weight = jnp.asarray(np.random.default_rng(1).normal(size=want.shape),
                         jnp.float32)
    grads = [jax.grad(lambda *args, f=f: jnp.sum(f(*args) * weight),
                      argnums=tuple(range(6)))(*inputs.values())
             for f in (_chunked(chunk), _recurrence)]
    for name, got_g, want_g in zip(names, *grads):
        assert _rel(got_g, want_g) < TOL, name


def test_a_length_that_is_no_multiple_of_the_chunk_raises_by_name():
    inputs = _inputs(0, 1, 24, 2, 4, 1, 8)
    with pytest.raises(ValueError, match="no multiple of chunk_size 16"):
        _chunked(16)(**inputs)
    with pytest.raises(ValueError, match="3 heads over 2 groups"):
        bad = _inputs(0, 1, 16, 3, 4, 2, 8)
        _chunked(16)(**bad)


def test_the_backward_keeps_its_inputs_not_the_decay_matrices():
    """The scan is rematerialised: what its backward keeps are the inputs,
    so the residuals of ``L`` positions are O(L), not the ``[chunks, heads,
    chunk, chunk]`` matrices (O(L · chunk)) and never ``L`` states."""
    inputs = _inputs(0, 1, 64, 4, 8, 1, 16)
    _, vjp = jax.vjp(_chunked(16), *inputs.values())
    kept = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(vjp)
               if hasattr(leaf, "shape"))
    given = sum(int(np.prod(v.shape)) for v in inputs.values())
    assert kept <= 2 * given


def test_decay_and_state_in_bf16_is_another_result():
    """``state_dtype`` bf16 (the check's control) rounds the running sums,
    the decays and the carried state: far outside what the float32 scan
    differs from the recurrence by."""
    inputs = _inputs(3, 1, 256, 4, 8, 1, 16)
    args = (inputs["x"], inputs["dt"], -jnp.exp(inputs["a_log"]),
            inputs["b"], inputs["c"], inputs["d"])
    sound = ssd_scan(*args, chunk=128)
    rounded = ssd_scan(*args, chunk=128, state_dtype=jnp.bfloat16)
    assert _rel(sound, _recurrence(**inputs)) < TOL
    assert _rel(rounded, sound) > 50 * TOL


def test_causal_conv_is_four_shifted_adds():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 12, 6)), jnp.float32)
    kernel = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(6,)), jnp.float32)
    got = np.asarray(causal_conv1d(x, kernel, bias))
    want = np.zeros_like(got) + np.asarray(bias)
    for t in range(12):
        for k in range(4):
            if t - 3 + k >= 0:      # tap 3 meets the position itself
                want[:, t] += np.asarray(x)[:, t - 3 + k] * np.asarray(kernel)[k]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # causal: a later position moves nothing before it
    moved = causal_conv1d(x.at[:, 7].add(1.0), kernel, bias)
    np.testing.assert_array_equal(np.asarray(moved)[:, :7], got[:, :7])
    assert not np.allclose(np.asarray(moved)[:, 7], got[:, 7])
