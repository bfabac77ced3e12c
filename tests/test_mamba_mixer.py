"""The Mamba-2 mixer and attention without rotation (ISSUE 41): the program's
modules against the plain reference kept with the benchmark
(``benchmark/configs/nemotron3_super_d11_tp8_ep64.py``), and the SHARES
tests that tie a chip's cut to the model: the outputs of all the
tensor-parallel ranks' head shares add up to the uncut layer's (``W_out`` and
``W_o`` have no bias, and a Mamba-2 group's gated norm never crosses ranks).
Float32 on the CPU."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from tensorflowonspark_tpu.models import transformer as tfm

NEMOTRON = common.load_module("configs", "nemotron3_super_d11_tp8_ep64")

# the chunked scan against a scan over positions, both float32: 1e-6 to 1e-5
TOL = 1e-4

D_MODEL, HEADS, DIM, GROUPS, STATE, CHUNK, LENGTH = 32, 8, 4, 4, 8, 8, 32
CFG = {"mamba_num_heads": HEADS, "mamba_head_dim": DIM, "n_groups": GROUPS,
       "ssm_state_size": STATE, "conv_kernel": 4, "layer_norm_epsilon": 1e-5,
       "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 4}


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def _mixer(heads=HEADS, groups=GROUPS):
    return tfm.Mamba2(heads, DIM, groups, STATE, 4, CHUNK,
                      compute_dtype=jnp.float32)


def _u(seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=(2, LENGTH, D_MODEL)), jnp.float32)


def _params(seed=1):
    """The mixer's parameters with every leaf drawn (the initialisers leave
    the conv's bias 0 and ``D`` and the norm's weights 1: a swapped channel
    would not show)."""
    params = _mixer().init(jax.random.PRNGKey(seed), _u())["params"]
    rng = np.random.default_rng(seed)
    for name in ("conv_bias", "D", "norm_scale"):
        params[name] = jnp.asarray(
            rng.uniform(0.5, 1.5, params[name].shape), jnp.float32)
    return params


def test_the_mixer_is_the_reference_mixer():
    params, u = _params(), _u()
    got = _mixer().apply({"params": params}, u)
    want = NEMOTRON._reference_mamba(CFG, params, u)
    assert got.shape == u.shape
    assert _rel(got, want) < TOL
    grads = [jax.grad(lambda p, f=f: jnp.sum(jnp.sin(f(p))))(params)
             for f in (lambda p: _mixer().apply({"params": p}, u),
                       lambda p: NEMOTRON._reference_mamba(CFG, p, u))]
    for (path, got_g), want_g in zip(
            jax.tree_util.tree_flatten_with_path(grads[0])[0],
            jax.tree.leaves(grads[1])):
        assert _rel(got_g, want_g) < 10 * TOL, jax.tree_util.keystr(path)


def test_the_seeded_mixer_is_mamba2_s():
    """``A`` in [1, 16], ``D`` = 1, ``Δ``'s bias the inverse softplus of a
    draw from [time_step_min, time_step_max]."""
    params = _mixer().init(jax.random.PRNGKey(5), _u())["params"]
    a = np.exp(np.asarray(params["A_log"]))
    assert a.min() >= 1.0 and a.max() <= 16.0 and a.std() > 0
    np.testing.assert_array_equal(params["D"], 1.0)
    dt = np.log1p(np.exp(np.asarray(params["dt_bias"])))    # softplus
    assert dt.min() >= 0.001 * 0.999 and dt.max() <= 0.1 * 1.001
    assert params["in_proj"]["kernel"].shape == (
        D_MODEL, 2 * HEADS * DIM + 2 * GROUPS * STATE + HEADS)


def _mamba_share(params, rank: int):
    """Rank ``rank`` of ``GROUPS`` tensor-parallel ranks: its B/C group, the
    group's heads, their channels of every parameter."""
    per, inner, gn = HEADS // GROUPS, HEADS * DIM, GROUPS * STATE
    chan = np.arange(rank * per * DIM, (rank + 1) * per * DIM)
    state = np.arange(rank * STATE, (rank + 1) * STATE)
    head = np.arange(rank * per, (rank + 1) * per)
    xbc = np.concatenate([chan, inner + state, inner + gn + state])
    cols = np.concatenate([chan, inner + xbc, 2 * inner + 2 * gn + head])
    return {"in_proj": {"kernel": params["in_proj"]["kernel"][:, cols]},
            "conv_kernel": params["conv_kernel"][:, xbc],
            "conv_bias": params["conv_bias"][xbc],
            "A_log": params["A_log"][head], "D": params["D"][head],
            "dt_bias": params["dt_bias"][head],
            "norm_scale": params["norm_scale"][chan],
            "out_proj": {"kernel": params["out_proj"]["kernel"][chan]}}


def test_the_head_shares_of_a_mamba_layer_add_up_to_the_layer():
    """8 heads in 4 groups over 4 ranks: a rank holds one B/C group and its
    two heads; the four partial outputs add up to the uncut mixer's, and to
    the uncut reference's."""
    params, u = _params(), _u()
    whole = _mixer().apply({"params": params}, u)
    shares = [_mixer(HEADS // GROUPS, 1).apply(
        {"params": _mamba_share(params, rank)}, u) for rank in range(GROUPS)]
    assert _rel(sum(shares), whole) < TOL
    assert _rel(sum(shares), NEMOTRON._reference_mamba(CFG, params, u)) < TOL
    # no share is the layer, and a share is its own reference
    assert _rel(shares[0], whole) > 0.1
    cut = {**CFG, "mamba_num_heads": HEADS // GROUPS, "n_groups": 1}
    assert _rel(shares[2], NEMOTRON._reference_mamba(
        cut, _mamba_share(params, 2), u)) < TOL


def _attention(heads, kv_heads, impl="xla"):
    return tfm.Attention(heads, 4, attn_impl=impl, compute_dtype=jnp.float32,
                         n_kv_heads=kv_heads, rope=False)


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_the_head_shares_of_an_attention_layer_add_up_to_the_layer(impl):
    """8 query heads over 2 K/V heads, no rotation, over 4 ranks: more ranks
    than K/V heads, so a rank holds 2 query heads and the ONE K/V head they
    read (each K/V head lives on two ranks); the partial outputs through
    ``W_o``'s rows add up to the uncut layer's and the reference's."""
    u = _u(3)
    params = _attention(8, 2).init(jax.random.PRNGKey(2), u)["params"]
    whole = _attention(8, 2, impl).apply({"params": params}, u)
    assert _rel(whole, NEMOTRON._reference_attention(CFG, params, u)) < TOL
    shares = []
    for rank in range(4):
        q, kv = slice(2 * rank, 2 * rank + 2), slice(rank // 2, rank // 2 + 1)
        share = {"q_proj": {"kernel": params["q_proj"]["kernel"][:, q]},
                 "k_proj": {"kernel": params["k_proj"]["kernel"][:, kv]},
                 "v_proj": {"kernel": params["v_proj"]["kernel"][:, kv]},
                 "o_proj": {"kernel": params["o_proj"]["kernel"][q]}}
        shares.append(_attention(2, 1, impl).apply({"params": share}, u))
    assert _rel(sum(shares), whole) < TOL
    assert _rel(shares[1], whole) > 0.1


def test_attention_without_rotation_takes_no_position():
    """``rope=False``: the positions change nothing, and the turned layer
    is another function of the same weights."""
    u = _u(4)
    params = _attention(8, 2).init(jax.random.PRNGKey(2), u)["params"]
    plain = _attention(8, 2).apply({"params": params}, u)
    moved = _attention(8, 2).apply({"params": params}, u,
                                   jnp.arange(LENGTH) + 100)
    np.testing.assert_array_equal(plain, moved)
    turned = tfm.Attention(8, 4, attn_impl="xla", compute_dtype=jnp.float32,
                           n_kv_heads=2).apply({"params": params}, u)
    assert _rel(turned, plain) > 0.01
    with pytest.raises(NotImplementedError, match="rope=False"):
        tfm.Attention(8, 4, decode=True, max_decode_len=8, rope=False,
                      compute_dtype=jnp.float32).init(
                          jax.random.PRNGKey(0), u[:, :1])
