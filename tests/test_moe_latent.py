"""LatentMoE (ISSUE 41): ``parallel/ep.MoEMLP(expert_act="relu2", latent=)``
against the plain reference kept with the benchmark
(``benchmark/configs/nemotron3_super_d11_tp8_ep64.py``): relu² experts of
two matrices in a latent between the layer's own two maps, a top 22 of many
through the held path, and the SHARES test: the held ranges' parts (each
through ``W_2``) plus the shared expert counted ONCE add up to the uncut
layer.  SwiGLU layers keep their parameter trees.  Float32 on the CPU."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from tensorflowonspark_tpu.models import transformer as tfm
from tensorflowonspark_tpu.parallel.ep import MoEMLP

NEMOTRON = common.load_module("configs", "nemotron3_super_d11_tp8_ep64")

# a sort and a grouped matmul against a loop over experts, both float32
TOL = 1e-4
D, LATENT, FF, EXPERTS, TOP = 32, 16, 24, 16, 5


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def _cfg(held, experts=EXPERTS, top=TOP):
    return {"router_experts": experts, "num_experts_per_tok": top,
            "experts_held": list(held), "norm_topk_prob": True,
            "routed_scaling_factor": 5.0}


def _layer(held=None, experts=EXPERTS, top=TOP, **kw):
    return MoEMLP(D, FF, experts, top, None, held=held, scoring="sigmoid",
                  selection_bias=True, routed_scale=5.0, expert_act="relu2",
                  latent=LATENT, **kw)


def _x(seed=0, n=48):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(1, n, D)),
                       jnp.float32)


def _variables(experts=EXPERTS, top=TOP, seed=1):
    variables = _layer(None, experts, top).init(jax.random.PRNGKey(seed), _x())
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(seed + 1), (experts,))
    return variables["params"], {"e_score_correction_bias": bias}


def _share(params, first, end):
    return {**params, "experts_up": params["experts_up"][first:end],
            "experts_down": params["experts_down"][first:end]}


def test_relu2_experts_in_a_latent_are_the_reference():
    """All 16 experts on the chip (the dropless path) and a held range of
    them (the held path): output and every gradient."""
    params, buffers = _variables()
    x = _x()
    assert set(params) == {"router", "latent_down", "latent_up",
                           "experts_up", "experts_down"}
    assert params["experts_up"].shape == (EXPERTS, LATENT, FF)
    assert params["experts_down"].shape == (EXPERTS, FF, LATENT)
    assert params["latent_down"]["kernel"].shape == (D, LATENT)
    for held in (None, (4, 12)):
        first, end = held or (0, EXPERTS)
        own = _share(params, first, end)

        def system(p, x):
            return _layer(held).apply({"params": p, "buffers": buffers}, x)

        def reference(p, x):
            out, _ = NEMOTRON._reference_moe(
                _cfg((first, end)), p, buffers["e_score_correction_bias"],
                x.reshape(-1, D))
            return out.reshape(x.shape)

        assert _rel(system(own, x), reference(own, x)) < TOL
        grads = [jax.grad(lambda p, x, f=f: jnp.sum(jnp.sin(f(p, x))),
                          argnums=(0, 1))(own, x) for f in (system, reference)]
        for (path, got), want in zip(
                jax.tree_util.tree_flatten_with_path(grads[0])[0],
                jax.tree.leaves(grads[1])):
            assert _rel(got, want) < 10 * TOL, jax.tree_util.keystr(path)


def test_the_held_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """16 experts over 4 chips of 4: every chip runs the router, both latent
    maps and the shared expert whole and its own experts' part; the parts
    (each through ``W_2``: it has no bias, so the sum of the maps is the map
    of the sum) add up to the uncut routed output, and the four LAYERS'
    outputs less three copies of the shared expert to the uncut layer."""
    params, buffers = _variables()
    x = _x(2)
    variables = {"params": params, "buffers": buffers}
    whole = _layer(None).apply(variables, x)
    parts = [_layer((first, first + 4)).apply(
        {"params": _share(params, first, first + 4), "buffers": buffers}, x)
        for first in range(0, EXPERTS, 4)]
    assert _rel(sum(parts), whole) < TOL
    assert _rel(parts[0], whole) > 0.05
    uncut, _ = NEMOTRON._reference_moe(
        _cfg((0, EXPERTS)), params, buffers["e_score_correction_bias"],
        x.reshape(-1, D))
    assert _rel(sum(parts), uncut.reshape(x.shape)) < TOL

    def layer(held):
        return tfm.MixerBlock(
            "E", 4, 8, FF, compute_dtype=jnp.float32, norm_eps=1e-5,
            n_experts=EXPERTS, moe_top_k=TOP, moe_held=held,
            moe_router=("sigmoid", True, 5.0), moe_shared_d_ff=40,
            moe_expert_act="relu2", moe_latent=LATENT)

    block = layer(None).init(jax.random.PRNGKey(7), x)
    block = {"params": {**block["params"], "moe": params},
             "buffers": {"moe": buffers}}
    assert set(block["params"]["shared"]) == {"up_proj", "down_proj"}
    u = NEMOTRON._rms_norm(x, block["params"]["norm"]["scale"], 1e-5)
    shared = NEMOTRON._relu2_mlp(
        u, block["params"]["shared"]["up_proj"]["kernel"],
        block["params"]["shared"]["down_proj"]["kernel"])
    whole = layer(None).apply(block, x) - x
    parts = [layer((first, first + 4)).apply(
        {**block, "params": {**block["params"],
                             "moe": _share(params, first, first + 4)}}, x) - x
        for first in range(0, EXPERTS, 4)]
    assert _rel(sum(parts) - 3 * shared, whole) < TOL
    assert _rel(sum(parts), whole) > 0.05       # four copies are not one


def test_a_top_22_of_many_goes_through_the_held_path():
    """22 of 64 with experts 0-7 held: a token has up to 8 held rows of its
    22 (the run-sum adds at most that many), and uneven routing fills more
    than one piece; output and routing against the reference."""
    experts, top, held = 64, 22, (0, 8)
    params, buffers = _variables(experts, top)
    # a bias that favours the held experts: most tokens choose most of them
    buffers = {"e_score_correction_bias":
               buffers["e_score_correction_bias"].at[:8].add(0.3)}
    own = _share(params, *held)
    x = _x(5, n=64)
    out, sown = _layer(held, experts, top).apply(
        {"params": own, "buffers": buffers}, x,
        mutable=["intermediates", "moe_stats"])
    want, top_idx = NEMOTRON._reference_moe(
        _cfg(held, experts, top), own, buffers["e_score_correction_bias"],
        x.reshape(-1, D))
    assert _rel(out, want.reshape(x.shape)) < TOL
    got_idx = np.sort(np.asarray(sown["intermediates"]["top_idx"][0]))
    np.testing.assert_array_equal(got_idx, np.sort(np.asarray(top_idx)))
    # some token holds more than 6 rows here (the runs seen before were 8 of
    # a top 8 and 6 of a top 6)
    per_token = (np.asarray(top_idx) < 8).sum(1)
    assert 6 < per_token.max() <= 8
    # well over twice the even share of 8 / 64: a second piece runs
    assert 0.25 < float(sown["moe_stats"]["held_pairs"][0]) <= 8 / 22


def test_the_other_forms_raise_by_name_and_swiglu_keeps_its_tree():
    x = _x()
    with pytest.raises(ValueError, match="relu2 experts and a latent"):
        MoEMLP(D, FF, 4, 2, 1.25, expert_act="relu2").init(
            jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="expert_act='gelu'"):
        MoEMLP(D, FF, 4, 2, None, expert_act="gelu").init(
            jax.random.PRNGKey(0), x)
    for capacity, held in ((None, None), (None, (1, 3)), (1.25, None)):
        params = MoEMLP(D, FF, 4, 2, capacity, held=held).init(
            jax.random.PRNGKey(0), x)["params"]
        here = 2 if held else 4
        assert jax.tree.map(lambda a: a.shape, params) == {
            "router": {"kernel": (D, 4)},
            "experts_gate": (here, D, FF), "experts_up": (here, D, FF),
            "experts_down": (here, FF, D)}
