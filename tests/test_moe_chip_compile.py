"""The held experts' layer at the four held cells' shapes, compiled for a
DESCRIBED v5e (nothing runs, no chip needed): SDAR's and Keye's (a piece of
16,384 and of 32,768 rows of 2,048, runs of 8), Kanana-2's (12,288 rows,
sigmoid router, runs of 6) and Nemotron-3's (5,632 latent rows of 1,024,
runs of 8 of a top 22, relu2 experts).  What interpret mode cannot show:
that Mosaic takes ``sum_tokens``' walk (scalar-prefetch tables, a grid whose
length is data), its ``[256, 128]`` weight matrix turned for the MXU and its
one-lane column of routing weights, beside the grouped matmul's kernels, and
that the gradient program holds the kernel in both directions under the
scopes the benchmark's readers sum by.  The topology is described inside a
fixture, never at import (only one process may load the TPU library; see the
on-chip-measurement guide)."""

from __future__ import annotations

import functools
import os
import re

import pytest


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: not here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


_ROUTER = dict(scoring="sigmoid", selection_bias=True)


@pytest.mark.parametrize("case", [
    # tokens, d_model, expert width, experts, held, top k, piece, further
    pytest.param((8192, 2048, 768, 128, 16, 8, 16384, {}), id="sdar"),
    pytest.param((16384, 2048, 768, 128, 16, 8, 32768, {}), id="keye"),
    pytest.param((8192, 2048, 768, 128, 16, 6, 12288,
                  dict(_ROUTER, routed_scale=2.448)), id="kanana2"),
    pytest.param((8192, 4096, 2688, 512, 8, 22, 5632,
                  dict(_ROUTER, routed_scale=5.0, expert_act="relu2",
                       latent=1024)), id="nemotron3"),
])
def test_the_held_layer_lowers_at_the_cells_shapes(one_chip, case,
                                                   monkeypatch):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from tensorflowonspark_tpu.ops import grouped_matmul as gm
    from tensorflowonspark_tpu.ops import sum_tokens as st
    from tensorflowonspark_tpu.parallel import ep as eplib

    n, d, f, e, held, k, piece, further = case
    assert eplib._piece_rows(n * k, held / e) == piece
    # the backend here is the CPU: the kernels are asked for by name
    monkeypatch.setattr(eplib, "grouped_matmul", functools.partial(
        gm.grouped_matmul, impl="pallas"))
    monkeypatch.setattr(eplib, "sum_tokens", functools.partial(
        st.sum_tokens, impl="pallas"))
    layer = eplib.MoEMLP(d, f, e, k, None, compute_dtype=jnp.bfloat16,
                         held=(0, held), **further)
    x = jax.ShapeDtypeStruct((1, n, d), jnp.bfloat16, sharding=one_chip)
    variables = jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                          sharding=one_chip),
        jax.eval_shape(lambda: layer.init(
            jax.random.key(0), jnp.zeros((1, n, d), jnp.bfloat16))))

    def loss(params, x, rest):
        y = layer.apply({**rest, "params": params}, x)
        return jnp.sum(y.astype(jnp.float32))

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without a chip: keep it out of the cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        hlo = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
            variables["params"], x,
            {name: tree for name, tree in variables.items()
             if name != "params"}).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    kernels = [line for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    sums = [line for line in kernels if "sum_tokens" in line]
    # the first piece's two, the loop's forward and the loop's backward
    # (its second forward's sum is dead there: only the cotangents are used)
    assert len(sums) == 4
    forward = [line for line in sums if "transpose(" not in line]
    assert all("/moe/combine/" in line for line in forward)
    # the dispatch's backward of the first piece; in the loop's backward,
    # which is a program of its own, the scopes are the loop's
    assert any("/moe/dispatch/" in line
               for line in sums if "transpose(" in line)
    assert not any("/moe/experts/" in line for line in sums)
    # the routing weights' cotangent goes back by a piece-long scatter: no
    # gather as long as the layer's pairs is left (the parent's was 6.7 ms a
    # step in Nemotron-3's cell)
    assert not re.search(rf"\[{n * k}\]\S* gather\(", hlo)
    # ... and the router picks its weights by a one-hot of the choice: the
    # compiled scope holds no gather and no scatter (9.3 ms a step there)
    router = [line for line in hlo.splitlines() if "/moe/router/" in line]
    assert not [line for line in router
                if re.search(r" (gather|scatter)\(", line)]
    assert any(" select(" in line for line in router)
    assert len(kernels) - len(sums) >= 3 * (3 if "expert_act" in further
                                            else 2)
