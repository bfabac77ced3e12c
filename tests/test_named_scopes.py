"""``jax.named_scope`` where the device time goes (ISSUE 23): each train
step lowered at tiny widths carries the scope names in its HLO ``op_name``
metadata, so a trace viewer and a later reduction find them after a
refactor.  Metadata only: nothing here runs a step."""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import optax
import pytest

from tensorflowonspark_tpu.models import registry, resnet, transformer
from tensorflowonspark_tpu.parallel import dp


def _hlo_op_names(lowered) -> str:
    """The lowered step as an HLO module proto: every instruction's
    ``metadata.op_name`` (``jit(step)/loss_and_grad/...``) is in it as plain
    bytes (``as_hlo_text`` prints no metadata)."""
    proto = lowered.compiler_ir(dialect="hlo").as_serialized_hlo_module_proto()
    return proto.decode("latin-1")


def _lm_step_text(**model_overrides) -> str:
    model = registry.build({"model": "transformer", "vocab_size": 64,
                            "d_model": 32, "n_layers": 1, "n_heads": 2,
                            "d_ff": 64, **model_overrides})
    ids = jnp.zeros((2, 16), jnp.int32)
    variables = jax.eval_shape(lambda: model.init(jax.random.key(0), ids))
    optimizer = optax.adamw(1e-3)
    state = jax.eval_shape(
        lambda p, b: dp.TrainState.create(p, optimizer, b),
        variables["params"], variables.get("buffers"))
    step = dp.make_train_step(transformer.make_loss_fn(model), optimizer)
    return _hlo_op_names(step.lower(state, {"input_ids": ids}))


def test_lm_step_names_attention_mlp_head_and_both_step_halves():
    text = _lm_step_text(attn_impl="pallas_interpret")
    for scope in ("loss_and_grad", "optimizer_update", "attention", "mlp",
                  "lm_head_loss", "flash_fwd", "flash_bwd"):
        assert f"/{scope}/" in text, scope


@pytest.mark.parametrize("capacity", [None, 1.25],
                         ids=["dropless", "capacity"])
def test_moe_step_names_router_dispatch_experts_combine_and_qk_norm(capacity):
    """ISSUE 25: the expert layer's four stages under either routing rule,
    and QK-norm, forward and backward (the readers in
    ``benchmark/layer_metrics/moe_*.py`` sum device time by these)."""
    text = _lm_step_text(n_experts=4, moe_top_k=2, qk_norm=True,
                         moe_capacity_factor=capacity, attn_impl="xla")
    for scope in ("moe/router", "moe/dispatch", "moe/experts", "moe/combine",
                  "qk_norm", "optimizer_update"):
        assert f"/{scope}/" in text, scope
        backward = [line for line in text.split("jit(step)")
                    if f"/{scope}/" in line and "transpose(" in line]
        assert backward or scope == "optimizer_update", scope
    if capacity is None:    # a grouped matmul, not a scan over blocks of rows
        assert "/moe/experts/while" not in text


def test_latent_step_names_its_projections_the_shared_expert_and_router():
    """ISSUE 39: latent attention's projections (both halves of the layer:
    what feeds the kernels and the output projection), the flash kernels
    under it, the shared expert and the router, forward and backward (the
    readers in ``benchmark/layer_metrics/mla_*.py``, ``moe_shared_ms`` and
    ``moe_router_ms`` sum device time by these)."""
    text = _lm_step_text(
        n_layers=2, n_heads=4, n_experts=4, moe_top_k=2,
        moe_capacity_factor=None, attn_impl="pallas_interpret",
        latent_attention={"kv_lora_rank": 16, "qk_nope_head_dim": 8,
                          "qk_rope_head_dim": 4, "v_head_dim": 8},
        moe_router={"scoring": "sigmoid", "selection_bias": True,
                    "routed_scale": 2.448},
        moe_shared_d_ff=32, layer_ffn=[48, 0])
    for scope in ("mla/project", "flash_fwd", "flash_bwd", "moe/shared",
                  "moe/router", "moe/dispatch", "moe/experts"):
        assert f"/{scope}/" in text, scope
        backward = [line for line in text.split("jit(step)")
                    if f"/{scope}/" in line and "transpose(" in line]
        assert backward or scope in ("flash_fwd", "flash_bwd"), scope
    for name in ("q_proj", "kv_a_proj", "kv_b_proj", "o_proj"):
        assert f"/mla/project/{name}/" in text, name
    # the leading layer is dense: its FFN is no expert's
    assert "/block_0/mlp/" in text and "/block_0/moe/" not in text
    assert "/block_1/moe/" in text


def test_window_layers_kernels_carry_scopes_of_their_own():
    """ISSUE 48: a global layer's kernels under ``flash_fwd`` / ``flash_bwd``
    and a window layer's under ``flash_fwd_window`` / ``flash_bwd_window``,
    layer by layer and under ``remat`` too (the recomputed block keeps the
    scope and runs no forward kernel under it, ISSUE 49; ``swa_flash_*`` and
    ``flash_bwd_ms`` / ``bd_flash_fwd_ms`` tell band from full by these),
    beside the expert layer's ``moe/router`` (which reads the layer's input
    here), ``moe/dispatch`` and ``moe/experts``."""
    text = _lm_step_text(
        n_layers=2, n_heads=14, n_kv_heads=2, d_head=4, n_experts=4,
        moe_top_k=2, moe_capacity_factor=None, moe_held=[0, 2],
        moe_expert_act="reglu", moe_router_input="layer", remat=True,
        layer_attention=[[0, False], [8, True]],
        attn_impl="pallas_interpret")
    for scope in ("flash_fwd", "flash_bwd", "flash_fwd_window",
                  "flash_bwd_window", "moe/router", "moe/dispatch",
                  "moe/experts"):
        assert f"/{scope}/" in text, scope
    assert re.search(r'/block_0/[^\s"]*/flash_fwd/', text)
    assert re.search(r'/block_1/[^\s"]*/flash_fwd_window/', text)
    assert not re.search(r'/block_0/[^\s"]*/flash_(fwd|bwd)_window/', text)
    assert not re.search(r'/block_1/[^\s"]*/flash_(fwd|bwd)/', text)
    # the block runs again in the backward pass and keeps the scope for the
    # layouts of q, k and v it makes again; the KERNEL is not there a second
    # time (the policy kept its output and log-sum-exp), under either scope
    for scope in ("flash_fwd", "flash_fwd_window"):
        again = [line for line in text.split("jit(step)")
                 if f"/{scope}/" in line and "transpose(" in line]
        assert again, scope
        assert not [line for line in again if "_flash_fwd_pallas" in line]
        assert [line for line in text.split("jit(step)")
                if f"/{scope}/" in line and "_flash_fwd_pallas" in line]


def test_hyper_connected_step_names_maps_mixing_and_the_mtp_module():
    """ISSUE 45: a model with residual streams names a hyper-connection's
    maps (``hc/maps``: norm, product, sigmoid, exp, Sinkhorn), the mix a
    sub-layer reads (``hc/pre``) and what it writes back (``hc/post``),
    forward and backward, and the multi-token-prediction module (``mtp``):
    its projection, its layer with the layer's own scopes inside, and its
    pass of the head and the loss, dense and fused (the readers ``hc_mix_ms``,
    ``hc_maps_ms``, ``hc_mix_roofline`` and ``mtp_ms`` sum device time by
    these); the query latent's two projections are latent attention's."""
    config = dict(
        n_layers=2, n_heads=2, n_experts=4, moe_top_k=2,
        moe_capacity_factor=None, attn_impl="pallas_interpret",
        latent_attention={"kv_lora_rank": 16, "qk_nope_head_dim": 8,
                          "qk_rope_head_dim": 4, "v_head_dim": 8},
        q_lora_rank=12, rope_scaling={
            "type": "yarn", "factor": 64, "mscale": 1, "mscale_all_dim": 1,
            "original_max_position_embeddings": 8},
        moe_router={"scoring": "sigmoid", "selection_bias": True,
                    "routed_scale": 2.0},
        moe_shared_d_ff=32, layer_ffn=[48, 0],
        hyper_connections={"hc_mult": 4, "hc_sinkhorn_iters": 3,
                           "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
                           "mhc_h_res_clamp_max": 30},
        num_nextn_predict_layers=1, remat=True)
    text = _lm_step_text(**config)
    for scope in ("hc/maps", "hc/pre", "hc/post", "mtp", "mla/project",
                  "flash_fwd", "flash_bwd", "moe/shared", "moe/experts"):
        assert f"/{scope}/" in text, scope
        backward = [line for line in text.split("jit(step)")
                    if f"/{scope}/" in line and "transpose(" in line]
        assert backward or scope in ("flash_fwd", "flash_bwd"), scope
    for name in ("q_a_proj", "q_b_proj", "kv_a_proj", "kv_b_proj", "o_proj"):
        assert f"/mla/project/{name}/" in text, name
    # the module's layer keeps the scopes a layer has, inside ``mtp``
    for inner in ("hc/maps", "hc/pre", "hc/post", "mla/project",
                  "moe/experts", "lm_head_loss", "mtp_eh_proj"):
        assert re.search(rf"/mtp/[^\s\"]*{inner}/", text), inner
    assert re.search(r'/mtp/[^\s"]*mtp_block[^\s"]*/hc_mlp/hc/maps/', text)
    assert re.search(r'/block_1/[^\s"]*hc_attn/hc/maps/', text)
    # the fused loss's second pass of the head is the module's too: a scope
    # opened directly in the differentiated function is wrapped in one more
    model = registry.build({"model": "transformer", "vocab_size": 64,
                            "d_model": 32, "d_ff": 64, **config})
    ids = jnp.zeros((2, 16), jnp.int32)
    variables = jax.eval_shape(lambda: model.init(jax.random.key(0), ids))
    loss_fn = transformer.make_loss_fn(model, vocab_chunk=32)
    fused = _hlo_op_names(jax.jit(jax.grad(
        lambda p, b: loss_fn(p, {"input_ids": ids}, b)[0])).lower(
            variables["params"], variables["buffers"]))
    assert "(mtp_loss)/mtp/lm_head_loss/" in fused


def test_mixer_step_names_the_state_space_mixer_and_the_latent_maps():
    """ISSUE 41: a model of one mixer a layer names the Mamba-2 mixer
    (``ssm``) and its five parts, LatentMoE's two maps (``moe/latent``), the
    shared expert, the router and the experts, and attention's kernels,
    forward and backward (the readers ``ssm_mixer_ms``, ``ssm_scan_ms``,
    ``ssm_scan_roofline`` and ``moe_latent_ms`` sum device time by these)."""
    text = _lm_step_text(
        n_layers=3, n_heads=4, n_kv_heads=1, d_head=8, layer_mixer=list("ME*"),
        ssm={"n_heads": 4, "head_dim": 8, "n_groups": 1, "state_size": 16,
             "conv_kernel": 4, "chunk_size": 8, "dt_min": 0.001,
             "dt_max": 0.1, "dt_floor": 1e-4},
        rope=False, d_ff=24, n_experts=8, moe_held=[0, 4], moe_top_k=3,
        moe_capacity_factor=None,
        moe_router={"scoring": "sigmoid", "selection_bias": True,
                    "routed_scale": 5.0},
        moe_shared_d_ff=40, moe_expert_act="relu2", moe_latent=16,
        attn_impl="pallas_interpret")
    for scope in ("ssm", "ssm/in_proj", "ssm/conv", "ssm/scan",
                  "ssm/gate_norm", "ssm/out_proj", "moe/latent", "moe/shared",
                  "moe/router", "moe/dispatch", "moe/experts", "attention",
                  "flash_fwd", "flash_bwd"):
        assert f"/{scope}/" in text, scope
        backward = [line for line in text.split("jit(step)")
                    if f"/{scope}/" in line and "transpose(" in line]
        assert backward or scope in ("flash_fwd", "flash_bwd"), scope
    for name in ("latent_down", "latent_up"):
        assert f"/moe/latent/{name}/" in text, name
    # the scan is no loop over positions: its one loop is the chunk states'
    assert "/ssm/scan/" in text and "/ssd/state/" in text
    for part in ("decay", "intra", "inter"):
        assert f"/ssd/{part}/while" not in text, part
    # a mixer a layer: no layer has attention AND an FFN
    assert "/block_0/ssm/" in text and "/block_0/attn/" not in text
    assert "/block_1/moe/" in text and "/block_1/attn/" not in text
    assert "/block_2/attn/" in text and "/block_2/mlp/" not in text
    assert "/cos" not in text         # nothing turns: no rotation anywhere


def test_kda_step_names_the_mixer_s_parts_and_the_latent_layer_s_kernels():
    """ISSUE 52: a ``Block`` model whose attention slot is Kimi Delta
    Attention or latent attention names, under the flax path ``attn``, the
    KDA mixer (``kda``) and its four parts, forward and backward, with the
    four big projections under attention's names (the readers
    ``kda_mixer_ms``, ``kda_scan_ms``, ``kda_conv_ms`` and
    ``kda_scan_roofline`` sum device time by these, ``lm_attn_proj_ms`` the
    projections), and the latent layer its projections and the flash
    kernels; nothing turns."""
    text = _lm_step_text(
        n_layers=2, n_heads=2, d_ff=24,
        layer_attention=[[0, False, "kda"], [0, False, "latent"]],
        kda={"n_heads": 2, "head_dim": 8, "conv_kernel": 4, "chunk_size": 8},
        latent_attention={"kv_lora_rank": 16, "qk_nope_head_dim": 8,
                          "qk_rope_head_dim": 4, "v_head_dim": 8},
        layer_ffn=[48, 0], n_experts=4, moe_held=[0, 2], moe_top_k=2,
        moe_capacity_factor=None,
        moe_router={"scoring": "sigmoid", "selection_bias": True,
                    "routed_scale": 2.446},
        moe_shared_d_ff=24, remat=True, attn_impl="pallas_interpret")
    for scope in ("kda", "kda/conv", "kda/gates", "kda/scan",
                  "kda/gate_norm", "mla/project", "attention", "flash_fwd",
                  "flash_bwd", "moe/shared", "moe/router", "mlp"):
        assert f"/{scope}/" in text, scope
        backward = [line for line in text.split("jit(step)")
                    if f"/{scope}/" in line and "transpose(" in line]
        assert backward or scope in ("flash_fwd", "flash_bwd"), scope
    for name in ("q_proj", "k_proj", "v_proj", "o_proj"):
        assert f"/block_0/attn/kda/{name}/" in text, name
    for name in ("f_a_proj", "f_b_proj", "g_a_proj", "g_b_proj", "b_proj"):
        assert f"/block_0/attn/kda/kda/gates/{name}/" in text, name
    assert "/block_1/attn/attn._latent_attention/mla/project/q_proj/" in text
    # the op's parts; its loops are one over groups of chunks (what is in
    # memory at once) and one over the chunk states: none over positions
    for part in ("decay", "intra", "solve", "inter"):
        assert f"/kda_op/{part}/" in text, part
    assert "/kda_op/inter/while/body/" in text
    for part in ("decay", "intra", "solve"):
        assert f"/kda_op/{part}/while" not in text, part
    assert "/block_0/attn/attention/" not in text   # no kernel in a KDA layer
    assert "/block_1/attn/kda/" not in text
    assert "/cos" not in text         # nothing turns: no rotation anywhere


def test_the_kda_kernels_sit_under_kda_scan_once_a_direction():
    """ISSUE 54: on the kernel path (a chunk of whole tiles under
    ``attn_impl="pallas_interpret"``) the op's two kernels carry ``kda/scan``
    (what ``kda_scan_ms`` and ``kda_scan_roofline`` sum by), ``kda_op/fwd``
    in the forward pass and ``kda_op/bwd`` in the backward pass, in place of
    the XLA form's four parts; under ``remat`` the recomputed block runs
    neither again (the policy kept the output and the chunk states)."""
    text = _lm_step_text(
        n_layers=2, n_heads=2, d_ff=24,
        layer_attention=[[0, False, "kda"], [0, False, "latent"]],
        kda={"n_heads": 2, "head_dim": 8, "conv_kernel": 4, "chunk_size": 16},
        latent_attention={"kv_lora_rank": 16, "qk_nope_head_dim": 8,
                          "qk_rope_head_dim": 4, "v_head_dim": 8},
        layer_ffn=[48, 0], n_experts=4, moe_held=[0, 2], moe_top_k=2,
        moe_capacity_factor=None, moe_shared_d_ff=24, remat=True,
        attn_impl="pallas_interpret")
    ops = [line for line in text.split("jit(step)") if "/kda_op/" in line]
    assert ops and all("/block_0/attn/kda/kda/scan/kda_op/" in line
                       for line in ops)
    forward = [line for line in ops if "/kda_op/fwd/kda_fwd/" in line]
    backward = [line for line in ops if "/kda_op/bwd/kda_bwd/" in line]
    assert forward and backward
    assert not [line for line in forward if "transpose(" in line]
    assert all("transpose(" in line and "/checkpoint/" in line
               for line in backward)
    for part in ("decay", "intra", "solve", "inter"):
        assert f"/kda_op/{part}/" not in text, part
    # the rest of the mixer keeps its scopes, both passes
    for scope in ("kda/conv", "kda/gates", "kda/gate_norm"):
        assert [line for line in text.split("jit(step)")
                if f"/{scope}/" in line and "transpose(" in line], scope


def _sum_tokens_as_on_the_chip(monkeypatch):
    """``sum_tokens`` as the Pallas kernel (interpreter mode), counted."""
    import functools

    from tensorflowonspark_tpu import telemetry
    from tensorflowonspark_tpu.ops import sum_tokens as st
    from tensorflowonspark_tpu.parallel import ep

    monkeypatch.setattr(ep, "sum_tokens", functools.partial(
        st.sum_tokens, impl="pallas_interpret"))
    return telemetry.counter("moe.kernels.sum_tokens")


def _sum_kernel_ops(text: str) -> list:
    """The op_names of the ``sum_tokens`` kernel's instructions (an op_name
    runs to the first byte that is no text; the module's table of source
    files names whatever the process has traced before)."""
    return [name for name in (re.match(r"[\x20-\x7e]*", chunk).group()
                              for chunk in text.split("jit(step)"))
            if "_sum_tokens_pallas" in name]


def test_the_held_sum_is_the_combine_s_forward_and_the_dispatch_s_backward(
        monkeypatch):
    """ISSUE 43: the ``sum_tokens`` kernel's device time stays under the
    scopes of what it replaced, ``moe/combine`` forward and ``moe/dispatch``
    backward, and nothing of it lands under ``moe/experts``
    (``moe_dispatch_ms`` and ``moe_experts_ms`` go on reading the same
    work)."""
    built = _sum_tokens_as_on_the_chip(monkeypatch)
    before = built.value()
    text = _lm_step_text(n_experts=8, moe_held=[0, 4], moe_top_k=2,
                         moe_capacity_factor=None, attn_impl="xla")
    # one layer, one piece: the forward traced to shape the parameters, then
    # the step's forward and backward
    assert built.value() - before == 3
    ops = _sum_kernel_ops(text)
    forward = [line for line in ops if "transpose(" not in line]
    backward = [line for line in ops if "transpose(" in line]
    assert forward and backward
    assert all("/moe/combine/" in line for line in forward)
    assert all("/moe/dispatch/" in line for line in backward)
    assert not any("/moe/experts/" in line for line in ops)


@pytest.mark.parametrize("capacity", [None, 1.25],
                         ids=["dropless", "capacity"])
def test_the_other_routing_rules_build_no_sum_kernel(capacity, monkeypatch):
    """ISSUE 43: without ``held`` the dropless path (``_dispatch`` /
    ``_combine``) and the capacity rule are the programs they were: no
    ``sum_tokens`` in them, nothing counted."""
    built = _sum_tokens_as_on_the_chip(monkeypatch)
    before = built.value()
    text = _lm_step_text(n_experts=4, moe_top_k=2,
                         moe_capacity_factor=capacity, attn_impl="xla")
    assert not _sum_kernel_ops(text) and built.value() == before


def test_a_dense_step_has_no_moe_scope():
    text = _lm_step_text(attn_impl="xla")
    assert "/moe/" not in text and "/qk_norm/" not in text
    assert "/mla/" not in text and "/ssm/" not in text


def test_fused_head_loss_is_named_too():
    model = registry.build({"model": "transformer", "vocab_size": 64,
                            "d_model": 32, "n_layers": 1, "n_heads": 2,
                            "d_ff": 64})
    ids = jnp.zeros((2, 16), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), ids)["params"])
    loss_fn = transformer.make_loss_fn(model, vocab_chunk=32)
    text = _hlo_op_names(jax.jit(loss_fn).lower(params, {"input_ids": ids}))
    assert "/lm_head_loss/" in text


def test_gradient_accumulation_keeps_the_step_scopes():
    model = registry.build({"model": "transformer", "vocab_size": 64,
                            "d_model": 32, "n_layers": 1, "n_heads": 2,
                            "d_ff": 64})
    ids = jnp.zeros((4, 16), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), ids)["params"])
    optimizer = optax.sgd(1e-2)
    state = jax.eval_shape(lambda p: dp.TrainState.create(p, optimizer),
                           params)
    step = dp.make_train_step(transformer.make_loss_fn(model), optimizer,
                              accum_steps=2)
    text = _hlo_op_names(step.lower(state, {"input_ids": ids}))
    assert "/loss_and_grad/" in text and "/optimizer_update/" in text


@pytest.mark.parametrize("stem,stages", [("imagenet", 4), ("cifar", 3)])
def test_resnet_step_names_every_stage(stem, stages):
    config = ({"model": "resnet50", "num_classes": 10, "width": 8}
              if stem == "imagenet"
              else {"model": "resnet_cifar", "depth_blocks": 1, "width": 8})
    model = registry.build(config)
    size = 32
    images = jnp.zeros((2, size, size, 3), jnp.float32)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.key(0), images, train=False))
    optimizer = optax.sgd(0.1, momentum=0.9)
    state = jax.eval_shape(
        lambda v: dp.BNTrainState.create(v["params"], v["batch_stats"],
                                         optimizer), variables)
    step = dp.make_bn_train_step(resnet.make_loss_fn(model), optimizer)
    text = _hlo_op_names(step.lower(
        state, {"image": images, "label": jnp.zeros((2,), jnp.int32)}))
    assert len(model.stage_sizes) == stages
    for scope in ["loss_and_grad", "optimizer_update", "stem", "head"] + [
            f"stage{i + 1}" for i in range(stages)]:
        assert f"/{scope}/" in text, scope
