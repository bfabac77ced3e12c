"""The multi-token-prediction module in the loss (ISSUE 45): ``Transformer(
mtp_layers=1)`` through ``make_loss_fn`` against the plain reference of
``benchmark/configs/xing4_29b_a4b_d5_tp8_ep8.py`` at a small size on the CPU,
float32 on both sides: both losses, and the gradients of every parameter (the
SHARED embedding's and head's are the sums of their two uses); and that a
model without the module trains under the loss it had, bit for bit."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from tensorflowonspark_tpu.models import transformer as tfm

NAME = "xing4_29b_a4b_d5_tp8_ep8"


@pytest.fixture(scope="module")
def small():
    """The configuration at its rehearsal size: two layers (one dense), the
    MTP module, four streams, a query latent, YaRN, 2 of 8 experts held."""
    mod = common.load_module("configs", NAME)
    cfg = common.read_json(f"{common.HERE}/configs/{NAME}.json")
    for key, value in cfg["rehearsal"].items():
        cfg[key] = ({**cfg[key], **value} if isinstance(value, dict)
                    else value)
    return mod, cfg


@pytest.mark.parametrize("vocab_chunk", [0, 64], ids=["dense", "fused"])
def test_both_losses_and_every_gradient_match_the_reference(small,
                                                            vocab_chunk):
    mod, cfg = small
    tfm_, model = mod._model(cfg)
    loss_fn = tfm_.make_loss_fn(model, aux_loss_coef=0.0,
                                vocab_chunk=vocab_chunk, router_z_coef=0.0,
                                mtp_coef=cfg["mtp_loss_weight"])
    params, buffers = mod._init_state(cfg, jax.random.key(5))
    ids = jax.random.randint(jax.random.key(6), (2, 48), 0, cfg["vocab_size"])

    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        loss_fn, has_aux=True))(params, {"input_ids": ids}, buffers)

    def reference(published):
        logits, logits_mtp, _routing = mod.reference_forward(
            cfg, published, buffers, ids)
        return mod.reference_loss(cfg, logits, logits_mtp, ids)

    (ref_loss, ref_mtp), ref_grads = jax.jit(jax.value_and_grad(
        reference, has_aux=True))(mod.published_layout(cfg, params))
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    assert float(metrics["mtp_loss"]) == pytest.approx(float(ref_mtp),
                                                       rel=1e-5)
    assert float(loss) == pytest.approx(
        float(metrics["lm_loss"])
        + cfg["mtp_loss_weight"] * float(metrics["mtp_loss"]), rel=1e-6)
    grads = mod.published_layout(cfg, grads)
    for (path, own), ref in zip(
            jax.tree_util.tree_flatten_with_path(grads)[0],
            jax.tree.leaves(ref_grads)):
        name = jax.tree_util.keystr(path)
        scale = float(jnp.abs(ref).max())
        assert scale > 0, name
        assert float(jnp.abs(own - ref).max()) < 2e-4 * scale + 1e-7, name
    # the module reaches the shared embedding and head: without its loss
    # their gradients are other gradients
    alone = jax.jit(jax.grad(lambda p: tfm_.make_loss_fn(
        model, aux_loss_coef=0.0, vocab_chunk=vocab_chunk, router_z_coef=0.0,
        mtp_coef=0.0)(p, {"input_ids": ids}, buffers)[0]))(params)
    for name in ("embed", "lm_head"):
        both, one = jax.tree.leaves(grads[name])[0], jax.tree.leaves(
            alone[name])[0]
        assert float(jnp.abs(both - one).max()) > 1e-3 * float(
            jnp.abs(both).max()), name


def test_a_masked_row_weighs_each_loss_by_its_own_targets(small):
    mod, cfg = small
    tfm_, model = mod._model(cfg)
    loss_fn = tfm_.make_loss_fn(model, aux_loss_coef=0.0, router_z_coef=0.0)
    params, buffers = mod._init_state(cfg, jax.random.key(5))
    ids = jax.random.randint(jax.random.key(6), (2, 24), 0, cfg["vocab_size"])
    mask = jnp.ones((2, 24)).at[:, 16:].set(0.0)
    _, masked = jax.jit(loss_fn)(
        params, {"input_ids": ids, "loss_mask": mask}, buffers)
    (logits, logits_mtp), _ = jax.jit(lambda p, b: model.apply(
        {"params": p, "buffers": b}, ids,
        mutable=["aux_loss", "moe_stats", "hc_stats"]))(params, buffers)

    def mean_nll(logits, targets):
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))

    assert float(masked["lm_loss"]) == pytest.approx(
        float(mean_nll(logits[:, :15], ids[:, 1:16])), rel=1e-5)
    assert float(masked["mtp_loss"]) == pytest.approx(
        float(mean_nll(logits_mtp[:, :14], ids[:, 2:16])), rel=1e-5)


def _loss_fn_of_the_parent(model, aux_loss_coef=0.01, vocab_chunk=0,
                           router_z_coef=1e-3):
    """``make_loss_fn`` as the parent commit had it (one head, one shift),
    verbatim but for what it imported."""
    sown = ["aux_loss", "moe_stats"] if model.n_experts else ["aux_loss"]

    def _variables(params, buffers):
        return ({"params": params} if buffers is None
                else {"params": params, "buffers": buffers})

    def _reduce(nll, batch, updates):
        mask = batch.get("loss_mask")
        if mask is not None:
            mask = mask[:, 1:].astype(jnp.float32)
            loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        else:
            loss = jnp.mean(nll)
        return tfm._with_sown_terms(loss, updates, aux_loss_coef,
                                    router_z_coef)

    if vocab_chunk:
        from tensorflowonspark_tpu.ops.xent import blockwise_cross_entropy

        hidden_model = model.clone(return_hidden=True)

        def fused_loss_fn(params, batch, buffers=None):
            ids = batch["input_ids"]
            h, updates = hidden_model.apply(_variables(params, buffers), ids,
                                            mutable=sown)
            b, s, d = h.shape
            h = h[:, :-1].reshape(b * (s - 1), d)
            targets = ids[:, 1:].reshape(-1)
            mask = batch.get("loss_mask")
            if mask is not None:
                mask = mask[:, 1:].reshape(-1).astype(jnp.float32)
            with jax.named_scope("lm_head_loss"):
                total = blockwise_cross_entropy(
                    h, params["lm_head"]["kernel"].astype(h.dtype), targets,
                    mask, chunk=vocab_chunk)
            if mask is None:
                loss = total / (b * (s - 1))
            else:
                loss = total / jnp.maximum(jnp.sum(mask), 1.0)
            return tfm._with_sown_terms(loss, updates, aux_loss_coef,
                                        router_z_coef)

        return fused_loss_fn

    def loss_fn(params, batch, buffers=None):
        ids = batch["input_ids"]
        logits, updates = model.apply(_variables(params, buffers), ids,
                                      mutable=sown)
        with jax.named_scope("lm_head_loss"):
            logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
            targets = ids[:, 1:]
            nll = -jnp.take_along_axis(logp, targets[..., None],
                                       axis=-1)[..., 0]
        return _reduce(nll, batch, updates)

    return loss_fn


@pytest.mark.parametrize("vocab_chunk", [0, 32], ids=["dense", "fused"])
@pytest.mark.parametrize("experts", [0, 4], ids=["dense_ffn", "experts"])
def test_without_the_module_the_loss_is_the_one_it_was(vocab_chunk, experts):
    """``num_nextn_predict_layers`` 0: loss, metrics and gradients equal, to
    the bit, those of the parent commit's ``make_loss_fn`` (bf16 compute, the
    cells' own)."""
    model = tfm.build_transformer({
        "vocab_size": 64, "d_model": 32, "n_layers": 2, "n_heads": 2,
        "d_ff": 48, "n_experts": experts, "attn_impl": "xla",
        "num_nextn_predict_layers": 0})
    assert model.mtp_layers == 0
    ids = jax.random.randint(jax.random.key(0), (2, 16), 0, 64)
    params = model.init(jax.random.key(1), ids)["params"]
    batch = {"input_ids": ids}
    now = jax.jit(jax.value_and_grad(tfm.make_loss_fn(
        model, vocab_chunk=vocab_chunk), has_aux=True))(params, batch)
    then = jax.jit(jax.value_and_grad(_loss_fn_of_the_parent(
        model, vocab_chunk=vocab_chunk), has_aux=True))(params, batch)
    assert set(now[0][1]) == set(then[0][1])
    for own, ref in zip(jax.tree.leaves(now), jax.tree.leaves(then)):
        np.testing.assert_array_equal(np.asarray(own), np.asarray(ref))
