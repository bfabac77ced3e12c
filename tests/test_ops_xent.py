"""Blockwise cross-entropy (ops/xent.py): parity of the weighted sum and of
its three cotangents with the dense log-softmax path, over block budgets
that do and do not divide the rows, and the structure of what it traces
(one head product forward, three differentiated)."""

import jax
import jax.flatten_util  # noqa: F401 - registers jax.flatten_util
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu import telemetry
from tensorflowonspark_tpu.ops import xent
from tensorflowonspark_tpu.ops.xent import blockwise_cross_entropy

N, D, V = 24, 16, 50


def _data(n=N, v=V, dtype=np.float32):
    rng = np.random.RandomState(0)
    h = jnp.asarray(rng.randn(n, D).astype(np.float32), dtype)
    w = jnp.asarray(rng.randn(D, v).astype(np.float32) * 0.3, dtype)
    t = jnp.asarray(rng.randint(0, v, (n,)).astype(np.int32))
    return h, w, t


@pytest.fixture(autouse=True)
def chunks_of_a_few_rows(monkeypatch):
    """The floor on a chunk's rows (2,048: the carried ``dw``'s traffic) would
    make one chunk of every case here."""
    monkeypatch.setattr(xent, "_MIN_ROWS", 8)


def _dense_nll(h, w, t):
    logp = jax.nn.log_softmax((h @ w).astype(jnp.float32))
    return -jnp.take_along_axis(logp, t[:, None], axis=-1)[:, 0]


def _row_weights(n):
    """A weight a row with zeros in it (a loss mask, block diffusion's
    ``masked / t``)."""
    w = np.random.RandomState(1).rand(n).astype(np.float32) + 0.5
    w[[1, n // 2, n - 1]] = 0.0
    return jnp.asarray(w)


# block budgets [N, chunk]: 16 and 7 give row chunks that divide N (8 rows,
# 3 chunks), 50 and 64 one chunk; with 21 rows the last chunk is padded
@pytest.mark.parametrize("chunk,n,v", [(16, N, V), (50, N, V), (64, N, V),
                                       (7, 21, V), (16, 21, 130)])
def test_forward_parity(chunk, n, v):
    h, w, t = _data(n, v)
    got = jax.jit(lambda *a: blockwise_cross_entropy(*a, chunk=chunk))(h, w, t)
    np.testing.assert_allclose(float(got), float(jnp.sum(_dense_nll(h, w, t))),
                               rtol=1e-5)


@pytest.mark.parametrize("n,v,chunk,min_rows,rows", [
    (24, V, 16, 8, 8), (21, V, 16, 8, 8), (24, V, 50, 8, 24),
    (24, V, 16, 2048, 24),              # fewer rows than the floor: one chunk
    (8190, 50304, 8384, 8, 1368),       # OLMoE's cell by its block alone
    (8190, 50304, 8384, 2048, 2048),    # and under the floor: four chunks
    (16383, 18992, 9600, 2048, 8192),   # Keye's: two chunks, one padded row
    (4096, 18992, 9600, 2048, 2048)])   # SDAR's
def test_row_chunk_from_shapes(monkeypatch, n, v, chunk, min_rows, rows):
    monkeypatch.setattr(xent, "_MIN_ROWS", min_rows)
    assert xent._row_chunk(n, v, chunk) == rows


def _grads(loss, *args):
    return jax.jit(jax.grad(loss, argnums=tuple(range(len(args)))))(*args)


def _assert_close(got, want, rtol=1e-4, atol=1e-5):
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=rtol, atol=atol)


def test_grad_parity():
    h, w, t = _data()

    def dense_loss(h, w):
        return jnp.mean(_dense_nll(h, w, t))

    def fused_loss(h, w):
        return blockwise_cross_entropy(h, w, t, chunk=16) / N

    _assert_close(_grads(fused_loss, h, w), _grads(dense_loss, h, w))


@pytest.mark.parametrize("n", [N, 21], ids=["whole_chunks", "padded_rows"])
@pytest.mark.parametrize("upstream", [1.0, 0.1], ids=["g1", "mtp_coef"])
def test_weighted_grad_parity(n, upstream):
    """A weight a row with zeros in it, an upstream cotangent (0.1: the MTP
    coefficient), N that is no multiple of the row chunk: ``dh``, ``dw`` and
    the weights' own cotangent (the rows' nll)."""
    h, w, t = _data(n)
    weights = _row_weights(n)

    def dense_loss(h, w, weights):
        return upstream * jnp.sum(weights * _dense_nll(h, w, t))

    def fused_loss(h, w, weights):
        return upstream * blockwise_cross_entropy(h, w, t, weights, chunk=16)

    got = _grads(fused_loss, h, w, weights)
    _assert_close(got, _grads(dense_loss, h, w, weights))
    # zero-weight rows reach nothing
    zero = np.asarray(weights) == 0.0
    assert not np.asarray(got[0])[zero].any()
    np.testing.assert_allclose(np.asarray(got[2]),
                               upstream * np.asarray(_dense_nll(h, w, t)),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n", [N, 21], ids=["whole_chunks", "padded_rows"])
def test_bf16_operands(n):
    """bf16 hidden and head, as the cells cast them: float32 logits and
    sums, gradients in the operands' dtype."""
    h, w, t = _data(n, dtype=jnp.bfloat16)
    weights = _row_weights(n)

    def dense_loss(h, w):
        nll = _dense_nll(h.astype(jnp.float32), w.astype(jnp.float32), t)
        return jnp.sum(weights * nll) / n

    def fused_loss(h, w):
        return blockwise_cross_entropy(h, w, t, weights, chunk=16) / n

    np.testing.assert_allclose(float(jax.jit(fused_loss)(h, w)),
                               float(dense_loss(h, w)), rtol=1e-5)
    got = _grads(fused_loss, h, w)
    assert [g.dtype for g in got] == [jnp.bfloat16, jnp.bfloat16]
    _assert_close(got, _grads(dense_loss, h, w), rtol=2e-2, atol=1e-4)


def _head_products(jaxpr, v, outer=""):
    """The scope path of every ``dot_general`` of a jaxpr, sub-jaxprs (whose
    name stacks start at their equation) included, one of whose operands or
    whose result spans the vocabulary."""
    found = []
    for eqn in jaxpr.eqns:
        scope = f"{outer}/{eqn.source_info.name_stack}"
        if eqn.primitive.name == "dot_general":
            shapes = [x.aval.shape for x in (*eqn.invars, *eqn.outvars)]
            if any(v in shape for shape in shapes):
                found.append(scope)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_head_products(sub, v, scope))
    return found


def _counters():
    counts = telemetry.snapshot()["counters"]
    return counts.get("xent.calls", 0), counts.get("xent.grad_in_forward", 0)


@pytest.mark.parametrize("mtp", [0, 1], ids=["one_pass", "mtp_module"])
def test_three_head_products_a_pass_under_the_heads_scope(monkeypatch, mtp):
    """The differentiated loss holds THREE vocabulary-sized products a chunk
    of a pass of the head (the parent held four: the logits twice), all under
    the scope ``lm_head_loss``; the undifferentiated loss holds one."""
    from tensorflowonspark_tpu.models import transformer as tfm

    monkeypatch.setattr(xent, "_MIN_ROWS", 2048)    # the 30 rows: one chunk

    vocab = 72
    model = tfm.build_transformer({
        "vocab_size": vocab, "d_model": 32, "n_layers": 1, "n_heads": 2,
        "d_ff": 48, "attn_impl": "xla", "num_nextn_predict_layers": mtp})
    ids = jnp.zeros((2, 16), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), ids)["params"])
    fused = tfm.make_loss_fn(model, vocab_chunk=24)
    passes = 1 + mtp

    def loss_fn(params, batch):
        # as ``dp.make_train_step`` has it: a transform names itself around
        # the outermost scope it meets, ``jvp(loss_and_grad)/lm_head_loss``
        with jax.named_scope("loss_and_grad"):
            return fused(params, batch)

    calls, in_forward = _counters()
    forward = jax.make_jaxpr(loss_fn)(params, {"input_ids": ids})
    assert len(_head_products(forward.jaxpr, vocab)) == passes
    assert _counters() == (calls + passes, in_forward)

    grad = jax.make_jaxpr(jax.value_and_grad(loss_fn, has_aux=True))(
        params, {"input_ids": ids})
    products = _head_products(grad.jaxpr, vocab)
    assert len(products) == 3 * passes
    assert all("lm_head_loss" in s.split("/") for s in products), products
    assert sum("mtp" in s.split("/") for s in products) == 3 * mtp
    # every differentiated call counts in both counters
    after = _counters()
    assert after[0] - calls - passes == after[1] - in_forward >= passes


def test_transformer_fused_loss_matches_dense():
    """make_loss_fn(vocab_chunk=...) end-to-end parity on a tiny LM."""
    from tensorflowonspark_tpu.models import transformer as tfm

    model = tfm.Transformer(vocab_size=37, d_model=16, n_layers=1, n_heads=2,
                            attn_impl="xla", compute_dtype=jnp.float32)
    ids = jnp.asarray(np.random.RandomState(1).randint(0, 37, (2, 12)),
                      jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]

    dense = tfm.make_loss_fn(model)
    fused = tfm.make_loss_fn(model, vocab_chunk=16)
    batch = {"input_ids": ids}

    ld, md = jax.jit(dense)(params, batch)
    lf, mf = jax.jit(fused)(params, batch)
    np.testing.assert_allclose(float(lf), float(ld), rtol=1e-5)
    np.testing.assert_allclose(float(mf["lm_loss"]), float(md["lm_loss"]),
                               rtol=1e-5)

    gd = jax.jit(jax.grad(lambda p, b: dense(p, b)[0]))(params, batch)
    gf = jax.jit(jax.grad(lambda p, b: fused(p, b)[0]))(params, batch)
    flat_d, _ = jax.flatten_util.ravel_pytree(gd)
    flat_f, _ = jax.flatten_util.ravel_pytree(gf)
    np.testing.assert_allclose(np.asarray(flat_f), np.asarray(flat_d),
                               rtol=2e-4, atol=1e-5)
