"""ResNet model family tests (parity config 3, BASELINE.json:9).

Runs on the virtual 8-device CPU mesh (conftest) with a tiny ResNet so the
sharded train-step path — dp batch split + fsdp param shard + BN stat
mutation — is exercised exactly as the flagship runs it on a pod.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tensorflowonspark_tpu.models import resnet
from tensorflowonspark_tpu.parallel import dp as dplib
from tensorflowonspark_tpu.parallel import mesh as meshlib


def tiny_resnet():
    return resnet.ResNet(stage_sizes=(1, 1, 1, 1), num_classes=8, width=8,
                         compute_dtype=jnp.float32)


def make_state(model, mesh, optimizer):
    # jit the init: one (persistently cached) XLA program instead of
    # hundreds of eager per-op compiles — 1-core-box wall-clock hygiene
    variables = jax.jit(lambda k: model.init(
        k, jnp.zeros((1, 32, 32, 3), jnp.float32), train=True))(jax.random.PRNGKey(0))
    params = meshlib.shard_tree(mesh, variables["params"])
    batch_stats = meshlib.shard_tree(
        mesh, variables["batch_stats"],
        jax.tree.map(lambda _: meshlib.replicated(mesh), variables["batch_stats"]))
    return dplib.BNTrainState.create(params, batch_stats, optimizer)


def make_batch(mesh, n=16, num_classes=8, seed=0):
    rng = np.random.RandomState(seed)
    return meshlib.shard_batch(mesh, {
        "image": rng.rand(n, 32, 32, 3).astype(np.float32),
        "label": (np.arange(n) % num_classes).astype(np.int32),
    })


def test_forward_shapes():
    model = tiny_resnet()
    variables = jax.jit(lambda k: model.init(
        k, jnp.zeros((1, 32, 32, 3), jnp.float32), train=True))(jax.random.PRNGKey(0))
    logits = jax.jit(lambda v, x: model.apply(v, x, train=False))(
        variables, jnp.zeros((4, 32, 32, 3)))
    assert logits.shape == (4, 8)
    assert logits.dtype == jnp.float32


def test_resnet50_registry_builds():
    from tensorflowonspark_tpu.models.registry import build

    model = build({"model": "resnet50", "num_classes": 10})
    assert model.stage_sizes == (3, 4, 6, 3)
    assert model.num_classes == 10


def test_train_step_descends_loss_fsdp_mesh():
    mesh = meshlib.make_mesh(dp=-1, fsdp=2)
    model = tiny_resnet()
    optimizer = optax.sgd(0.05, momentum=0.9)
    state = make_state(model, mesh, optimizer)
    step_fn = dplib.make_bn_train_step(resnet.make_loss_fn(model, weight_decay=0.0),
                                       optimizer)
    batch = make_batch(mesh)
    losses = []
    for _ in range(5):
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
    assert int(jax.device_get(state.step)) == 5


def test_batch_stats_update():
    mesh = meshlib.make_mesh(dp=-1)
    model = tiny_resnet()
    optimizer = optax.sgd(0.05)
    state = make_state(model, mesh, optimizer)
    before = jax.device_get(state.batch_stats)
    step_fn = dplib.make_bn_train_step(resnet.make_loss_fn(model, weight_decay=0.0),
                                       optimizer)
    state, _ = step_fn(state, make_batch(mesh))
    after = jax.device_get(state.batch_stats)
    diffs = jax.tree.map(lambda a, b: float(np.abs(a - b).max()), before, after)
    assert max(jax.tree.leaves(diffs)) > 0.0


def test_fsdp_shardings_split_largest_divisible_dim():
    mesh = meshlib.make_mesh(dp=-1, fsdp=2)
    tree = {"kernel": jnp.zeros((6, 8)), "bias": jnp.zeros((3,)), "scalar": jnp.zeros(())}
    shardings = meshlib.fsdp_shardings(mesh, tree)
    assert shardings["kernel"].spec == jax.sharding.PartitionSpec(None, "fsdp")
    # bias dim 3 is not divisible by 2 -> replicated
    assert shardings["bias"].spec == jax.sharding.PartitionSpec()
    assert shardings["scalar"].spec == jax.sharding.PartitionSpec()


@pytest.mark.dryrun
@pytest.mark.slow
def test_graft_entry_dryrun():
    """The driver's multichip gate runs this same entry point directly every
    round — the ONE test whose coverage is independently re-executed outside
    the suite.  Opt-in (`-m dryrun`, ~90s: six full SPMD train-step compiles)
    so the default gate can afford to include every other slow test.  Also
    marked ``slow``: a bare ``-m 'not slow'`` on the command line REPLACES the
    addopts marker filter, and this duplicate of the driver's own gate should
    not ride back in through that door."""
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)


def test_graft_entry_forward_tiny():
    """entry() builds the real ResNet-50; too big for CPU CI — check the
    callable contract on a tiny clone instead."""
    model = tiny_resnet()
    variables = jax.jit(lambda k: model.init(
        k, jnp.zeros((1, 32, 32, 3), jnp.float32), train=True))(jax.random.PRNGKey(0))

    def forward(params, batch_stats, images):
        return model.apply({"params": params, "batch_stats": batch_stats},
                           images, train=False)

    out = jax.jit(forward)(variables["params"], variables["batch_stats"],
                           jnp.zeros((2, 32, 32, 3)))
    assert out.shape == (2, 8)
