"""Device-mesh construction and sharding helpers.

The reference handed out ``CUDA_VISIBLE_DEVICES`` strings (``gpu_info.py``)
and wired nodes via ``TF_CONFIG`` (``TFSparkNode.py:~260-300``).  The TPU
equivalent of "cluster wiring" is a named ``jax.sharding.Mesh``: SPMD
programs annotate shardings over its axes and XLA inserts the collectives
(all-reduce over ICI for data-parallel gradients, etc.).

Axis convention (SURVEY.md §2.3 disposition column):
- ``dp``   — data parallelism (the reference's only strategy, now sync SPMD);
- ``fsdp`` — parameter-sharded data parallelism (zero-style);
- ``tp``   — tensor/model parallelism (reference: absent; first-class here);
- ``sp``   — sequence/context parallelism for long-context (ring attention);
- ``ep``   — expert parallelism;
- ``pp``   — pipeline parallelism.
Unused axes default to size 1 so one mesh shape serves every model family.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXES = ("dp", "fsdp", "tp", "sp", "ep", "pp")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """A named mesh layout; axes omitted at construction default to 1."""

    dp: int = 1
    fsdp: int = 1
    tp: int = 1
    sp: int = 1
    ep: int = 1
    pp: int = 1

    @property
    def size(self) -> int:
        return math.prod(self.axis_sizes())

    def axis_sizes(self) -> tuple[int, ...]:
        return tuple(getattr(self, a) for a in AXES)


def make_mesh(devices: Sequence[jax.Device] | None = None, **axis_sizes: int) -> Mesh:
    """Build a Mesh with the standard axis names.

    Any axis given as ``-1`` absorbs the remaining devices (like a reshape
    wildcard).  With no axes at all, everything lands on ``dp``.

    On real hardware, ``jax.devices()`` order already reflects ICI topology
    (jax returns devices in a topology-aware order); axis order places the
    innermost axes (``pp`` last) on the nearest neighbours, so put the
    bandwidth-hungry axis (``tp``/``sp``) after ``dp``/``fsdp`` as this
    layout does.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    sizes = {a: int(axis_sizes.get(a, 1)) for a in AXES}
    unknown = set(axis_sizes) - set(AXES)
    if unknown:
        raise ValueError(f"unknown mesh axes {sorted(unknown)}; valid: {AXES}")
    wilds = [a for a, s in sizes.items() if s == -1]
    if len(wilds) > 1:
        raise ValueError("at most one axis may be -1")
    fixed = math.prod(s for s in sizes.values() if s != -1)
    if wilds:
        if n % fixed:
            raise ValueError(f"{n} devices not divisible by fixed axes product {fixed}")
        sizes[wilds[0]] = n // fixed
    elif not axis_sizes:
        sizes["dp"] = n
    elif fixed != n:
        raise ValueError(f"mesh axes product {fixed} != device count {n}")
    arr = np.array(devices).reshape([sizes[a] for a in AXES])
    return Mesh(arr, AXES)


def spec(mesh: Mesh) -> MeshSpec:
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    return MeshSpec(**{a: shape.get(a, 1) for a in AXES})


def batch_sharding(mesh: Mesh, extra_dims: int = 0) -> NamedSharding:
    """Sharding for a batch: leading dim split over (dp, fsdp), rest replicated."""
    return NamedSharding(mesh, P(("dp", "fsdp"), *([None] * extra_dims)))


def batch_shards(mesh: Mesh) -> tuple[int, list[list[jax.Device]]]:
    """Who holds which rows of a batch under ``batch_sharding``.

    Returns the number of row shards of the GLOBAL batch (``dp × fsdp``) and,
    for every shard this process holds, in row order, the addressable devices
    that hold it: the devices along ``tp``/``sp``/``ep``/``pp`` share one.
    Shard ``j`` of the list is rows ``[j·r, (j+1)·r)`` of a process-local
    batch of ``r × len(list)`` rows.
    """
    axes = spec(mesh)
    total = axes.dp * axes.fsdp
    owners: dict[int, list[jax.Device]] = {}
    index_map = batch_sharding(mesh).addressable_devices_indices_map((total,))
    for device, (rows,) in index_map.items():
        owners.setdefault(rows.indices(total)[0], []).append(device)
    return total, [owners[k] for k in sorted(owners)]


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def fsdp_shardings(mesh: Mesh, tree):
    """Per-leaf NamedShardings sharding params over the ``fsdp`` axis (ZeRO-3
    style: each leaf is split on its largest fsdp-divisible dimension; XLA
    inserts the all-gather before use and the reduce-scatter on gradients).

    Leaves too small to split (or with no divisible dim) stay replicated —
    that is the correct GSPMD idiom, not a fallback: tiny biases/BN scales
    cost nothing to replicate and sharding them would only add latency.
    """
    axis_size = dict(zip(mesh.axis_names, mesh.devices.shape)).get("fsdp", 1)

    def leaf_sharding(x) -> NamedSharding:
        if axis_size == 1 or not hasattr(x, "shape") or x.ndim == 0:
            return replicated(mesh)
        d = pick_shard_dim(x.shape, axis_size)
        if d is None:
            return replicated(mesh)
        pspec = [None] * x.ndim
        pspec[d] = "fsdp"
        return NamedSharding(mesh, P(*pspec))

    return jax.tree.map(leaf_sharding, tree)


def pick_shard_dim(shape, axis_size: int, taken=()) -> int | None:
    """Largest dim divisible by ``axis_size`` (skipping ``taken`` dims), or
    None if nothing splits evenly — the shared heuristic behind fsdp
    sharding here and ``tp.compose_fsdp``."""
    dims = sorted(range(len(shape)), key=lambda d: shape[d], reverse=True)
    for d in dims:
        if d in taken:
            continue
        if shape[d] % axis_size == 0 and shape[d] >= axis_size:
            return d
    return None


def is_multiprocess(mesh: Mesh) -> bool:
    """True when the mesh spans devices owned by other processes.

    This is the multi-host SPMD case (``jax.distributed`` initialized, one
    controller per host): ``jax.device_put`` cannot target non-addressable
    devices, so array placement must go through the process-local assembly
    APIs instead (see ``shard_batch``/``shard_tree``).
    """
    if jax.process_count() == 1:
        return False
    pid = jax.process_index()
    return any(d.process_index != pid for d in mesh.devices.flat)


def shard_tree(mesh: Mesh, tree, shardings=None):
    """Place a pytree on the mesh under the given (or fsdp-derived) shardings.

    Stages through host memory for the same donation-safety reason as
    ``dp.replicate`` (fresh buffers; sources may live on any device subset).

    Multi-process meshes: every process must hold the same full host value
    (the usual case — params from a shared init seed or a restored
    checkpoint); each process materializes only its addressable shards via
    ``jax.make_array_from_callback``.
    """
    shardings = shardings if shardings is not None else fsdp_shardings(mesh, tree)
    if is_multiprocess(mesh):
        def put_global(x, s):
            x = np.asarray(x)
            return jax.make_array_from_callback(x.shape, s, lambda idx: x[idx])
        return jax.tree.map(put_global, tree, shardings)
    return jax.tree.map(
        lambda x, s: jax.device_put(np.asarray(x), s), tree, shardings
    )


def shard_batch(mesh: Mesh, batch):
    """Place a host batch onto the mesh, sharded along the leading axis.

    Single process: a plain ``device_put`` split over ``(dp, fsdp)``.

    Multi-process (``jax.distributed``): each host holds a DISJOINT local
    batch (its own streamed partitions — reference ``InputMode.SPARK`` feed
    closures, ``TFSparkNode.py:~430-510``); the global batch is their
    concatenation in process order, assembled without any cross-host copy by
    ``jax.make_array_from_process_local_data``.  The global leading dim is
    ``local_batch × (processes spanning the batch axes)``, so the jitted SPMD
    step sees one global batch while each host only ever touches its own
    rows.
    """
    if is_multiprocess(mesh):
        def put_local(x):
            x = np.asarray(x)
            return jax.make_array_from_process_local_data(
                batch_sharding(mesh, extra_dims=x.ndim - 1), x)
        return jax.tree.map(put_local, batch)
    return jax.tree.map(
        lambda x: jax.device_put(x, batch_sharding(mesh, extra_dims=x.ndim - 1)),
        batch,
    )
