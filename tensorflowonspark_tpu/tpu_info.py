"""TPU chip discovery and topology assignment — the ``gpu_info`` replacement.

Reference (``tensorflowonspark/gpu_info.py``): parse ``nvidia-smi``, pick
free GPUs with randomized retries to dodge allocation races between
executors sharing a host, export ``CUDA_VISIBLE_DEVICES``.

TPU-native redesign (SURVEY.md §2.2 row "Hops-YARN GPU scheduling", §5.2):
TPU chips are per-host hardware, not a shared pool to race over, and the
platform already knows its own topology.  So this module:

- **discovers** what this process can see (``device_summary`` — platform,
  chip kind, count, per-chip mesh coordinates from PJRT) for the node's
  coordinator registration payload;
- **assigns** race-free: ``plan_topology`` computes each host's process
  index and chip-coordinate block centrally (the coordinator calls it once,
  replacing gpu_info's randomized retries with deterministic assignment);
- **scopes visibility** for subprocesses: ``chip_visibility_env`` returns
  the env (``TPU_VISIBLE_CHIPS``/``TPU_PROCESS_BOUNDS``-style, or
  ``JAX_PLATFORMS``/``XLA_FLAGS`` for CPU simulation) that makes a child
  process see only its slice — the ``CUDA_VISIBLE_DEVICES`` analogue.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence


def is_tpu_available() -> bool:
    """Reference parity: ``gpu_info.is_gpu_available()``.

    Initialises the backend (and so claims the chips for this process); a
    backend that cannot initialise raises — it is not reported as "no TPU".
    """
    import jax

    return any(d.platform == "tpu" for d in jax.devices())


def _forced_cpu_device_count() -> int:
    """CPU device count jax will create, from env alone.

    ``JAX_NUM_CPU_DEVICES`` wins (it is what ``chip_visibility_env`` emits
    per node and overrides the flag inside jax); else the conftest-style
    ``--xla_force_host_platform_device_count`` in XLA_FLAGS; else 1."""
    import os
    import re

    n = os.environ.get("JAX_NUM_CPU_DEVICES")
    if n:
        return int(n)
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)",
                  os.environ.get("XLA_FLAGS", ""))
    return int(m.group(1)) if m else 1


# What a node that owns no accelerator reports: the evaluator sidecar and the
# ingest workers never compute, so the node runtime never initialises a
# backend for them — on a TPU host that would take the chips from the trainer.
NO_DEVICES = {"platform": "none", "device_kind": "none", "num_devices": 0,
              "coords": [], "process_index": 0}
# What a node registers with when the environment does not pin its devices:
# it will claim its accelerator (or learn it needs none) once it knows its
# role.  The coordinator tolerates heartbeat silence while this stands —
# backend initialisation keeps the interpreter lock.
CLAIM_PENDING = {"platform": "pending"}


def env_device_summary() -> dict | None:
    """The device summary read from the environment alone, or None when the
    environment does not pin it down.

    Env-forced CPU platform and jax not loaded yet: the env already states
    exactly what the backend would report, so synthesize it instead of paying
    a ~3s jax import + backend init in every node process (control-plane-only
    nodes and every CPU test node never need the backend).  Never touches a
    backend, so it is safe before a node knows its role."""
    import os
    import sys

    if "jax" not in sys.modules and os.environ.get(
            "JAX_PLATFORMS", "").split(",")[0] == "cpu":
        return {
            "platform": "cpu",
            "device_kind": "cpu",
            "num_devices": _forced_cpu_device_count(),
            "coords": [],
            "process_index": 0,
        }
    return None


def device_summary() -> dict:
    """What this process sees; goes into the node's coordinator metadata so
    the driver's ``cluster_info`` reports real hardware per node.

    Unless the environment pins the answer (:func:`env_device_summary`) this
    initialises the backend — which CLAIMS the chips for this process — and a
    backend that cannot initialise raises instead of being reported as
    ``platform: none``: a node that was meant to compute must not carry on
    into ``map_fun`` without its accelerator."""
    summary = env_device_summary()
    if summary is not None:
        return summary
    import sys

    from tensorflowonspark_tpu import telemetry
    from tensorflowonspark_tpu.telemetry import xla_events

    # Two lifecycle stages (README "Observability"): the import alone, then
    # the backend's initialisation = the chip claim.  ``preloaded``: the
    # map_fun's module (or jax.distributed.initialize) already imported jax,
    # so the import reads 0 here and its time is in ``node.spawn``.
    with telemetry.lifecycle("node.import_jax",
                             preloaded="jax" in sys.modules):
        import jax
    xla_events.install()
    # local_devices/process_index, NOT jax.devices(): after
    # jax.distributed.initialize the latter is pod-global, and every node
    # would report the whole pod's chips instead of its own.
    with telemetry.lifecycle("node.claim"):
        devices = jax.local_devices()
        platform = jax.default_backend()
    return {
        "platform": platform,
        "device_kind": devices[0].device_kind if devices else "none",
        "num_devices": len(devices),
        "coords": [list(getattr(d, "coords", ()) or ()) for d in devices],
        "process_index": jax.process_index(),
    }


@dataclasses.dataclass(frozen=True)
class HostAssignment:
    """One host's slot in the pod: its process id and global chip slice."""

    executor_id: int
    process_id: int
    chip_start: int      # first global chip index owned by this host
    num_chips: int

    @property
    def chip_ids(self) -> tuple[int, ...]:
        return tuple(range(self.chip_start, self.chip_start + self.num_chips))


def plan_topology(chip_counts: Sequence[int]) -> list[HostAssignment]:
    """Deterministic global chip numbering from per-host chip counts.

    Called centrally (driver/coordinator) with each registered node's
    ``device_summary()["num_devices"]``, in executor-id order.  No retries,
    no races — the reference's gpu_info randomized-pick loop is replaced by
    one authoritative assignment (SURVEY.md §5.2 disposition).
    """
    out = []
    start = 0
    for i, n in enumerate(chip_counts):
        out.append(HostAssignment(executor_id=i, process_id=i,
                                  chip_start=start, num_chips=int(n)))
        start += int(n)
    return out


def total_chips(assignments: Sequence[HostAssignment]) -> int:
    return sum(a.num_chips for a in assignments)


def default_mesh_axes(n_chips: int, *, model_parallel: int = 1) -> dict:
    """Recommended mesh axis sizes for a chip count: everything on ``dp``
    except an optional ``tp`` factor (must divide the chip count)."""
    if n_chips % model_parallel:
        raise ValueError(f"model_parallel {model_parallel} does not divide "
                         f"chip count {n_chips}")
    return {"dp": n_chips // model_parallel, "tp": model_parallel}


def chip_visibility_env(chip_ids: Sequence[int], *, platform: str = "tpu",
                        simulate_chips: int | None = None,
                        bounds: str | None = None) -> dict[str, str]:
    """Env for a child process that must see only ``chip_ids``.

    On TPU hosts this is the ``CUDA_VISIBLE_DEVICES`` analogue
    (``TPU_VISIBLE_CHIPS`` plus single-process bounds, the libtpu
    convention for carving a host's chips between processes).  With
    ``platform='cpu'`` it returns the virtual-device simulation env used by
    tests and the multi-process local launcher.

    ``bounds`` overrides ``TPU_CHIPS_PER_PROCESS_BOUNDS`` ("x,y,z").  Pass it
    whenever real host topology is known (e.g. derived from discovered device
    coords — v2/v3 hosts are ``2,2,1``); without it the value is a
    *best-effort guess* (square grid, else ``1,n,1``) which libtpu may reject
    or mis-map on hosts whose physical layout differs.
    """
    if platform == "cpu":
        n = simulate_chips if simulate_chips is not None else len(chip_ids)
        return {
            "JAX_PLATFORMS": "cpu",
            # Both spellings: JAX_NUM_CPU_DEVICES is the authoritative config
            # knob; the flag form covers older JAX versions that only read
            # XLA_FLAGS.
            "JAX_NUM_CPU_DEVICES": str(max(1, n)),
            "XLA_FLAGS": f"--xla_force_host_platform_device_count={max(1, n)}",
            # Cross-process CPU collectives (the ICI/DCN simulation for
            # multi-process jax.distributed runs): gloo is the only portable
            # in-tree implementation.  Harmless for single-process use.
            "JAX_CPU_COLLECTIVES_IMPLEMENTATION": "gloo",
        }
    ids = ",".join(str(int(c)) for c in chip_ids)
    n = len(chip_ids)
    if bounds is None:
        side = max(1, int(math.isqrt(n)))
        if side * side != n:
            side = 1  # non-square slice: 1 x n bounds
        bounds = f"{side},{n // side},1"
    return {
        "TPU_VISIBLE_CHIPS": ids,
        "TPU_CHIPS_PER_PROCESS_BOUNDS": bounds,
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
    }


def bounds_from_coords(coords: Sequence[Sequence[int]]) -> str | None:
    """Derive ``TPU_CHIPS_PER_PROCESS_BOUNDS`` from discovered device coords
    (``device_summary()["coords"]``).

    Returns None when coords are unavailable, malformed, or do not form a
    dense axis-aligned box (a non-contiguous chip selection has no valid
    bounds string — the span's volume would disagree with the chip count and
    libtpu would mis-map).
    """
    if not coords:
        return None
    pts = {tuple(int(x) for x in c) for c in coords}
    if len(pts) != len(list(coords)) or any(len(p) != 3 for p in pts):
        return None
    lo = [min(p[i] for p in pts) for i in range(3)]
    hi = [max(p[i] for p in pts) for i in range(3)]
    span = [hi[i] - lo[i] + 1 for i in range(3)]
    if span[0] * span[1] * span[2] != len(pts):
        return None  # holes: the selection is not a dense box
    return ",".join(str(s) for s in span)
