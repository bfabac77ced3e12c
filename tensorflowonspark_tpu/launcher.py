"""Process launchers — the Spark-role replacement for process placement.

In the reference, Spark places one long-running task per executor
(``sc.parallelize(...).foreachPartition(TFSparkNode.run(...))``,
``TFCluster.py:~340-360``) and YARN/Hops provisions the hosts.  Here a
launcher backend owns process placement (SURVEY.md §7.1-4):

- ``LocalLauncher`` — N node processes on this machine via multiprocessing
  (the test/dev path, mirroring the reference's ``local-cluster[N,...]``
  test trick, SURVEY.md §4).
- ``SubprocessLauncher`` — N node processes as fresh OS subprocesses, each
  with its own environment.  Required for per-process accelerator
  visibility (``TPU_VISIBLE_CHIPS`` / ``JAX_NUM_CPU_DEVICES``) and for
  ``jax.distributed`` runs, where env must be in place *before* the child
  interpreter starts.
- ``TPUPodLauncher`` — placement across the hosts of a TPU pod slice; one
  node process per TPU-VM host, spawned over a pluggable transport
  (default: ``ssh``; ``transport='local'`` runs every "host" on this
  machine for single-box pods and tests).  Composes
  ``tpu_info.chip_visibility_env`` + ``bounds_from_coords`` so each
  process sees exactly its chip slice.

All launchers expose the same surface consumed by ``cluster.TPUCluster``:
``launch(configs, log_dir)``, ``processes`` (handles with ``.exitcode``),
``join(timeout)``, ``alive()``, ``terminate()``.
"""

from __future__ import annotations

import multiprocessing as mp
import os
import shlex
import socket
import subprocess
import sys
import time
from typing import Callable, Sequence

import cloudpickle

from tensorflowonspark_tpu.node import NodeConfig


def _child_entry(payload: bytes, log_path: str | None) -> None:
    """Module-level child target (picklable under the 'spawn' start method)."""
    if log_path:
        f = open(log_path, "a", buffering=1)
        os.dup2(f.fileno(), sys.stdout.fileno())
        os.dup2(f.fileno(), sys.stderr.fileno())
    config: NodeConfig = cloudpickle.loads(payload)
    from tensorflowonspark_tpu.node import node_main

    sys.exit(node_main(config))


def _stamped_payload(config: NodeConfig) -> bytes:
    """The pickled config, stamped with this spawn's epoch time and host:
    the start of the node's lifecycle stage ``node.spawn``."""
    config.spawned = (time.time(), socket.gethostname())
    return cloudpickle.dumps(config)


class _RespawnMixin:
    """Shared supervised-restart scaffolding: launch-time config capture and
    the reap-then-respawn of one slot.  Subclasses provide ``_spawn_one`` and
    a ``self._procs`` list of handles exposing
    ``is_alive/terminate/kill/join``."""

    def _remember_launch(self, configs: Sequence["NodeConfig"],
                         log_dir: str | None) -> None:
        self._configs = list(configs)
        self._log_dir = log_dir

    @property
    def configs(self) -> list["NodeConfig"]:
        """The per-slot NodeConfigs of the most recent launch()."""
        return list(self._configs)

    def respawn(self, index: int, config: "NodeConfig | None" = None) -> None:
        """Replace the process at ``index`` with a fresh one (supervised
        restart path).  Reaps the predecessor FIRST — terminate, then kill —
        so a zombie (alive but fenced) can never share the slot's ports or
        accelerators with its replacement; the old handle (and its exit
        code) is dropped, keeping shutdown's exit-code audit about the
        processes that finished the job."""
        old = self._procs[index]
        if old.is_alive():
            old.terminate()
            old.join(5.0)
            if old.is_alive():
                old.kill()
        old.join(5.0)
        self._procs[index] = self._spawn_one(index, config or self._configs[index])

    def spawn_more(self, configs: Sequence["NodeConfig"]) -> None:
        """Append fresh node processes to a LIVE launch (cluster.resize
        scale-out): each config's ``launch_index`` must equal its position
        in the extended process list — the registration-time key the driver
        uses to map executor ids back to process handles."""
        for offset, config in enumerate(configs):
            expect = len(self._procs) + offset
            if config.launch_index != expect:
                raise ValueError(
                    f"spawn_more config at position {offset} has "
                    f"launch_index {config.launch_index}, expected {expect}")
        for config in configs:
            self._configs.append(config)
            try:
                self._procs.append(self._spawn_one(config.launch_index, config))
            except Exception:
                # keep _configs and _procs the same length: a later
                # spawn_more validates launch_index against len(_procs),
                # and a dangling config would desynchronize them for good
                self._configs.pop()
                raise


class LocalLauncher(_RespawnMixin):
    """Spawn node processes on the local host.

    Uses the 'spawn' start method: forking a process after JAX/XLA has
    initialized in the driver is unsafe, and spawn matches how real TPU-VM
    hosts start fresh Python processes.  ``map_fun`` travels via cloudpickle
    (the same closure-shipping contract Spark gave the reference).

    Env caveat: ``config.env`` is applied inside ``node_main`` — after the
    child interpreter started and, because spawn re-imports the driver's
    ``__main__``, possibly after jax was imported (``node.
    _apply_jax_env_config`` repairs the JAX config vars).  Vars a native
    library reads when it loads (``TPU_VISIBLE_CHIPS``) need
    ``SubprocessLauncher``.
    """

    def __init__(self, env: dict[str, str] | None = None):
        self.env = dict(env or {})
        self._procs: list[mp.Process] = []
        self._configs: list[NodeConfig] = []
        self._log_dir: str | None = None

    def launch(self, configs: Sequence[NodeConfig], log_dir: str | None = None) -> None:
        # Re-launchable: a fresh cluster must not inherit handles of a
        # previous run (launch_index -> process mapping relies on positions
        # matching THIS launch's configs).  Leftovers still alive — e.g. a
        # prior run that raised before shutdown — are terminated, not
        # silently orphaned holding ports/accelerators.
        if any(p.is_alive() for p in self._procs):
            self.terminate()
        self._procs = []
        self._remember_launch(configs, log_dir)
        for i, config in enumerate(configs):
            config.env = {**self.env, **config.env}
            self._procs.append(self._spawn_one(i, config))

    def _spawn_one(self, i: int, config: NodeConfig) -> mp.Process:
        ctx = mp.get_context("spawn")
        log_path = os.path.join(self._log_dir, f"node_{i}.log") if self._log_dir else None
        payload = _stamped_payload(config)
        p = ctx.Process(target=_child_entry, args=(payload, log_path), name=f"tpu-node-{i}")
        p.daemon = False
        p.start()
        return p

    @property
    def processes(self) -> list[mp.Process]:
        return list(self._procs)

    def join(self, timeout: float | None = None) -> bool:
        """Join all node processes; True if all exited within the timeout."""
        deadline = None if timeout is None else time.monotonic() + timeout
        for p in self._procs:
            remaining = None if deadline is None else max(0.0, deadline - time.monotonic())
            p.join(remaining)
        return all(p.exitcode is not None for p in self._procs)

    def alive(self) -> list[int]:
        return [i for i, p in enumerate(self._procs) if p.is_alive()]

    def terminate(self) -> None:
        for p in self._procs:
            if p.is_alive():
                p.terminate()
        for p in self._procs:
            p.join(5.0)
            if p.is_alive():
                p.kill()


class PopenHandle:
    """Adapt ``subprocess.Popen`` to the ``mp.Process``-ish handle surface
    (``exitcode``/``is_alive``/``join``/``terminate``/``kill``) that
    ``TPUCluster.shutdown`` consumes."""

    def __init__(self, proc: subprocess.Popen):
        self.proc = proc

    @property
    def pid(self) -> int:
        return self.proc.pid

    @property
    def exitcode(self) -> int | None:
        return self.proc.poll()

    def is_alive(self) -> bool:
        return self.proc.poll() is None

    def join(self, timeout: float | None = None) -> None:
        try:
            self.proc.wait(timeout)
        except subprocess.TimeoutExpired:  # toslint: allow-silent(mp.Process.join contract: a timed-out join returns with the process still alive)
            pass

    def terminate(self) -> None:
        if self.is_alive():
            self.proc.terminate()

    def kill(self) -> None:
        if self.is_alive():
            self.proc.kill()


def _node_command() -> list[str]:
    """The command line that runs one node from a stdin payload.

    ``node_entry`` is a dedicated module NOT imported by the package
    ``__init__`` — running ``-m`` on a module that is also imported as a
    package attribute would execute it twice as two distinct module objects
    (runpy's 'found in sys.modules' hazard)."""
    return [sys.executable, "-m", "tensorflowonspark_tpu.node_entry"]


def _pythonpath_env() -> dict[str, str]:
    """PYTHONPATH that reproduces the driver's ``sys.path`` in a fresh local
    interpreter, so cloudpickled map_funs resolve their defining modules
    (and this package itself imports from a source checkout).  The same
    contract Spark gave the reference by shipping the driver's PYTHONPATH /
    egg to executors; ``multiprocessing`` spawn does it implicitly for
    ``LocalLauncher``."""
    entries = [p for p in sys.path if p and os.path.isdir(p)]
    pkg_parent = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if pkg_parent not in entries:
        entries.append(pkg_parent)
    return {"PYTHONPATH": os.pathsep.join(entries)}


class SubprocessLauncher(_RespawnMixin):
    """Spawn node processes as fresh OS subprocesses with per-node env.

    Each child runs ``python -m tensorflowonspark_tpu.node_entry`` and reads
    its cloudpickled ``NodeConfig`` from stdin.  ``config.env`` is merged
    into the *OS-level* environment of the child, so load-time consumers
    (libtpu chip visibility, jax's import-time config snapshot) see it —
    the property ``LocalLauncher`` cannot provide.
    """

    def __init__(self, env: dict[str, str] | None = None):
        self.env = dict(env or {})
        self._procs: list[PopenHandle] = []
        self._configs: list[NodeConfig] = []
        self._log_dir: str | None = None

    def launch(self, configs: Sequence[NodeConfig], log_dir: str | None = None) -> None:
        if any(p.is_alive() for p in self._procs):
            self.terminate()  # re-launchable (see LocalLauncher.launch)
        self._procs = []
        self._remember_launch(configs, log_dir)
        for i, config in enumerate(configs):
            config.env = {**self.env, **config.env}
            self._procs.append(self._spawn_one(i, config))

    def _spawn_one(self, i: int, config: NodeConfig) -> PopenHandle:
        child_env = {**os.environ, **_pythonpath_env(), **config.env}
        if self._log_dir:
            log_f = open(os.path.join(self._log_dir, f"node_{i}.log"), "ab", buffering=0)
        else:
            log_f = None
        payload = _stamped_payload(config)
        proc = subprocess.Popen(
            _node_command(),
            stdin=subprocess.PIPE,
            stdout=log_f if log_f else None,
            stderr=subprocess.STDOUT if log_f else None,
            env=child_env,
        )
        proc.stdin.write(payload)
        proc.stdin.close()
        if log_f is not None:
            log_f.close()  # child holds its own fd now
        return PopenHandle(proc)

    @property
    def processes(self) -> list[PopenHandle]:
        return list(self._procs)

    def join(self, timeout: float | None = None) -> bool:
        deadline = None if timeout is None else time.monotonic() + timeout
        for p in self._procs:
            remaining = None if deadline is None else max(0.0, deadline - time.monotonic())
            p.join(remaining)
        return all(p.exitcode is not None for p in self._procs)

    def alive(self) -> list[int]:
        return [i for i, p in enumerate(self._procs) if p.is_alive()]

    def terminate(self) -> None:
        for p in self._procs:
            p.terminate()
        for p in self._procs:
            p.join(5.0)
            if p.is_alive():
                p.kill()


class TPUPodLauncher(_RespawnMixin):
    """Placement across the hosts of a TPU pod slice.

    One node process per TPU-VM host; each process sees that host's chips
    (or an explicit slice of them) and joins the global mesh via
    ``jax.distributed`` (``NodeConfig.jax_distributed=True`` is forced).

    Transports:
    - ``'ssh'`` (default): ``ssh <host> env K=V... python -m
      tensorflowonspark_tpu.launcher`` with the pickled config streamed over
      stdin.  Requires passwordless ssh and the package importable on the
      remote host — the TPU-VM idiom (reference parity:
      ``TFCluster.py:~340-360`` used Spark's executor placement instead).
    - ``'local'``: every "host" is this machine; used for single-host
      multi-process pods and for tests.
    - a callable ``transport(host, command, env) -> subprocess.Popen`` for
      custom fabrics (GKE exec, tpu-vm ssh wrappers, ...).

    ``chip_slices`` optionally gives each host's chip ids (e.g. two
    processes splitting one host's 4 chips: ``[[0, 1], [2, 3]]``); the env
    is then derived via ``tpu_info.chip_visibility_env``, with process
    bounds from ``tpu_info.bounds_from_coords`` when ``chip_coords`` (the
    discovered per-chip mesh coordinates) is supplied.  Without slices,
    each process sees everything its host exposes — the common whole-host
    pod layout.
    """

    def __init__(
        self,
        hosts: Sequence[str],
        transport: str | Callable = "ssh",
        env: dict[str, str] | None = None,
        chip_slices: Sequence[Sequence[int]] | None = None,
        chip_coords: Sequence[Sequence[Sequence[int]]] | None = None,
        platform: str = "tpu",
        simulate_chips: int | None = None,
    ):
        if chip_slices is not None and len(chip_slices) != len(hosts):
            raise ValueError("chip_slices must have one entry per host")
        self.hosts = list(hosts)
        self.transport = transport
        self.env = dict(env or {})
        self.chip_slices = [list(s) for s in chip_slices] if chip_slices else None
        self.chip_coords = chip_coords
        self.platform = platform
        self.simulate_chips = simulate_chips
        self._procs: list[PopenHandle] = []
        self._configs: list[NodeConfig] = []
        self._log_dir: str | None = None

    # -- env composition -----------------------------------------------------

    def host_env(self, index: int) -> dict[str, str]:
        """The accelerator-visibility env for host ``index``."""
        from tensorflowonspark_tpu import tpu_info

        env = dict(self.env)
        if self.chip_slices is not None:
            bounds = None
            if self.chip_coords is not None:
                bounds = tpu_info.bounds_from_coords(self.chip_coords[index])
            env.update(tpu_info.chip_visibility_env(
                self.chip_slices[index], platform=self.platform,
                simulate_chips=self.simulate_chips, bounds=bounds))
        elif self.platform == "cpu":
            env.update(tpu_info.chip_visibility_env(
                (), platform="cpu", simulate_chips=self.simulate_chips))
        return env

    # -- spawning ------------------------------------------------------------

    def _spawn(self, host: str, env: dict[str, str], payload: bytes,
               log_f) -> PopenHandle:
        command = _node_command()
        if callable(self.transport):
            proc = self.transport(host, command, env)
        elif self.transport == "local":
            proc = subprocess.Popen(
                command, stdin=subprocess.PIPE,
                stdout=log_f if log_f else None,
                stderr=subprocess.STDOUT if log_f else None,
                env={**os.environ, **_pythonpath_env(), **env})
        elif self.transport == "ssh":
            # ssh joins argv into ONE remote shell line, so every env value
            # and command token must be shell-quoted (XLA_FLAGS routinely
            # holds spaces; unquoted values would also be an injection hole).
            env_prefix = ["env"] + [
                shlex.quote(f"{k}={v}") for k, v in sorted(env.items())]
            remote = env_prefix + [shlex.quote(c) for c in command]
            proc = subprocess.Popen(
                ["ssh", "-o", "BatchMode=yes", host] + remote,
                stdin=subprocess.PIPE,
                stdout=log_f if log_f else None,
                stderr=subprocess.STDOUT if log_f else None)
        else:
            raise ValueError(f"unknown transport {self.transport!r}")
        proc.stdin.write(payload)
        proc.stdin.close()
        return PopenHandle(proc)

    def launch(self, configs: Sequence[NodeConfig], log_dir: str | None = None) -> None:
        if len(configs) != len(self.hosts):
            raise ValueError(
                f"pod launcher got {len(configs)} configs for {len(self.hosts)} hosts")
        if any(p.is_alive() for p in self._procs):
            self.terminate()  # re-launchable (see LocalLauncher.launch)
        self._procs = []
        self._remember_launch(configs, log_dir)
        for i, (host, config) in enumerate(zip(self.hosts, configs)):
            config.jax_distributed = True  # a pod IS a jax.distributed job
            config.env = {**self.host_env(i), **config.env}
            self._procs.append(self._spawn_one(i, config))

    def _spawn_one(self, i: int, config: NodeConfig) -> PopenHandle:
        log_f = None
        if self._log_dir:
            log_f = open(os.path.join(self._log_dir, f"node_{i}.log"), "ab", buffering=0)
        payload = _stamped_payload(config)
        try:
            return self._spawn(self.hosts[i], config.env, payload, log_f)
        finally:
            if log_f is not None:
                log_f.close()

    def respawn(self, index: int, config: NodeConfig | None = None) -> None:
        """A pod is one ``jax.distributed`` job — a restarted process cannot
        rejoin the live XLA world, so there is nothing a per-slot respawn
        could correctly do (``cluster.run`` refuses ``elastic`` with this
        launcher up front; this guard catches direct callers)."""
        raise NotImplementedError(
            "TPUPodLauncher cannot respawn a single slot of a live "
            "jax.distributed pod; relaunch the whole pod instead")

    def spawn_more(self, configs: Sequence[NodeConfig]) -> None:
        """A pod's process count is fixed by its jax.distributed world size;
        ``cluster.resize`` refuses distributed jobs up front — this guard
        catches direct callers."""
        raise NotImplementedError(
            "TPUPodLauncher cannot grow a live jax.distributed pod; "
            "relaunch the pod at the new size instead")

    @property
    def processes(self) -> list[PopenHandle]:
        return list(self._procs)

    def join(self, timeout: float | None = None) -> bool:
        deadline = None if timeout is None else time.monotonic() + timeout
        for p in self._procs:
            remaining = None if deadline is None else max(0.0, deadline - time.monotonic())
            p.join(remaining)
        return all(p.exitcode is not None for p in self._procs)

    def alive(self) -> list[int]:
        return [i for i, p in enumerate(self._procs) if p.is_alive()]

    def terminate(self) -> None:
        for p in self._procs:
            p.terminate()
        for p in self._procs:
            p.join(5.0)
            if p.is_alive():
                p.kill()


