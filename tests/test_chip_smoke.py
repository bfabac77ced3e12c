"""One process owns the chip — the pure-Python half of what ``chip_smoke.py``
proves on hardware: the compile cache is placed from outside, sidecar roles
never initialise a backend, several executors are not left to fight for one
host's chips, and the smoke refuses anything but a TPU.

Everything here is sub-second (the file sorts early in the clock-bound tier-1
run); the one test that spawns the real script is ``slow``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import types

import pytest

import chip_smoke
from tensorflowonspark_tpu import cluster as tcluster
from tensorflowonspark_tpu import node as tnode
from tensorflowonspark_tpu import tpu_info
from tensorflowonspark_tpu.coordinator import CoordinatorServer
from tests import mapfuns

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- compile cache placed from outside ----------------------------------------

def _bootstrap_in_fresh_process(env_dir: str | None) -> tuple[str, bool]:
    """(cache dir in effect, jax imported?) from a jax-free interpreter."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run(
        [sys.executable, "-c",
         "import os, sys, xla_cache_bootstrap as b;"
         "d = b.enable_persistent_cache();"
         "assert os.environ['JAX_COMPILATION_CACHE_DIR'] == d;"
         "print(d); print('jax' in sys.modules)"],
        cwd=_REPO, env=env, check=True, capture_output=True, text=True,
        timeout=60).stdout.split()
    return out[0], out[1] == "True"


def test_cache_bootstrap_uses_external_dir_verbatim(tmp_path):
    chosen = str(tmp_path / "somewhere" / "else")
    assert _bootstrap_in_fresh_process(chosen) == (chosen, False)


def test_cache_bootstrap_defaults_to_fixed_checkout_path():
    assert _bootstrap_in_fresh_process(None) == (
        os.path.join(_REPO, ".jax_cache"), False)


# -- roles that never compute never touch the backend -------------------------

def test_only_compute_roles_claim_a_backend_and_only_after_registration(
        monkeypatch):
    """A trainer, an evaluator sidecar and an ingest worker join one cluster
    (``node_main`` in-process, one thread each) on a host whose environment
    pins no platform — the situation on a TPU host.  Only the trainer
    claims a backend, and only once its role is known."""
    events: dict[str, list[str]] = {}   # thread name -> what it did, in order
    roles: dict[str, str] = {}          # thread name -> assigned role
    lock = threading.Lock()

    def note(what: str) -> None:
        with lock:
            events.setdefault(threading.current_thread().name, []).append(what)

    def claim():
        # the whole cluster has registered by the time a node knows its
        # role: a claim made before registering would see fewer than 3
        note(f"backend_claimed_with_{len(server.cluster_info())}_registered")
        return {"platform": "fake", "device_kind": "fake", "num_devices": 1,
                "coords": [], "process_index": 0}

    def map_fun(args, ctx):
        roles[threading.current_thread().name] = ctx.job_name
        note("map_fun")

    monkeypatch.setattr(tpu_info, "env_device_summary", lambda: None)
    monkeypatch.setattr(tpu_info, "device_summary", claim)
    # node_main reconfigures root logging for its own process; keep pytest's
    monkeypatch.setattr(tnode.logging, "basicConfig", lambda **kw: None)
    from tensorflowonspark_tpu.ingest import service

    monkeypatch.setattr(service, "ingest_worker_main", map_fun)
    server = CoordinatorServer(
        3, [("chief", 0), ("evaluator", 0), ("ingest", 0)], authkey=b"k" * 16)
    addr = server.start("127.0.0.1")
    try:
        config = tnode.NodeConfig(
            coordinator_addr=addr, authkey=b"k" * 16, map_fun=map_fun,
            heartbeat_interval=0.05, reservation_timeout=30.0)
        exit_codes: list[int] = []
        nodes = [threading.Thread(
            target=lambda: exit_codes.append(tnode.node_main(config)),
            name=f"node-{i}", daemon=True) for i in range(3)]
        for t in nodes:
            t.start()
        for t in nodes:
            t.join(30.0)
        assert not any(t.is_alive() for t in nodes)
        assert exit_codes == [0, 0, 0]
        by_role = {roles[name]: evs for name, evs in events.items()}
        # the ingest role runs the data-service loop (patched to map_fun)
        assert by_role == {
            "chief": ["backend_claimed_with_3_registered", "map_fun"],
            "evaluator": ["map_fun"], "ingest": ["map_fun"]}
        final = {m["job_name"]: m["device"] for m in server.cluster_info()}
        assert final["chief"]["platform"] == "fake"
        assert final["evaluator"] == final["ingest"] == tpu_info.NO_DEVICES
    finally:
        server.stop()


def test_claiming_node_is_allowed_heartbeat_silence():
    """Backend initialisation keeps the interpreter lock, so a node cannot
    heartbeat while it claims its accelerator; killing it mid-claim would
    leave the chip unusable.  The dead-node window stretches for exactly as
    long as the node's device block is the registration placeholder."""
    import time

    server = CoordinatorServer(2, authkey=b"k" * 16)   # never started: no I/O
    ids = [server._dispatch({"op": "register", "meta": {
        "host": "h", "device": dict(tpu_info.CLAIM_PENDING)}})["executor_id"]
        for _ in range(2)]
    silent_since = time.monotonic() - 60.0   # a minute without a beat
    for i in ids:
        server._last_seen[i] = silent_since
    assert server.dead_nodes(12.0) == []     # both still claiming
    assert server._dispatch({"op": "update_meta", "executor_id": ids[0],
                             "patch": {"device": tpu_info.NO_DEVICES}})["ok"]
    assert server.dead_nodes(12.0) == [ids[0]]   # claim over: plain rule
    server._last_seen[ids[1]] = time.monotonic() - 300.0
    assert ids[1] in server.dead_nodes(12.0)     # the allowance is bounded


def test_device_summary_does_not_swallow_backend_failure(monkeypatch):
    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(tpu_info, "env_device_summary", lambda: None)
    # a stand-in module: importing the real jax costs seconds in this file
    monkeypatch.setitem(sys.modules, "jax",
                        types.SimpleNamespace(local_devices=boom))
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        tpu_info.device_summary()
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        monkeypatch.setitem(sys.modules, "jax",
                            types.SimpleNamespace(devices=boom))
        tpu_info.is_tpu_available()


# -- several executors, one host's chips --------------------------------------

def test_chip_fight_rules():
    tpu = {"JAX_PLATFORMS": "tpu"}
    slices = [{**tpu, **tpu_info.chip_visibility_env([i])} for i in range(4)]
    assert tcluster._chip_fight(slices, 4) is None
    assert tcluster._chip_fight([tpu, tpu], 1) is None      # trainer + sidecar
    assert tcluster._chip_fight([{"JAX_PLATFORMS": "cpu"}] * 3, 3) is None
    assert tcluster._chip_fight([{}, {}], 2) is None        # auto: not judged
    assert "no TPU_VISIBLE_CHIPS" in tcluster._chip_fight([tpu, tpu], 2)
    assert "already holds" in tcluster._chip_fight(
        [slices[0], slices[1], slices[0]], 3)


def test_run_refuses_executors_that_would_fight_for_the_tpu():
    with pytest.raises(ValueError, match="one process owns a TPU chip"):
        tcluster.run(mapfuns.noop, num_executors=2,
                     env={"JAX_PLATFORMS": "tpu"})


# -- chip_smoke.py refuses anything but a TPU ---------------------------------

def _fake_cluster(device=None, errors=()):
    coordinator = types.SimpleNamespace(
        errors=lambda: list(errors),
        cluster_info=lambda: [{"executor_id": 0, "device": device}])
    return types.SimpleNamespace(
        coordinator=coordinator,
        launcher=types.SimpleNamespace(alive=lambda: [0]))


def test_smoke_names_the_platform_it_found_instead_of_a_tpu():
    cpu = {"platform": "cpu", "device_kind": "cpu", "num_devices": 8}
    with pytest.raises(SystemExit, match="found platform 'cpu'"):
        chip_smoke.await_device(_fake_cluster(cpu), "tpu")


def test_smoke_reports_a_tpu_that_would_not_initialise():
    err = {"executor_id": 0, "traceback": "Traceback ...\nRuntimeError: "
           "Unable to initialize backend 'tpu': No jellyfish device found."}
    with pytest.raises(SystemExit, match="no TPU here.*No jellyfish"):
        chip_smoke.await_device(_fake_cluster(errors=[err]), "tpu")


def test_kernel_operand_rows_reads_custom_call_lines_only():
    hlo = "\n".join([
        '%fusion.1 = bf16[256,2048,128]{2,1,0} fusion(%p0), kind=kLoop',
        '%cc = (bf16[64,2048,128]{2,1,0}, f32[64,2048,128]{2,1,0}) '
        'custom-call(bf16[64,2048,128]{2,1,0} %q, bf16[64,2048,128]{2,1,0} '
        '%k, bf16[64,2048,128]{2,1,0} %v), '
        'custom_call_target="tpu_custom_call"'])
    assert set(chip_smoke.kernel_operand_rows(hlo)) == {64}


def test_verdict_line_holds_ok_and_the_device_and_nothing_else():
    """The last stdout line is read by a checker that accepts exactly these
    keys; the summary (phases, cache, claim) goes on the line before it."""
    facts = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}
    assert json.loads(chip_smoke.verdict_line(True, facts)) == {
        "ok": True, "device": facts}
    line = chip_smoke.verdict_line(False, {**facts, "extra": 1})
    assert "\n" not in line
    assert json.loads(line) == {"ok": False, "device": facts}


@pytest.mark.slow
def test_default_chip_smoke_fails_without_a_chip():
    """The real script, default arguments, on this chip-less box: non-zero
    exit, no result line, and it says what it found."""
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=_REPO,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU here" in proc.stderr
    for line in proc.stdout.splitlines():
        if line.startswith("{"):
            assert not json.loads(line).get("ok")
