"""The time a process is up and not stepping has names (ISSUE 35).

- a one-node job through ``tos.run`` with a ``log_dir``: the run report's
  ``lifecycle`` block lists every once-a-process stage once, in order, with
  the node's derived exit, and the job's driver never imported jax;
- the XLA listener (``telemetry/xla_events.py``) in two fresh processes over
  one temporary cache directory: a miss, then a hit;
- ``TOS_METRICS=0``: every new call is the shared no-op;
- ``build_lifecycle`` and the listener's nested-trace rule on hand-made events.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

from tensorflowonspark_tpu import telemetry
from tensorflowonspark_tpu.telemetry import trace as ttrace
from tensorflowonspark_tpu.telemetry import xla_events

_TESTS = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_TESTS)

DRIVER_STAGES = ["cluster.launch", "cluster.await_registrations",
                 "shutdown.eof", "shutdown.join", "shutdown.gather"]
NODE_STAGES = ["node.spawn", "node.register", "node.import_jax", "node.claim",
               "node.map_fun", "node.drain"]

_JOB = textwrap.dedent("""
    import json, sys
    import tensorflowonspark_tpu as tos
    import lifecycle_mapfuns

    if __name__ == "__main__":
        log_dir, preload = sys.argv[1], sys.argv[2] == "preload"
        args = {"rider": lifecycle_mapfuns.Preload("jax")} if preload else {}
        cluster = tos.run(lifecycle_mapfuns.jit_once, args, num_executors=1,
                          log_dir=log_dir, reservation_timeout=60)
        dump = cluster.debug_dump()
        cluster.shutdown()
        print(json.dumps({"jax_in_driver": "jax" in sys.modules,
                          "dump_has_block": "-- lifecycle" in dump}))
""")


def _run_script(tmp_path, source: str, *argv: str, env: dict | None = None):
    script = tmp_path / "script.py"
    script.write_text(source)
    proc = subprocess.run(
        [sys.executable, str(script), *argv], cwd=_REPO, text=True,
        capture_output=True, timeout=180,
        env={**os.environ, "PYTHONPATH": os.pathsep.join([_REPO, _TESTS]),
             **(env or {})})
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("mode", ["preload", "pinned"])
def test_one_node_job_reports_its_lifecycle(tmp_path, mode):
    """``preload``: jax is in the node before ``node_main``, as under a
    map_fun whose module imports it: the path of a chip run, every stage.
    ``pinned``: the CPU environment states the device summary, the node
    neither imports jax nor claims at its start, and the block says so by
    listing neither stage."""
    log_dir = str(tmp_path / "logs")
    said = _run_script(tmp_path, _JOB, log_dir, mode)
    assert said == {"jax_in_driver": False, "dump_has_block": True}
    with open(os.path.join(log_dir, "run_report.json")) as f:
        report = json.load(f)
    block = report["lifecycle"]
    assert sorted(block) == ["driver", "node0"]
    want = {"driver": DRIVER_STAGES,
            "node0": [s for s in NODE_STAGES if mode == "preload"
                      or s not in ("node.import_jax", "node.claim")]}
    for key, names in want.items():
        stages = block[key]["stages"]
        # every stage once, in the order the table of the README gives
        assert [st["stage"] for st in stages] == names
        starts = [st["start"] for st in stages]
        assert starts == sorted(starts)
        assert all(st["secs"] >= 0 for st in stages)
        assert stages[0]["gap_secs"] is None
        assert all(st["gap_secs"] is not None for st in stages[1:])
        # one process's stages follow one another inside the job
        assert sum(st["secs"] for st in stages) <= report["wall_secs"]
        counters = report["nodes"]["driver" if key == "driver" else "0"][
            "counters"]
        for st in stages:
            assert counters[st["stage"] + ".calls"] == 1
            assert counters[st["stage"] + ".us"] == pytest.approx(
                st["secs"] * 1e6, abs=1.0)
    node = block["node0"]
    if mode == "preload":
        by_name = {st["stage"]: st for st in node["stages"]}
        assert by_name["node.import_jax"]["preloaded"] is True
        assert by_name["node.claim"]["secs"] > 0
    assert node["exit_secs"] >= 0 and node["exit_secs_exact"] is True
    # the listener was there for the map_fun's program, wherever installed
    assert node["xla"]["programs"] >= 1 and node["xla"]["backend_secs"] > 0
    # the histogram twin of node.map_fun stays
    assert report["histograms"]["node.map_fun_secs"]["count"] == 1


_LISTENER = textwrap.dedent("""
    import json, sys
    import jax, jax.numpy as jnp
    jax.config.update("jax_compilation_cache_dir", sys.argv[1])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from tensorflowonspark_tpu import telemetry
    from tensorflowonspark_tpu.telemetry import trace, xla_events

    xla_events.PROGRAM_FLOOR_SECS = 0.0      # the probe compiles in no time
    assert xla_events.install() and xla_events.install()

    @jax.jit
    def lifecycle_probe(x):
        return jnp.tanh(x) @ x

    lifecycle_probe(jnp.ones((16, 16))).block_until_ready()
    events = [e for e in trace.flight_snapshot()["events"]
              if e["kind"] == "xla_program"
              and e["fun_name"] == "jit(lifecycle_probe)"]
    print(json.dumps({"counters": telemetry.snapshot()["counters"],
                      "events": events}))
""")


def test_listener_tells_a_miss_from_a_hit(tmp_path):
    cache = str(tmp_path / "cache")
    first = _run_script(tmp_path, _LISTENER, cache)
    second = _run_script(tmp_path, _LISTENER, cache)
    for said, cache_said in ((first, "miss"), (second, "hit")):
        (event,) = said["events"]
        assert event["cache"] == cache_said
        assert event["secs"] == pytest.approx(
            event["trace_secs"] + event["lower_secs"] + event["backend_secs"])
        assert said["counters"]["xla.backend.us"] > 0
        assert said["counters"]["xla.programs"] >= 1
    assert first["counters"]["xla.cache.misses"] >= 1
    assert "xla.cache.hits" not in first["counters"]
    assert second["counters"]["xla.cache.hits"] >= 1
    assert "xla.cache.misses" not in second["counters"]
    load = second["counters"]["xla.cache_load.us"]
    assert 0 < load <= second["counters"]["xla.backend.us"]
    assert second["events"][0]["cache_load_secs"] > 0


@pytest.fixture
def fresh_telemetry():
    """A registry and a tracer of this test's own; the process's are put
    back from the environment afterwards."""
    yield
    telemetry.reset()
    ttrace.reset()


def test_metrics_off_makes_every_new_call_the_shared_noop(fresh_telemetry,
                                                          monkeypatch):
    telemetry.reset(enabled=False)
    tracer = ttrace.reset(enabled=True)
    assert telemetry.lifecycle("node.claim") is ttrace.NULL_SPAN
    telemetry.record_lifecycle("node.spawn", 1.0, 2.0)
    monkeypatch.setattr(xla_events, "_installed", False)
    monkeypatch.setitem(sys.modules, "jax", object())   # no attribute is read
    assert xla_events.install() is False
    snap = tracer.flight_snapshot()
    assert snap["events"] == [] and snap["spans"] == []
    assert telemetry.snapshot()["counters"] == {}


def test_install_never_imports_jax(monkeypatch):
    monkeypatch.setattr(xla_events, "_installed", False)
    monkeypatch.delitem(sys.modules, "jax", raising=False)
    assert xla_events.install() is False
    assert "jax" not in sys.modules


def test_lifecycle_leaves_counters_a_span_and_one_flight_event(fresh_telemetry):
    telemetry.reset(enabled=True)
    tracer = ttrace.reset(enabled=True)
    with telemetry.lifecycle("node.import_jax", preloaded=True):
        pass
    anchor_mono, anchor_ns, _host = tracer.anchor
    telemetry.record_lifecycle("node.spawn", anchor_ns / 1e9 - 3.0, 2.5)
    counters = telemetry.snapshot()["counters"]
    assert counters["node.import_jax.calls"] == 1
    assert counters["node.spawn.us"] == 2_500_000
    snap = tracer.flight_snapshot()
    live, recorded = snap["events"]
    assert (live["kind"], live["stage"], live["preloaded"]) == (
        "lifecycle", "node.import_jax", True)
    assert live["start"] == pytest.approx(live["wall"], abs=0.5)
    assert (recorded["stage"], recorded["secs"]) == ("node.spawn", 2.5)
    by_name = {s["n"]: s for s in snap["spans"]}
    assert by_name["node.import_jax"]["tags"] == {"preloaded": True}
    # a stage recorded after the fact sits where it was, on the span clock
    assert by_name["node.spawn"]["t0"] == pytest.approx(anchor_mono - 3.0)
    assert by_name["node.spawn"]["d"] == 2.5


def _span(event, start, end, fun_name):
    xla_events._on_time_span(event, start, end, fun_name=fun_name)


def test_a_nested_trace_is_counted_once_and_programs_get_their_parts(
        fresh_telemetry):
    telemetry.reset(enabled=True)
    tracer = ttrace.reset(enabled=False)
    xla_events._pending().reset()
    # step traces for 2.0 s; attention (0.5 s) and mlp (0.25 s) are jitted
    # functions traced inside it and report first, at their ends
    _span(xla_events.TRACE_EVENT, 100.25, 100.75, "attention")
    _span(xla_events.TRACE_EVENT, 101.0, 101.25, "mlp")
    _span(xla_events.TRACE_EVENT, 100.0, 102.0, "step")
    # a lowering rule that traces a helper inside the lowering
    _span(xla_events.TRACE_EVENT, 102.125, 102.25, "where")
    _span(xla_events.LOWER_EVENT, 102.0, 102.5, "jit(step)")
    xla_events._on_event(xla_events.CACHE_HIT_EVENT)
    xla_events._on_duration(xla_events.CACHE_LOAD_EVENT, 0.75)
    _span(xla_events.BACKEND_EVENT, 102.5, 103.5, "jit(step)")
    # a one-op program under the floor: in the counters alone
    _span(xla_events.TRACE_EVENT, 104.0, 104.001, "add")
    _span(xla_events.BACKEND_EVENT, 104.001, 104.011, "jit(add)")
    xla_events._on_event(xla_events.CACHE_MISS_EVENT)
    counters = telemetry.snapshot()["counters"]
    assert counters["xla.trace.us"] == 2_126_000      # not 2.875 s
    assert counters["xla.lower.us"] == 375_000        # 0.5 s less the helper
    assert counters["xla.backend.us"] == 1_010_000
    assert counters["xla.cache_load.us"] == 750_000
    assert (counters["xla.programs"], counters["xla.cache.hits"],
            counters["xla.cache.misses"]) == (2, 1, 1)
    (event,) = [e for e in tracer.flight_snapshot()["events"]
                if e["kind"] == "xla_program"]
    assert event["fun_name"] == "jit(step)" and event["cache"] == "hit"
    assert event["start"] == 100.0      # the outer trace's, which came last
    assert (event["trace_secs"], event["lower_secs"], event["backend_secs"],
            event["cache_load_secs"], event["secs"]) == (2.125, 0.375, 1.0,
                                                         0.75, 3.5)


def test_nesting_is_told_apart_however_many_traces_a_step_holds(
        fresh_telemetry):
    """A step's trace holds thousands of nested traces (5,308 in a run of
    ``phi3_mini_d4_train_2k``): each is counted once."""
    telemetry.reset(enabled=True)
    ttrace.reset(enabled=False)
    xla_events._pending().reset()
    for i in range(6000):           # 6,000 helpers of 0.25 ms, one a ms
        _span(xla_events.TRACE_EVENT, 200.0 + i / 1024,
              200.0 + i / 1024 + 1 / 4096, "helper")
    _span(xla_events.TRACE_EVENT, 199.0, 207.0, "step")
    assert telemetry.snapshot()["counters"]["xla.trace.us"] == pytest.approx(
        8_000_000, abs=6000)        # each event rounds to a microsecond
    xla_events._pending().reset()


def _lifecycle_event(node, stage, end, secs, **tags):
    return {"kind": "lifecycle", "node": node, "t": end, "t0": end,
            "wall": 1000.0 + end, "stage": stage,
            "start": 1000.0 + end - secs, "secs": secs, **tags}


def test_build_lifecycle_orders_stages_and_derives_the_exit():
    events = [
        _lifecycle_event("driver", "cluster.launch", 0.5, 0.5),
        _lifecycle_event("node0", "node.spawn", 2.0, 1.5),
        _lifecycle_event("node0", "node.claim", 12.0, 9.0, platform="tpu"),
        {"kind": "xla_program", "node": "node0", "t": 20.0, "t0": 20.0,
         "wall": 1020.0, "fun_name": "jit(step)", "start": 1015.0,
         "secs": 5.0, "trace_secs": 1.0, "lower_secs": 0.5,
         "backend_secs": 3.5, "cache_load_secs": 0.0, "cache": "miss"},
        {"kind": "death", "node": "driver", "t": 21.0, "executor": 3},
        _lifecycle_event("node0", "node.drain", 30.0, 2.0),
        _lifecycle_event("driver", "shutdown.join", 34.0, 5.0),
    ]
    nodes = {"0": {"counters": {"xla.programs": 40, "xla.trace.us": 1_500_000,
                                "xla.backend.us": 4_000_000,
                                "xla.cache.misses": 7}},
             "driver": {"counters": {}}}
    block = telemetry.build_lifecycle(events, nodes)
    node = block["node0"]
    assert [(st["stage"], st["gap_secs"]) for st in node["stages"]] == [
        ("node.spawn", None), ("node.claim", 1.0), ("node.drain", 16.0)]
    assert node["stages"][1]["platform"] == "tpu"
    assert node["stages"][0]["start"] == 1000.5
    assert node["programs"] == [
        {"start": 1015.0, "secs": 5.0, "fun_name": "jit(step)",
         "trace_secs": 1.0, "lower_secs": 0.5, "backend_secs": 3.5,
         "cache_load_secs": 0.0, "cache": "miss"}]
    assert node["xla"] == {"programs": 40, "cache_hits": 0, "cache_misses": 7,
                           "trace_secs": 1.5, "lower_secs": 0.0,
                           "backend_secs": 4.0, "cache_load_secs": 0.0}
    # join ended 4 s after the node's drain did, and was waiting for it
    assert (node["exit_secs"], node["exit_secs_exact"]) == (4.0, True)
    assert "xla" not in block["driver"] and "exit_secs" not in block["driver"]
    text = telemetry.debug_dump({"nodes": nodes}, block)
    assert "node.claim" in text and "program jit(step)" in text

    # a node that was gone before the driver began to join: an upper bound
    events[-1] = _lifecycle_event("driver", "shutdown.join", 34.0, 0.001)
    late = telemetry.build_lifecycle(events, nodes)["node0"]
    assert (late["exit_secs"], late["exit_secs_exact"]) == (4.0, False)
    # chaos dumps share their process's key; other kinds are not stages
    dumped = telemetry.build_lifecycle(
        [_lifecycle_event("flight:node1", "node.spawn", 2.0, 1.0)], {})
    assert list(dumped) == ["node1"]
    assert telemetry.build_lifecycle([events[4]], nodes) == {}
