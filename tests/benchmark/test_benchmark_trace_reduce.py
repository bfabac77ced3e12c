"""``trace_reduce`` on the trace recorded on a v5e (4 steps of a small
program with one Pallas kernel) and on synthetic planes.  Reads the file
with jaxlib's ProfileData; no backend is initialised beyond the CPU."""

from __future__ import annotations

import os

import pytest

from benchmark import common
from benchmark import trace_reduce as tr

RECORDED = os.path.join(common.HERE, "testdata", "tpu_v5e_4steps.xplane.pb")
SPANS = ("feed_wait", "step_dispatch", "fetch")


@pytest.fixture(scope="module")
def recorded():
    return tr.load(RECORDED)


def test_recorded_trace_planes_and_runs(recorded):
    planes = tr.device_planes(recorded)
    assert [p["name"] for p in planes] == ["/device:TPU:0"]
    assert tr.program_runs(recorded) == 4
    assert len(tr.line_events(planes[0], tr.OPS_LINE)) == 24


def test_recorded_busy_is_the_union_and_far_below_the_window(recorded):
    window = tr.traced_window(recorded, "traced_window")
    b = tr.busy(recorded, window)
    by_hand = sum(d for _n, _s, d in tr.line_events(
        tr.device_planes(recorded)[0], tr.OPS_LINE)) * 1e-9
    # ops on this line do not overlap, so the union is their sum
    assert b["busy_s"] == pytest.approx(by_hand, rel=1e-6)
    assert b["window_s"] == pytest.approx(0.0187, rel=0.01)
    assert 0 < b["busy_s"] / b["window_s"] < 0.01
    assert b["per_device_busy_s"] == [b["busy_s"]]


def test_recorded_kernel_time_per_step(recorded):
    assert tr.ops_count(recorded, tr.is_pallas_kernel) == 4
    per_step_us = tr.ops_seconds(recorded, tr.is_pallas_kernel) / 4 * 1e6
    assert 4.0 < per_step_us < 4.4          # 4.19 us per call in the trace
    top = tr.top_ops(recorded, 3)
    assert top[0][0] == "convolution_tanh_fusion"
    assert top[1][0].startswith("pallas:")
    assert top[0][1] == pytest.approx(4 * 12.598e-6, rel=0.01)


def test_recorded_gaps_go_to_the_benchmark_spans(recorded):
    s = tr.summarize(recorded, SPANS, "traced_window")
    gaps = dict(s["idle_gaps"])
    assert gaps["feed_wait"] > 0.012         # 4 sleeps of 3 ms and the waits
    assert sum(gaps.values()) == pytest.approx(
        s["window_s"] - s["busy_s"], rel=1e-6)
    assert not any("traced_window" in k for k in gaps)
    assert s["collectives"] is None and s["pallas_calls"] == 4


def test_opcode_parsing():
    fusion = ("%fusion.3 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(bf16[8]{0} "
              "%p), kind=kLoop, calls=%fc")
    kernel = ('%step.1 = (bf16[2,512,128]{2,1,0:T(8,128)(2,1)S(1)}, f32[2]) '
              'custom-call(bf16[2] %a), custom_call_target="tpu_custom_call"')
    start = "%all-reduce-start.1 = f32[10]{0} all-reduce-start(f32[10]{0} %g)"
    assert tr.op_name(fusion) == "fusion.3" and tr.opcode(fusion) == "fusion"
    assert tr.opcode(kernel) == "custom-call" and tr.is_pallas_kernel(kernel)
    assert tr.is_collective(start) and not tr.is_collective(fusion)
    assert tr.is_collective("%all-reduce-scatter.2 = f32[4] fusion(f32[8] %x)")
    assert tr.opcode("not an instruction") == ""
    assert tr.op_label(kernel) == "pallas:step.1"
    assert tr.op_label(start) == "collective:all-reduce-start.1"


def test_interval_arithmetic():
    assert tr.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)]) == [(0, 3), (5, 7)]
    assert tr.total([(0, 3), (5, 7)]) == 5
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 4)], [(0, 4)]) == []
    assert tr.clip([(0, 4), (6, 9)], (3, 7)) == [(3, 4), (6, 7)]


def test_self_time_of_nested_events():
    events = [("while", 0.0, 10.0), ("body_a", 1.0, 3.0), ("body_b", 5.0, 2.0),
              ("after", 10.0, 1.0)]
    assert dict(tr.self_times(events)) == {
        "while": 5.0, "body_a": 3.0, "body_b": 2.0, "after": 1.0}


def _two_device_trace():
    ar = "%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %g), replica_groups={}"
    conv = "%conv.1 = bf16[8]{0} convolution(bf16[8]{0} %x, bf16[8]{0} %w)"

    def plane(n, ops, extra_lines=()):
        return {"name": f"/device:TPU:{n}", "lines": [
            {"name": "XLA Modules", "events": [("jit_step(1)", 0.0, 100.0)]},
            {"name": "XLA Ops", "events": ops}, *extra_lines]}
    # device 0: compute 0-60, all-reduce 60-80 (fully exposed), compute 80-100
    # device 1: compute 0-70 on the ops line, the all-reduce 50-90 runs beside
    #           it on the async line: 20 of its 40 ns are covered by compute
    return {"planes": [
        plane(0, [(conv, 0.0, 60.0), (ar, 60.0, 20.0), (conv, 80.0, 20.0)]),
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [(conv, 0.0, 70.0), (ar, 50.0, 40.0)]}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ("step_dispatch", 0.0, 5.0)]}]},
    ]}


def test_exposed_collective_time_on_two_synthetic_devices():
    trace = _two_device_trace()
    c = tr.collectives(trace)
    assert c["per_device_collective_s"] == pytest.approx([20e-9, 40e-9])
    assert c["collective_s"] == pytest.approx(30e-9)
    # exposed: all 20 ns on device 0, 90-70 = 20 ns on device 1
    assert c["exposed_s"] == pytest.approx(20e-9)
    b = tr.busy(trace)
    assert b["per_device_busy_s"] == pytest.approx([100e-9, 90e-9])
    assert b["busy_s"] == pytest.approx(95e-9)
    assert b["window_s"] == pytest.approx(100e-9)
    assert tr.top_ops(trace, 1)[0][0] == "conv.1"


def test_no_device_operation_gives_nothing():
    host_only = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": [("feed_wait", 0.0, 5.0)]}]}]}
    assert tr.busy(host_only) is None
    assert tr.summarize(host_only, SPANS) is None
    assert tr.collectives(host_only) is None


def test_unattributed_gap_names_the_host_function():
    conv = "%conv.1 = bf16[8]{0} convolution(bf16[8]{0} %x)"
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            (conv, 0.0, 10.0), (conv, 50.0, 10.0), (conv, 100.0, 10.0)]}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ("feed_wait", 12.0, 36.0),
            ("$loop.py:7 outer", 55.0, 60.0),
            ("$feeding.py:300 next_batch", 62.0, 36.0)]}]}]}
    gaps = dict(tr.idle_gaps(trace, SPANS))
    assert gaps == {"feed_wait": pytest.approx(40e-9),
                    "unattributed:feeding.py:300 next_batch":
                    pytest.approx(40e-9)}
