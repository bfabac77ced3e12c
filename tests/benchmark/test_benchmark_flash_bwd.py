"""Attention's backward as kernels (ISSUE 26): the gradient program of the
flash kernel compiled for a DESCRIBED v5e at the three LM cells' real
shapes holds Pallas kernels under ``flash_bwd`` and no loop there, and the
two readers ``flash_bwd_ms`` / ``flash_bwd_roofline`` against a hand-made
run.  Nothing runs on a chip; the topology is described inside a fixture,
never at import (see the on-chip-measurement guide)."""

from __future__ import annotations

import os

import pytest

from benchmark import common, scope_times

LM_CELLS = ["phi3_mini_d4_train_2k", "phi3_mini_d4_train_512",
            "olmoe_1b_7b_d1_train_4k"]
READERS = ["flash_bwd_ms", "flash_bwd_roofline"]
PALLAS = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: not here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("workload", LM_CELLS)
def test_backward_is_kernels_and_no_loop_at_the_cells_shapes(one_chip,
                                                             workload):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from tensorflowonspark_tpu.ops.attention import flash_attention

    cell = common.resolve_cell(workload)
    cfg, traffic = cell["config"], cell["traffic"]
    heads = cfg["num_attention_heads"]
    shape = (traffic["rows_per_chip"], traffic["seq_len"], heads,
             cfg["hidden_size"] // heads)
    assert shape[1:] in [(2048, 32, 96), (512, 32, 96), (4096, 16, 128)]
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, impl="pallas")
        return jnp.sum(out.astype(jnp.float32))

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without a chip: keep it out of the cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        hlo = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
            q, q, q).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    lines = hlo.splitlines()
    kernels = [ln for ln in lines if PALLAS in ln]
    assert len(kernels) >= 2
    # the dk/dv pass and the dq pass carry the scope the reader sums by
    assert len([ln for ln in kernels if "flash_bwd" in ln]) == 2
    assert len([ln for ln in kernels if "flash_fwd" in ln]) == 1
    loops = [ln for ln in lines if " while(" in ln and "flash_bwd" in ln]
    assert not loops, loops[:2]


# -- the readers --------------------------------------------------------------

STEP = "jit(step)/jit(main)/loss_and_grad/"
BWD = STEP + "transpose(jvp(Transformer))/block_0/attn/attention/flash_bwd/"


def _run(monkeypatch, sums: dict | None) -> dict:
    """A traced run as ``run.py`` hands it to a reader, two traced steps; the
    device self-time by scope is ``sums`` (seconds over the traced window)
    instead of an xplane's (that decoding is test_benchmark_olmoe.py's)."""
    monkeypatch.setattr(scope_times, "run_scope_seconds", lambda run: sums)
    # one forward call needs 20 us of compute and 4 us of memory traffic
    cost = {"flops": 197e12 * 20e-6, "bytes": 819e9 * 4e-6}
    return {"cell": {"workload": LM_CELLS[0],
                     "config": {"num_hidden_layers": 4}},
            "trace": {"busy_s": 1.0},
            "facts": {"traced_steps": 2, "kernels": {"flash_fwd": cost}},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


SUMS = {
    BWD + "pallas_call:": 600e-6,               # both passes, both steps
    BWD + "transpose:": 150e-6,                 # layout is the scope's too
    BWD + "reduce_sum:": 50e-6,
    STEP + "jvp(Transformer)/block_0/attn/attention/flash_fwd/pallas_call:":
        100e-6,
    STEP + "jvp(Transformer)/block_0/mlp/dot_general:": 900e-6,
    "": 30e-6,
}


@pytest.mark.parametrize("metric,expected", [
    ("flash_bwd_ms", 0.4),                      # 800 us over two steps
    # four layers x 2.5 x 20 us of compute = 200 us against 400 us
    ("flash_bwd_roofline", 50.0),
])
def test_flash_bwd_readers_on_a_hand_made_run(monkeypatch, metric, expected):
    reader = common.load_module("layer_metrics", metric)
    run = _run(monkeypatch, SUMS)
    assert reader.read(run) == pytest.approx(expected)
    if metric == "flash_bwd_roofline":
        assert reader.bound(run) == "compute"
        run["facts"]["kernels"]["flash_fwd"]["bytes"] *= 10   # 2 x 40 us
        assert reader.bound(run) == "memory"
        assert reader.read(run) == pytest.approx(100 * 320e-6 / 400e-6)


@pytest.mark.parametrize("metric", READERS)
def test_flash_bwd_readers_find_nothing_without_a_trace_or_a_scope(
        monkeypatch, metric):
    """An untraced run (the real ``run_scope_seconds``), a trace without a
    scope at all, and a traced program that names no ``flash_bwd`` (no
    attention in the step): None, no raise."""
    reader = common.load_module("layer_metrics", metric)
    run = _run(monkeypatch, SUMS)
    monkeypatch.undo()
    assert reader.read({**run, "trace": None}) is None
    assert reader.read(_run(monkeypatch, None)) is None
    others = {k: v for k, v in SUMS.items() if "flash_bwd" not in k}
    assert reader.read(_run(monkeypatch, others)) is None
    if metric == "flash_bwd_roofline":
        run = _run(monkeypatch, SUMS)
        assert reader.read({**run, "peaks": None}) is None
        run["facts"]["kernels"] = {}
        assert reader.read(run) is None and reader.bound(run) is None


@pytest.mark.parametrize("metric", READERS)
def test_reader_matches_its_manifest_entry_appended_after_what_was_there(
        metric):
    manifest = common.load_manifest()
    names = [m["name"] for m in manifest["per_layer"]]
    entry = manifest["per_layer"][names.index(metric)]
    reader = common.load_module("layer_metrics", metric)
    assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
        entry["layer"], entry["unit"], entry["moves"])
    assert entry["layer"] == "kernels" and entry["source"] == "device_trace"
    assert entry["better"] == {"flash_bwd_ms": "lower",
                               "flash_bwd_roofline": "higher"}[metric]
    assert entry["workloads"] == LM_CELLS
    moved = {m["name"]: m for m in manifest["end_to_end"]}[entry["moves"]]
    assert set(entry["workloads"]) <= set(moved["workloads"])
    # appended: after everything ISSUE 25 left, the ms reader first
    assert names.index("moe_optimizer_ms") < names.index("flash_bwd_ms")
    assert names.index("flash_bwd_roofline") == names.index("flash_bwd_ms") + 1
    for cell in LM_CELLS:
        assert metric in [m["name"] for m in
                          common.resolve_cell(cell)["per_layer"]]
