"""The eight per-layer metrics that split ``setup_s`` (ISSUE 35): each reader
against its manifest entry and against a run report RECORDED on the chip
(``benchmark/testdata/run_report_phi3_mini_d4_train_2k.json``: the whole
``logs/run_report.json`` of one warm traced run of that cell), its value
worked out from the file's counters by hand; nothing to read (``None``) for
a missing report, a stale one, and a program without the counters, as the
parent commit is.  No cluster, no backend."""

from __future__ import annotations

import json
import os
import shutil

import pytest

from benchmark import common, run_report

CELL = "phi3_mini_d4_train_2k"
RECORDED = os.path.join(common.HERE, "testdata", f"run_report_{CELL}.json")

LIFECYCLE = "process start"
XLA = "entry, lifecycle, compile cache"
# name, layer, unit, the chief's counters it adds up (microseconds unless a count)
CASES = [
    ("start_spawn_s", LIFECYCLE, "s", ("node.spawn.us",)),
    ("start_register_s", LIFECYCLE, "s", ("node.register.us",)),
    ("start_import_jax_s", LIFECYCLE, "s", ("node.import_jax.us",)),
    ("start_chip_claim_s", LIFECYCLE, "s", ("node.claim.us",)),
    ("xla_trace_lower_s", XLA, "s", ("xla.trace.us", "xla.lower.us")),
    ("xla_backend_s", XLA, "s", ("xla.backend.us",)),
    ("xla_cache_load_s", XLA, "s", ("xla.cache_load.us",)),
    ("xla_cache_misses", XLA, "programs", ("xla.cache.misses",)),
]
IDS = [case[0] for case in CASES]


@pytest.fixture
def work_dir(tmp_path, monkeypatch):
    """An empty work directory in place of ``.bench_data/benchmark``."""
    monkeypatch.setattr(common, "WORK_DIR", str(tmp_path))
    return tmp_path


def _ensure(work_dir) -> str:
    logs = work_dir / "runs" / CELL / "logs"
    logs.mkdir(parents=True, exist_ok=True)
    return str(logs / "run_report.json")


def _place(work_dir, report: dict) -> None:
    with open(_ensure(work_dir), "w") as f:
        json.dump(report, f)


def _recorded() -> dict:
    with open(RECORDED) as f:
        return json.load(f)


def _run(window_epoch_start: float) -> dict:
    return {"cell": {"workload": CELL},
            "facts": {"window_epoch_start": window_epoch_start}}


@pytest.mark.parametrize("name,layer,unit,counters", CASES, ids=IDS)
def test_reader_matches_its_manifest_entry(name, layer, unit, counters):
    manifest = common.load_manifest()
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    reader = common.load_module("layer_metrics", name)
    assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
        entry["layer"], entry["unit"], entry["moves"]) == (
            layer, unit, "setup_s")
    assert entry["source"] == "program_counter"
    assert entry["better"] == "lower"
    # set-up is every cell's: no list of cells, like claim_s
    assert "workloads" not in entry
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves"}
    # appended after what the benchmark had
    names = [m["name"] for m in manifest["per_layer"]]
    assert names.index(name) > names.index("dsa_index_roofline")


@pytest.mark.parametrize("name,layer,unit,counters", CASES, ids=IDS)
def test_reader_reads_the_recorded_report(work_dir, name, layer, unit,
                                          counters):
    report = _recorded()
    shutil.copy(RECORDED, _ensure(work_dir))
    chief = report["nodes"]["0"]["counters"]
    scale = 1 if unit == "programs" else 1e6
    want = sum(chief.get(c, 0) for c in counters) / scale
    reader = common.load_module("layer_metrics", name)
    # the window of that run began before its report was written
    got = reader.read(_run(report["written_at"] - 30.0))
    assert got == pytest.approx(want)
    assert got >= 0


def test_the_recorded_run_closes():
    """What the acceptance criteria hold every cell's traced run to, on the
    recorded one: a warm run of the dense LM cell on a v5e."""
    report = _recorded()
    chief = report["nodes"]["0"]["counters"]
    assert chief.get("xla.cache.misses", 0) == 0            # it was warm
    assert chief["xla.cache.hits"] >= 1
    assert 0 < chief["xla.cache_load.us"] <= chief["xla.backend.us"]
    stages = [st["stage"] for st in report["lifecycle"]["node0"]["stages"]]
    assert stages == ["node.spawn", "node.register", "node.import_jax",
                      "node.claim", "node.map_fun", "node.drain"]
    start = sum(chief[f"node.{s}.us"] for s in
                ("spawn", "register", "import_jax", "claim")) / 1e6
    by_name = {st["stage"]: st for st in report["lifecycle"]["node0"]["stages"]}
    # the four start stages end before the map_fun begins
    assert start <= (by_name["node.map_fun"]["start"]
                     - by_name["node.spawn"]["start"])
    # and XLA's work is inside the map_fun
    xla = report["lifecycle"]["node0"]["xla"]
    assert (xla["trace_secs"] + xla["lower_secs"] + xla["backend_secs"]
            <= by_name["node.map_fun"]["secs"])


@pytest.mark.parametrize("name,layer,unit,counters", CASES, ids=IDS)
def test_reader_finds_nothing_to_read(work_dir, name, layer, unit, counters):
    reader = common.load_module("layer_metrics", name)
    # no report at all: the run directory is new, or the program writes none
    assert reader.read(_run(0.0)) is None
    # LAST run's report: written before this run's window began
    report = _recorded()
    _place(work_dir, report)
    assert reader.read(_run(report["written_at"] + 1.0)) is None
    assert reader.read(_run(report["written_at"] - 1.0)) is not None
    # the parent commit's report: no stage, no listener
    chief = report["nodes"]["0"]["counters"]
    report["nodes"]["0"]["counters"] = {
        k: v for k, v in chief.items()
        if not k.startswith(("xla.", "node.", "cluster.", "shutdown."))}
    _place(work_dir, report)
    assert reader.read(_run(report["written_at"] - 1.0)) is None
    # a torn file reads as no report
    (work_dir / "runs" / CELL / "logs" / "run_report.json").write_text("{")
    assert reader.read(_run(0.0)) is None


def test_a_cold_run_reads_zero_seconds_of_cache_load(work_dir):
    """No program came from the cache: the counter never moved and is absent,
    while ``xla.programs`` says the listener was there."""
    report = _recorded()
    chief = report["nodes"]["0"]["counters"]
    for gone in ("xla.cache_load.us", "xla.cache.hits"):
        chief.pop(gone, None)
    chief["xla.cache.misses"] = 31
    _place(work_dir, report)
    run = _run(report["written_at"] - 1.0)
    assert common.load_module("layer_metrics", "xla_cache_load_s").read(run) == 0.0
    assert common.load_module("layer_metrics", "xla_cache_misses").read(run) == 31
    assert run_report.seconds(run, "xla.backend.us", witness="xla.programs") > 0
