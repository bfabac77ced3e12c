"""Tier-1 smoke for the committed serving microbench (ISSUE 5 satellite,
pipelined configs added by ISSUE 7): one tiny run of every config must go
end-to-end and produce sane stats — the guard that keeps
``bench_serving.py`` importable and runnable as the serving path evolves
(numbers in BENCH_r07.json / BENCH_r09.json / PERF_NOTES come from full
runs on an idle box)."""

from __future__ import annotations

import pytest


def test_bench_serving_quick_config_runs():
    import bench_serving  # repo root is on sys.path via conftest

    results = bench_serving.bench(quick=True)
    assert results["max_batch"] == 64 and results["num_nodes"] == 2
    for label in ("1row", "1row_tcp", "1row_tcp_pipe", "1row_tcp_pool",
                  "64row_tcp", "64row_tcp_pipe"):
        r = results["configs"][label]
        assert r["requests"] > 0
        assert r["qps"] > 0
        assert r["p50_ms"] > 0 and r["p99_ms"] >= r["p50_ms"]
        assert r["rows_per_s"] >= r["qps"]
    assert results["configs"]["1row"]["transport"] == "inprocess"
    assert results["configs"]["64row_tcp"]["request_rows"] == 64
    assert results["configs"]["1row_tcp_pipe"]["transport"] == "tcp pipe=8"
    assert results["configs"]["1row_tcp_pool"]["transport"] == "tcp pool"
    # the table renderer stays in sync with the result schema
    table = bench_serving.markdown_table(results)
    assert "1row_tcp_pipe" in table and "qps" in table


def test_bench_serving_trace_mode_renderer_and_flag():
    """--trace-breakdown schema: the renderer and the CLI flag stay in sync
    with the result shape (the full traced run itself is exercised by
    BENCH_r10 runs and tests/test_trace.py's e2e — not re-run here, the
    smoke budget is one cluster)."""
    import bench_serving

    results = {
        "mode": "trace-breakdown",
        "compare": {"qps_off": [100.0, 110.0], "qps_on": [99.0, 108.0],
                    "best_off": 110.0, "best_on": 108.0,
                    "on_overhead_pct": 1.82},
        "breakdown": {"load": {"qps": 100.0},
                      "stages": {"serve.wire": {"n": 5, "p50_ms": 1.5,
                                                "p99_ms": 3.0}}},
    }
    table = bench_serving.trace_table(results)
    assert "serve.wire" in table and "+1.82%" in table
    # the flag parses (argparse wiring)
    with pytest.raises(SystemExit):
        bench_serving.main(["--help"])
