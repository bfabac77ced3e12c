"""Tier-1 smoke for the committed autoscaling bench (ISSUE 9): one quick
1x -> 4x -> 1x run must go end-to-end with the real policy loop and pass
its own acceptance gate — the guard that keeps ``bench_autoscale.py``
importable and runnable as the resize/serving paths evolve (numbers in
BENCH_r11.json come from full runs on an idle box)."""

from __future__ import annotations

import pytest


def test_bench_autoscale_quick_runs_and_tracks_step():
    import bench_autoscale  # repo root is on sys.path via conftest

    results = bench_autoscale.bench(quick=True)
    assert [r["phase"] for r in results["phases"]] == ["1x", "4x", "1x"]
    for r in results["phases"]:
        assert r["requests"] > 0 and r["qps"] > 0
        assert r["p99_ms"] >= r["p50_ms"] > 0
    # the gate the full run records into BENCH_r11.json
    acc = results["acceptance"]
    assert acc["scaled_out_on_step"], results["decisions"]["counts"]
    assert acc["scaled_back_in"], results["trajectory"][-5:]
    assert acc["errors_other"] == 0, results["errors_other"][:3]
    # the decision trail carries its stats justification
    counts = results["decisions"]["counts"]
    assert counts["scale_out"] >= 1 and counts["scale_in"] >= 1
    assert all("stats" in d for d in results["decisions"]["decisions"])
    # the sampled trajectory actually moved
    assert max(s["replicas"] for s in results["trajectory"]) > 1
    assert results["trajectory"][-1]["replicas"] == 1
    # the table renderer stays in sync with the result schema
    table = bench_autoscale.markdown_table(results)
    assert "4x" in table and "scale_out" in table
    # the CLI flag parses (argparse wiring)
    with pytest.raises(SystemExit):
        bench_autoscale.main(["--help"])
