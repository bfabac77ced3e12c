"""The repository's benchmark: harness, yardstick and data (BENCHMARK.json)."""
