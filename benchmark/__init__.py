"""The benchmark: harness, yardstick and data files. See PERF.md and BENCHMARK.json."""
