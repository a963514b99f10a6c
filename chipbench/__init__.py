"""The benchmark of the served path on the chip (see BENCHMARK.json, PERF.md)."""
