"""On-chip benchmark of the SP-FL system (see BENCHMARK.json and PERF.md)."""
