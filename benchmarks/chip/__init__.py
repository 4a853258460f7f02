"""On-chip benchmark of kneaded serving: see BENCHMARK.json and PERF.md."""
