"""On-chip benchmark of the backfill job: see BENCHMARK.json and PERF.md."""
