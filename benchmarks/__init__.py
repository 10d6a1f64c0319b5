"""The cell benchmark: see PERF.md and BENCHMARK.json at the repo root."""
