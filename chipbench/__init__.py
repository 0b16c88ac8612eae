"""Chip benchmark of the checkpoint service: see BENCHMARK.json and PERF.md."""
