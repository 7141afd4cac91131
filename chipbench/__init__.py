"""Chip benchmark of the vision serving path (see PERF.md and BENCHMARK.json)."""
