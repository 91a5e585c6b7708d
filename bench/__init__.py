"""Chip benchmark of W4A4+LRC serving (see BENCHMARK.json and PERF.md)."""
