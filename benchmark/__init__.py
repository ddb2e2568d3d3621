"""Chip benchmark of the served rebalance path (see BENCHMARK.json, PERF.md)."""
