"""Chip benchmark of the vectorized Nezha engine (see BENCHMARK.json)."""
