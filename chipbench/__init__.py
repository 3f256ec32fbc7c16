"""Chip benchmark of the compression service (see BENCHMARK.json)."""
