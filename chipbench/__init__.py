"""Chip benchmark of the serving system (see BENCHMARK.json)."""
