"""Chip benchmark of the FLAD serving and training paths (see BENCHMARK.json)."""
