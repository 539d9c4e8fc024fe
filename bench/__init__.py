"""Chip benchmark of the Hecaton trainer (see BENCHMARK.json)."""
