"""Chip benchmark of the GAQ force field: one cell of BENCHMARK.json per run."""
