"""Chip benchmark of the coded trainer (``BENCHMARK.json``)."""
