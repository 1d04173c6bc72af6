"""Chip benchmark of the LM training path: see ``run.py`` and BENCHMARK.json."""
