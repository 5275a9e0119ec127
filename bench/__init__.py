"""Chip benchmark of the training path: see ``bench/run.py`` and PERF.md."""
