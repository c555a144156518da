"""Chip benchmark of the SSL training path: see ``run.py`` and ``PERF.md``."""
