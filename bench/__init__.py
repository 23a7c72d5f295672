"""Chip benchmark of the serving engine: see ``bench/run.py``."""
