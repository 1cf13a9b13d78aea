"""Chip benchmark of the decision service (see BENCHMARK.json and run.py)."""
