"""Chip benchmark for the repository's serving path (see PERF.md)."""
