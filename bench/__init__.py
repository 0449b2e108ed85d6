"""Chip benchmark of the lockstep engine (see PERF.md)."""
