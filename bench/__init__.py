"""The on-chip benchmark of the served alignment path (see PERF.md)."""
