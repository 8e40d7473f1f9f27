"""Chip benchmark for the planned block-sparse FFN path (see PERF.md)."""
