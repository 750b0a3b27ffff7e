"""Chip benchmark of the router and the what-if engine: see run.py."""
