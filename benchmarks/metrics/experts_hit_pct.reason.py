"""Held experts that got a row in a decode step, over every held expert of every expert layer: the window's mean of the engine's own count."""
from benchmarks.inside_serve import experts_hit_pct as read  # noqa: F401
