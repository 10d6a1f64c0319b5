"""Median host time of one train step, ending in float(loss)."""
from benchmarks.readers import step_ms as read  # noqa: F401
