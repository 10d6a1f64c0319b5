"""Median host time of engine.step (it ends in a sync)."""
from benchmarks.readers import step_ms as read  # noqa: F401
