"""Median host time of engine.prefill."""
from benchmarks.readers import prefill_ms as read  # noqa: F401
