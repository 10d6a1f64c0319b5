"""Median lateness of the load generator against its schedule."""
from benchmarks.readers import generator_late_ms as read  # noqa: F401
