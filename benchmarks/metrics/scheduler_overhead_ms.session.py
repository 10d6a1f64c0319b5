"""Seconds of serve.hop (executor hand-off) and serve.emit (bookkeeping) per decode step of the window."""
from benchmarks.inside import scheduler_overhead_ms as read  # noqa: F401
