"""Seconds in the engine's check, put, dispatch and read phases per decode step of the window."""
from benchmarks.inside import decode_host_ms as read  # noqa: F401
