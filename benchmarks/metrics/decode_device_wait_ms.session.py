"""Seconds in serve.engine.wait (the step's one device-to-host fetch: the row of picks of the step before) per decode step of the window."""
from benchmarks.inside import decode_device_wait_ms as read  # noqa: F401
