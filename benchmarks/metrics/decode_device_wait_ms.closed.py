"""Seconds in serve.engine.wait (the first slot's read, which waits for the device) per decode step of the window."""
from benchmarks.inside import decode_device_wait_ms as read  # noqa: F401
