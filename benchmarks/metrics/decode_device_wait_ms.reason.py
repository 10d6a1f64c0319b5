"""Seconds in serve.engine.wait (the step's one device-to-host fetch: the argmax row and the three expert counts) per decode step of the window."""
from benchmarks.inside import decode_device_wait_ms as read  # noqa: F401
