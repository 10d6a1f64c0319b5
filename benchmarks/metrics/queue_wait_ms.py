"""Median wait from the replica's __call__ to the request's prefill."""
from benchmarks.readers import queue_wait_ms as read  # noqa: F401
