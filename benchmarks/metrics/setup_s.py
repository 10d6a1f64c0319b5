"""Process start to window start, compile included (host clock)."""
from benchmarks.readers import setup_s as read  # noqa: F401
