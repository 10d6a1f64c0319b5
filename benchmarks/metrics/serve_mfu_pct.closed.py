"""Model FLOPs of the traced prefills and decode steps over the slice at the chip's peak."""
from benchmarks.readers import serve_mfu_pct as read  # noqa: F401
