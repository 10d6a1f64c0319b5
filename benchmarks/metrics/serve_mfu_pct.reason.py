"""Model FLOPs of the traced prefills and decode steps (a row's expected share of the held experts, attention by layer kind) over the slice at the chip's peak."""
from benchmarks.readers import serve_mfu_pct as read  # noqa: F401
