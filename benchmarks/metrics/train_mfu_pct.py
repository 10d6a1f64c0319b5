"""Model FLOPs of the traced steps over the slice at the chip's peak; recompute not counted."""
from benchmarks.readers import train_mfu_pct as read  # noqa: F401
