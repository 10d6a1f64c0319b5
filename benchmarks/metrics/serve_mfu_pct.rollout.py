"""Model FLOPs of the traced prefills and decode steps (the matrices of every layer, each Mamba layer's convolution and recurrence, the two attention layers' pairs) over the slice at the chip's peak."""
from benchmarks.readers import serve_mfu_pct as read  # noqa: F401
