"""Model FLOPs of the traced prefills and decode steps (the self-decoder's matrices, scans and window pairs at every position; the rest of the full layer, the cross-decoder and the head at the positions whose logits are needed; the full and cross layers' pairs) over the slice at the chip's peak."""
from benchmarks.readers import serve_mfu_pct as read  # noqa: F401
