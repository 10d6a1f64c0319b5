"""Model FLOPs of the traced prefills and decode steps (the matrices of every layer, the head for the rows whose logits are needed, each layer's retention: the products with the state and the pairs inside a chunk) over the slice at the chip's peak."""
from benchmarks.readers import serve_mfu_pct as read  # noqa: F401
