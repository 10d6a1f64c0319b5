"""Prompt tokens prefilled in the window over the seconds the engine's prefill calls took (dispatch to first token fetched), by the engine's own counter and span."""
from benchmarks.inside_scan import prefill_tokens_per_s as read  # noqa: F401
