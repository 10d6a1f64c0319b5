"""Output tokens of every request answered inside the window, per second of it, counted at the client."""
from benchmarks.readers import serve_tokens_per_s as read  # noqa: F401
