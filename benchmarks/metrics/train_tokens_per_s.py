"""report() callbacks that reach the driver inside the window, times the batch's tokens, per second."""
from benchmarks.readers import train_tokens_per_s as read  # noqa: F401
