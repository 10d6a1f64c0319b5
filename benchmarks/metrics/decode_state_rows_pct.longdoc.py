"""Of the rows whose whole state the dispatched decode steps read and wrote (the engine's own count), the share that answered a request (the scheduler's count)."""
from benchmarks.inside_step import state_rows_pct as read  # noqa: F401
