"""Of the decode program's device seconds, the share that no part names."""
from benchmarks.inside_parts import unscoped_pct as read  # noqa: F401
