"""Of the decode program's device seconds in the traced slice, the share no part of the block names (the check on the whole: parts + this = the program)."""
from benchmarks.inside_parts import unscoped_pct as read  # noqa: F401
