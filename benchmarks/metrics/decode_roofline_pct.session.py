"""Least seconds the chip could take for the traced decode steps (the family's bytes a step: every weight once, each row's Mamba state and window of K/V, the one growing cache once for each of the eight layers that read it) over the decode program's device seconds."""
from benchmarks.inside import decode_roofline_pct as read  # noqa: F401
