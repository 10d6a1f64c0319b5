"""Least seconds the chip could take for the traced decode steps (the family's bytes a step, the held experts that got a row by the engine's own count) over the decode program's device seconds."""
from benchmarks.inside import decode_roofline_pct as read  # noqa: F401
