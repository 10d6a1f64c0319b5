"""Least seconds the chip could take for the traced decode steps (the family's bytes a step: every layer weight and the head once, each row's retention state read and written in every layer) over the decode program's device seconds."""
from benchmarks.inside import decode_roofline_pct as read  # noqa: F401
