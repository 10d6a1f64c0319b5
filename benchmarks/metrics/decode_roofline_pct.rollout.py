"""Least seconds the chip could take for the traced decode steps (the family's bytes a step: every parameter once, each row's recurrent state read and written in every Mamba layer, the K and V attended) over the decode program's device seconds."""
from benchmarks.inside import decode_roofline_pct as read  # noqa: F401
