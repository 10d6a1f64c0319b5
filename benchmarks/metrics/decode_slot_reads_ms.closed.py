"""Seconds in the engine's per-slot one-element reads (check, read) per decode step of the window."""
from benchmarks.inside import decode_slot_reads_ms as read  # noqa: F401
