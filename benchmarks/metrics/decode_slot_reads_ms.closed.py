"""Seconds in the engine's check and read phases (host only since PR 26: a device read that comes back into the step shows here) per decode step."""
from benchmarks.inside import decode_slot_reads_ms as read  # noqa: F401
