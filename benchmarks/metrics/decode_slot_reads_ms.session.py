"""Seconds in the engine's check and read phases per decode step of the window: host only, so a device read that comes back into the step shows here."""
from benchmarks.inside import decode_slot_reads_ms as read  # noqa: F401
