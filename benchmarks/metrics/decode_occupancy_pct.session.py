"""Slot-steps over steps times slots, from the scheduler's counters over the window."""
from benchmarks.readers import decode_occupancy_pct as read  # noqa: F401
