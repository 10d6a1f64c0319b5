"""The flash forward kernel's least possible time over its device time in the traced steps."""
from benchmarks.inside import flash_fwd_roofline_pct as read  # noqa: F401
