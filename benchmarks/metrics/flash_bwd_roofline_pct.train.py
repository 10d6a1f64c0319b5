"""The two flash backward kernels' least possible time (priced together) over their device time."""
from benchmarks.inside import flash_bwd_roofline_pct as read  # noqa: F401
