"""Share of the decode loop's time (admit, step, emit) in which active slots stood still for a prefill."""
from benchmarks.inside import prefill_stall_pct as read  # noqa: F401
