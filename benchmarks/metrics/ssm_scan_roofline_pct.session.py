"""The prefills' selective-scan calls, one a Mamba layer of the self-decoder: their least possible time by the family's cost (the recurrence's FLOPs over the peak or its bytes over the bandwidth) over the ssm_scan kernel's device time in slot_prefill."""
from benchmarks.inside_scan import prefill_scan_roofline_pct as read  # noqa: F401
