"""The prefills' retention calls: their least possible time by the family's cost (the retention's FLOPs over the peak or its bytes over the bandwidth) over the retention_chunk kernel's device time in slot_prefill."""
from benchmarks import inside_scan


def read(obs):
    return inside_scan.prefill_kernel_roofline_pct(
        obs, "retention_chunk", "prefill_retention_costs")
