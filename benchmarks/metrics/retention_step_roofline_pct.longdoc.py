"""The decode steps' state passes: their least possible time by the family's cost (each row's state read and written once over the bandwidth, or the step's FLOPs over the peak) over the retention_step kernel's device time in slot_decode_step."""
from benchmarks import inside_step


def read(obs):
    return inside_step.decode_kernel_roofline_pct(
        obs, "retention_step", "retention_step_costs")
