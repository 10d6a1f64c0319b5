"""The prefills' windowed and full flash forward calls: their least possible time by the family's cost over the kernel's device time in slot_prefill."""
from benchmarks.inside_serve import prefill_flash_roofline_pct as read  # noqa: F401
