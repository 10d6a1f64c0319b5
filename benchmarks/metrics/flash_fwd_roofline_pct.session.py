"""The prefills' windowed flash forward calls, one a window layer of the self-decoder: their least possible time by the family's cost over the kernel's device time in slot_prefill."""
from benchmarks.inside_serve import prefill_flash_roofline_pct as read  # noqa: F401
