"""The decode steps' attention over the one growing cache, a call the full layer and each cross layer: their least possible time by the family's cost (the K and V of the positions the step's rows attend over the bandwidth) over the decode_attend kernel's device time in slot_decode_step."""
from benchmarks.inside_attend import decode_attend_roofline_pct as read  # noqa: F401
