"""The fullest held expert's rows over the mean held expert's in the window's mean decode step (1 = an even load), from the engine's own counts."""
from benchmarks.inside_serve import expert_rows_max_over_mean as read  # noqa: F401
