"""Of the prompt positions the engine prefilled in the window, the share the cross-decoder ran over (the engine's own two counters): 100 over the mean prompt while a prefill stops at the cross-decoder, 100 once it does not."""
from benchmarks.inside_attend import prefill_cross_rows_pct as read  # noqa: F401
