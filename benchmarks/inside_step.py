"""Readers of what a serving program whose layers keep a summary a slot
records about its decode step: the calls of a named kernel of the
decode program in the device trace, priced by the family, and the
engine's own count of the rows whose state a step moved
(``serve.engine.state_rows``, a ``phase_add`` sum a dispatched step,
read as the window's delta) beside the scheduler's count of the rows it
answered. What a kernel's call costs is the family's ``costs.py``;
nothing here knows a block. A program that keeps no such count, a
family that prices no such call and a trace that names no such kernel
read as None, so the line still prints.
"""

from __future__ import annotations

from benchmarks import inside, peaks
from benchmarks.readers import family_costs, started_in_slice, traced

STATE_ROWS = "serve.engine.state_rows"


def decode_kernel_roofline_pct(obs, kernel: str, price: str):
    """The least seconds the chip could take for the calls of
    ``kernel`` in the decode program in the traced slice (each priced
    by the family's ``costs.<price>(config, rows)`` at the mean rows of
    the decode steps begun in the slice: the larger of its FLOPs over
    the peak and its bytes over the bandwidth) over the device seconds
    of that kernel there."""
    trace, costs = traced(obs), family_costs(obs)
    program = getattr(costs, "DECODE_PROGRAM", None)
    price = getattr(costs, price, None)
    if trace is None or program is None or price is None:
        return None
    seconds, calls = inside.kernel_totals(obs, program, (kernel,))[kernel]
    steps = started_in_slice(obs["steps"], trace)
    if seconds <= 0.0 or calls == 0 or not steps:
        return None
    rows = sum(s[2] for s in steps) / len(steps)
    least = peaks.roofline_seconds(
        price(obs["run"]["config"], rows),
        peaks.peaks_of(obs["device"]["kind"]))["seconds"]
    return 100.0 * calls * least / seconds


def state_rows_pct(obs):
    """Of the rows whose state the dispatched decode steps read and
    wrote, the share that answered a request: the scheduler's rows
    answered over the engine's rows stepped, both over the window. The
    rest rode for nobody: a finished request's one step more, a step
    dispatched ahead and dropped."""
    moved = inside.phase_seconds(obs, (STATE_ROWS,))
    answered = (obs["decode_after"]["slot_steps"]
                - obs["decode_before"]["slot_steps"])
    if not moved or answered <= 0:
        return None
    return 100.0 * answered / moved
