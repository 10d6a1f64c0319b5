"""Readers of what a serving program whose layers share one growing
cache records: the calls of the decode step's attention kernel in the
device trace, priced by the family from the step's rows *and the
positions they attend* (``inside_step.decode_kernel_roofline_pct``
prices a call from the rows alone, which is right for a state that
does not grow and wrong for rows that do), and the engine's own count
of the prompt positions its prefills ran the layers behind the last
mixing one over (``serve.engine.prefill_cross_rows``, a ``phase_add``
pair a prefill, read as the window's delta beside
``serve.engine.prefill_tokens``). What a call costs is the family's
``costs.py``; nothing here knows a block. A program that keeps no such
count, a family that prices no such call and a trace that names no
such kernel read as None, so the line still prints.
"""

from __future__ import annotations

from benchmarks import inside, peaks
from benchmarks.inside_scan import ENGINE_PREFILL_TOKENS
from benchmarks.readers import family_costs, started_in_slice, traced

ATTEND_KERNEL = "decode_attend"
PREFILL_CROSS_ROWS = "serve.engine.prefill_cross_rows"


def decode_attend_roofline_pct(obs):
    """The least seconds the chip could take for the calls of the
    decode step's attention kernel in the traced slice over the device
    seconds of that kernel in the decode program there. A call's least
    is the mean, over the decode steps begun in the slice and the calls
    the family prices in each (``costs.decode_attend_costs(config,
    rows, positions)``, a call a layer that reads the growing cache),
    of the larger of its FLOPs over the peak and its bytes over the
    bandwidth; the calls are the trace's own."""
    trace, costs = traced(obs), family_costs(obs)
    program = getattr(costs, "DECODE_PROGRAM", None)
    price = getattr(costs, "decode_attend_costs", None)
    if trace is None or program is None or price is None:
        return None
    seconds, calls = inside.kernel_totals(
        obs, program, (ATTEND_KERNEL,))[ATTEND_KERNEL]
    steps = started_in_slice(obs["steps"], trace)
    if seconds <= 0.0 or calls == 0 or not steps:
        return None
    chip = peaks.peaks_of(obs["device"]["kind"])
    priced = [peaks.roofline_seconds(call, chip)["seconds"]
              for _, _, rows, attended in steps
              for call in price(obs["run"]["config"], rows, attended)]
    if not priced:
        return None
    return 100.0 * calls * (sum(priced) / len(priced)) / seconds


def prefill_cross_rows_pct(obs):
    """Of the prompt positions prefilled in the window, the share that
    the layers behind the last one that mixes over the sequence ran
    over, by the engine's own counters."""
    ran = inside.phase_seconds(obs, (PREFILL_CROSS_ROWS,))
    tokens = inside.phase_seconds(obs, (ENGINE_PREFILL_TOKENS,))
    if ran is None or not tokens:
        return None
    return 100.0 * ran / tokens
