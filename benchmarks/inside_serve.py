"""Readers of what a serving program with expert layers and prefill
kernels records: the engine's own counters of the experts a decode step
touched (``serve.engine.experts_hit``, ``.expert_rows``,
``.expert_rows_max``: ``phase_add`` sums a step, read through
``inside.engine_counts`` as the window's means) and the flash forward
calls of the prefill program in the device trace. What a count means in
a model (how many experts a layer holds, what a prefill's kernel calls
cost) is the family's ``costs.py``; nothing here knows a block. A
program that keeps no such counter, a family that prices no such call
and a trace that names no such kernel read as None, so the line still
prints.
"""

from __future__ import annotations

from benchmarks import inside, peaks
from benchmarks.readers import family_costs, traced

EXPERTS_HIT = "serve.engine.experts_hit"
EXPERT_ROWS = "serve.engine.expert_rows"
EXPERT_ROWS_MAX = "serve.engine.expert_rows_max"
PREFILL_PROGRAM = "slot_prefill"
PREFILL_KERNEL = "flash_fwd"


def _held(obs):
    """(experts held in an expert layer, expert layers), or None where
    the family has no expert layers to count."""
    count = getattr(family_costs(obs), "experts_held", None)
    return count(obs["run"]["config"]) if count else None


def experts_hit_pct(obs):
    """Of the experts this chip holds, over all its expert layers, the
    share that got at least one row in a decode step: the window's
    mean. A step reads the weights of those alone."""
    hit, held = inside.engine_counts(obs).get(EXPERTS_HIT), _held(obs)
    if hit is None or not held:
        return None
    return 100.0 * hit / (held[0] * held[1])


def expert_rows_max_over_mean(obs):
    """The fullest held expert's rows over the mean held expert's, each
    summed over the expert layers, in the window's mean decode step: 1
    is an even load; the fullest expert sets how long a layer's grouped
    products run."""
    counts, held = inside.engine_counts(obs), _held(obs)
    fullest, rows = counts.get(EXPERT_ROWS_MAX), counts.get(EXPERT_ROWS)
    if fullest is None or not rows or not held:
        return None
    return fullest * held[0] / rows


def _share_inside(span, lo, hi):
    t0, t1 = span[0], span[1]
    if t1 <= t0:
        return 1.0 if lo <= t0 < hi else 0.0
    return max(0.0, min(t1, hi) - max(t0, lo)) / (t1 - t0)


def prefill_flash_roofline_pct(obs):
    """The least seconds the chip could take for the attention kernel
    calls of the prefills in the traced slice (each call priced by the
    family's ``costs.prefill_flash_costs`` at the prompt's length: the
    larger of its FLOPs over the peak and its bytes over the bandwidth;
    a prefill that straddles an edge of the slice counts by the share
    of its host span inside) over the device seconds of the forward
    kernel in the prefill program there."""
    trace, costs = traced(obs), family_costs(obs)
    price = getattr(costs, "prefill_flash_costs", None)
    if trace is None or price is None:
        return None
    seconds, calls = inside.kernel_totals(
        obs, PREFILL_PROGRAM, (PREFILL_KERNEL,))[PREFILL_KERNEL]
    if seconds <= 0.0 or calls == 0:
        return None
    chip = peaks.peaks_of(obs["device"]["kind"])
    lo, hi = trace["slice"]
    least = sum(
        _share_inside(span, lo, hi) * peaks.roofline_seconds(call, chip)[
            "seconds"]
        for span in obs.get("prefills", [])
        for call in price(obs["run"]["config"], span[2]))
    return 100.0 * least / seconds if least > 0.0 else None
