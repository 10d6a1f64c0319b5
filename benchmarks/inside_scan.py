"""Readers of what a serving program with recurrent layers records: the
scan kernel's calls of the prefill program in the device trace, and the
engine's own span and counter of its prefills
(``serve.engine.prefill``, seconds from dispatch to the first token
fetched, and ``serve.engine.prefill_tokens``, the prompts' lengths:
``phase`` and ``phase_add`` sums, read as the window's deltas). What a
prefill's kernel calls cost is the family's ``costs.py``; nothing here
knows a block. A program that keeps no such span, a family that prices
no such call and a trace that names no such kernel read as None, so the
line still prints.
"""

from __future__ import annotations

from benchmarks import inside, peaks
from benchmarks.inside_serve import PREFILL_PROGRAM, _share_inside
from benchmarks.readers import family_costs, traced

SCAN_KERNEL = "ssm_scan"
ENGINE_PREFILL = "serve.engine.prefill"
ENGINE_PREFILL_TOKENS = "serve.engine.prefill_tokens"


def prefill_kernel_roofline_pct(obs, kernel: str, price: str):
    """The least seconds the chip could take for the calls of ``kernel``
    that the prefills in the traced slice make (each call priced by the
    family's ``costs.<price>`` at the prompt's length: the larger of
    its FLOPs over the peak and its bytes over the bandwidth; a prefill
    that straddles an edge of the slice counts by the share of its host
    span inside) over the device seconds of that kernel in the prefill
    program there."""
    trace = traced(obs)
    price = getattr(family_costs(obs), price, None)
    if trace is None or price is None:
        return None
    seconds, calls = inside.kernel_totals(
        obs, PREFILL_PROGRAM, (kernel,))[kernel]
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


def prefill_scan_roofline_pct(obs):
    return prefill_kernel_roofline_pct(obs, SCAN_KERNEL,
                                       "prefill_scan_costs")


def prefill_tokens_per_s(obs):
    """Prompt tokens the engine prefilled in the window over the
    seconds its prefill calls took, each from its dispatch to its first
    token on the host: what a prefill costs the decoding rows, which
    stand still for it."""
    tokens = inside.phase_seconds(obs, (ENGINE_PREFILL_TOKENS,))
    seconds = inside.phase_seconds(obs, (ENGINE_PREFILL,))
    if not tokens or not seconds:
        return None
    return tokens / seconds
