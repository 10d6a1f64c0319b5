"""The arithmetic the per-metric readers share. A reader takes the
run's observations (``obs``: what the driver and the worker recorded)
and returns a number, or None where it finds nothing to read; each
metric's own file under ``metrics/`` picks one.

``obs`` holds: ``run`` (the cell's configuration, its family's
directory, traffic mix, seconds),
``window`` [t0, t1] on the host's monotonic clock, ``setup_s``,
``requests`` (serving: one record per request, with the client's and the
replica's stamps), ``reports`` (training: [arrival, phase, step, loss]),
``steps`` and ``prefills`` (host spans of the engine's or the trainer's
calls inside the window), ``decode_before``/``decode_after`` (the
scheduler's counters), ``trace`` (the reduced profiler trace of the
traced slice, or None) and ``device``.
"""

from __future__ import annotations

import math
import statistics

from benchmarks import loader, peaks


def median_ms(seconds):
    seconds = list(seconds)
    return statistics.median(seconds) * 1e3 if seconds else None


def answered(obs):
    """Requests answered, and rightly, before the window closed."""
    t_end = obs["window"][1]
    return [r for r in obs["requests"] if r.get("ok") and r["done"] <= t_end]


# ---- end to end

def setup_s(obs):
    return obs["setup_s"]


def serve_tokens_per_s(obs):
    """Output tokens made inside the window, per second of it. The server
    answers a request whole, so its tokens count when the client has the
    answer. A request that was still decoding when the window closed
    counts the share of its tokens that fell inside: they come one a
    decode step from the replica's prefill stamp to its reply, and the
    answer is waited for. Without that share a run's rate would hang on
    which requests happened to end just before the close."""
    t_end = obs["window"][1]
    total = 0.0
    for r in obs["requests"]:
        if not r.get("ok"):
            continue
        if r["done"] <= t_end:
            total += len(r["tokens"])
        elif r.get("t_prefill") is not None and r["t_prefill"] < t_end:
            total += len(r["tokens"]) * (t_end - r["t_prefill"]) / (
                r["t_done"] - r["t_prefill"])
    return total / obs["seconds"]


def request_p95_s(obs):
    """95th percentile, over every request due in the window, of reply
    time less due time; one that failed or never came ranks last, at the
    longest wait the run allows."""
    t_end = obs["window"][1]
    worst = t_end + 60.0
    waits = sorted((r["done"] if r.get("ok") else worst) - r["due"]
                   for r in obs["requests"] if "due" in r and "sent" in r)
    if not waits:
        return None
    return waits[max(0, math.ceil(0.95 * len(waits)) - 1)]


def train_tokens_per_s(obs):
    """Tokens of the steps whose report reached the driver inside the
    window, and of the step under way at its close the share that fell
    inside (the worker runs on past the close, so that step's report
    comes too), per second of the window. The window opens as a step
    starts. Whole steps alone would make the rate jump by one step's
    worth, 1 part in 60, between runs that differ by a millisecond."""
    lo, hi = obs["window"]
    mix = obs["run"]["traffic"]
    at = sorted(t for t, phase, *_ in obs["reports"]
                if phase == "run" and t >= lo)
    inside = [t for t in at if t <= hi]
    after = [t for t in at if t > hi]
    steps = float(len(inside))
    if after:
        begun = inside[-1] if inside else lo
        steps += (hi - begun) / (after[0] - begun)
    return steps * mix["batch"] * mix["seq"] / obs["seconds"]


# ---- host spans and counters

def step_ms(obs):
    return median_ms(s[1] - s[0] for s in obs["steps"])


def prefill_ms(obs):
    return median_ms(s[1] - s[0] for s in obs.get("prefills", []))


def frontdoor_ms(obs):
    """Client reply time less the time the replica's __call__ held the
    request: the proxy, the router and two hops."""
    return median_ms((r["done"] - r["sent"]) - (r["t_done"] - r["t_call"])
                     for r in answered(obs))


def queue_wait_ms(obs):
    return median_ms(r["t_prefill"] - r["t_call"] for r in answered(obs)
                     if r.get("t_prefill") is not None)


def generator_late_ms(obs):
    return median_ms(r["sent"] - r["due"] for r in obs["requests"]
                     if "due" in r and "sent" in r)


def decode_occupancy_pct(obs):
    a, b = obs["decode_before"], obs["decode_after"]
    steps = b["steps"] - a["steps"]
    if steps <= 0:
        return None
    slots = obs["run"]["traffic"]["slots"]
    return 100.0 * (b["slot_steps"] - a["slot_steps"]) / (steps * slots)


# ---- the traced slice

def traced(obs):
    trace = obs.get("trace")
    return trace if trace and trace.get("window_s", 0) > 0 else None


def _peaks(obs):
    return peaks.peaks_of(obs["device"]["kind"])


def family_costs(obs):
    """The arithmetic of the cell's model family (its ``costs.py``)."""
    return loader.family_module(obs["run"]["family"], "costs")


def device_idle_pct(obs):
    trace = traced(obs)
    if trace is None:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def started_in_slice(spans, trace):
    lo, hi = trace["slice"]
    return [s for s in spans if lo <= s[0] < hi]


def serve_mfu_pct(obs):
    """The model FLOPs of the prefills and decode steps that started in
    the traced slice, over the slice at the chip's bf16 peak."""
    trace = traced(obs)
    if trace is None:
        return None
    config, costs = obs["run"]["config"], family_costs(obs)
    flops = 0.0
    for _, _, rows, attended in started_in_slice(obs["steps"], trace):
        flops += costs.forward_flops(config, rows, attended, logit_rows=rows)
    for _, _, length in started_in_slice(obs["prefills"], trace):
        flops += costs.forward_flops(
            config, length, length * (length + 1) // 2, logit_rows=1)
    if flops == 0.0:
        return None
    return 100.0 * flops / (trace["window_s"]
                            * _peaks(obs)["bf16_flops_per_s"])


def train_mfu_pct(obs):
    trace = traced(obs)
    if trace is None:
        return None
    mix = obs["run"]["traffic"]
    steps = len(started_in_slice(obs["steps"], trace))
    if not steps:
        return None
    flops = steps * family_costs(obs).train_flops(
        obs["run"]["config"], mix["batch"], mix["seq"])
    return 100.0 * flops / (trace["window_s"]
                            * _peaks(obs)["bf16_flops_per_s"])
