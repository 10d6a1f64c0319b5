"""The one general traffic generator. A traffic mix is a data file
(``workloads/<traffic>.json``); this module turns it and ``--seed``
into work. Every seed gets the same set of sizes and arrivals, started
at another point of the same cycle, and its own token ids: runs differ
in their inputs, not in how much work they hold.

Serving mixes (``"kind": "serve"``):
  ``loop``            "closed" (``clients`` callers, each sends its next
                      request when the last is answered) or "open"
                      (arrivals on a schedule at ``rate_per_s``)
  ``slots``, ``slot_len``   the replica's decode batch and cache rows
  ``prompt_lengths``, ``prompt_weights``   the lengths and their shares
  ``output_tokens``   {"min", "max"}: uniform, spaced evenly over a cycle
  ``cycle``           requests in one cycle of the pattern
  ``check_requests``  how many finished requests the reference re-reads
Training mixes (``"kind": "train"``): ``batch``, ``seq``, ``remat``,
``checked_steps``, ``trace_steps``.
"""

from __future__ import annotations

import functools
import math
import random
from typing import Dict, List, Optional


def _apportion(weights: List[float], n: int) -> List[int]:
    """n items split by weights, largest remainders first."""
    total = float(sum(weights))
    exact = [w / total * n for w in weights]
    counts = [int(x) for x in exact]
    by_remainder = sorted(range(len(weights)),
                          key=lambda i: exact[i] - counts[i], reverse=True)
    for i in by_remainder[:n - sum(counts)]:
        counts[i] += 1
    return counts


def cycle_of(traffic: dict) -> List[dict]:
    """One cycle of the mix: ``cycle`` requests, each with its prompt
    length, its output length and (open loop) the gap since the request
    before it. It depends on the file alone, never on the seed."""
    n = int(traffic["cycle"])
    rng = random.Random(int(traffic.get("pattern_seed", 0)))
    lengths = traffic["prompt_lengths"]
    weights = traffic.get("prompt_weights") or [1.0] * len(lengths)
    prompts = [length for length, k in zip(lengths, _apportion(weights, n))
               for _ in range(k)]
    lo, hi = traffic["output_tokens"]["min"], traffic["output_tokens"]["max"]
    outputs = [lo + round(i * (hi - lo) / max(1, n - 1)) for i in range(n)]
    rng.shuffle(prompts)
    rng.shuffle(outputs)
    gaps: List[Optional[float]] = [None] * n
    if traffic["loop"] == "open":
        rate = float(traffic["rate_per_s"])
        # the exponential distribution's quantiles: Poisson arrivals
        # with a fixed set of gaps
        gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
        rng.shuffle(gaps)
    return [{"prompt_len": p, "max_tokens": o, "gap_s": g}
            for p, o, g in zip(prompts, outputs, gaps)]


def serve_plan(traffic: dict, seed: int, seconds: float) -> List[dict]:
    """The requests of one run, in order. Open loop: every request due
    inside the window, with ``due_s`` from the window's start. Closed
    loop: more than the window can finish (``due_s`` None); the clients
    take them in order."""
    cycle = cycle_of(traffic)
    start = int(seed) % len(cycle)
    plan, t, i = [], 0.0, 0
    if traffic["loop"] == "open":
        while True:
            item = cycle[(start + i) % len(cycle)]
            t += item["gap_s"]
            if t >= seconds:
                break
            plan.append({"index": i, "due_s": t, **item})
            i += 1
        return plan
    # closed: bounded by one token a millisecond per client, far above
    # any engine here
    budget = int(traffic["clients"]) * seconds * 1000.0
    while budget > 0:
        item = cycle[(start + i) % len(cycle)]
        plan.append({"index": i, "due_s": None, **item})
        budget -= item["max_tokens"]
        i += 1
    return plan


def prompt_tokens(seed: int, index: int, length: int, vocab: int) -> List[int]:
    rng = random.Random(f"{int(seed)}/{int(index)}")
    return [rng.randrange(vocab) for _ in range(length)]


def check_sample(seed: int, finished: List[dict], k: int) -> List[dict]:
    """Which finished requests the reference re-reads: the longest, and
    ``k - 1`` more drawn from the seed."""
    if not finished:
        return []
    longest = max(finished,
                  key=lambda r: (r["prompt_len"] + len(r["tokens"]),
                                 -r["index"]))
    rest = [r for r in finished if r is not longest]
    rng = random.Random(f"check/{int(seed)}")
    return [longest] + rng.sample(rest, min(k - 1, len(rest)))


def train_batch(seed, step, batch: int, seq: int, vocab: int) -> Dict:
    """Step ``step``'s batch, made on the device: rows that all differ,
    a fresh draw each step. ``seed`` is the run's seed or its key
    (``reference.seed_key(seed)``): the same batch either way. (Called
    under jit by the worker and by the reference alike; imports jax
    late so the driver stays off it.)"""
    import jax

    from benchmarks.reference import seed_key

    key = seed_key(seed) if isinstance(seed, int) else seed
    key = jax.random.fold_in(jax.random.fold_in(key, 7), step)
    tokens = jax.random.randint(key, (batch, seq + 1), 0, vocab)
    return {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}


def batch_maker(seed: int, batch: int, seq: int, vocab: int):
    """``step -> batch`` through one compiled program that takes the
    seed's key as an argument. With the seed a constant of the program
    every seed had a program of its own, which missed the compile cache
    in every run's set-up (PERF.md section 6)."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import seed_key

    key = seed_key(seed)
    make = jax.jit(functools.partial(train_batch, batch=batch, seq=seq,
                                     vocab=vocab))
    return lambda step: make(key, jnp.int32(step))
