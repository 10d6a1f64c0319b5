"""Readers of what the program records about itself: the phase sums of
``DecodeScheduler.stats()["phases"]`` (``{name: [count, seconds]}``,
cumulative, made by ``ray_tpu.util.phases.phase`` inside the scheduler
and the slot engine), the flash kernels under their own names in the
device trace, and the decode program's device time there. A program that
records neither (the commits before PR 25) reads as None, so its line
still prints.

Phase sums are deltas of ``obs["decode_after"]`` less
``obs["decode_before"]``: the interval of ``decode_occupancy_pct``.
"""

from __future__ import annotations

import re

from benchmarks import peaks
from benchmarks.readers import family_costs, started_in_slice, traced

ENGINE_HOST = ("serve.engine.check", "serve.engine.put",
               "serve.engine.dispatch", "serve.engine.read")
SLOT_READS = ("serve.engine.check", "serve.engine.read")
ENGINE_WAIT = ("serve.engine.wait",)
SCHEDULER_OVERHEAD = ("serve.hop", "serve.emit")
ADMIT_STALL = ("serve.admit_stall",)
LOOP = ("serve.admit", "serve.step", "serve.emit")
# whatever else an engine records under this prefix is a counter of its
# own (``phase_add(name, n)``), which its family's costs may price
ENGINE = "serve.engine."

TRAIN_PROGRAM = "local_step"


def phase_seconds(obs, names):
    """Seconds the window added to the phases ``names``, or None where
    the program recorded one of them nowhere."""
    before = obs["decode_before"].get("phases") or {}
    after = obs["decode_after"].get("phases")
    if after is None or any(name not in after for name in names):
        return None
    return sum(after[name][1] - before.get(name, [0, 0.0])[1]
               for name in names)


def steps_in_window(obs):
    return obs["decode_after"]["steps"] - obs["decode_before"]["steps"]


def ms_per_step(obs, names):
    seconds, steps = phase_seconds(obs, names), steps_in_window(obs)
    if seconds is None or steps <= 0:
        return None
    return 1e3 * seconds / steps


def share_pct(obs, part, whole):
    top, bottom = phase_seconds(obs, part), phase_seconds(obs, whole)
    if top is None or not bottom or steps_in_window(obs) <= 0:
        return None
    return 100.0 * top / bottom


def kernel_of(instruction, kernels):
    """Which of ``kernels`` an HLO instruction is a call of, or None. The
    TPU compiler names a Mosaic call after the ``name=`` of its
    ``pallas_call`` and numbers it: ``flash_fwd.15``."""
    kernel = re.sub(r"\.\d+$", "", instruction)
    return kernel if kernel in kernels else None


def kernel_totals(obs, program, kernels):
    """(device seconds, calls) in the traced slice of each of the
    ``kernels``, summed over the ``op_totals`` entries
    ``<program>/<instruction>`` that are calls of it."""
    totals = ((obs.get("trace") or {}).get("op_totals")) or {}
    found = {k: [0.0, 0] for k in kernels}
    for name, (seconds, calls) in totals.items():
        where, _, instruction = name.partition("/")
        kernel = kernel_of(instruction, kernels)
        if where == program and kernel is not None:
            found[kernel][0] += seconds
            found[kernel][1] += calls
    return found


def roofline_pct(obs, kernels, counted, cost_of):
    """The least time the chip could take for the calls of ``counted``
    (``cost_of`` prices one, at the shape the cell's family gives for its
    configuration and traffic files) over the device time
    of all the ``kernels`` that do that work together."""
    found = kernel_totals(obs, TRAIN_PROGRAM, kernels)
    seconds = sum(s for s, _ in found.values())
    calls = found[counted][1]
    if seconds <= 0.0 or calls == 0:
        return None
    shape = family_costs(obs).flash_shape(obs["run"]["config"],
                                          obs["run"]["traffic"])
    least = peaks.roofline_seconds(cost_of(*shape),
                                   peaks.peaks_of(obs["device"]["kind"]))
    return 100.0 * calls * least["seconds"] / seconds


def engine_counts(obs):
    """name -> the window's mean a decode step of each counter the
    engine keeps under ``serve.engine.`` beside its timed phases (none
    in ``JaxSlotEngine``; a sparse-expert engine counts the experts it
    touched)."""
    steps, timed = steps_in_window(obs), ENGINE_HOST + ENGINE_WAIT
    if steps <= 0:
        return {}
    return {name: phase_seconds(obs, (name,)) / steps
            for name in obs["decode_after"].get("phases") or {}
            if name.startswith(ENGINE) and name not in timed}


def decode_roofline_pct(obs):
    """The least seconds the chip could take for the decode steps begun
    in the traced slice (each step's bytes by the family's
    ``costs.decode_step_bytes`` over the memory bandwidth, or its FLOPs
    over the peak where that is longer) over the device seconds in the
    slice in which an operation of the family's decode program
    (``costs.DECODE_PROGRAM``) ran. A family without the two names, or
    a trace that does not name the program's operations, reads None."""
    trace, costs = traced(obs), family_costs(obs)
    program = getattr(costs, "DECODE_PROGRAM", None)
    if trace is None or program is None or not hasattr(
            costs, "decode_step_bytes"):
        return None
    seconds = trace["program_seconds"].get(program, 0.0)
    steps = started_in_slice(obs["steps"], trace)
    if seconds <= 0.0 or not steps:
        return None
    config, chip = obs["run"]["config"], peaks.peaks_of(obs["device"]["kind"])
    counts = engine_counts(obs)
    least = sum(peaks.roofline_seconds(
        {"flops": costs.forward_flops(config, rows, attended,
                                      logit_rows=rows),
         "bytes": costs.decode_step_bytes(config, rows, attended, counts)},
        chip)["seconds"] for _, _, rows, attended in steps)
    return 100.0 * least / seconds


def decode_device_wait_ms(obs):
    return ms_per_step(obs, ENGINE_WAIT)


def decode_host_ms(obs):
    return ms_per_step(obs, ENGINE_HOST)


def decode_slot_reads_ms(obs):
    return ms_per_step(obs, SLOT_READS)


def scheduler_overhead_ms(obs):
    return ms_per_step(obs, SCHEDULER_OVERHEAD)


def prefill_stall_pct(obs):
    return share_pct(obs, ADMIT_STALL, LOOP)


def flash_fwd_roofline_pct(obs):
    return roofline_pct(obs, ("flash_fwd",), "flash_fwd",
                        peaks.flash_fwd_cost)


def flash_bwd_roofline_pct(obs):
    return roofline_pct(obs, ("flash_bwd_dkv", "flash_bwd_dq"),
                        "flash_bwd_dkv", peaks.flash_bwd_cost)
