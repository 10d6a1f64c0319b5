"""Readers of a decode step's device time by named part of the block.
The program names the parts: ``DecodeScheduler.stats()["parts"]`` is
``{program: {instruction: [run, part]}}``, made from the compiled
executable that runs (which run of alike layers and which part of the
block each device operation of the decode step computes), and reaches
here as ``obs["decode_after"]["parts"]``. The device trace names every
operation of the slice ``<program>/<instruction>``
(``obs["trace"]["op_totals"]``); the join of the two is seconds by
(run, part). What the parts are called is the program's and the
callers' (each metric's own file passes the names it sums); nothing here
knows a block. A program that gives no table (the commits before PR 38,
an engine that compiles nothing, an executable whose scopes are another
tree's), a trace that names no operation of the program and a slice in
which no step began read as None, so the line still prints.
"""

from __future__ import annotations

from benchmarks.readers import family_costs, started_in_slice, traced


def _joined(obs):
    """(the decode program's name, the trace, {(run, part): seconds} of
    the operations the table names), or None where one of them is
    missing. An operation the table lacks (a ``while``, which holds its
    body's operations and would count them twice) is left out."""
    trace = traced(obs)
    program = getattr(family_costs(obs), "DECODE_PROGRAM", None)
    table = ((obs.get("decode_after") or {}).get("parts") or {}).get(program)
    if trace is None or program is None or not table:
        return None
    found, seen = {}, False
    for name, (seconds, _) in (trace.get("op_totals") or {}).items():
        where, _, instruction = name.partition("/")
        if where != program:
            continue
        seen = True
        if instruction in table:
            key = tuple(table[instruction])
            found[key] = found.get(key, 0.0) + seconds
    return (program, trace, found) if seen else None


def part_seconds(obs):
    """{(run, part): device seconds in the traced slice} of the decode
    program's operations that lie in a part (a run's ``layer_weights``
    is one; an operation outside every run and part is in none)."""
    joined = _joined(obs)
    if joined is None:
        return None
    return {key: s for key, s in joined[2].items() if key[1] is not None}


def part_ms(obs, parts):
    """Device milliseconds a decode step in ``parts``, summed over all
    runs: their seconds in the traced slice over the decode steps begun
    in it."""
    seconds = part_seconds(obs)
    if seconds is None:
        return None
    steps = started_in_slice(obs["steps"], obs["trace"])
    if not steps:
        return None
    return 1e3 * sum(s for (_, part), s in seconds.items()
                     if part in parts) / len(steps)


def unscoped_pct(obs):
    """Of the device seconds in which an operation of the decode
    program ran, the share that no part names: operations outside every
    run and part, the loops' own time between the operations of their
    bodies, and whatever the table lacks. The check on the whole: the
    parts and this share add up to the program, and a container counted
    beside its body would drive it below zero."""
    joined = _joined(obs)
    if joined is None:
        return None
    program, trace, found = joined
    whole = (trace.get("program_seconds") or {}).get(program, 0.0)
    if whole <= 0.0 or not started_in_slice(obs["steps"], trace):
        return None
    named = sum(s for (_, part), s in found.items() if part is not None)
    return 100.0 * (whole - named) / whole
