"""Spans for a loop that turns every few milliseconds (the decode step):
always on, summed where they happen, read as deltas.

``with phase("serve.step"):`` adds the body's ``time.perf_counter``
duration to a table ``name -> [count, seconds]`` and, where jax is
already imported, lies inside a ``jax.profiler.TraceAnnotation`` of the
same name: nothing while no profiler session is open, a span on the
device trace's clock while one is.

Whose table: the one the calling thread or task is :class:`recording`
into, else the process's. A :class:`~ray_tpu.serve.decode_scheduler.
DecodeScheduler` records its loop and, for the length of each engine
call, the executor thread into a table of its own, so what an engine
times inside the call lands with the scheduler that made it, whatever
wraps the engine, and two schedulers in a process do not mix. Such a
table has one writer a name at a time (the loop's task, or the one
engine call it awaits), so no lock is taken.

This module imports nothing of ``ray_tpu`` and never imports jax: hot
paths use it without arming what ``ray_tpu.util.tracing`` arms at
import. That module offers the same names beside its task spans.
"""

from __future__ import annotations

import contextvars
import sys
import time
from typing import Dict, List, Optional

Table = Dict[str, List]     # name -> [count, seconds], cumulative

_process: Table = {}
_table: contextvars.ContextVar[Table] = contextvars.ContextVar(
    "ray_tpu_phase_table", default=_process)


class recording:
    """``with recording(table):`` the phases of this thread, or of this
    asyncio task, go into ``table`` (a dict) until the block ends."""

    __slots__ = ("table", "_token")

    def __init__(self, table: Table):
        self.table = table

    def __enter__(self) -> Table:
        self._token = _table.set(self.table)
        return self.table

    def __exit__(self, *exc) -> None:
        _table.reset(self._token)


def phase_add(name: str, seconds: float) -> None:
    """Count one occurrence of ``name`` that took ``seconds``."""
    table = _table.get()
    entry = table.get(name)
    if entry is None:
        entry = table.setdefault(name, [0, 0.0])
    entry[0] += 1
    entry[1] += seconds


class phase:
    """Times the body into the current table, also when the body
    raises, and keeps the duration as ``.seconds``."""

    __slots__ = ("name", "seconds", "_annotation", "_t0")

    def __init__(self, name: str):
        self.name = name
        self.seconds = 0.0

    def __enter__(self) -> "phase":
        profiler = sys.modules.get("jax.profiler")
        self._annotation = None
        if profiler is not None:
            self._annotation = profiler.TraceAnnotation(self.name)
            self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        phase_add(self.name, self.seconds)


def phase_totals(prefix: str = "",
                 table: Optional[Table] = None) -> Table:
    """A copy of ``{name: [count, seconds]}`` for the names under
    ``prefix`` in ``table``, or in the current one."""
    if table is None:
        table = _table.get()
    return {name: list(entry) for name, entry in list(table.items())
            if name.startswith(prefix)}
