"""From a profiler trace to numbers: device busy time, the operations
that took most of it, and the idle gaps by what the host was doing.

The reduction works on plain lists of ``(name, start_ns, duration_ns)``
so that it can be checked on a hand-built trace; :func:`read_xplane`
fills them from an ``.xplane.pb`` with ``jax.profiler.ProfileData``
alone. The traced slice is the benchmark's own ``bench.window``
annotation, which sits on the same clock as the device's events.
"""

from __future__ import annotations

import bisect
import glob
import itertools
import os
from typing import Dict, Iterable, Iterator, List, Tuple

Event = Tuple[str, float, float]           # name, start_ns, duration_ns

WINDOW = "bench.window"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"        # one event for each run of a program
# host annotations that label idle gaps: the benchmark's own, and the
# program's (``ray_tpu.util.phases.phase``), which lie inside them
HOST_SPANS = ("engine.step", "engine.prefill", "train.step")
PROGRAM_SPANS = ("serve.", "train.")        # by the start of their names


def labels_gaps(name: str) -> bool:
    return name in HOST_SPANS or name.startswith(PROGRAM_SPANS)


def merge(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Union of [start, end) intervals as a sorted, disjoint list."""
    out: List[List[float]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e - s))
    return out


def busy_ns(events: Iterable[Event], lo: float, hi: float) -> float:
    """Nanoseconds of [lo, hi) in which at least one event ran."""
    return sum(e - s for s, e in merge(
        (s, s + d) for _, s, d in clip(events, lo, hi)))


def short_name(name: str) -> str:
    """An operation's own name. The device's events carry the whole HLO
    instruction (``%fusion.3 = bf16[8,2048]{...} fusion(...)``): keep
    what stands before the ``=``. A program's event is named
    ``jit_step(<hash>)``: keep ``step``."""
    name = name.split(" = ", 1)[0].split("(", 1)[0].strip().lstrip("%")
    return (name[4:] if name.startswith("jit_") else name)[:80]


def in_programs(ops: Iterable[Event], modules: Iterable[Event]) -> List[Event]:
    """Operations named ``<program>/<operation>``: two programs number
    their instructions alike (each has a ``while.4``), so an operation's
    name says little without the program that was running."""
    modules = sorted(modules, key=lambda e: e[1])
    starts = [e[1] for e in modules]
    out = []
    for name, start, dur in ops:
        i = bisect.bisect_right(starts, start) - 1
        inside = i >= 0 and start < modules[i][1] + modules[i][2]
        program = short_name(modules[i][0]) if inside else "?"
        out.append((f"{program}/{short_name(name)}", start, dur))
    return out


def self_times(events: Iterable[Event]) -> List[Event]:
    """Each event with the time of the events nested inside it taken
    off: a ``while`` over the layers holds every operation of its body,
    and its own time is what is left between them."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    own = [e[2] for e in evs]
    stack: List[Tuple[float, int]] = []         # (end, index), innermost last
    for i, (_, start, dur) in enumerate(evs):
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack and start + dur <= stack[-1][0]:
            own[stack[-1][1]] -= dur
        stack.append((start + dur, i))
    return [(name, start, max(0.0, t)) for (name, start, _), t in zip(evs, own)]


def top_ops(events: Iterable[Event], lo: float, hi: float,
            k: int = 10) -> List[List]:
    """[name, seconds] of the k operations with most time of their own
    in [lo, hi)."""
    total: Dict[str, float] = {}
    for name, _, dur in self_times(clip(events, lo, hi)):
        total[name] = total.get(name, 0.0) + dur
    ranked = sorted(total.items(), key=lambda kv: kv[1], reverse=True)
    return [[name, ns / 1e9] for name, ns in ranked[:k]]


def covering(thread: List[Event], starts: List[float], moment: float,
             look_back: int = 400) -> Iterator[Event]:
    """The events of one host thread that cover a moment, innermost
    first. A thread's events nest, so of those that started before the
    moment the later one lies inside the earlier."""
    i = bisect.bisect_right(starts, moment) - 1
    for j in range(i, max(-1, i - look_back - 1), -1):
        if moment < thread[j][1] + thread[j][2]:
            yield thread[j]


def what_host_did(thread: List[Event], starts: List[float],
                  moment: float) -> Tuple[float, str]:
    """(start, label) of the innermost span that covers a moment on one
    host thread, or (-inf, "") where none does. A span of the program
    labels the gap by its name (``serve.engine.wait``); one of the
    benchmark's own, which is coarser, adds the innermost event inside
    it (``engine.prefill: PjitFunction``)."""
    innermost = None
    for name, start, _ in covering(thread, starts, moment):
        if name in HOST_SPANS:
            return start, (name if innermost is None
                           else f"{name}: {innermost}")
        if name.startswith(PROGRAM_SPANS):
            return start, name
        innermost = innermost or short_name(name)
    return float("-inf"), ""


def gap_label(threads: List[List[Event]], starts: List[List[float]],
              lo: float, hi: float) -> str:
    """What the host was doing for most of the gap [lo, hi). The gap is
    cut wherever an event of a host thread begins or ends inside it;
    each piece belongs to the span that covers it, of the threads' the
    one that started last, which is the innermost (the loop's
    ``serve.step`` waits on one thread while the engine's
    ``serve.engine.wait`` runs on another); the label with most of the
    gap's time names it, "" where that is no span. (By the gap's middle
    alone a train step's 5 ms boundary gap was now the end of one step,
    now the space before the next.)"""
    cuts = {lo, hi}
    for thread, begun in zip(threads, starts):
        inside = thread[bisect.bisect_right(begun, lo):
                        bisect.bisect_left(begun, hi)]
        for _, start, dur in itertools.chain(
                covering(thread, begun, lo), inside):
            cuts.update(x for x in (start, start + dur) if lo < x < hi)
    cuts = sorted(cuts)
    covered: Dict[str, float] = {}
    for a, b in zip(cuts, cuts[1:]):
        _, label = max((what_host_did(t, st, (a + b) / 2.0)
                        for t, st in zip(threads, starts)),
                       default=(0.0, ""))
        covered[label] = covered.get(label, 0.0) + (b - a)
    return max(covered, key=covered.get)


def idle_gaps(device: Iterable[Event], threads: Iterable[List[Event]],
              lo: float, hi: float, k: int = 10,
              short_ns: float = 2000.0) -> List[List]:
    """[label, seconds]: idle time of the device in [lo, hi), summed by
    what the host was doing for most of each gap (:func:`gap_label`;
    ``between host spans`` where no span covers most of it). Gaps under
    ``short_ns`` are the launches between one operation and the next
    and are summed apart."""
    busy = merge((s, s + d) for _, s, d in clip(device, lo, hi))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    threads = [sorted(t, key=lambda e: e[1]) for t in threads]
    starts = [[e[1] for e in t] for t in threads]
    total: Dict[str, float] = {}
    for s, e in gaps:
        if e - s < short_ns:
            label = f"between operations, under {short_ns / 1e3:g} us each"
        else:
            found = gap_label(threads, starts, s, e)
            label = f"in {found}" if found else "between host spans"
        total[label] = total.get(label, 0.0) + (e - s)
    ranked = sorted(total.items(), key=lambda kv: kv[1], reverse=True)
    return [[label, ns / 1e9] for label, ns in ranked[:k]]


def op_totals(events: Iterable[Event], lo: float,
              hi: float) -> Dict[str, List[float]]:
    """name -> [device seconds, calls] of every operation in [lo, hi):
    what the kernel readers look their kernels up in."""
    total: Dict[str, List[float]] = {}
    for name, _, dur in clip(events, lo, hi):
        entry = total.setdefault(name, [0.0, 0])
        entry[0] += dur / 1e9
        entry[1] += 1
    return total


def program_seconds(events: Iterable[Event], lo: float,
                    hi: float) -> Dict[str, float]:
    """program -> device seconds in [lo, hi) in which an operation of
    it ran (operations named ``<program>/<operation>`` by
    :func:`in_programs`). A union, because a ``while`` holds the
    operations of its body: their durations summed would count the body
    twice."""
    by_program: Dict[str, List[Event]] = {}
    for event in clip(events, lo, hi):
        by_program.setdefault(event[0].partition("/")[0], []).append(event)
    return {program: busy_ns(evs, lo, hi) / 1e9
            for program, evs in by_program.items()}


def reduce_trace(planes: Dict[str, Dict[str, List[Event]]]) -> Dict:
    """``planes[plane][line] -> events`` to the numbers of one traced
    slice, averaged over the device planes found."""
    windows = [e for lines in planes.values() for evs in lines.values()
               for e in evs if e[0] == WINDOW]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    _, lo, dur = max(windows, key=lambda e: e[2])
    hi = lo + dur
    devices = {name: in_programs(lines.get(OPS_LINE, []),
                                 lines.get(MODULES_LINE, []))
               for name, lines in sorted(planes.items())
               if name.startswith(DEVICE_PLANE)}
    if not devices:
        raise ValueError(
            f"the trace has no {DEVICE_PLANE}* plane: {sorted(planes)}")
    # the host threads on which the benchmark's own spans lie
    host = [evs for name, lines in planes.items()
            if not name.startswith(DEVICE_PLANE) for evs in lines.values()
            if any(labels_gaps(e[0]) for e in evs)]
    busiest = max(devices.values(), key=lambda evs: busy_ns(evs, lo, hi))
    return {
        "window_s": dur / 1e9,
        "busy_s": sum(busy_ns(evs, lo, hi) for evs in devices.values())
        / len(devices) / 1e9,
        "device_ops": top_ops(busiest, lo, hi),
        "idle_gaps": idle_gaps(busiest, host, lo, hi),
        "op_totals": op_totals(busiest, lo, hi),
        "program_seconds": program_seconds(busiest, lo, hi),
        "n_devices": len(devices),
    }


def read_xplane(log_dir: str) -> Dict[str, Dict[str, List[Event]]]:
    """Every plane, line and event of the newest trace under a
    ``jax.profiler.start_trace`` directory."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    planes: Dict[str, Dict[str, List[Event]]] = {}
    for plane in ProfileData.from_file(files[-1]).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            # threads share a name ("python3"): keep each a line of its own
            key = line.name
            while key in lines:
                key += "+"
            lines[key] = [
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in line.events]
    return planes


def describe(planes: Dict[str, Dict[str, List[Event]]], k: int = 12) -> Dict:
    """Plane and line names with their heaviest event names, and the
    device's custom calls (the Pallas kernels) as the trace names them:
    what one looks at by hand before writing a reader against a trace."""
    out = {}
    for pname, lines in planes.items():
        for lname, evs in lines.items():
            total: Dict[str, float] = {}
            for name, _, dur in evs:
                total[name] = total.get(name, 0.0) + dur
            top = sorted(total.items(), key=lambda kv: kv[1], reverse=True)
            entry = out[f"{pname} | {lname}"] = {
                "events": len(evs),
                "top": [[n[:160], ns / 1e9] for n, ns in top[:k]]}
            calls = [[n[:1200], ns / 1e9] for n, ns in top
                     if "custom-call" in n or "custom_call" in n]
            if calls:
                entry["custom_calls"] = calls[:8]
    return out
