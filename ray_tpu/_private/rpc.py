"""Asyncio RPC transport: framed msgpack control plane with raw byte frames.

Role-equivalent of the reference's templated gRPC layer (reference:
src/ray/rpc/grpc_server.h, client_call.h): every control-plane service
(GCS, raylet, core worker) is an ``RpcServer`` with named async handlers,
and clients hold one multiplexed connection per peer. Large payloads travel
as separate length-prefixed raw frames after the msgpack envelope so object
data is never re-encoded by msgpack.

Wire format per message:
    [u32 body_len][msgpack body][u64 buf_len + raw bytes] * nbufs
    body = [kind, seq, method, header, nbufs]
kinds: 0=request 1=reply 2=error 3=push (one-way).

Headers for the high-traffic methods are typed through the generated
stubs in ``_private/protocol.py`` (schema-checked at lint time by the
rpc-schema/protocol-stub rules, drift-gated by lint/schemagen.py). The
protocol version negotiated at registration lands on
``Connection.peer_protocol_version``; the envelope itself never changes
shape, so mixed-version peers always frame-interoperate.
"""

from __future__ import annotations

import asyncio
import itertools
import logging
import os
import pickle
import socket
import struct
import threading
import time
from collections import deque
from typing import Any, Awaitable, Callable, Dict, List, Optional, Sequence, Tuple

import cloudpickle
import msgpack

from ray_tpu._private import faultpoints

logger = logging.getLogger(__name__)


# Per-method latency histogram boundaries (seconds) for the Prometheus
# export — control-plane RPCs live in the 100us..1s band; the tails are
# exactly what the flight recorder exists to catch.
RPC_LATENCY_BUCKETS = (0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0,
                       5.0)

# Strong references for fire-and-forget tasks: asyncio itself keeps
# only a WEAK reference to running tasks, so a spawn whose handle is
# dropped can be garbage-collected mid-flight with its exception
# swallowed. Tasks park here until done (set mutation is GIL-atomic —
# multiple loops in one process, e.g. a head node, share it safely).
_SPAWNED: set = set()
_spawn_errors = None


def _spawn_error_counter():
    global _spawn_errors
    if _spawn_errors is None:
        from ray_tpu._private.metrics import Counter
        _spawn_errors = Counter(
            "ray_tpu_background_task_errors_total",
            "Exceptions raised by fire-and-forget background tasks "
            "(rpc.spawn_logged), labeled by task name.")
    return _spawn_errors


def spawn_logged(coro, what: str, loop=None) -> "asyncio.Task":
    """Tracked fire-and-forget: create a task, hold a strong reference
    until it finishes, and turn an unhandled exception into a log line
    plus a ``ray_tpu_background_task_errors_total`` count instead of a
    silent GC-time mutter. Returns the task (callers may still await
    or cancel it). ``what`` labels the spawn in logs and metrics."""
    if loop is None:
        loop = asyncio.get_event_loop()
    task = loop.create_task(coro)
    _SPAWNED.add(task)

    def _done(t, _what=what):
        _SPAWNED.discard(t)
        if t.cancelled():
            return
        exc = t.exception()
        if exc is not None:
            try:
                _spawn_error_counter().inc(labels={"what": _what})
            except Exception as me:  # metrics must not mask the log line
                logger.debug("background-task error counter "
                             "unavailable: %r", me)
            logger.error("background task %r died: %r", _what, exc,
                         exc_info=exc)

    task.add_done_callback(_done)
    return task


def _pct_block(samples: Sequence[float]) -> dict:
    """Percentile summary (ms) of a latency reservoir; ``{"count": 0}``
    when empty (metrics.percentile raises on empty input)."""
    from ray_tpu._private.metrics import percentile

    # list(deque) is one C-level copy under the GIL — safe against a
    # concurrent single-writer append (sorted() over a live deque is
    # not: deques raise on mutation-during-iteration).
    lat = sorted(list(samples))
    if not lat:
        return {"count": 0}
    return {
        "count": len(lat),
        "p50_ms": round(percentile(lat, 0.50) * 1e3, 3),
        "p90_ms": round(percentile(lat, 0.90) * 1e3, 3),
        "p99_ms": round(percentile(lat, 0.99) * 1e3, 3),
        "max_ms": round(lat[-1] * 1e3, 3),
    }


class _WindowedMax:
    """Rotating two-bucket max: ``read()`` reports the worst of the
    last one-to-two windows and ages out entirely after two quiet
    windows (a method or loop that goes silent must not pin its last
    spike forever). Shared by the per-method cells and the loop probes
    so the roll/expiry logic cannot diverge by copy."""

    __slots__ = ("win_max", "prev_max", "win_start")

    def __init__(self):
        self.win_max = 0.0
        self.prev_max = 0.0
        self.win_start = time.monotonic()

    def note(self, value: float, window: float) -> None:
        now = time.monotonic()
        if now - self.win_start >= window:
            # roll: the finished window becomes "previous"; a gap of
            # 2+ windows means both buckets are stale — start fresh
            self.prev_max = self.win_max \
                if now - self.win_start < 2 * window else 0.0
            self.win_max = 0.0
            self.win_start = now
        if value > self.win_max:
            self.win_max = value

    def read(self, window: float) -> float:
        age = time.monotonic() - self.win_start
        if age >= 2 * window:
            return 0.0
        if age >= window:
            return self.win_max
        return max(self.win_max, self.prev_max)


class _MethodStats:
    """One wire method's cells, one side (server or client).

    SINGLE-WRITER CONTRACT (audited for raylint; the benign-race
    fixture in tests/test_lint.py encodes this decision): every mutator
    runs only on the process's IO-loop thread — handlers, sync-fast-
    path replies, client done-callbacks and push sends all run there —
    so the counter cells have exactly one writer and need no lock.
    Snapshots may run on a foreign thread (metrics scrape): counters
    can be one tick stale, never torn (each mutation is a single
    STORE_ATTR / GIL-atomic deque append, and reservoirs are copied
    with one C-level ``list()`` call before sorting). Guarding this
    with a lock would put an acquire/release on every RPC for no
    observable difference.

    ``max`` is a WINDOWED max (two rotating buckets of
    ``telemetry.window_s``): dashboards see the worst of the last one
    to two windows, not an all-time high-water mark a restart ago.
    Reservoirs are bounded deques that drop OLDEST when full —
    recency-biased percentiles — with the drop count derivable (and
    reported) as ``count - len(reservoir)``."""

    __slots__ = ("count", "errors", "timeouts", "inflight", "total",
                 "queue_total", "bytes_in", "bytes_out", "push_count",
                 "push_bytes", "wmax",
                 "lat_res", "queue_res", "lat_buckets", "queue_buckets")

    def __init__(self, reservoir: int):
        self.count = 0
        self.errors = 0
        self.timeouts = 0
        self.inflight = 0
        self.total = 0.0
        self.queue_total = 0.0
        self.bytes_in = 0
        self.bytes_out = 0
        self.push_count = 0
        self.push_bytes = 0
        self.wmax = _WindowedMax()
        self.lat_res: "deque[float]" = deque(maxlen=reservoir)
        self.queue_res: "deque[float]" = deque(maxlen=reservoir)
        self.lat_buckets = [0] * (len(RPC_LATENCY_BUCKETS) + 1)
        self.queue_buckets = [0] * (len(RPC_LATENCY_BUCKETS) + 1)

    def _note_max(self, dt: float, window: float) -> None:
        self.wmax.note(dt, window)

    def windowed_max(self, window: float) -> float:
        return self.wmax.read(window)

    @staticmethod
    def _bucket(buckets: list, value: float) -> None:
        for i, b in enumerate(RPC_LATENCY_BUCKETS):
            if value <= b:
                buckets[i] += 1
                return
        buckets[-1] += 1


class _LoopProbe:
    """ONE event loop's lag probe (the ``instrumented_io_context``
    analog): ``tick()`` rides the existing periodic loops (raylet
    heartbeat, core-worker metrics report, GCS liveness monitor — no
    new thread, no own timer) and measures how long a READY callback
    waits for the loop: ``call_soon`` at t0, stamp the delta when the
    callback actually runs. That delta IS the queueing delay every
    other callback on this loop is currently paying. Samples feed a
    bounded reservoir + windowed max; a lag above
    ``loop_slow_callback_threshold_ms`` logs a WARNING and counts into
    the owner's process-wide ``slow_callbacks`` (slow *handlers* are
    attributed by name in ``note_server`` and count there too — the
    loop was occupied either way).

    Probes are NAMED, one per component loop
    (``RpcTelemetry.loop_probe("raylet"|"core"|"gcs")``): an
    in-process head runs the raylet and the driver CoreWorker on
    DIFFERENT loop threads, and a stall on one must never be shipped
    as lag of the other — each component ticks and snapshots its own
    probe, keeping the single-writer contract per cell."""

    __slots__ = ("owner", "name", "ticks", "lag_res", "wmax",
                 "_pending")

    def __init__(self, owner: "RpcTelemetry", name: str = "main"):
        self.owner = owner
        self.name = name
        self.ticks = 0
        self.lag_res: "deque[float]" = deque(maxlen=1024)
        self.wmax = _WindowedMax()
        self._pending = False

    def tick(self) -> None:
        """Schedule one lag measurement (loop thread only; one in
        flight at a time — overlapping cadences share the sample)."""
        if not self.owner.enabled or self._pending:
            return
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            return
        self._pending = True
        loop.call_soon(self._cb, time.monotonic())

    def _cb(self, t0: float) -> None:
        self._pending = False
        lag = time.monotonic() - t0
        self.ticks += 1
        self.lag_res.append(lag)
        self.wmax.note(lag, self.owner.window_s)
        if lag * 1e3 >= self.owner.slow_ms:
            self.owner.slow_callbacks += 1
            logger.warning("event loop lag (%s): a ready callback "
                           "waited %.1f ms (threshold %.0f ms)",
                           self.name, lag * 1e3, self.owner.slow_ms)

    def snapshot(self) -> dict:
        return {
            "ticks": self.ticks,
            "slow_callbacks": self.owner.slow_callbacks,
            "lag": _pct_block(self.lag_res),
            "lag_max_ms": round(
                self.wmax.read(self.owner.window_s) * 1e3, 3),
        }


class RpcTelemetry:
    """Per-process control-plane flight recorder (reference: the
    per-handler event stats instrumented_io_context.h builds into every
    event loop, plus the client call managers' latency tracking).

    Server side (per method): exec-time reservoir percentiles, QUEUE
    delay (frame arrival -> handler start — loop scheduling, separated
    from exec so "the loop was busy" and "the handler was slow" are
    distinguishable), bytes in/out, in-flight and error counts, and a
    windowed max. Client side (per method): call latency, error and
    timeout/cancel counts, bytes written, push count/bytes. Plus the
    loop-lag probe, connection redial count, and a bounded drained ring
    of SLOW CALL records (anything over ``slow_ms``) that feeds
    ``timeline()``'s cat="rpc" slices.

    All bounded, drop-counted, single-writer on the IO-loop thread
    (see _MethodStats). Shipped cross-process piggybacked on the
    existing cadences: raylets on the heartbeat, workers/drivers on the
    metrics-report loop (``ReportRpcTelemetry``)."""

    SLOW_CALLS_MAX = 256

    def __init__(self):
        self.enabled = True
        self.reservoir = 512
        self.slow_ms = 200.0
        self.window_s = 60.0
        self.server: Dict[str, _MethodStats] = {}
        self.client: Dict[str, _MethodStats] = {}
        self.redials = 0
        # process-wide: slow handlers (note_server) + slow lag samples
        # from ANY probe — "something occupied an event loop too long"
        self.slow_callbacks = 0
        # named per-loop probes (see _LoopProbe): each component ticks
        # and ships its OWN loop's probe — an in-process head's driver
        # loop stall must never read as raylet loop lag
        self.probes: Dict[str, _LoopProbe] = {}
        self.loop = self.loop_probe("main")
        self._slow: "deque[dict]" = deque()
        # MONOTONIC drop total; drain reports deltas against
        # _slow_dropped_flushed (the series' honest-truncation rule: a
        # zero-reset would race a concurrent _note_slow increment from
        # another loop thread into a lost or re-reported drop)
        self.slow_dropped = 0
        self._slow_dropped_flushed = 0
        self._wire_cache: Dict[str, dict] = {}
        self._wire_ts: Dict[str, float] = {}

    def loop_probe(self, name: str = "main") -> _LoopProbe:
        p = self.probes.get(name)
        if p is None:
            p = self.probes[name] = _LoopProbe(self, name)
        return p

    def configure(self, config) -> None:
        """Apply the process config (called by Raylet/CoreWorker/GCS
        init; module-level state, so the last caller wins — components
        sharing a process share one config anyway)."""
        self.enabled = bool(
            getattr(config, "rpc_telemetry_enabled", True))
        self.reservoir = max(
            16, int(getattr(config, "rpc_telemetry_reservoir", 512)))
        self.slow_ms = float(
            getattr(config, "loop_slow_callback_threshold_ms", 200.0))
        self.window_s = max(
            1.0, float(getattr(config, "rpc_stats_window_s", 60.0)))

    def _entry(self, table: Dict[str, _MethodStats],
               method: str) -> _MethodStats:
        e = table.get(method)
        if e is None:
            e = table[method] = _MethodStats(self.reservoir)
        return e

    # ------------------------------------------------------- server side

    def note_request(self, method: str, nbytes: int) -> None:
        """Frame arrival of a request (recv loop): bytes in + in-flight."""
        e = self._entry(self.server, method)
        e.bytes_in += nbytes
        e.inflight += 1

    def note_done(self, method: str) -> None:
        """In-flight decrement WITHOUT a completion record: balances a
        note_request whose handler finished after ``enabled`` was
        flipped off (the toggle must never strand phantom in-flight
        counts)."""
        e = self.server.get(method)
        if e is not None:
            e.inflight = max(0, e.inflight - 1)

    def note_server(self, method: str, queue_dt: float, exec_dt: float,
                    bytes_out: int, error: bool, peer: str = "") -> None:
        """Handler completion (any path: task-wrapped, sync fast path,
        deferred future, raised)."""
        e = self._entry(self.server, method)
        e.count += 1
        e.inflight = max(0, e.inflight - 1)
        e.total += exec_dt
        e.queue_total += queue_dt
        e.bytes_out += bytes_out
        if error:
            e.errors += 1
        e.lat_res.append(exec_dt)
        e.queue_res.append(queue_dt)
        e._bucket(e.lat_buckets, exec_dt)
        e._bucket(e.queue_buckets, queue_dt)
        e._note_max(exec_dt, self.window_s)
        if exec_dt * 1e3 >= self.slow_ms:
            self.slow_callbacks += 1
            self._note_slow("server", method, exec_dt, queue_dt, peer)
            logger.warning(
                "slow RPC handler %s: %.1f ms exec (%.1f ms queued, "
                "threshold %.0f ms, peer %s)", method, exec_dt * 1e3,
                queue_dt * 1e3, self.slow_ms, peer)

    # ------------------------------------------------------- client side

    def note_client_send(self, method: str, nbytes: int) -> None:
        self._entry(self.client, method).bytes_out += nbytes

    def note_client(self, method: str, dt: float, fut) -> None:
        """Reply-future completion: latency + error/timeout verdict."""
        e = self._entry(self.client, method)
        e.count += 1
        e.total += dt
        if fut.cancelled():
            # wait_for timeouts cancel the reply future — counted as
            # timeouts (explicit caller cancellation lands here too)
            e.timeouts += 1
        elif fut.exception() is not None:
            e.errors += 1
        e.lat_res.append(dt)
        e._bucket(e.lat_buckets, dt)
        e._note_max(dt, self.window_s)
        if dt * 1e3 >= self.slow_ms:
            self._note_slow("client", method, dt, 0.0, "")

    def note_push(self, method: str, nbytes: int) -> None:
        e = self._entry(self.client, method)
        e.push_count += 1
        e.push_bytes += nbytes
        e.bytes_out += nbytes

    def note_redial(self) -> None:
        self.redials += 1

    # -------------------------------------------------------- slow calls

    def _note_slow(self, side: str, method: str, dur: float,
                   queue_dt: float, peer: str) -> None:
        if len(self._slow) >= self.SLOW_CALLS_MAX:
            self.slow_dropped += 1
            return
        # wall-clock ts so the record merges onto the same timeline
        # clock as tasks/objects/pulls; stamped back to the call start
        self._slow.append({
            "side": side, "method": method,
            "ts": time.time() - dur,
            "dur_ms": round(dur * 1e3, 3),
            "queue_ms": round(queue_dt * 1e3, 3),
            "peer": peer,
        })

    def drain_slow_calls(self) -> Tuple[List[dict], int]:
        """-> (records, dropped): pop everything buffered (GIL-atomic
        popleft — an append racing the drain lands in the next one) and
        the drop count since the last drain."""
        out = []
        buf = self._slow
        for _ in range(len(buf)):
            try:
                out.append(buf.popleft())
            except IndexError:
                break
        total = self.slow_dropped
        dropped = total - self._slow_dropped_flushed
        self._slow_dropped_flushed = total
        return out, dropped

    # --------------------------------------------------------- snapshots

    def _side_snapshot(self, table: Dict[str, _MethodStats],
                       percentiles: bool) -> Dict[str, dict]:
        out = {}
        window = self.window_s
        for method, e in list(table.items()):
            count = e.count
            d = {
                "count": count,
                "mean_ms": round(e.total / count * 1e3, 3)
                if count else 0.0,
                "total_s": round(e.total, 3),
                "max_ms": round(e.windowed_max(window) * 1e3, 3),
                "errors": e.errors,
                "timeouts": e.timeouts,
                "inflight": e.inflight,
                "bytes_in": e.bytes_in,
                "bytes_out": e.bytes_out,
            }
            if e.push_count:
                d["push_count"] = e.push_count
                d["push_bytes"] = e.push_bytes
            if count:
                d["queue_mean_ms"] = round(
                    e.queue_total / count * 1e3, 3)
            if percentiles:
                d["exec"] = _pct_block(e.lat_res)
                d["queue"] = _pct_block(e.queue_res)
                # reservoirs drop OLDEST when full: the honest count
                d["dropped_samples"] = max(0, count - len(e.lat_res))
            out[method] = d
        return out

    def snapshot(self, percentiles: bool = True,
                 probe: str = "main") -> dict:
        """Full snapshot. ``probe`` names the loop whose lag block to
        carry as ``loop`` — each shipping component passes its own
        ("raylet"/"core"/"gcs"), so a reporter's loop block is always
        the loop that reporter actually runs on."""
        return {
            "server": self._side_snapshot(self.server, percentiles),
            "client": self._side_snapshot(self.client, percentiles),
            "loop": self.loop_probe(probe).snapshot(),
            "redials": self.redials,
        }

    def wire(self, min_interval: float = 1.0,
             probe: str = "main") -> dict:
        """Snapshot for the shipping cadences, recomputed at most every
        ``min_interval`` seconds (per probe): sorting every reservoir
        4-20x/s on the heartbeat would buy nothing a dashboard can
        see."""
        now = time.monotonic()
        if now - self._wire_ts.get(probe, -1e9) >= min_interval:
            self._wire_cache[probe] = self.snapshot(percentiles=True,
                                                    probe=probe)
            self._wire_ts[probe] = now
        return self._wire_cache[probe]

    def handler_brief(self) -> Dict[str, dict]:
        """Compact per-handler block for heartbeat ``stats`` — the
        pre-flight-recorder ``rpc_handlers`` shape (count/mean/total/
        max), kept for the node-stats surface."""
        out = {}
        window = self.window_s
        for method, e in list(self.server.items()):
            out[method] = {
                "count": e.count,
                "mean_ms": round(e.total / e.count * 1e3, 3)
                if e.count else 0.0,
                "total_s": round(e.total, 3),
                "max_ms": round(e.windowed_max(window) * 1e3, 3),
            }
        return out

    def prom_snapshot(self) -> dict:
        """Per-method latency histograms in the metrics-registry wire
        format (metrics.py snapshot dicts) — merged into whatever this
        process already ships (heartbeat ``metrics`` key /
        ``ReportMetrics``), so the GCS renders real cumulative
        Prometheus histograms without a new transport."""
        bounds = list(RPC_LATENCY_BUCKETS)

        def hist(desc, table, buckets_of, sum_of):
            values = []
            for method, e in list(table.items()):
                buckets = buckets_of(e)
                count = sum(buckets)
                if not count:
                    continue
                values.append([[["method", method]],
                               [list(buckets), round(sum_of(e), 6),
                                count]])
            return {"kind": "histogram", "description": desc,
                    "boundaries": bounds, "values": values}

        return {
            "ray_tpu_rpc_server_seconds": hist(
                "Server-side RPC handler exec time by method",
                self.server, lambda e: e.lat_buckets,
                lambda e: e.total),
            "ray_tpu_rpc_server_queue_seconds": hist(
                "Server-side RPC queueing delay (frame arrival to "
                "handler start) by method",
                self.server, lambda e: e.queue_buckets,
                lambda e: e.queue_total),
            "ray_tpu_rpc_client_seconds": hist(
                "Client-side RPC call latency by method",
                self.client, lambda e: e.lat_buckets,
                lambda e: e.total),
        }


telemetry = RpcTelemetry()


class _HandlerStatsView:
    """Back-compat facade over ``telemetry.server`` (the old module
    global ``handler_stats``): same ``note``/``snapshot`` surface, same
    snapshot keys — ``max_ms`` is now the WINDOWED max (satellite fix:
    an all-time max made dashboards show a cold-start spike forever)."""

    def note(self, method: str, dt: float) -> None:
        telemetry.note_server(method, 0.0, dt, 0, False)

    def snapshot(self) -> Dict[str, dict]:
        return telemetry.handler_brief()


handler_stats = _HandlerStatsView()


class RpcTelemetryTable:
    """GCS-side aggregation of per-reporter telemetry snapshots (the
    queryable plane behind ``state.list_rpc()`` / ``summary_rpc()`` /
    ``/api/rpc``). Reporters that stop shipping age out on the same TTL
    as metric snapshots; slow-call records accumulate in a capped ring
    with an honest drop counter (they feed ``timeline()``'s cat="rpc"
    slices)."""

    SLOW_CALLS_MAX = 2048
    TTL_S = 60.0

    def __init__(self):
        # reporter -> (ts, snapshot)
        self._reporters: Dict[str, Tuple[float, dict]] = {}
        self.slow_calls: "deque[dict]" = deque()
        self.slow_dropped = 0

    def ingest(self, reporter: str, payload: dict) -> None:
        snap = payload.get("snapshot")
        if snap:
            self._reporters[reporter] = (time.time(), snap)
        for rec in payload.get("slow_calls") or ():
            if len(self.slow_calls) >= self.SLOW_CALLS_MAX:
                self.slow_calls.popleft()
                self.slow_dropped += 1
            self.slow_calls.append({**rec, "reporter": reporter})
        self.slow_dropped += int(payload.get("slow_calls_dropped") or 0)

    def prune(self) -> None:
        cutoff = time.time() - self.TTL_S
        for key in [k for k, (ts, _) in self._reporters.items()
                    if ts < cutoff]:
            del self._reporters[key]

    def reporters(self) -> Dict[str, dict]:
        self.prune()
        return {k: snap for k, (_, snap) in self._reporters.items()}

    def rows(self, method: Optional[str] = None,
             reporter: Optional[str] = None,
             side: Optional[str] = None) -> List[dict]:
        """Flat per-(reporter, side, method) rows, filterable: method
        substring, reporter prefix, side exact ("server"/"client")."""
        out = []
        for rep, snap in sorted(self.reporters().items()):
            if reporter and not rep.startswith(reporter):
                continue
            for sd in ("server", "client"):
                if side and sd != side:
                    continue
                for m, d in sorted((snap.get(sd) or {}).items()):
                    if method and method not in m:
                        continue
                    out.append({"reporter": rep, "side": sd,
                                "method": m, **d})
        return out

    def loops(self) -> Dict[str, dict]:
        return {rep: snap.get("loop") or {}
                for rep, snap in sorted(self.reporters().items())}

    def summary(self) -> Dict[str, dict]:
        """Cluster-wide per-method aggregate. Counts/bytes/errors/
        in-flight are summed over the SERVER rows only (every call the
        cluster saw is observed by exactly one server; summing both
        sides would double-count anything a client reporter also
        watched) — a method nothing serves (one-way pushes recorded
        client-side only) falls back to its client rows. ``timeouts``
        sums the CLIENT rows (only callers see timeouts); latency
        percentiles take the WORST row of either side (a conservative
        "slowest reporter" view, not a pooled population — the raw
        reservoirs never leave their process)."""
        per_side: Dict[str, Dict[str, dict]] = {}
        worst: Dict[str, dict] = {}
        reporters: Dict[str, set] = {}
        for row in self.rows():
            key = row["method"]
            side = row["side"]
            d = per_side.setdefault(key, {}).setdefault(side, {
                "count": 0, "errors": 0, "timeouts": 0, "inflight": 0,
                "bytes_in": 0, "bytes_out": 0})
            for k in ("count", "errors", "timeouts", "inflight",
                      "bytes_in", "bytes_out"):
                d[k] += row.get(k, 0)
            w = worst.setdefault(key, {"max_ms": 0.0,
                                       "exec_p99_ms": 0.0,
                                       "queue_p99_ms": 0.0})
            w["max_ms"] = max(w["max_ms"], row.get("max_ms", 0.0))
            w["exec_p99_ms"] = max(
                w["exec_p99_ms"],
                (row.get("exec") or {}).get("p99_ms", 0.0))
            w["queue_p99_ms"] = max(
                w["queue_p99_ms"],
                (row.get("queue") or {}).get("p99_ms", 0.0))
            reporters.setdefault(key, set()).add(row["reporter"])
        agg: Dict[str, dict] = {}
        for key, sides in per_side.items():
            src = sides.get("server") or sides["client"]
            agg[key] = {
                "count": src["count"], "errors": src["errors"],
                "timeouts": sides.get("client", {}).get("timeouts", 0),
                "inflight": src["inflight"],
                "bytes_in": src["bytes_in"],
                "bytes_out": src["bytes_out"],
                **worst[key],
                "reporters": len(reporters[key]),
                "sides": sorted(sides),
            }
        return agg

KIND_REQUEST = 0
KIND_REPLY = 1
KIND_ERROR = 2
KIND_PUSH = 3

_U32 = struct.Struct("<I")
_U64 = struct.Struct("<Q")

Handler = Callable[["Connection", Any, List[bytes]], Awaitable[Any]]

# Write-buffer size above which senders apply backpressure by awaiting
# drain. Below it, writes are fire-and-forget into the transport buffer —
# one syscall per event-loop flush instead of one drain await per message.
DRAIN_HIGH_WATER = 4 * 1024 * 1024


def _pack_msg(kind: int, seq: int, method: str, header: Any,
              bufs: Sequence[bytes]) -> List[bytes]:
    """Frames are any buffer objects (bytes, or LIVE memoryviews on
    the zero-copy data plane — pickle-5 frames, shm chunk slices);
    they ride to the socket as-is, never flattened. Length framing
    uses nbytes: len(memoryview) counts elements, not bytes."""
    body = msgpack.packb([kind, seq, method, header, len(bufs)],
                         use_bin_type=True)
    parts = [_U32.pack(len(body)), body]
    for b in bufs:
        parts.append(_U64.pack(
            b.nbytes if isinstance(b, memoryview) else len(b)))
        parts.append(b)
    return parts


def _parts_len(parts: Sequence[Any]) -> int:
    """Wire bytes of a packed message (telemetry accounting)."""
    return sum(b.nbytes if isinstance(b, memoryview) else len(b)
               for b in parts)


def _try_parse_msg(buf: bytearray, pos: int, env_cache: list):
    """Parse ONE complete message from ``buf`` starting at ``pos``.

    Returns ``(msg, next_pos)`` on success or ``(None, needed)`` where
    ``needed`` is the minimum total buffer length before a retry can
    possibly succeed (so partially-received large frames aren't
    re-parsed on every arriving TCP chunk). Parsing is synchronous —
    the recv loop awaits the socket once per chunk, not per field
    (profiled: readexactly per length prefix cost ~6us/message).

    ``env_cache`` is a one-slot list caching the decoded msgpack
    envelope of the HEAD message across retries: a multi-buf message
    trickling in over several chunks would otherwise re-decode its
    body at every buf-length threshold. The caller clears it when a
    message completes (only the head message is ever parsed)."""
    n = len(buf)
    if n - pos < 4:
        return None, pos + 4
    (body_len,) = _U32.unpack_from(buf, pos)
    p = pos + 4
    if n - p < body_len:
        return None, p + body_len
    if env_cache[0] is not None:
        kind, seq, method, header, nbufs = env_cache[0]
    else:
        kind, seq, method, header, nbufs = env = msgpack.unpackb(
            memoryview(buf)[p:p + body_len], raw=False)
        env_cache[0] = env
    p += body_len
    if nbufs == 0:
        return (kind, seq, method, header, []), p
    bufs = []
    for _ in range(nbufs):
        if n - p < 8:
            return None, p + 8
        (blen,) = _U64.unpack_from(buf, p)
        p += 8
        if n - p < blen:
            return None, p + blen
        bufs.append(bytes(memoryview(buf)[p:p + blen]))
        p += blen
    return (kind, seq, method, header, bufs), p


class Connection:
    """One duplex connection. Used symmetrically: either side can issue
    requests and pushes once established (workers serve PushTask on the same
    connection they used to register, like the reference's bidirectional
    core-worker channels)."""

    def __init__(self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter,
                 handlers: Dict[str, Handler], peer_name: str = "?"):
        self.reader = reader
        self.writer = writer
        self.handlers = handlers
        self.peer_name = peer_name
        self._seq = itertools.count(1)
        self._pending: Dict[int, asyncio.Future] = {}
        self._closed = False
        # Write coalescing: messages buffer here and flush once per loop
        # iteration — one syscall for a whole burst of small messages
        # instead of one sendmsg each (~120us apiece on this box).
        self._loop = asyncio.get_running_loop()
        self._out: List[bytes] = []
        self._flush_scheduled = False
        # Serializes writer.drain(): pre-3.12 FlowControlMixin supports
        # only ONE drain waiter per transport (single _drain_waiter slot).
        self._drain_lock = asyncio.Lock()
        self.on_disconnect: List[Callable[["Connection"], None]] = []
        # Wire-protocol version negotiated with this peer (see
        # _private/protocol.py). Stamped by the registration handshakes
        # (GCS RegisterNode sets it server-side, the raylet sets it on
        # its gcs_conn from the reply); None = peer never advertised,
        # treat as MIN_PROTOCOL_VERSION. The transport itself is
        # deliberately version-blind — versioning rides header keys,
        # never the envelope, so old and new framing interoperate.
        self.peer_protocol_version: Optional[int] = None
        # Arbitrary per-connection state stamped by services (worker id etc).
        self.tags: Dict[str, Any] = {}
        self._recv_task: Optional[asyncio.Task] = None

    def start(self):
        self._recv_task = asyncio.get_running_loop().create_task(self._recv_loop())

    def _write_nowait(self, parts: List[bytes]):
        """Coalescing buffered write (loop thread only): parts land in the
        out-buffer and flush once per loop iteration."""
        if self._closed:
            raise ConnectionError(f"connection to {self.peer_name} is closed")
        self._out.extend(parts)
        if not self._flush_scheduled:
            self._flush_scheduled = True
            self._loop.call_soon(self._flush)

    def _flush(self):
        self._flush_scheduled = False
        if self._closed or not self._out:
            self._out.clear()
            return
        out, self._out = self._out, []
        try:
            if len(out) > 8 and sum(map(len, out)) < 262144:
                # A burst of small messages: one join + one send beats a
                # long iovec through sendmsg (memcpy is cheaper than the
                # kernel's per-iovec accounting at these sizes). Bursts
                # carrying big raw frames scatter-write instead — no
                # extra copy on the data plane.
                self.writer.write(b"".join(out))
            else:
                self.writer.writelines(out)
        except Exception:
            self._mark_closed()

    def _needs_drain(self) -> bool:
        transport = self.writer.transport
        return (transport is not None and
                transport.get_write_buffer_size() > DRAIN_HIGH_WATER)

    async def _drain(self):
        async with self._drain_lock:
            await self.writer.drain()

    async def _send(self, parts: List[bytes]):
        self._write_nowait(parts)
        if self._needs_drain():
            await self._drain()

    def call_nowait(self, method: str, header: Any = None,
                    bufs: Sequence[bytes] = ()) -> asyncio.Future:
        """Issue a request without a coroutine round trip (loop thread
        only). Returns the reply future; the pending entry is dropped by a
        done callback so abandoned futures don't leak."""
        seq = next(self._seq)
        fut = asyncio.get_running_loop().create_future()
        self._pending[seq] = fut
        if telemetry.enabled:
            # one combined done callback: pending cleanup + client-side
            # per-method latency/outcome accounting (batched transports
            # amortize this — one note per PushTasks batch, never per
            # task)
            t0 = time.monotonic()

            def _done(f, _m=method, _t0=t0, _s=seq):
                self._pending.pop(_s, None)
                telemetry.note_client(_m, time.monotonic() - _t0, f)

            fut.add_done_callback(_done)
        else:
            fut.add_done_callback(lambda f: self._pending.pop(seq, None))
        parts = _pack_msg(KIND_REQUEST, seq, method, header, bufs)
        if telemetry.enabled:
            telemetry.note_client_send(method, _parts_len(parts))
        if faultpoints.armed:
            # fault plane: a dropped request is never written (the
            # caller's timeout governs), a duplicated one is written
            # twice (handler idempotence probe), a severed connection
            # fails every pending future right here. NOTE: this is a
            # sync seam on the loop thread, so an armed ``delay``
            # blocks the WHOLE loop — deliberately: it models loop
            # occupancy/GIL stalls (the failure mode the heartbeat
            # timeout was widened for), not per-message latency.
            act = faultpoints.fire("rpc.call.send", method=method,
                                   peer=self.peer_name)
            if act == "drop":
                return fut
            if act == "sever":
                self._mark_closed()
                return fut
            if act == "duplicate":
                self._write_nowait(list(parts))
        self._write_nowait(parts)
        return fut

    async def call(self, method: str, header: Any = None,
                   bufs: Sequence[bytes] = (), timeout: float | None = None):
        fut = self.call_nowait(method, header, bufs)
        if self._needs_drain():
            await self._drain()
        if timeout is not None:
            return await asyncio.wait_for(fut, timeout)
        return await fut

    async def push(self, method: str, header: Any = None,
                   bufs: Sequence[bytes] = ()):
        """One-way message; no reply expected."""
        parts = _pack_msg(KIND_PUSH, 0, method, header, bufs)
        if telemetry.enabled:
            telemetry.note_push(method, _parts_len(parts))
        await self._send(parts)

    def push_nowait(self, method: str, header: Any = None,
                    bufs: Sequence[bytes] = ()):
        """One-way message from the loop thread, coalesced like replies
        (used for streamed per-task actor results and the streaming-
        lease pushes: GrantLeaseCredits, ReportLeaseDemand). Routes
        through the same ``rpc.call.send`` fault seam as requests so
        chaos schedules can drop/sever/duplicate the one-way lanes too
        — a lost credit grant is a first-class failure mode."""
        parts = _pack_msg(KIND_PUSH, 0, method, header, bufs)
        if telemetry.enabled:
            telemetry.note_push(method, _parts_len(parts))
        if faultpoints.armed:
            act = faultpoints.fire("rpc.call.send", method=method,
                                   peer=self.peer_name)
            if act == "drop":
                return
            if act == "sever":
                self._mark_closed()
                return
            if act == "duplicate":
                self._write_nowait(list(parts))
        self._write_nowait(parts)

    async def _recv_loop(self):
        read = self.reader.read
        buf = bytearray()
        pos = 0
        needed = 4
        env_cache = [None]
        try:
            while True:
                chunk = await read(262144)
                if not chunk:
                    break  # EOF
                # frame-arrival stamp, ONE clock read per chunk (not per
                # message): queueing delay for every request parsed out
                # of this chunk is measured from here to handler start
                arr_ts = time.monotonic() if telemetry.enabled else 0.0
                if pos:
                    del buf[:pos]
                    needed -= pos
                    pos = 0
                buf += chunk
                if len(buf) < needed:
                    continue
                while True:
                    start = pos
                    msg, p = _try_parse_msg(buf, pos, env_cache)
                    if msg is None:
                        needed = p
                        break
                    pos = p
                    env_cache[0] = None
                    self._dispatch(*msg, arr_ts, p - start)
                if pos == len(buf):
                    buf.clear()
                    pos = 0
                    needed = 4
        except (ConnectionError, OSError):
            pass
        except Exception:
            logger.exception("rpc recv loop error (peer %s)", self.peer_name)
        finally:
            self._mark_closed()

    def _dispatch(self, kind, seq, method, header, bufs,
                  arr_ts=0.0, nbytes=0):
        if kind == KIND_REPLY:
            fut = self._pending.get(seq)
            if fut is not None and not fut.done():
                fut.set_result((header, bufs))
        elif kind == KIND_REQUEST:
            if arr_ts:
                telemetry.note_request(method, nbytes)
            handler = self.handlers.get(method)
            if handler is not None and \
                    getattr(handler, "rpc_sync", False):
                # Sync fast path: no per-request asyncio.Task. The
                # handler returns a reply tuple or a Future.
                self._handle_sync(handler, seq, method, header, bufs,
                                  arr_ts)
                return
            spawn_logged(
                self._handle(seq, method, header, bufs, arr_ts),
                f"rpc-handle:{method}", loop=self._loop)
        elif kind == KIND_PUSH:
            handler = self.handlers.get(method)
            if handler is None:
                logger.warning("no handler for push %s", method)
            else:
                spawn_logged(
                    self._run_push(handler, header, bufs),
                    f"rpc-push:{method}", loop=self._loop)
        elif kind == KIND_ERROR:
            fut = self._pending.get(seq)
            if fut is not None and not fut.done():
                fut.set_exception(pickle.loads(bufs[0]))

    async def _run_push(self, handler, header, bufs):
        try:
            await handler(self, header, bufs)
        except Exception:
            logger.exception("push handler error")

    def _reply_nowait(self, seq: int, method: str, result) -> int:
        """Write the reply; returns the wire byte count (0 when the
        reply was faulted away or the connection is gone)."""
        if isinstance(result, tuple) and len(result) == 2 and \
                isinstance(result[1], (list, tuple)):
            rheader, rbufs = result
        else:
            rheader, rbufs = result, ()
        if faultpoints.armed and self._fault_reply(method):
            return 0
        parts = _pack_msg(KIND_REPLY, seq, method, rheader, rbufs)
        try:
            self._write_nowait(parts)
        except (ConnectionError, OSError):
            self._mark_closed()
            return 0
        return _parts_len(parts) if telemetry.enabled else 0

    def _fault_reply(self, method: str) -> bool:
        """Server-side reply fault seam (both the sync fast path and
        the task-wrapped path route through here): True = the reply
        must NOT be sent. ``drop`` loses only the reply — the handler
        already ran, so the caller's retry probes idempotence; ``sever``
        tears the connection down mid-reply (the reference failure for
        "did my mutation land?" client logic). Sync seam on the loop
        thread: an armed ``delay`` here stalls the whole loop by
        design (loop-occupancy fault), like ``rpc.call.send``."""
        act = faultpoints.fire("rpc.reply.send", method=method,
                               peer=self.peer_name)
        if act == "drop":
            return True
        if act == "sever":
            self._mark_closed()
            return True
        return False

    def _reply_error_nowait(self, seq: int, method: str, e: BaseException):
        try:
            payload = cloudpickle.dumps(e)
        except Exception:
            payload = cloudpickle.dumps(RuntimeError(repr(e)))
        try:
            self._write_nowait(_pack_msg(KIND_ERROR, seq, method, None, [payload]))
        except (ConnectionError, OSError):
            self._mark_closed()

    def _handle_sync(self, handler, seq: int, method: str, header, bufs,
                     arr_ts: float = 0.0):
        """Dispatch a handler marked ``rpc_sync``: called inline on the
        recv loop; may return a Future for deferred replies. Queueing
        delay here is parse backlog within the chunk (the Nth request
        of a burst starts after N-1 sync replies)."""
        t0 = time.monotonic()
        queue_dt = t0 - arr_ts if arr_ts else 0.0
        tel = telemetry if telemetry.enabled else None
        try:
            if faultpoints.armed:
                # exec-side fault seam (see _handle): a delay here is a
                # slow HANDLER, attributable by method name
                faultpoints.fire("rpc.handler", method=method,
                                 peer=self.peer_name)
            result = handler(self, header, bufs)
        except Exception as e:  # noqa: BLE001 — propagate to caller
            if tel:
                tel.note_server(method, queue_dt,
                                time.monotonic() - t0, 0, True,
                                self.peer_name)
            elif arr_ts:
                telemetry.note_done(method)
            self._reply_error_nowait(seq, method, e)
            return
        if isinstance(result, asyncio.Future):
            def _on_done(fut: asyncio.Future):
                error = fut.cancelled() or fut.exception() is not None
                nbytes = 0
                if fut.cancelled():
                    self._reply_error_nowait(
                        seq, method, RuntimeError(f"{method} cancelled"))
                elif fut.exception() is not None:
                    self._reply_error_nowait(seq, method, fut.exception())
                else:
                    nbytes = self._reply_nowait(seq, method, fut.result())
                if telemetry.enabled:
                    telemetry.note_server(
                        method, queue_dt, time.monotonic() - t0,
                        nbytes, error, self.peer_name)
                elif arr_ts:
                    telemetry.note_done(method)
            result.add_done_callback(_on_done)
        else:
            nbytes = self._reply_nowait(seq, method, result)
            if tel:
                tel.note_server(method, queue_dt,
                                time.monotonic() - t0, nbytes, False,
                                self.peer_name)
            elif arr_ts:
                telemetry.note_done(method)

    async def _handle(self, seq: int, method: str, header, bufs,
                      arr_ts: float = 0.0):
        handler = self.handlers.get(method)
        # t0 is HANDLER START inside the spawned task: arr_ts -> t0 is
        # the loop's scheduling/queueing delay (the instrumented-asio
        # queue_ms), t0 -> done is handler exec — reported apart so "the
        # loop was busy" never masquerades as "the handler was slow".
        t0 = time.monotonic()
        queue_dt = t0 - arr_ts if arr_ts else 0.0
        exec_dt = 0.0
        nbytes = 0
        error = False
        try:
            if handler is None:
                raise RuntimeError(f"no handler for method {method!r}")
            if faultpoints.armed:
                # exec-side fault seam: an armed ``delay`` is a SYNC
                # sleep inside this handler's task — the handler shows
                # slow (exec) and, the loop being blocked, every
                # concurrently-queued request shows queueing delay:
                # the delay_storm attribution scenario.
                faultpoints.fire("rpc.handler", method=method,
                                 peer=self.peer_name)
            try:
                result = await handler(self, header, bufs)
            finally:
                # raising handlers count too — the misbehaving methods
                # are exactly the ones latency stats must show
                exec_dt = time.monotonic() - t0
            if isinstance(result, tuple) and len(result) == 2 and \
                    isinstance(result[1], (list, tuple)):
                rheader, rbufs = result
            else:
                rheader, rbufs = result, ()
            if faultpoints.armed and self._fault_reply(method):
                return
            parts = _pack_msg(KIND_REPLY, seq, method, rheader, rbufs)
            if telemetry.enabled:
                nbytes = _parts_len(parts)
            await self._send(parts)
        except (ConnectionError, OSError):
            error = True
            self._mark_closed()
        except Exception as e:  # noqa: BLE001 — propagate to caller
            error = True
            if not exec_dt:
                exec_dt = time.monotonic() - t0
            try:
                # raylint: disable=async-blocking — bounded error reply (one exception object)
                payload = cloudpickle.dumps(e)
            except Exception:
                # raylint: disable=async-blocking — same bounded error path
                payload = cloudpickle.dumps(RuntimeError(repr(e)))
            try:
                await self._send(_pack_msg(KIND_ERROR, seq, method, None, [payload]))
            except (ConnectionError, OSError):
                self._mark_closed()
        finally:
            if telemetry.enabled:
                telemetry.note_server(method, queue_dt, exec_dt, nbytes,
                                      error, self.peer_name)
            elif arr_ts:
                # recording was flipped off mid-flight: still balance
                # note_request's in-flight increment
                telemetry.note_done(method)

    def _mark_closed(self):
        if self._closed:
            return
        self._closed = True
        for fut in self._pending.values():
            if not fut.done():
                fut.set_exception(ConnectionError(
                    f"connection to {self.peer_name} lost"))
        self._pending.clear()
        # snapshot: callbacks may unregister themselves (or siblings)
        # from the live list mid-iteration, which would skip entries
        for cb in list(self.on_disconnect):
            try:
                cb(self)
            except Exception:
                logger.exception("on_disconnect callback failed")
        try:
            self.writer.close()
        except (OSError, RuntimeError):
            pass  # transport already torn down / loop already closed

    @property
    def closed(self) -> bool:
        return self._closed

    async def close(self):
        self._mark_closed()
        # Reap the recv loop on an EXTERNAL close: the transport
        # teardown delivers it EOF eventually, but a loop shutting down
        # right after close() (chaos teardown, tests) would otherwise
        # destroy a still-pending task and log noise. The loop's own
        # finally path never reaches here (it IS the current task).
        task = self._recv_task
        if task is not None and not task.done() and \
                task is not asyncio.current_task():
            task.cancel()
            await asyncio.gather(task, return_exceptions=True)


class RpcServer:
    """Listens on tcp://host:port or unix://path; spawns a Connection per
    client, dispatching to ``handlers``."""

    def __init__(self, handlers: Dict[str, Handler], name: str = "server"):
        self.handlers = handlers
        self.name = name
        self.address: str = ""
        self._server: Optional[asyncio.AbstractServer] = None
        self.connections: List[Connection] = []
        self.on_connect: List[Callable[[Connection], None]] = []

    async def _on_client(self, reader, writer):
        conn = Connection(reader, writer, self.handlers,
                          peer_name=f"client-of-{self.name}")
        self.connections.append(conn)
        conn.on_disconnect.append(lambda c: self.connections.remove(c)
                                  if c in self.connections else None)
        for cb in self.on_connect:
            cb(conn)
        conn.start()

    async def listen(self, address: str = "") -> str:
        if address.startswith("unix://"):
            path = address[len("unix://"):]
            if os.path.exists(path):
                os.unlink(path)
            self._server = await asyncio.start_unix_server(self._on_client, path=path)
            self.address = address
        else:
            host, port = "127.0.0.1", 0
            if address.startswith("tcp://"):
                hp = address[len("tcp://"):]
                host, _, p = hp.rpartition(":")
                port = int(p)
            self._server = await asyncio.start_server(
                self._on_client, host=host, port=port,
                family=socket.AF_INET)
            port = self._server.sockets[0].getsockname()[1]
            self.address = f"tcp://{host}:{port}"
        return self.address

    async def close(self):
        if self._server is not None:
            self._server.close()
        for conn in list(self.connections):
            await conn.close()
        if self._server is not None:
            # after the connections: since Python 3.12 wait_closed()
            # waits for every accepted connection to be gone, so
            # awaiting it first never returns while a peer stays
            # connected
            await self._server.wait_closed()


async def connect(address: str, handlers: Dict[str, Handler] | None = None,
                  timeout: float = 10.0, retry_interval: float = 0.05,
                  peer_name: str = "") -> Connection:
    """Dial an RpcServer, retrying until ``timeout`` (the server process may
    still be booting)."""
    deadline = asyncio.get_running_loop().time() + timeout
    last_err: Exception | None = None
    while True:
        try:
            if address.startswith("unix://"):
                reader, writer = await asyncio.open_unix_connection(
                    address[len("unix://"):])
            else:
                hp = address[len("tcp://"):] if address.startswith("tcp://") else address
                host, _, p = hp.rpartition(":")
                reader, writer = await asyncio.open_connection(host, int(p))
            break
        except (ConnectionError, OSError, FileNotFoundError) as e:
            last_err = e
            if telemetry.enabled:
                # redial accounting: every failed dial attempt counts
                # (a restarting GCS shows as a redial burst here)
                telemetry.note_redial()
            if asyncio.get_running_loop().time() > deadline:
                raise ConnectionError(
                    f"could not connect to {address}: {last_err}") from last_err
            await asyncio.sleep(retry_interval)
    conn = Connection(reader, writer, handlers or {},
                      peer_name=peer_name or address)
    conn.start()
    return conn


class EventLoopThread:
    """A dedicated asyncio loop on a daemon thread.

    The public API (``get``/``put``/``remote``) is synchronous like the
    reference's; all IO runs on this loop (the analog of the core worker's
    internal io_service, reference: src/ray/core_worker/core_worker.h
    io_service_ member).
    """

    def __init__(self, name: str = "ray-tpu-io"):
        self.loop = asyncio.new_event_loop()
        self._thread = threading.Thread(target=self._run, name=name, daemon=True)
        self._thread.start()

    def _run(self):
        asyncio.set_event_loop(self.loop)
        self.loop.run_forever()

    def run(self, coro, timeout: float | None = None):
        """Run a coroutine on the loop from a foreign thread, blocking."""
        fut = asyncio.run_coroutine_threadsafe(coro, self.loop)
        return fut.result(timeout)

    def call_soon(self, coro):
        return asyncio.run_coroutine_threadsafe(coro, self.loop)

    def stop(self):
        async def _drain():
            tasks = [t for t in asyncio.all_tasks(self.loop)
                     if t is not asyncio.current_task()]
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)

        if self.loop.is_running():
            try:
                asyncio.run_coroutine_threadsafe(
                    _drain(), self.loop).result(timeout=3)
            except Exception:
                logger.debug("loop drain at stop failed", exc_info=True)
            self.loop.call_soon_threadsafe(self.loop.stop)
            self._thread.join(timeout=5)
        if not self.loop.is_closed():
            self.loop.close()
