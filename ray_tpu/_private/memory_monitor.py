"""Node memory watchdog: ordered degradation instead of kernel OOM roulette.

Role parity: the reference's raylet-side memory monitor
(reference: src/ray/common/memory_monitor.h MemoryMonitor +
src/ray/raylet/worker_killing_policy.cc RetriableFIFOWorkerKillingPolicy):
a user task that balloons RSS must get the *task* killed — retriably,
observably — never a random process picked by the kernel OOM killer
(which on a loaded node is as likely to be the raylet or the GCS as the
offender, turning one bad task into a whole-node death).

The watchdog piggybacks on the raylet heartbeat cadence (no extra
thread, no extra timer): every ``memory_monitor_interval_s`` it reads
node memory usage (cgroup v2 / cgroup v1 / ``/proc/meminfo`` — a
container's limit wins over the host total) and a per-worker RSS
snapshot from ``/proc/<pid>/statm``. Crossing
``memory_usage_threshold`` triggers, IN ORDER:

1. **Store pressure relief** — ``ShmStoreServer.relieve_memory_pressure``
   drains the recycle pool and evicts/spills LRU objects (tmpfs pages
   ARE node memory; freeing data beats killing compute).
2. **Worker kill** — if relief couldn't free enough, SIGKILL the worker
   running the MOST-RECENTLY-STARTED retriable task (reference policy:
   newest first, so long-running work is protected). Never the last
   leased worker making progress, never actor workers, never drivers
   (drivers aren't in the raylet's worker table). The owner is told
   first (``WorkerOOMKilled`` push) so the death surfaces as a
   retriable :class:`ray_tpu.exceptions.OutOfMemoryError` with the RSS
   snapshot in ``cause_info`` — retried under the dedicated
   ``task_oom_retries`` budget with jittered backoff, not the generic
   worker-death budget.
3. **Lease backpressure** — while above the threshold the raylet stops
   granting new leases: requests are answered with the existing
   spillback reply when a remote node has capacity (work drains off
   the hot node) or a typed ``retry_later`` the owner backs off on —
   instead of admitting more work the watchdog would immediately kill.

Determinism: the ``memory.poll`` faultpoint lets tests inject a
simulated usage fraction / per-pid RSS (``hook`` action mutating the
``sim`` ctx dict), ``memory.kill`` fires before every kill (``drop``
suppresses it), and ``lease.backpressure`` fires per rejected lease —
the whole sequence replays from a seeded schedule (tests/chaos.py
``oom_storm``). Zero cost disarmed: one ``faultpoints.armed`` check.

Counters: ``ray_tpu_memory_monitor_kills_total`` and
``ray_tpu_lease_backpressure_rejects_total`` on the cluster /metrics
endpoint, plus honest per-node counts in heartbeat stats /
``GetNodeStats`` / ``ray_tpu.state.summary_nodes()``.
"""

from __future__ import annotations

import logging
import os
import time
from collections import deque
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

from ray_tpu._private import faultpoints

logger = logging.getLogger(__name__)

_PAGE_SIZE = os.sysconf("SC_PAGE_SIZE") if hasattr(os, "sysconf") else 4096

# cgroup limits at or above this are "no limit" sentinels (v1 reports
# PAGE_COUNTER_MAX ~= 2^63/PAGE_SIZE when unlimited).
_CGROUP_NO_LIMIT = 1 << 60

# Per-poll ceiling on store relief work: _evict/_spill do synchronous
# file writes on the raylet event loop, and an unbounded node-scale
# deficit (GBs over threshold) would stall heartbeats for seconds —
# risking the dead-node timeout the watchdog exists to prevent.
# Successive polls continue the relief incrementally.
RELIEF_MAX_BYTES_PER_POLL = 256 * 1024 * 1024


# --------------------------------------------------------------------------
# Prometheus-side counters (same lazy-registration pattern as
# data_channel._plane_metrics: registered in whichever process runs the
# raylet, shipped by that process's metric reporter).
# --------------------------------------------------------------------------

_prom = None


def _monitor_metrics() -> dict:
    global _prom
    if _prom is None:
        from ray_tpu._private import metrics as m
        _prom = {
            "kills": m.Counter(
                "ray_tpu_memory_monitor_kills_total",
                "Workers SIGKILLed by the node memory watchdog (each "
                "kill surfaces as a retriable OutOfMemoryError at the "
                "task's owner)"),
            "backpressure_rejects": m.Counter(
                "ray_tpu_lease_backpressure_rejects_total",
                "Lease requests rejected (spilled or told retry-later) "
                "because the node was above memory_usage_threshold"),
        }
    return _prom


# --------------------------------------------------------------------------
# memory readers (cgroup-aware; tiny procfs/sysfs reads, never disk IO)
# --------------------------------------------------------------------------


def _read_int_file(path: str) -> Optional[int]:
    try:
        # one-line procfs/sysfs read: µs-scale, memory-backed, never disk
        with open(path, "rb") as f:
            raw = f.read().strip()
    except OSError:
        return None
    if raw == b"max":
        return None
    try:
        return int(raw)
    except ValueError:
        return None


def _cgroup_memory() -> Optional[Tuple[int, int]]:
    """(used, limit) from the cgroup this process lives in, or None when
    uncontained (no cgroup files, or an unlimited limit). A container's
    limit is the honest "node total" — the kernel OOM killer fires at
    the cgroup boundary, not the host's."""
    # v2 unified hierarchy
    cur = _read_int_file("/sys/fs/cgroup/memory.current")
    if cur is not None:
        lim = _read_int_file("/sys/fs/cgroup/memory.max")
        if lim is not None and 0 < lim < _CGROUP_NO_LIMIT:
            return cur, lim
    # v1
    cur = _read_int_file("/sys/fs/cgroup/memory/memory.usage_in_bytes")
    if cur is not None:
        lim = _read_int_file("/sys/fs/cgroup/memory/memory.limit_in_bytes")
        if lim is not None and 0 < lim < _CGROUP_NO_LIMIT:
            return cur, lim
    return None


def _meminfo_memory() -> Optional[Tuple[int, int]]:
    """(used, total) from /proc/meminfo: used = total - available, the
    same definition the kernel OOM heuristics work from."""
    total = avail = None
    try:
        # /proc/meminfo is memory-backed (µs-scale read)
        with open("/proc/meminfo", "rb") as f:
            for line in f:
                if line.startswith(b"MemTotal:"):
                    total = int(line.split()[1]) * 1024
                elif line.startswith(b"MemAvailable:"):
                    avail = int(line.split()[1]) * 1024
                if total is not None and avail is not None:
                    break
    except (OSError, ValueError, IndexError):
        return None
    if total is None or avail is None or total <= 0:
        return None
    return total - avail, total


def _psutil_memory() -> Optional[Tuple[int, int]]:
    try:
        import psutil
        vm = psutil.virtual_memory()
        return int(vm.total - vm.available), int(vm.total)
    except Exception:  # noqa: BLE001 — no psutil / exotic platform
        return None


# Resolved memory source, cached after the first successful read: the
# full probe chain (cgroup v2 -> cgroup v1 -> meminfo -> psutil) costs
# ~0.5ms when the box is uncontained — fallthrough attempts against
# files that don't exist or report "max" — while the steady-state
# winner reads in ~60µs. Re-resolved only if the cached source fails.
_memory_source: Optional[Any] = None


def node_memory_usage() -> Tuple[int, int]:
    """(used_bytes, total_bytes) for this node — cgroup limit first
    (container-aware: the kernel OOM killer fires at the cgroup
    boundary), /proc/meminfo next, psutil as the portable fallback.
    (0, 0) when nothing is readable (the watchdog then idles: no
    relief, no kills, no backpressure)."""
    global _memory_source
    src = _memory_source
    if src is not None:
        got = src()
        if got is not None:
            return got
        _memory_source = None  # cached source vanished: re-resolve
    for fn in (_cgroup_memory, _meminfo_memory, _psutil_memory):
        got = fn()
        if got is not None:
            _memory_source = fn
            return got
    return 0, 0


def process_rss(pid: int) -> int:
    """Resident set size of ``pid`` in bytes via /proc/<pid>/statm
    (field 2 = resident pages). 0 for a dead/unreadable pid."""
    try:
        # one-line procfs read: µs-scale, memory-backed
        with open(f"/proc/{pid}/statm", "rb") as f:
            return int(f.read().split()[1]) * _PAGE_SIZE
    except (OSError, ValueError, IndexError):
        return 0


class MemoryMonitor:
    """The per-raylet watchdog. Owns no thread: the raylet's heartbeat
    loop calls :meth:`poll` and the interval gate inside decides whether
    this beat actually samples. Collaborators arrive as callables so the
    monitor is unit-testable without a raylet:

    * ``workers()`` -> iterable of WorkerHandle-shaped objects
      (``state``/``pid``/``worker_id``/``leased_at``/``lease_retriable``)
    * ``kill_worker(handle, cause_dict)`` -> performs owner notification
      + SIGKILL (the raylet's ``_oom_kill_worker``)
    * ``store`` -> ShmStoreServer (``relieve_memory_pressure``)
    """

    def __init__(self, config, store, nid12: str,
                 workers: Callable[[], Iterable[Any]],
                 kill_worker: Callable[[Any, dict], None]):
        self.enabled = bool(getattr(config, "memory_monitor_enabled", True))
        self.threshold = float(
            getattr(config, "memory_usage_threshold", 0.95))
        self.interval_s = float(
            getattr(config, "memory_monitor_interval_s", 0.5))
        self.store = store
        self.nid12 = nid12
        self.workers = workers
        self.kill_worker = kill_worker
        self._last_poll = 0.0
        # last-poll snapshot (served by GetNodeStats / heartbeat stats)
        self.pressure = False
        self.used = 0
        self.total = 0
        self.usage_fraction = 0.0
        self.workers_rss: Dict[str, int] = {}     # wid12 -> bytes
        # honest cumulative counters (process lifetime)
        self.kills = 0
        self.backpressure_rejects = 0
        self.relief_bytes = 0
        self.polls = 0
        # last 64 watchdog actions, for observability and the ordering
        # test (relief must precede any kill within a poll)
        self.history: Any = deque(maxlen=64)

    # ------------------------------------------------------------- sampling

    def due(self) -> bool:
        """Whether an unforced :meth:`poll` would sample now."""
        return self.enabled and \
            time.monotonic() - self._last_poll >= self.interval_s

    def live_pids(self) -> list:
        return [w.pid for w in self.workers()
                if w.pid and w.state != "dead"]

    @staticmethod
    def read_procfs(pids) -> Tuple[Tuple[int, int], Dict[int, int]]:
        """Everything one poll reads from procfs: ((used, total) of the
        node, {pid: rss}). Touches no monitor state, so the raylet runs
        it on an executor thread: these reads are µs-scale on a quiet
        node but block for SECONDS while a process maps or unmaps a
        TPU's memory (measured on a v5e host: 3-5 s per TPU-client
        start or exit), and a raylet loop blocked that long misses its
        heartbeats."""
        return node_memory_usage(), {pid: process_rss(pid) for pid in pids}

    def _pick_victim(self):
        """The most-recently-started retriable task's worker — never the
        last leased worker (someone must keep making progress), never
        actors (their restart machinery is a different failure domain),
        never drivers (not in the raylet's worker table at all)."""
        leased = [w for w in self.workers()
                  if w.state == "leased" and w.pid]
        if len(leased) < 2:
            return None
        # oom_kill_pending: a victim already dispatched to the (async,
        # owner-acked) kill path but not yet dead — re-selecting it on
        # the next poll would double-count the kill and double-notify
        # the owner.
        cands = [w for w in leased
                 if getattr(w, "lease_retriable", False)
                 and not getattr(w, "oom_kill_pending", False)]
        if not cands:
            return None
        return max(cands, key=lambda w: getattr(w, "leased_at", 0.0))

    # --------------------------------------------------------------- poll

    def note_backpressure(self) -> None:
        """One lease request rejected under pressure (counted by the
        raylet's lease path; the Prometheus counter rides along)."""
        self.backpressure_rejects += 1
        _monitor_metrics()["backpressure_rejects"].inc()

    def note_kill(self) -> None:
        """One watchdog kill actually LANDED (the raylet's async kill
        path calls this at SIGKILL time): honest counters never count
        a dispatch the re-grant guard aborted."""
        self.kills += 1
        _monitor_metrics()["kills"].inc()

    def poll(self, force: bool = False, procfs=None) -> None:
        """One watchdog evaluation (interval-gated unless ``force``).
        Runs the ordered degradation sequence when over the threshold:
        store relief first, then at most ONE worker kill per poll (a
        storm kills one victim per interval, not the whole pool at
        once — each kill frees memory the next poll re-measures).
        ``procfs`` is a :meth:`read_procfs` result taken just before
        (off the event loop); without one the poll reads inline."""
        if not self.enabled:
            # never leave pressure LATCHED by a disable: the raylet
            # gates lease admission on this flag, and no future poll
            # could clear it — every lease would retry-later forever
            self.pressure = False
            return
        now = time.monotonic()
        if not force and now - self._last_poll < self.interval_s:
            return
        self._last_poll = now
        self.polls += 1
        sim: Dict[str, Any] = {}
        if faultpoints.armed:
            # simulated-RSS seam: a ``hook`` mutates ``sim`` (keys
            # ``usage_fraction`` and ``rss_by_pid``) to drive the whole
            # sequence deterministically; ``drop`` skips this poll.
            # ``pids`` carries the live worker pids so seeded chaos
            # hooks can ramp a random worker's simulated RSS.
            act = faultpoints.fire("memory.poll", node=self.nid12,
                                   sim=sim, pids=self.live_pids())
            if act == "drop":
                return
        (used, total), rss = procfs or self.read_procfs(self.live_pids())
        if "usage_fraction" in sim and total > 0:
            used = int(float(sim["usage_fraction"]) * total)
        rss = {**rss, **(sim.get("rss_by_pid") or {})}
        self.workers_rss = {
            w.worker_id.hex()[:12]: int(rss.get(w.pid, 0))
            for w in self.workers() if w.pid and w.state != "dead"}
        self.used, self.total = used, total
        self.usage_fraction = used / total if total else 0.0
        if total <= 0 or self.usage_fraction < self.threshold:
            self.pressure = False
            return
        self.pressure = True
        # (1) pressure relief: recycle-pool drain + LRU evict/spill.
        # tmpfs store pages are node memory — freeing data is strictly
        # cheaper than killing compute, so it always runs first. The
        # ask is clamped per poll (bounded loop stall; see
        # RELIEF_MAX_BYTES_PER_POLL).
        need = used - int(self.threshold * total)
        ask = min(need, RELIEF_MAX_BYTES_PER_POLL)
        freed = self.store.relieve_memory_pressure(ask)
        if freed:
            self.relief_bytes += freed
            self.history.append({"ts": time.time(), "action": "relief",
                                 "freed_bytes": freed, "need_bytes": need,
                                 "ask_bytes": ask})
        if freed >= ask:
            # relief delivered its full slice: still making progress,
            # nobody dies this poll (the next poll re-measures and
            # continues — or escalates once the store runs dry)
            return
        # (2) one kill per poll: newest retriable leased worker.
        victim = self._pick_victim()
        if victim is None:
            return  # backpressure (3) is the raylet lease path's job
        wid12 = victim.worker_id.hex()[:12]
        if faultpoints.armed:
            act = faultpoints.fire("memory.kill", node=self.nid12,
                                   worker=wid12, pid=victim.pid)
            if act == "drop":
                return
        cause = {
            "kind": "WORKER_OOM",
            "node_id": self.nid12,
            "worker_id": victim.worker_id.hex(),
            "message": (f"node memory {self.usage_fraction:.1%} above "
                        f"threshold {self.threshold:.0%}; watchdog "
                        f"killed the newest retriable task's worker"),
            "usage_fraction": round(self.usage_fraction, 4),
            "threshold": self.threshold,
            "workers_rss": dict(self.workers_rss),
        }
        victim.oom_kill_pending = True
        # counters increment in note_kill() when the SIGKILL actually
        # lands — a dispatch aborted by the raylet's re-grant guard
        # (the lease completed during the owner-ack wait) is not a kill
        self.history.append({"ts": time.time(), "action": "kill",
                             "worker": wid12, "pid": victim.pid,
                             "rss": self.workers_rss.get(wid12, 0)})
        logger.warning(
            "memory watchdog killing worker %s (pid %s, rss %s): node "
            "at %.1f%% >= %.0f%%", wid12, victim.pid,
            self.workers_rss.get(wid12, 0), self.usage_fraction * 100,
            self.threshold * 100)
        self.kill_worker(victim, cause)

    # -------------------------------------------------------------- stats

    def snapshot(self) -> dict:
        """Watchdog state for GetNodeStats (full) — heartbeat stats
        carry the flat subset (see raylet._heartbeat_stats)."""
        return {
            "enabled": self.enabled,
            "threshold": self.threshold,
            "interval_s": self.interval_s,
            "pressure": self.pressure,
            "used_bytes": self.used,
            "total_bytes": self.total,
            "usage_fraction": round(self.usage_fraction, 4),
            "workers_rss_bytes": dict(self.workers_rss),
            "kills_total": self.kills,
            "backpressure_rejects_total": self.backpressure_rejects,
            "relief_bytes_total": self.relief_bytes,
            "polls": self.polls,
            "history": list(self.history),
        }
