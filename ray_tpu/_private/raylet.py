"""Raylet: per-node control — worker pool, leases, scheduling, object plane.

Role parity: reference raylet (src/ray/raylet/node_manager.h NodeManager,
worker_pool.h WorkerPool, scheduling/cluster_task_manager.h) plus the
node-local shared-memory store it hosts (the plasma thread in the reference,
src/ray/object_manager/object_manager.cc ObjectStoreRunner) and the
node-to-node object transfer path (src/ray/object_manager/object_manager.h
Push/Pull).

Protocol surface (all framed-msgpack RPC, see rpc.py):
  workers   : RegisterWorker, ActorExited, SealObject, GetObjectInfo,
              EnsureObjectLocal, PinObject, FreeObject
  clients   : RequestWorkerLease, ReturnWorker (lease pipelining is
              client-side, reference: direct_task_transport.h);
              streaming leases: ReportLeaseDemand (owner -> raylet
              push, backlog refresh), GrantLeaseCredits (raylet ->
              owner push, pre-granted worker slots + window target),
              RevokeLeaseCredits (raylet -> owner call, reclaim of
              unused credits)
  GCS       : ScheduleActorCreation, KillActorWorker, PreparePGBundle,
              CommitPGBundle, ReturnPGBundle, DrainSelf
  raylets   : FetchObjectMeta (pull probe) + FetchObjectChunk (legacy
              chunk serve); bulk chunk bytes ride the striped raw-socket
              data plane (data_channel.py), never this control stream
  ops       : GetNodeStats, GetLogs, DumpWorkerStacks, SetResource

The reference's per-node dashboard/runtime-env AGENT process
(dashboard/agent.py + raylet/agent_manager.h:43) is folded INTO this
raylet by design: runtime envs (working_dir packages, pip installs)
materialize lazily in workers keyed by env hash, and the agent's
stats/log/stack serving is the ops RPC surface above — one less
process per node, same capabilities.
"""

from __future__ import annotations

import asyncio
import itertools
import logging
import os
import signal
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional, Set, Tuple

from ray_tpu._private import backoff as backoff_mod
from ray_tpu._private import compile_cache
from ray_tpu._private import faultpoints
from ray_tpu._private import protocol
from ray_tpu._private import rpc
from ray_tpu._private import runtime_env as runtime_env_mod
from ray_tpu._private.config import RayTpuConfig
from ray_tpu._private.ids import NodeID, ObjectID, WorkerID
from ray_tpu._private.scheduler import (
    GRANT, INFEASIBLE, SPILL, WAIT, NodeView, PendingRequest, make_backend,
)
from ray_tpu._private.object_events import (
    LEAK_CLEARED, LEAK_RECLAIMED, LEAKED, PULLED, ObjectEventBuffer,
)
from ray_tpu._private.shm_store import (
    ShmStoreServer, map_cache_stats as _map_cache_stats,
)
from ray_tpu._private.task_events import (
    LEASE_GRANTED, PENDING_LEASE, SPILLBACK, TRANSFER, TaskEventBuffer,
)

logger = logging.getLogger(__name__)

# Prometheus counters for the SPMD layer (distributed_array.py verbs
# executed by this raylet). Lazily registered like data_channel's
# _plane_metrics: the counters exist only in processes that actually
# run gathers/gang leases, and ride the existing metric reporters —
# no new transport.
_spmd_prom = None


def _spmd_metrics() -> dict:
    global _spmd_prom
    if _spmd_prom is None:
        from ray_tpu._private import metrics as m
        _spmd_prom = {
            "reshard_bytes": m.Counter(
                "ray_tpu_reshard_bytes_total",
                "DistributedArray bytes moved by GatherShards "
                "(reshard/all-gather/all-reduce destinations)"),
            "gang_leases": m.Counter(
                "ray_tpu_gang_leases_total",
                "SPMD gang leases granted (one per all-or-nothing "
                "N-worker booking round)"),
            "collective_bytes": m.Counter(
                "ray_tpu_collective_bytes_total",
                "DistributedArray collective wire bytes this node "
                "pulled, by algorithm (ring reduce-scatter/all-gather "
                "steps vs the fold GatherShards path)"),
        }
    return _spmd_prom


def _read_file_chunk(path: str, pos: int, limit: int = 256 * 1024) -> bytes:
    """Bounded read at an offset — executor-thread helper so the log
    monitor never does file I/O on the event loop."""
    with open(path, "rb") as f:
        f.seek(pos)
        return f.read(limit)


def _read_file_tail(path: str, limit: int) -> bytes:
    """Last ``limit`` bytes of a file (executor-thread helper)."""
    with open(path, "rb") as f:
        f.seek(0, os.SEEK_END)
        size = f.tell()
        f.seek(max(0, size - limit))
        return f.read()


WORKER_IDLE = "idle"
WORKER_LEASED = "leased"
WORKER_ACTOR = "actor"
WORKER_STARTING = "starting"
WORKER_DEAD = "dead"

# Chips bound to one process -> libtpu's x,y,z bounds for that process
# (TPU_CHIPS_PER_PROCESS_BOUNDS), for a lease smaller than the host.
_TPU_PROCESS_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1"}


class WorkerHandle:
    def __init__(self, worker_id: bytes, pid: int, proc: Optional[subprocess.Popen]):
        self.worker_id = worker_id
        self.pid = pid
        self.proc = proc
        # "zygote" | "popen" | "" (externally started / not yet known)
        self.spawned_via = ""
        # Chip indices this process was bound to at spawn (a worker
        # started for a `TPU` lease); empty for every pool worker.
        self.tpu_chips: Tuple[int, ...] = ()
        self.address = ""
        self.conn: Optional[rpc.Connection] = None
        self.state = WORKER_STARTING
        self.lease_id: Optional[int] = None
        self.actor_id: bytes = b""
        self.job_id: bytes = b""
        self.started_at = time.time()
        # Memory-watchdog victim ordering (memory_monitor.py): when the
        # current lease was granted, and whether its sample task is
        # retriable — the watchdog kills the NEWEST retriable leased
        # worker first and never touches non-retriable work.
        self.leased_at = 0.0
        self.lease_retriable = False
        # set once the watchdog dispatched this worker to the async
        # owner-acked kill path (prevents double-selection)
        self.oom_kill_pending = False
        # Runtime env this worker last activated: leases prefer a match
        # (reference: worker_pool.h:135 runtime_env_hash PopWorker key).
        self.env_hash: str = ""


class LeaseEntry:
    def __init__(self, lease_id: int, worker: WorkerHandle,
                 resources: Dict[str, float], client: rpc.Connection):
        self.lease_id = lease_id
        self.worker = worker
        self.resources = resources
        self.client = client


class CreditWindow:
    """Per-(owner connection, scheduling class) streaming-lease state:
    how many pre-granted worker slots this owner may hold, sized from
    its reported backlog and the real scheduler view, renewed on the
    heartbeat cadence and revocable at any time (memory pressure zeroes
    the window; stale demand shrinks it). A credit is an ordinary
    LeaseEntry — owner-liveness reclaim, ReturnWorker, and the memory
    watchdog's victim ordering all see it exactly like a legacy lease."""

    __slots__ = ("conn", "sched_class", "resources", "env_hash",
                 "retriable", "demand", "demand_ts", "lease_ids",
                 "target", "last_revoke_ts", "revoking")

    def __init__(self, conn: rpc.Connection, sched_class: int,
                 resources: Dict[str, float], env_hash: str,
                 retriable: bool):
        self.conn = conn
        self.sched_class = sched_class
        self.resources = resources
        self.env_hash = env_hash
        # Victim eligibility for the memory watchdog (sample-task
        # approximation, same as the legacy lease summary's flag).
        self.retriable = retriable
        self.demand = 0          # last reported backlog (tasks)
        self.demand_ts = 0.0     # when that report landed (monotonic)
        self.lease_ids: Set[int] = set()  # outstanding credits
        self.target = -1         # last window target pushed to the owner
        self.last_revoke_ts = 0.0
        self.revoking = False


class Raylet:
    def __init__(self, config: RayTpuConfig, num_cpus: float,
                 custom_resources: Optional[Dict[str, float]] = None,
                 session_dir: str = "/tmp/ray_tpu", node_name: str = ""):
        self.config = config
        self.node_id = NodeID.from_random()
        self.node_name = node_name or f"node-{self.node_id.hex()[:8]}"
        self.session_dir = session_dir
        self.resources_total: Dict[str, float] = {"CPU": float(num_cpus)}
        if custom_resources:
            self.resources_total.update(custom_resources)
        self.resources_available = dict(self.resources_total)
        # The `TPU` resource counts this host's chips, and each unit is
        # one chip INDEX bound to one process: index -> the Popen that
        # holds it. An index is free again only once that process has
        # exited (libtpu's hold on the device ends with the process,
        # not with the SIGKILL), which _claim_tpu_chips checks lazily.
        n_tpu = self.resources_total.get("TPU", 0.0)
        if n_tpu != int(n_tpu):
            raise ValueError(
                f"the TPU resource counts whole chips, got {n_tpu}")
        self._num_tpu_chips = int(n_tpu)
        self._tpu_chip_holders: Dict[int, subprocess.Popen] = {}

        self.store = ShmStoreServer(
            capacity_bytes=config.object_store_memory,
            spill_dir=os.path.join(session_dir, "spill", self.node_id.hex()[:8]),
            spilling_enabled=config.object_spilling_enabled,
            external_storage_url=config.spill_external_storage_url)

        # Structured event log (reference: util/event.h RAY_EVENT).
        # Emissions ALSO land in the bounded cluster-event buffer and
        # ride the heartbeat into the GCS ClusterEventTable — the
        # queryable event plane (events.py); the file tier alone is
        # gated by event_log_enabled.
        from ray_tpu._private.events import ClusterEventBuffer, EventEmitter
        self.cluster_events = ClusterEventBuffer(
            getattr(config, "cluster_event_buffer_size", 4096))
        self.events = EventEmitter(
            "raylet", os.path.join(session_dir, "logs")
            if config.event_log_enabled else None,
            buffer=self.cluster_events)
        # Control-plane flight recorder (rpc.py): per-method telemetry
        # + loop-lag probe config for this process.
        rpc.telemetry.configure(config)

        self.workers: Dict[bytes, WorkerHandle] = {}
        self.leases: Dict[int, LeaseEntry] = {}
        self._lease_counter = itertools.count(1)
        self._req_counter = itertools.count(1)
        self.max_workers = int(config.max_workers_per_node or max(1, int(num_cpus)))
        self._num_starting = 0
        # Zygote worker factory (zygote.py): one pre-imported template
        # process this raylet fork()s workers from. Launched at node
        # boot when workers are prestarted, else on first demand; once
        # it fails, every later spawn stays on the cold-Popen path.
        self._zygote: Optional[Any] = None
        self._zygote_failed = False
        # Live async reapers for SIGKILLed/“disconnected” worker procs —
        # kept so stop() can await the stragglers instead of leaking
        # zombies past the raylet's lifetime.
        self._reap_tasks: Set[asyncio.Task] = set()

        # Pending lease requests in arrival order: req_id -> (PendingRequest,
        # reply future). The scheduler seam consumes this queue each tick.
        self._pending: Dict[int, Tuple[PendingRequest, asyncio.Future]] = {}
        self.backend = make_backend(config.scheduler_backend)

        # Cluster view for spillback (fed by GCS NODE pubsub + polling).
        self.remote_nodes: Dict[bytes, dict] = {}

        # Placement group reservations: (pg_id, bundle_idx) -> resources.
        self._pg_prepared: Dict[Tuple[bytes, int], Dict[str, float]] = {}
        self._pg_committed: Set[Tuple[bytes, int]] = set()
        # Per-bundle remaining capacity for leases inside a PG.
        self._pg_available: Dict[Tuple[bytes, int], Dict[str, float]] = {}

        self.gcs_conn: Optional[rpc.Connection] = None
        # wire version agreed with the GCS at registration (RegisterNode
        # handshake); MIN until the first register completes
        self.negotiated_protocol_version = protocol.MIN_PROTOCOL_VERSION
        self._server = rpc.RpcServer(self._handlers(), name="raylet")
        self.address = ""
        self._peer_raylets: Dict[str, rpc.Connection] = {}
        self._owner_conns: Dict[str, rpc.Connection] = {}
        self._hb_task: Optional[asyncio.Task] = None
        self._tick_scheduled = False
        self._closing = False
        # Pull state (reference: PullManager): dedupe + admission control.
        self._active_pulls: Dict[ObjectID, asyncio.Task] = {}
        self._pull_inflight_bytes = 0
        # Admission waiters park on this Condition and are notified on
        # every pull completion (no sleep-polling on the loop).
        self._pull_cond = asyncio.Condition()
        # Striped data plane (data_channel.py): bulk chunk bytes ride
        # dedicated raw sockets, never the RPC control stream.
        self.data_server: Optional[Any] = None
        self.data_address = ""
        self._data_channels: Dict[str, Any] = {}
        # Pull-side node directory for peers that registered BEFORE this
        # raylet subscribed to NODE (the pubsub view misses them): filled
        # on demand from the GCS, used ONLY by the pull path — the
        # scheduler's cluster view stays the pubsub one.
        self._node_directory: Dict[bytes, dict] = {}
        self._node_dir_refresh: Optional[asyncio.Task] = None
        # Serve-side attachment cache: chunked pulls hit the same segment
        # many times; re-mmap'ing per chunk would sit on the transfer hot
        # path (reference: ObjectBufferPool holds chunk buffers open).
        self._serve_attachments: Dict[str, Any] = {}
        self.num_leases_granted = 0
        self.num_spillbacks = 0
        # Streaming-lease credit windows: (id(owner conn), scheduling
        # class) -> CreditWindow. Issuance rides demand registration
        # (RequestWorkerLease backlog / ReportLeaseDemand pushes) plus
        # the heartbeat cadence; every credit is accounted as a real
        # LeaseEntry against resources_available — never a side ledger.
        self._credit_windows: Dict[Tuple[int, int], CreditWindow] = {}
        self._credit_topup_scheduled = False
        self.num_credit_grants = 0
        self.num_credit_revoked = 0
        # SPMD gang leases (distributed_array.py): gang_id -> record
        # {epoch, members, broken, dead_members, created, owner_drop}
        # on the HOME raylet (the one the owner asked), plus the member
        # bookings THIS node holds for gangs homed on a peer raylet
        # (gang_id -> {epoch, lease_ids}). Epoch-fenced like actor
        # incarnations: any gang frame carrying an older epoch is
        # rejected, and a re-formation at a higher epoch releases the
        # previous incarnation's members before booking.
        self.gangs: Dict[bytes, dict] = {}
        self._gang_members: Dict[bytes, dict] = {}
        self.num_gang_leases = 0
        self.num_gang_rejects = 0
        # Ring-collective members this raylet hosts: member_id (28-byte
        # driver-minted id, one per collective x rank — several ranks
        # of ONE collective may live here in single-node runs) ->
        # accumulator record {segment mapping, layout, reduce spec,
        # per-step wire/fold counters}. Members are created by RingInit,
        # stepped by RingStep, and freed by RingFinish/RingAbort (or
        # the TTL sweep when a driver died between rounds).
        self._ring_members: Dict[bytes, dict] = {}
        self.num_ring_collectives = 0
        # Schedule latency (request arrival -> decision dispatched), a
        # bounded reservoir for percentile reporting (reference: the
        # north-star p50/p99 schedule-latency metric, BASELINE.json).
        from collections import deque as _deque
        self._sched_latencies: Any = _deque(maxlen=65536)
        # split reservoirs: arrival->first-decision / first-decision->grant
        self._decision_latencies: Any = _deque(maxlen=65536)
        self._grant_waits: Any = _deque(maxlen=65536)
        # (queue_len, wall_s) per scheduler tick — the pure decision
        # cost of the kernel, free of queueing effects.
        self._tick_durations: Any = _deque(maxlen=65536)
        # Task-lifecycle recorder (task_events.py): lease-queue / grant
        # / spillback transitions for the sample task each lease request
        # carries, plus TRANSFER records for data-plane pulls. Flushed
        # piggybacked on the heartbeat — never its own RPC.
        self.task_events = TaskEventBuffer(
            config.task_events_buffer_size,
            enabled=config.task_events_enabled)
        self._nid12 = self.node_id.hex()[:12]
        # Object-lifecycle recorder (object_events.py): the shm store
        # stamps seal/pin/expose/evict/spill/free + segment events into
        # this buffer; the raylet adds PULLED and the leak-detector
        # verdicts. Flushed piggybacked on the heartbeat (object_events
        # header keys) — never its own RPC.
        self.object_events = ObjectEventBuffer(
            config.object_events_buffer_size,
            enabled=config.object_events_enabled)
        self.store.events = self.object_events
        self.store.node_tag = self._nid12
        # Leak detector (object_events.py): owner address per stored
        # object (fed by SealObject's owner_address and the pull path),
        # consecutive dead-verdict counts, the currently-leaked set and
        # the reclaim counter. The sweep rides the heartbeat loop.
        self._object_owners: Dict[bytes, str] = {}
        self._leak_suspects: Dict[bytes, int] = {}
        self._leaked: Set[bytes] = set()
        self.leak_reclaims = 0
        self.leak_sweeps = 0
        self._last_leak_sweep = 0.0
        self._leak_sweep_task: Optional[asyncio.Task] = None
        # per-pull throughput reservoir (GB/s), reported by GetNodeStats
        self._pull_rates: Any = _deque(maxlen=4096)
        # bounded history of finished/aborted ring-collective members,
        # surfaced by GetNodeStats' collectives block (telemetry for
        # the bench's bandwidth assertion: wire bytes per rank)
        self._recent_collectives: Any = _deque(maxlen=64)
        # Host-stats collection handles, cached once: importing psutil
        # and constructing a fresh Process() every heartbeat wasted
        # ~100us/beat, and cpu_percent(interval=None) on a fresh
        # object has no "last call" to diff against (first sample is
        # meaningless 0.0) — the cached handle makes the since-last-
        # call sample real from the second beat on.
        try:
            import psutil as _psutil
            self._psutil = _psutil
            self._psutil_proc = _psutil.Process()
            _psutil.cpu_percent(interval=None)  # prime the diff sample
        except Exception:  # noqa: BLE001 — host stats are best-effort decoration
            self._psutil = None
            self._psutil_proc = None
        # procfs read in flight on an executor thread, and the host
        # stats of the last one finished (_poll_memory_monitor)
        self._procfs_read: Optional[asyncio.Future] = None
        self._host_stats_last: dict = {}
        # Node memory watchdog (memory_monitor.py): polled from the
        # heartbeat loop; turns memory pressure into ordered relief ->
        # retriable OOM kill -> lease backpressure instead of letting
        # the kernel OOM killer shoot a random process.
        from ray_tpu._private.memory_monitor import MemoryMonitor
        self.memory_monitor = MemoryMonitor(
            config, self.store, self._nid12,
            workers=lambda: self.workers.values(),
            kill_worker=self._oom_kill_worker)

    def _handlers(self):
        return {
            "RegisterWorker": self.handle_register_worker,
            "RequestWorkerLease": self.handle_request_worker_lease,
            "ReportLeaseDemand": self.handle_report_lease_demand,
            "ReturnWorker": self.handle_return_worker,
            "RequestGangLease": self.handle_request_gang_lease,
            "BookGangMembers": self.handle_book_gang_members,
            "ReleaseGangMembers": self.handle_release_gang_members,
            "ReleaseGangLease": self.handle_release_gang_lease,
            "GatherShards": self.handle_gather_shards,
            "RingInit": self.handle_ring_init,
            "RingStep": self.handle_ring_step,
            "RingFinish": self.handle_ring_finish,
            "RingAbort": self.handle_ring_abort,
            "ScheduleActorCreation": self.handle_schedule_actor_creation,
            "KillActorWorker": self.handle_kill_actor_worker,
            "ActorExited": self.handle_actor_exited,
            "SealObject": self.handle_seal_object,
            "AllocSegment": self.handle_alloc_segment,
            "AbortSegment": self.handle_abort_segment,
            "GetObjectInfo": self.handle_get_object_info,
            "EnsureObjectLocal": self.handle_ensure_object_local,
            "FetchObjectMeta": self.handle_fetch_object_meta,
            "FetchObjectChunk": self.handle_fetch_object_chunk,
            "PinObject": self.handle_pin_object,
            "FreeObject": self.handle_free_object,
            "PreparePGBundle": self.handle_prepare_pg_bundle,
            "CommitPGBundle": self.handle_commit_pg_bundle,
            "ReturnPGBundle": self.handle_return_pg_bundle,
            "GetNodeStats": self.handle_get_node_stats,
            "SetResource": self.handle_set_resource,
            "DumpWorkerStacks": self.handle_dump_worker_stacks,
            "GetLogs": self.handle_get_logs,
            "Published": self.handle_published,
        }

    # ------------------------------------------------------------- lifecycle

    async def start(self, gcs_address: str, listen_address: str = "") -> str:
        # Warm the native copy tier off-loop: copy_into on the chunked
        # pull path uses only the already-loaded module (it never
        # builds), so the one compile a cold cache costs happens here,
        # in an executor, before the raylet serves anything.
        from ray_tpu._private import native
        await asyncio.get_running_loop().run_in_executor(
            None, native.load_fastpath)
        sock_dir = os.path.join(self.session_dir, "sockets")
        os.makedirs(sock_dir, exist_ok=True)
        if not listen_address:
            listen_address = f"unix://{sock_dir}/raylet-{self.node_id.hex()[:12]}"
        self.address = await self._server.listen(listen_address)
        if self.config.data_plane_stripes > 0:
            # Bulk-transfer listener next to the RPC server (reference:
            # the object manager's own server, separate from the node
            # manager's — src/ray/object_manager/object_manager.h).
            from ray_tpu._private.data_channel import DataPlaneServer
            host = "127.0.0.1"
            if self.address.startswith("tcp://"):
                host = self.address[len("tcp://"):].rpartition(":")[0] \
                    or host
            self.data_server = DataPlaneServer(self.store, host=host)
            self.data_address = await self.data_server.start()
        self.gcs_address = gcs_address
        # Full handler map on the GCS connection too: the GCS issues
        # requests (actor scheduling, PG 2PC, kills) back over this pipe.
        self.gcs_conn = await rpc.connect(
            gcs_address, handlers=self._handlers(), peer_name="gcs")
        await self._register_with_gcs()
        self._hb_task = asyncio.get_running_loop().create_task(self._heartbeat_loop())
        self._log_monitor_task = asyncio.get_running_loop().create_task(
            self._log_monitor_loop())
        n_prestart = self.config.num_prestart_workers
        if n_prestart < 0:  # auto: one warm worker per CPU slot
            n_prestart = min(int(self.resources_total.get("CPU", 0)),
                             self.max_workers)
        for _ in range(n_prestart):
            self._start_worker_process()
        logger.info("raylet %s listening at %s (%s)",
                    self.node_id.hex()[:8], self.address, self.resources_total)
        self.events.emit("INFO", "RAYLET_STARTED",
                         f"raylet listening at {self.address}",
                         node=self.node_id.hex()[:12],
                         resources=self.resources_total)
        return self.address

    async def stop(self):
        self._closing = True
        if self._hb_task:
            self._hb_task.cancel()
        if getattr(self, "_log_monitor_task", None):
            self._log_monitor_task.cancel()
        if self._leak_sweep_task is not None and \
                not self._leak_sweep_task.done():
            self._leak_sweep_task.cancel()
        self.events.close()
        procs = []
        for w in list(self.workers.values()):
            self._kill_worker(w)
            if w.proc is not None:
                procs.append(w.proc)
        await self._reap_procs(procs)
        for t in list(self._reap_tasks):
            t.cancel()
        if self._zygote is not None:
            await self._zygote.close()
            self._zygote = None
        await self._server.close()
        if self.gcs_conn and not self.gcs_conn.closed:
            # Graceful departure: tell the GCS we're draining so a planned
            # shutdown isn't reported as a node failure (reference:
            # NodeManager drain / UnregisterNode path).
            try:
                await self.gcs_conn.call(
                    "DrainNode", {"node_id": self.node_id.binary()},
                    timeout=2)
            except (ConnectionError, asyncio.TimeoutError):
                pass
            await self.gcs_conn.close()
        for ch in list(self._data_channels.values()):
            await ch.close()
        self._data_channels.clear()
        # in-flight ring collectives die with the node: release their
        # leased accumulator segments (the driver's step RPC fails and
        # it aborts the surviving members on the other nodes)
        for mid, mrec in list(self._ring_members.items()):
            self._ring_members.pop(mid, None)
            self._discard_ring_member(mid, mrec, reason="raylet stopped")
        if self.data_server is not None:
            await self.data_server.close()
        for att in self._serve_attachments.values():
            try:
                att.close()
            except BufferError:
                pass
        self._serve_attachments.clear()
        self.store.shutdown()

    async def _log_monitor_loop(self):
        """Tail this node's worker log files and publish new lines to
        the GCS LOGS channel; drivers with log_to_driver print them
        (reference: python/ray/_private/log_monitor.py tailing into
        Redis pubsub, worker.py print_logs)."""
        log_dir = os.path.join(self.session_dir, "logs")
        offsets: Dict[str, int] = {}
        while not self._closing:
            await asyncio.sleep(0.25)
            try:
                names = [n for n in os.listdir(log_dir)
                         if n.startswith("worker-") and n.endswith(".log")]
            except FileNotFoundError:
                continue
            pid_by_wid_hex = {w.worker_id.hex(): w.pid
                              for w in self.workers.values()}
            for name in names:
                path = os.path.join(log_dir, name)
                pos = offsets.get(name, 0)
                try:
                    # Off-loop read: log files live on local disk, and a
                    # cold-cache 256 KiB read can stall the loop for ms.
                    chunk = await asyncio.get_running_loop() \
                        .run_in_executor(None, _read_file_chunk, path, pos)
                except OSError:
                    continue
                if not chunk:
                    continue
                # only publish complete lines; keep the tail buffered —
                # and only advance the offset over lines actually
                # published (a chatty worker's extra lines are picked up
                # by the next poll, never dropped). Split on raw \n so
                # the byte offset always matches the line count.
                cut = chunk.rfind(b"\n")
                if cut < 0:
                    continue
                raw = chunk[:cut].split(b"\n")
                if len(raw) > 1000:
                    raw = raw[:1000]
                    cut = sum(len(r) for r in raw) + len(raw) - 1
                lines = [r.decode("utf-8", "replace") for r in raw]
                offsets[name] = pos + cut + 1
                wid_hex = name[len("worker-"):-len(".log")]
                pid = next((p for w, p in pid_by_wid_hex.items()
                            if w.startswith(wid_hex)), 0)
                try:
                    await self.gcs_conn.call("Publish", {
                        "channel": "LOGS",
                        "msg": {"node": self.node_id.hex()[:12],
                                "ip": self.node_name or "local",
                                "pid": pid, "lines": lines},
                    })
                except ConnectionError:
                    pass  # heartbeat loop owns reconnects

    def _host_stats(self) -> dict:
        """psutil's view of the host. Executor-thread helper: it reads
        procfs, which can block for seconds while a process maps a
        TPU's memory (see MemoryMonitor.read_procfs)."""
        if self._psutil is None:
            return {}
        try:
            # cached module + Process handle (set at __init__):
            # interval=None is a non-blocking since-last-call
            # sample, real because init primed the first call
            vm = self._psutil.virtual_memory()
            du = self._psutil.disk_usage(self.session_dir or "/")
            return {
                "host_cpu_percent": self._psutil.cpu_percent(interval=None),
                "host_mem_used_bytes": float(vm.used),
                "host_mem_total_bytes": float(vm.total),
                "host_disk_used_bytes": float(du.used),
                "host_disk_total_bytes": float(du.total),
                "raylet_rss_bytes": float(
                    self._psutil_proc.memory_info().rss)}
        # raylint: disable=exception-hygiene — host stats are best-effort decoration
        except Exception:
            return {}

    def _read_procfs(self, pids) -> tuple:
        """Executor thread: all the procfs one beat wants."""
        return self.memory_monitor.read_procfs(pids), self._host_stats()

    def _poll_memory_monitor(self, loop) -> None:
        """One beat of the memory watchdog. procfs is read on an
        executor thread and the beat never waits for it: one beat
        starts a read, a later beat hands the finished sample to the
        watchdog (and to the heartbeat's host stats). While a read is
        held up — see MemoryMonitor.read_procfs — beats go on, with the
        previous host stats and no watchdog evaluation."""
        mon = self.memory_monitor
        read = self._procfs_read
        if read is None:
            self._procfs_read = loop.run_in_executor(
                None, self._read_procfs,
                mon.live_pids() if mon.due() else ())
        elif read.done():
            self._procfs_read = None
            # raylint: disable=async-blocking — the future is done: result() returns at once
            procfs, self._host_stats_last = read.result()
            mon.poll(procfs=procfs)

    def _heartbeat_stats(self, host_stats: dict) -> dict:
        """Flat per-node stats piggybacked on heartbeats → GCS metrics
        endpoint + dashboard API (reference: raylet resource/stats
        reports feeding the metrics agent, metric_defs.h gauges; host
        stats parity: reporter_agent.py:126 psutil collection)."""
        s = self.store.stats()
        out = {
            "num_workers": self._alive_worker_count(),
            "num_pending_leases": len(self._pending),
            "num_leases_granted": self.num_leases_granted,
            "num_credit_grants": self.num_credit_grants,
            "num_credit_revoked": self.num_credit_revoked,
            "num_credit_windows": len(self._credit_windows),
            "num_spillbacks": self.num_spillbacks,
            "store_used_bytes": s["used_bytes"],
            "store_num_objects": s["num_objects"],
            "store_num_spills": s["num_spills"],
            "store_num_evictions": s["num_evictions"],
            # Object-plane rollups (ISSUE 13 satellite): the store /
            # recycle-pool / map-cache / data-plane truth GetNodeStats
            # always had, now on every beat so summary_nodes() and the
            # dashboard show it without a per-node RPC.
            "store_capacity_bytes": s["capacity_bytes"],
            "store_num_pinned": s["num_pinned"],
            "store_num_spilled": s["num_spilled"],
            "store_recycle_bytes": s["recycle_pool_bytes"],
            "store_recycle_segments": s["recycle_pool_segments"],
            "store_lent_segments": s["recycle_lent_segments"],
            "store_lent_bytes": s["recycle_lent_bytes"],
            "data_plane_inflight_bytes": self._pull_inflight_bytes,
            "objects_leaked": len(self._leaked),
            "leak_reclaims": self.leak_reclaims,
        }
        mc = _map_cache_stats()
        out["map_cache_entries"] = mc["entries"]
        out["map_cache_bytes"] = mc["bytes"]
        out["map_cache_hits"] = mc["hits"]
        out["map_cache_misses"] = mc["misses"]
        mon = self.memory_monitor
        if mon is not None:
            # watchdog state rides every beat (flat, same style as the
            # spill/eviction counters): per-worker RSS sum, pressure
            # flag, cumulative kill/backpressure counts — all honest
            # (monotonic counters, last-poll gauges).
            out["workers_rss_bytes"] = sum(
                mon.workers_rss.values())
            out["memory_pressure"] = mon.pressure
            out["memory_usage_fraction"] = round(mon.usage_fraction, 4)
            out["memory_monitor_kills"] = mon.kills
            out["lease_backpressure_rejects"] = mon.backpressure_rejects
        out.update(host_stats)
        # NOTE: scheduler latency percentiles are deliberately NOT
        # computed here — sorting a 64k reservoir 4x/s on the event
        # loop would stall heartbeats under load; GetNodeStats computes
        # them on demand. Per-handler RPC latency (C4 instrumented-asio
        # parity) IS carried: the snapshot is a dozen small dict
        # entries, and the loop-lag flat keys below feed the per-node
        # Prometheus gauges (the RPC reservoirs ship separately in the
        # throttled rpc_telemetry beat key).
        from ray_tpu._private.rpc import handler_stats, telemetry
        out["rpc_handlers"] = handler_stats.snapshot()
        # this raylet loop's OWN probe (named: an in-process head's
        # driver loop stalls must never read as this node's lag)
        lp = telemetry.loop_probe("raylet").snapshot()
        lag = lp.get("lag") or {}
        out["loop_lag_p50_ms"] = lag.get("p50_ms", 0.0)
        out["loop_lag_p99_ms"] = lag.get("p99_ms", 0.0)
        out["loop_lag_max_ms"] = lp.get("lag_max_ms", 0.0)
        out["loop_slow_callbacks"] = lp.get("slow_callbacks", 0)
        out["loop_ticks"] = lp.get("ticks", 0)
        return out

    async def _heartbeat_loop(self):
        from ray_tpu._private import metrics as metrics_mod

        period = self.config.raylet_heartbeat_period_ms / 1000.0
        loop = asyncio.get_running_loop()
        while not self._closing:
            try:
                # Memory watchdog rides the heartbeat cadence (interval
                # gate inside poll) — BEFORE the heartbeat-drop fault
                # seam: a partitioned node must still protect itself
                # from the kernel OOM killer. Shielded: a watchdog
                # error (an armed hook that raises, an exotic procfs)
                # must degrade to a missed poll, never take down the
                # heartbeat loop — that would convert memory pressure
                # into the node death the watchdog exists to prevent.
                # Loop-lag probe rides this existing cadence (the
                # instrumented_io_context tick): one call_soon, no new
                # thread/timer.
                rpc.telemetry.loop_probe("raylet").tick()
                try:
                    was_pressure = self.memory_monitor.pressure
                    self._poll_memory_monitor(loop)
                    if was_pressure != self.memory_monitor.pressure:
                        # pressure transitions are cluster events (the
                        # per-reject counter rides the stats; emitting
                        # per reject would storm the bounded buffer)
                        if self.memory_monitor.pressure:
                            self.events.emit(
                                "WARNING", "MEMORY_PRESSURE",
                                f"memory pressure engaged at "
                                f"{self.memory_monitor.usage_fraction:.2f}"
                                f" usage; lease backpressure active",
                                node=self._nid12,
                                usage_fraction=round(
                                    self.memory_monitor.usage_fraction,
                                    4))
                        else:
                            self.events.emit(
                                "INFO", "MEMORY_PRESSURE_CLEARED",
                                "memory pressure cleared",
                                node=self._nid12,
                                backpressure_rejects=self.memory_monitor
                                .backpressure_rejects)
                    if was_pressure and not self.memory_monitor.pressure:
                        # pressure cleared: re-evaluate whatever the
                        # backpressure window parked (PG leases stay
                        # pending through it — nothing else ticks them)
                        self._schedule_tick()
                except Exception:  # noqa: BLE001 — missed poll < dead node
                    logger.exception("memory watchdog poll failed")
                # Streaming-lease window maintenance rides the same
                # beat, right after the watchdog poll: a pressure
                # crossing zeroes/revokes credit windows IMMEDIATELY —
                # before any lease backpressure decision — and stale
                # windows shrink here. Shielded like the watchdog: a
                # credit bug must cost a missed beat, not the node.
                try:
                    self._credit_beat()
                except Exception:  # noqa: BLE001 — missed beat < dead node
                    logger.exception("lease-credit beat failed")
                # Object-plane leak sweep rides the same beat (interval
                # gate inside) but runs as a BACKGROUND task: probing a
                # SIGKILLed owner costs a full refused-dial timeout,
                # and blocking the beat that long would make the GCS
                # declare this healthy node dead — the exact confusion
                # the detector exists to remove. Shielded like the
                # watchdog: a sweep bug costs a sweep, never the node.
                try:
                    self._maybe_start_leak_sweep()
                except Exception:  # noqa: BLE001 — missed sweep < dead node
                    logger.exception("object leak sweep failed to start")
                if faultpoints.armed:
                    # heartbeat-partition fault: ``drop`` suppresses the
                    # beat (fired BEFORE the event drain, so no task
                    # events are lost to a skipped beat); enough
                    # consecutive drops make the GCS declare this node
                    # dead — the re-registration path below must then
                    # resurrect it once beats resume.
                    act = await faultpoints.async_fire(
                        "raylet.heartbeat", node=self._nid12)
                    if act == "drop":
                        await asyncio.sleep(period)
                        continue
                beat = protocol.HeartbeatRequest(
                    node_id=self.node_id.binary(),
                    resources_available=self.resources_available,
                    stats=self._heartbeat_stats(self._host_stats_last))
                # Task-lifecycle events piggyback on the heartbeat
                # (never their own RPC); a beat lost to a restarting
                # GCS is bounded event loss, by design.
                events, dropped = self.task_events.drain_wire()
                if events or dropped:
                    beat.task_events = events
                    beat.task_events_dropped = dropped
                # Object-lifecycle events ride the same beat into the
                # GCS object table (bounded loss on a dropped beat, by
                # design — same contract as task events).
                oevents, odropped = self.object_events.drain_wire()
                if oevents or odropped:
                    beat.object_events = oevents
                    beat.object_events_dropped = odropped
                # Cluster events (events.py plane) ride the beat too:
                # node-local emissions (worker death, OOM kills, leak
                # reclaims, zygote fallbacks...) reach the GCS table
                # without their own RPC.
                cevents, cdropped = self.cluster_events.drain()
                if cevents or cdropped:
                    beat.cluster_events = cevents
                    beat.cluster_events_dropped = cdropped
                if not metrics_mod.core_reporter():
                    # standalone raylet process (worker node / headless
                    # head): no CoreWorker ships this process's metric
                    # registry, so the heartbeat carries it — with the
                    # per-method RPC latency histograms merged in
                    snap = metrics_mod.global_registry().snapshot()
                    if rpc.telemetry.enabled:
                        snap.update(rpc.telemetry.prom_snapshot())
                    if snap:
                        beat.metrics = snap
                    # full flight-recorder snapshot + drained slow
                    # calls (an in-process head's CoreWorker ships the
                    # shared process snapshot via ReportRpcTelemetry
                    # instead — one reporter per process, never two)
                    if rpc.telemetry.enabled:
                        slow, sdropped = \
                            rpc.telemetry.drain_slow_calls()
                        beat.rpc_telemetry = {
                            "snapshot": rpc.telemetry.wire(
                                probe="raylet"),
                            "slow_calls": slow,
                            "slow_calls_dropped": sdropped}
                reply, _ = await self.gcs_conn.call(
                    "Heartbeat", beat.to_header())
                if not protocol.HeartbeatReply.from_header(reply).ok:
                    # A restarted GCS does not know this node: re-register
                    # over the live connection (reference: raylets
                    # re-register after GCS failover).
                    await self._register_with_gcs()
            except ConnectionError:
                logger.warning("GCS connection lost; raylet reconnecting")
                if not await self._reconnect_gcs():
                    logger.error("GCS unreachable for %.0fs; heartbeat "
                                 "loop exiting",
                                 self.config.gcs_reconnect_timeout_s)
                    return
            await asyncio.sleep(period)

    async def _register_with_gcs(self):
        reply, _ = await self.gcs_conn.call(
            "RegisterNode",
            protocol.RegisterNodeRequest(
                node_id=self.node_id.binary(),
                address=self.address,
                # peers learn the bulk-transfer endpoint through the
                # NODE channel; "" = data plane disabled (pulls from
                # this node use the control-plane chunk path)
                data_address=self.data_address,
                resources=self.resources_total,
                node_name=self.node_name,
                protocol_version=protocol.PROTOCOL_VERSION).to_header())
        # Version handshake: a pre-versioning GCS's reply decodes as
        # version 1 via the stub's compat defaults; everything this
        # node sends afterwards must fit the NEGOTIATED version.
        rep = protocol.RegisterNodeReply.from_header(reply)
        self.negotiated_protocol_version = \
            protocol.negotiate(rep.negotiated_protocol_version)
        self.gcs_conn.peer_protocol_version = \
            protocol.negotiate(rep.protocol_version)
        await self.gcs_conn.call("Subscribe", {"channel": "NODE"})

    async def _reconnect_gcs(self) -> bool:
        """Dial the (restarting) GCS until it answers, then re-register
        (reference: gcs_server_address_updater + raylet re-registration
        on GCS failover). Redials back off exponentially with jitter
        (backoff.py) instead of the old fixed 0.2 s spin — a cluster of
        raylets must not stampede a GCS mid-journal-replay in
        lockstep."""
        bo = backoff_mod.from_config(
            self.config, deadline_s=self.config.gcs_reconnect_timeout_s)
        while not self._closing and not bo.expired():
            try:
                conn = await rpc.connect(
                    self.gcs_address, handlers=self._handlers(),
                    peer_name="gcs", timeout=5.0)
                self.gcs_conn = conn
                await self._register_with_gcs()
                logger.info("raylet %s re-registered with restarted GCS",
                            self.node_id.hex()[:8])
                return True
            except ConnectionError:
                await bo.sleep()
        return False

    async def handle_published(self, conn, header, bufs):
        msg = header["msg"]
        if header["channel"] == "NODE":
            nid = msg["node_id"]
            if nid == self.node_id.binary():
                return {}
            if msg["event"] == "alive":
                self.remote_nodes[nid] = {
                    "address": msg["address"],
                    "data_address": msg.get("data_address", ""),
                    "resources_total": msg["resources"],
                    "resources_available": dict(msg["resources"]),
                }
                # a joining node may carry capacity a WAITING
                # (infeasible-so-far) request needs: spill it there now
                self._schedule_tick()
            elif msg["event"] == "dead":
                pub_info = self.remote_nodes.pop(nid, None)
                dir_info = self._node_directory.pop(nid, None)
                info = pub_info or dir_info
                if info:
                    # a restarted peer binds a fresh data port: the old
                    # address key would never be looked up again, so
                    # the stale client's stripe sockets must go now
                    ch = self._data_channels.pop(
                        info.get("data_address", ""), None)
                    if ch is not None:
                        await ch.close()
        return {}

    # ----------------------------------------------------------- worker pool

    def _start_worker_process(
            self, force: bool = False,
            tpu_chips: Tuple[int, ...] = ()) -> Optional[WorkerHandle]:
        # The pool cap tracks CPU slots for task workers. Actor leases
        # pass force=True: their admission is governed by the resource
        # accounting (a zero-cpu actor must not starve on the process
        # cap — reference: dedicated workers per actor, worker_pool.cc).
        if not force and (self._num_starting + self._alive_worker_count()
                          >= self.max_workers):
            return None
        self._num_starting += 1
        log_dir = os.path.join(self.session_dir, "logs")
        os.makedirs(log_dir, exist_ok=True)
        worker_id = WorkerID.from_random()
        log_path = os.path.join(
            log_dir, f"worker-{worker_id.hex()[:12]}.log")
        handle = WorkerHandle(worker_id.binary(), 0, None)
        handle.tpu_chips = tuple(tpu_chips)
        self.workers[worker_id.binary()] = handle
        if tpu_chips:
            # The process that will hold the chips is always a fresh
            # interpreter: the template's children share its pinned-CPU
            # import state, and an accelerator client must never be
            # forked.
            self._popen_worker(handle, worker_id.hex(), log_path)
            for chip in tpu_chips:
                self._tpu_chip_holders[chip] = handle.proc
        elif self._zygote_eligible():
            # Fast path: fork the pre-imported template (zygote.py) —
            # spawn-to-registered is milliseconds instead of a full
            # interpreter boot. The pid lands asynchronously; the
            # handle is already registered so the pool accounting and
            # RegisterWorker see one consistent STARTING worker.
            try:
                self._ensure_zygote()
            except (OSError, subprocess.SubprocessError) as e:
                # launch itself failed (fork pressure, bad log dir):
                # same contract as a death mid-session — cold Popen for
                # this spawn and all later ones
                self._zygote_failed = True
                self._zygote = None
                logger.warning("zygote launch failed (%r); cold-Popen "
                               "fallback engaged", e)
                self.events.emit(
                    "WARNING", "ZYGOTE_FALLBACK",
                    f"zygote launch failed ({e!r}); cold-Popen "
                    f"fallback engaged for the session",
                    node=self._nid12)
                self._popen_worker(handle, worker_id.hex(), log_path)
                return handle
            rpc.spawn_logged(
                self._spawn_via_zygote(handle, worker_id.hex(), log_path),
                "raylet-spawn-via-zygote")
        else:
            self._popen_worker(handle, worker_id.hex(), log_path)
        return handle

    def _worker_env_overrides(
            self, worker_id_hex: str,
            tpu_chips: Tuple[int, ...] = ()) -> Dict[str, Optional[str]]:
        """Per-spawn environment deltas (None = unset), shared by both
        spawn paths: applied onto this process's env for a cold Popen
        and onto the template's env by a zygote-forked child."""
        ov: Dict[str, Optional[str]] = {
            "RAY_TPU_WORKER_ID": worker_id_hex}
        if tpu_chips:
            ov.update(self._tpu_worker_env(tpu_chips))
        else:
            # Every worker that holds no `TPU` runs CPU jax, whatever
            # this process inherited: a chip belongs to one process,
            # and the first pool worker to import jax would take it.
            ov["JAX_PLATFORMS"] = "cpu"
        # Fault arming is per-SPAWN state: forward the env var's value
        # as of RIGHT NOW, so a schedule armed after node boot reaches
        # zygote-forked children too (the template's baked-in env may
        # predate the arming) and a disarmed var is unset, not stale.
        ov[faultpoints.ENV_VAR] = os.environ.get(faultpoints.ENV_VAR)
        return ov

    def _tpu_worker_env(self, chips: Tuple[int, ...]) -> Dict[str, str]:
        """Environment that binds one process to ``chips``."""
        env = {
            # Named explicitly, so jax RAISES when the TPU client cannot
            # start; with the variable unset it would log a warning and
            # run the lease on the CPU.
            "JAX_PLATFORMS": "tpu,cpu",
            "RAY_TPU_CHIPS": ",".join(str(c) for c in chips),
            "JAX_COMPILATION_CACHE_DIR": compile_cache.cache_dir(),
        }
        if len(chips) < self._num_tpu_chips:
            # libtpu's process bounds: this process is a slice of its
            # own made of just these chips, so several may load libtpu
            # on one host side by side.
            env["TPU_VISIBLE_CHIPS"] = env["RAY_TPU_CHIPS"]
            env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = \
                _TPU_PROCESS_BOUNDS[len(chips)]
            env["TPU_PROCESS_BOUNDS"] = "1,1,1"
        return env

    def _claim_tpu_chips(self, k: int) -> Optional[Tuple[int, ...]]:
        """An aligned block of ``k`` chip indices no live process
        holds, or None while every such block still has a holder."""
        for chip, proc in list(self._tpu_chip_holders.items()):
            if proc.poll() is not None:
                del self._tpu_chip_holders[chip]
        for start in range(0, self._num_tpu_chips - k + 1, k):
            block = tuple(range(start, start + k))
            if not any(c in self._tpu_chip_holders for c in block):
                return block
        return None

    def _popen_worker(self, handle: WorkerHandle, worker_id_hex: str,
                      log_path: str) -> None:
        """Cold spawn: fresh interpreter via Popen (the pre-zygote path,
        kept as the universal fallback)."""
        env = dict(os.environ)
        for k, v in self._worker_env_overrides(
                worker_id_hex, handle.tpu_chips).items():
            if v is None:
                env.pop(k, None)
            else:
                env[k] = v
        out = open(log_path, "ab")
        try:
            proc = subprocess.Popen(
                [sys.executable, "-m", "ray_tpu._private.worker_main",
                 "--raylet-address", self.address,
                 "--gcs-address", self.gcs_address,
                 "--node-id", self.node_id.hex(),
                 "--worker-id", worker_id_hex,
                 "--session-dir", self.session_dir],
                stdout=out, stderr=subprocess.STDOUT, env=env,
                start_new_session=True)
        finally:
            # Popen dup'd the fd into the child: the parent's copy used
            # to leak one fd per spawn for the raylet's lifetime
            # (pinned by the chaos fd bracket in run_task_schedule).
            out.close()
        handle.pid = proc.pid
        handle.proc = proc
        handle.spawned_via = "popen"

    # ------------------------------------------------------ zygote factory

    def _zygote_eligible(self) -> bool:
        """Whether pool spawns may ride the fork-fast path right now.
        Cold Popen covers everything else: knob off, template already
        failed, non-Linux."""
        if not self.config.worker_zygote_enabled or self._zygote_failed:
            return False
        return sys.platform.startswith("linux")

    def _ensure_zygote(self) -> None:
        """Launch the template once. With prestarted workers (the
        default) this happens during ``start()``'s prestart loop, i.e.
        at node boot; the launch itself is just fork+exec — the
        template pays its import bill concurrently while early spawn
        requests queue in the socketpair buffer."""
        if self._zygote is not None:
            return
        from ray_tpu._private.zygote import ZygoteClient
        env = dict(os.environ)
        # The template imports the worker graph under the pool
        # workers' env (JAX_PLATFORMS=cpu), so nothing
        # accelerator-shaped can initialize pre-fork.
        for k, v in self._worker_env_overrides("").items():
            if k == "RAY_TPU_WORKER_ID":
                continue
            if v is None:
                env.pop(k, None)
            else:
                env[k] = v
        self._zygote = ZygoteClient.launch(
            session_dir=self.session_dir, env=env,
            preload=self.config.zygote_preload_modules,
            tag=self.node_id.hex()[:12])
        logger.info("zygote template launched (pid %s)",
                    self._zygote.proc.pid)

    async def _spawn_via_zygote(self, handle: WorkerHandle,
                                worker_id_hex: str, log_path: str) -> None:
        from ray_tpu._private.zygote import ZygoteError, ZygoteProc
        zygote = self._zygote
        if zygote is None:
            # a concurrent spawn failed and tore the factory down
            # between this task's creation and execution
            if not (self._closing or handle.state == WORKER_DEAD):
                self._popen_worker(handle, worker_id_hex, log_path)
            return
        try:
            pid = await asyncio.wait_for(
                zygote.spawn(
                    worker_id=worker_id_hex, log_path=log_path,
                    env_overrides=self._worker_env_overrides(worker_id_hex),
                    argv={"raylet_address": self.address,
                          "gcs_address": self.gcs_address,
                          "node_id": self.node_id.hex(),
                          "worker_id": worker_id_hex,
                          "session_dir": self.session_dir}),
                # strictly tighter than worker_register_timeout_s: the
                # actor-creation path waits that long for a registered
                # worker, so a wedged-but-alive template must fail over
                # to cold Popen with enough budget left for the Popen
                # worker to boot and register inside the same deadline
                timeout=max(2.0, self.config.worker_register_timeout_s / 3))
        except (ZygoteError, asyncio.TimeoutError, OSError) as e:
            # Zygote dead or wedged: engage the cold-Popen fallback for
            # this spawn and every later one (no template respawn —
            # deterministic behavior for the rest of the session).
            self._zygote_failed = True
            self._zygote = None
            logger.warning("zygote spawn failed (%r); cold-Popen "
                           "fallback engaged", e)
            self.events.emit(
                "WARNING", "ZYGOTE_FALLBACK",
                f"zygote spawn failed ({e!r}); cold-Popen fallback "
                f"engaged for the session", node=self._nid12)
            if zygote is not None:
                await zygote.close()
            if self._closing or handle.state == WORKER_DEAD or \
                    self.workers.get(handle.worker_id) is not handle:
                return
            self._popen_worker(handle, worker_id_hex, log_path)
            return
        handle.pid = pid
        handle.proc = ZygoteProc(pid)
        handle.spawned_via = "zygote"
        if handle.state == WORKER_DEAD:
            # torn down before the template reported the pid: the kill
            # that already ran had nothing to signal — finish it now
            handle.proc.kill()
            self._reap_proc_async(handle.proc)

    def _alive_worker_count(self) -> int:
        """Workers counted against the task-worker pool cap. Actor workers
        are excluded: an actor owns a dedicated process for its lifetime
        (reference: worker_pool.h dedicated workers), so a node with
        num_cpus task slots can still serve tasks while actors live.
        STARTING workers are excluded too — ``_num_starting`` already
        accounts for them, and double-counting halves the pool (every
        cap check is ``_num_starting + _alive_worker_count()``)."""
        return sum(1 for w in self.workers.values()
                   if w.state not in (WORKER_DEAD, WORKER_ACTOR,
                                      WORKER_STARTING))

    async def handle_register_worker(self, conn, header, bufs):
        wid = header["worker_id"]
        handle = self.workers.get(wid)
        if handle is None:
            # Externally started worker (tests / manual): adopt it.
            handle = WorkerHandle(wid, header.get("pid", 0), None)
            self.workers[wid] = handle
        else:
            self._num_starting = max(0, self._num_starting - 1)
            if not handle.pid:
                # zygote spawn whose pid report is still in flight on
                # the socketpair — the worker itself knows its pid
                handle.pid = header.get("pid", 0)
        handle.address = header["address"]
        handle.conn = conn
        handle.state = WORKER_IDLE
        conn.tags["worker_id"] = wid
        conn.on_disconnect.append(lambda c: self._on_worker_disconnect(wid))
        self._schedule_tick()
        # a fresh idle worker may fill a credit-window deficit
        self._schedule_credit_topup()
        return {"ok": True, "node_id": self.node_id.binary(),
                "config": self.config.to_json()}

    def _on_worker_disconnect(self, worker_id: bytes):
        handle = self.workers.get(worker_id)
        if handle is None or handle.state == WORKER_DEAD:
            return
        prev_state = handle.state
        handle.state = WORKER_DEAD
        self.events.emit(
            "WARNING", "WORKER_DIED",
            f"worker {worker_id.hex()[:12]} disconnected",
            pid=handle.pid, prev_state=prev_state,
            node=self.node_id.hex()[:12])
        logger.warning("worker %s (%s) disconnected", worker_id.hex()[:8], prev_state)
        if handle.lease_id is not None and handle.lease_id in self.leases:
            self._release_lease(handle.lease_id)
        if prev_state == WORKER_ACTOR:
            # Return the actor's resources (they're not lease-tracked).
            self._give_back(getattr(handle, "actor_resources", {}),
                            getattr(handle, "actor_pg_key", None))
            handle.actor_resources = {}
        # A worker that exited on its own (or was killed by something
        # else) still needs its status collected — _kill_worker never
        # ran for it.
        self._reap_proc_async(handle.proc)
        if prev_state == WORKER_ACTOR and handle.actor_id and not self._closing:
            async def _report():
                try:
                    await self.gcs_conn.call("ReportActorDeath", {
                        "actor_id": handle.actor_id,
                        "reason": "worker process died",
                        "cause": {"kind": "WORKER_DIED",
                                  "node_id": self.node_id.hex(),
                                  "worker_id": worker_id.hex()},
                        "expected": False})
                except ConnectionError:
                    pass
            rpc.spawn_logged(_report(), "raylet-report-worker-death")
        self.workers.pop(worker_id, None)
        self._schedule_tick()

    def _pop_idle_worker(self, env_hash: str = "") -> Optional[WorkerHandle]:
        fallback = None
        for w in self.workers.values():
            if w.tpu_chips:
                continue  # bound to chips for one lease: never pooled
            if w.state == WORKER_IDLE and w.conn is not None and not w.conn.closed:
                if w.env_hash == env_hash:
                    return w  # warm for this runtime env
                if fallback is None:
                    fallback = w
        return fallback

    def _kill_worker(self, handle: WorkerHandle):
        handle.state = WORKER_DEAD
        if handle.proc is not None:
            try:
                os.killpg(os.getpgid(handle.proc.pid), signal.SIGKILL)
            except (ProcessLookupError, PermissionError, OSError):
                try:
                    handle.proc.kill()
                except OSError:
                    pass  # process already gone
            self._reap_proc_async(handle.proc)

    def _reap_proc_async(self, proc) -> None:
        """Collect a dead worker process's exit status: SIGKILLed and
        crashed workers were never wait()ed, so their zombies
        accumulated for the raylet's lifetime (pinned by the chaos
        worker_kill no-zombie invariant). ``Popen.poll()`` reaps
        raylet-parented children; a ``ZygoteProc``'s zombie belongs to
        — and is reaped by — the zygote template."""
        if proc is None or proc.poll() is not None:
            return
        if self._closing:
            return  # stop()'s _reap_procs sweep collects everything

        async def _reap(bound_s: float = 10.0):
            loop = asyncio.get_running_loop()
            deadline = loop.time() + bound_s
            while proc.poll() is None and loop.time() < deadline:
                await asyncio.sleep(0.05)
            if proc.poll() is None:
                logger.warning("worker pid %s still alive %.0fs after "
                               "kill/disconnect", proc.pid, bound_s)

        task = asyncio.get_event_loop().create_task(_reap())
        self._reap_tasks.add(task)
        task.add_done_callback(self._reap_tasks.discard)

    async def _reap_procs(self, procs: List[Any],
                          timeout_s: float = 2.0) -> None:
        """Bounded shutdown sweep: stop() tears the loop down right
        after, so the async reapers can't be trusted to finish — poll
        (= waitpid WNOHANG for Popen) until every proc is collected or
        the bound expires."""
        loop = asyncio.get_running_loop()
        deadline = loop.time() + timeout_s
        pending = [p for p in procs if p is not None and p.poll() is None]
        while pending and loop.time() < deadline:
            await asyncio.sleep(0.02)
            pending = [p for p in pending if p.poll() is None]
        for p in pending:
            logger.warning("worker pid %s unreaped at raylet stop", p.pid)

    # -------------------------------------------------------------- leases

    async def handle_request_worker_lease(self, conn, header, bufs):
        summary = protocol.RequestWorkerLeaseRequest.from_header(
            header).summary
        req = PendingRequest(
            req_id=next(self._req_counter),
            scheduling_class=summary["scheduling_class"],
            resources=summary["resources"],
            strategy=summary.get("strategy", "DEFAULT"),
            pg_id=summary.get("pg_id") or b"",
            pg_bundle=summary.get("pg_bundle", -1),
            env_hash=runtime_env_mod.hash_runtime_env(
                summary.get("runtime_env")),
            arrival_ts=time.monotonic(),
            task_id=summary.get("task_id") or b"",
            retriable=bool(summary.get("retriable", False)),
        )
        if self.memory_monitor.pressure:
            # Lease backpressure (watchdog sequence step 3): above the
            # memory threshold this node admits NO new work — it would
            # only be killed. Spill to a node with capacity when one
            # exists (the existing spillback path drains work off the
            # hot node), else a typed retry-later the owner backs off
            # on (backoff.py pacing, core_worker._request_lease).
            # Credit windows were already zeroed/revoked by the
            # heartbeat's _credit_beat the moment pressure crossed —
            # revocation comes BEFORE rejection, never instead of it.
            return self._memory_backpressure_reply(req)
        if self.config.lease_credits_enabled and not req.pg_id:
            # The request's backlog opens/refreshes this owner's credit
            # window; the legacy grant below still proceeds (it IS the
            # bootstrap probe) and the topup books the remaining slots.
            self._note_credit_demand(conn, req,
                                     summary.get("backlog"))
        if self.task_events.enabled and req.task_id:
            # the lease request carries the SAMPLE task at the head of
            # the owner's queue — that task's lease wait starts here
            self.task_events.record(req.task_id, PENDING_LEASE,
                                    {"node": self._nid12})
        self._init_dep_state(req, summary.get("dep_info") or [])
        fut = asyncio.get_running_loop().create_future()
        fut.client = conn  # type: ignore[attr-defined]
        self._pending[req.req_id] = (req, fut)

        def _on_drop(c, rid=req.req_id):
            self._cancel_pending(rid)

        conn.on_disconnect.append(_on_drop)
        self._schedule_tick()
        try:
            return await fut
        finally:
            # Don't accumulate one closure per lease on a long-lived conn.
            if _on_drop in conn.on_disconnect:
                conn.on_disconnect.remove(_on_drop)

    def _init_dep_state(self, req: PendingRequest, dep_info: List[dict]):
        """Dependency manager role (reference: dependency_manager.h:51):
        build the per-node locality map from the owner-supplied replica
        index, and pre-pull missing plasma args so dispatch is gated on
        data being local (RequestTaskDependencies -> HandleObjectLocal)."""
        locality: Dict[bytes, int] = {}
        missing: List[Tuple[ObjectID, str, int]] = []
        for d in dep_info:
            oid = ObjectID(d["oid"])
            size = d.get("size", 0)
            if self.store.contains(oid):
                locality[self.node_id.binary()] = \
                    locality.get(self.node_id.binary(), 0) + size
                continue
            for nid in d.get("locations", []):
                locality[nid] = locality.get(nid, 0) + size
            if size > 0 and d.get("locations"):
                # A plasma object that lives elsewhere: prefetch it.
                missing.append((oid, d.get("owner", ""), size))
        req.locality = locality
        if missing:
            req.deps_ready = False
            rpc.spawn_logged(self._prefetch_deps(req, missing),
                             "raylet-prefetch-deps")

    async def _prefetch_deps(self, req: PendingRequest,
                             missing: List[Tuple[ObjectID, str, int]]):
        async def pull_one(oid, owner, size):
            try:
                reply = await self._ensure_local(oid, owner)
                return size if reply.get("ok") else 0
            except Exception:  # noqa: BLE001 — dispatch gating is advisory;
                return 0       # the executing worker re-resolves args itself

        pulled = sum(await asyncio.gather(
            *(pull_one(oid, owner, size) for oid, owner, size in missing)))
        req.deps_ready = True
        if pulled:
            # the prefetched bytes are now local: update the locality term
            req.locality[self.node_id.binary()] = \
                req.locality.get(self.node_id.binary(), 0) + pulled
        self._schedule_tick()

    def _cancel_pending(self, req_id: int):
        entry = self._pending.pop(req_id, None)
        if entry and not entry[1].done():
            entry[1].cancel()

    # ------------------------------------------------- memory watchdog seams

    def _backpressure_views(self) -> List[NodeView]:
        """Cluster view with the LOCAL node's availability zeroed: the
        scheduler's own spillback scoring then picks drain targets for
        backpressured leases exactly like an ordinary saturated-node
        spill."""
        views = self._node_views()
        for v in views:
            if v.is_local:
                v.available = {k: 0.0 for k in v.available}
        return views

    def _memory_backpressure_reply(self, req: PendingRequest,
                                   views: Optional[List[NodeView]] = None
                                   ) -> dict:
        """The reply for a lease request rejected under memory pressure.
        Reuses the real scheduler for target choice (see
        _backpressure_views; a tick-time flush passes the view list in
        so it is built once per tick, not once per request).
        PG-targeted requests can't move (the bundle's node was fixed at
        PG creation) — they always get retry-later."""
        self.memory_monitor.note_backpressure()
        if faultpoints.armed:
            faultpoints.fire("lease.backpressure", node=self._nid12)
        if not req.pg_id:
            if views is None:
                views = self._backpressure_views()
            decisions = self.backend.schedule(
                [req], views, self.config.scheduler_spread_threshold)
            if decisions and decisions[0].action == SPILL:
                self.num_spillbacks += 1
                if self.task_events.enabled and req.task_id:
                    self.task_events.record(
                        req.task_id, SPILLBACK,
                        {"node": self._nid12,
                         "target": decisions[0].spill_address,
                         "reason": "memory_pressure"})
                return {"granted": False,
                        "spill": decisions[0].spill_address}
        return {"granted": False, "retry_later": True,
                "reason": "node memory pressure"}

    def _oom_kill_worker(self, handle: WorkerHandle, cause: dict) -> None:
        """Watchdog kill (memory_monitor.py step 2), dispatched async:
        the SIGKILL must not land before the owner KNOWS this death is
        an OOM kill."""
        rpc.spawn_logged(self._oom_kill_worker_async(handle, cause),
                         "raylet-oom-kill-worker")

    async def _oom_kill_worker_async(self, handle: WorkerHandle,
                                     cause: dict) -> None:
        """Tell the lease's owner FIRST and wait for its ack — a
        fire-and-forget push races the worker-socket EOF the SIGKILL
        produces, and the owner's retry decision runs on whichever
        arrives first. Only once the owner has recorded the cause (so
        the death is retried under the dedicated task_oom_retries
        budget as OutOfMemoryError, not the generic worker-crash
        budget) does the SIGKILL go out. An unreachable/slow owner
        bounds the wait at 1 s: the kill proceeds and the death
        degrades honestly to a generic WorkerCrashedError retry."""
        lease_id = handle.lease_id
        if handle.state != WORKER_LEASED or lease_id is None or \
                self.workers.get(handle.worker_id) is not handle:
            handle.oom_kill_pending = False
            return  # died / returned / replaced since the poll selected it
        lease = self.leases.get(lease_id)
        if lease is not None and lease.client is not None and \
                not lease.client.closed:
            try:
                await asyncio.wait_for(lease.client.call(
                    "WorkerOOMKilled", protocol.WorkerOOMKilledRequest(
                        worker_id=handle.worker_id,
                        cause=cause).to_header()), timeout=1.0)
            # raylint: disable=exception-hygiene — best-effort notify: an owner that can't ack still gets a typed (generic) worker-crash retry
            except Exception:
                pass
        # Re-grant guard: the lease may have completed during the ack
        # wait and the worker gone idle — or been re-leased to a
        # DIFFERENT owner that was never notified. Killing now would
        # shoot an innocent task and burn its generic crash budget:
        # abort, let the next poll re-evaluate on fresh state.
        if handle.state != WORKER_LEASED or handle.lease_id != lease_id \
                or self.workers.get(handle.worker_id) is not handle:
            handle.oom_kill_pending = False
            return
        self.memory_monitor.note_kill()
        self.events.emit(
            "WARNING", "WORKER_OOM_KILLED",
            f"memory watchdog killed worker "
            f"{handle.worker_id.hex()[:12]}",
            pid=handle.pid, node=self._nid12,
            usage_fraction=cause.get("usage_fraction"),
            rss=cause.get("workers_rss", {}).get(
                handle.worker_id.hex()[:12]))
        # _kill_worker pre-sets WORKER_DEAD, which makes the later
        # socket-EOF hit _on_worker_disconnect's early return — so the
        # disconnect path would never reclaim this handle. Do the full
        # teardown here, like every other _kill_worker call site: lease
        # released (resources returned), handle dropped from the table.
        self._kill_worker(handle)
        if lease_id in self.leases:
            self._release_lease(lease_id, worker_alive=False)
        self.workers.pop(handle.worker_id, None)
        self._schedule_tick()

    def _schedule_tick(self):
        if self._tick_scheduled or self._closing:
            return
        self._tick_scheduled = True
        asyncio.get_event_loop().call_soon(self._run_tick)

    def _run_tick(self):
        self._tick_scheduled = False
        if self._closing or not self._pending:
            return
        if self.memory_monitor.pressure:
            # Backpressure covers requests queued BEFORE the threshold
            # crossing too: flush them with the same spill/retry-later
            # reply so they drain to other nodes instead of waiting to
            # be granted into a node that would kill them. PG-targeted
            # requests stay pending — their bundle is reserved HERE so
            # they can't move — but are NOT granted either: they park
            # until the pressure clears (the heartbeat loop ticks on
            # the pressure->clear transition).
            bp_views = self._backpressure_views()
            for rid in sorted(self._pending.keys()):
                req, fut = self._pending[rid]
                if req.pg_id or fut.done():
                    continue
                self._pending.pop(rid)
                self._note_latency(req)
                fut.set_result((self._memory_backpressure_reply(
                    req, views=bp_views), ()))
            return
        # PG-targeted requests bypass node scoring: the bundle's node was
        # fixed at PG creation (reference: placement-group scheduling
        # resources are node-local labels).
        nodes = self._node_views()
        ordered = sorted(self._pending.keys())
        reqs = []
        pg_grants = []
        for rid in ordered:
            req, fut = self._pending[rid]
            if req.pg_id:
                pg_grants.append((rid, req, fut))
            else:
                reqs.append(req)
        t_tick = time.monotonic()
        decisions = self.backend.schedule(
            reqs, nodes, self.config.scheduler_spread_threshold) if reqs else []
        if reqs:
            t_done = time.monotonic()
            self._tick_durations.append((len(reqs), t_done - t_tick))
            for req in reqs:
                if not req.first_decision_ts:
                    req.first_decision_ts = t_done
        for rid, req, fut in pg_grants:
            if not req.first_decision_ts:
                req.first_decision_ts = t_tick
        for d in decisions:
            req, fut = self._pending.get(d.req_id, (None, None))
            if req is None or fut.done():
                self._pending.pop(d.req_id, None)
                continue
            if d.action == GRANT:
                self._try_grant(d.req_id, req, fut)
            elif d.action == SPILL:
                self.num_spillbacks += 1
                self._pending.pop(d.req_id, None)
                self._note_latency(req)
                if self.task_events.enabled and req.task_id:
                    self.task_events.record(
                        req.task_id, SPILLBACK,
                        {"node": self._nid12,
                         "target": d.spill_address})
                fut.set_result(({"granted": False, "spill": d.spill_address}, ()))
            elif d.action == INFEASIBLE:
                if self.config.infeasible_task_policy == "wait":
                    continue  # stays pending until capacity appears
                self._pending.pop(d.req_id, None)
                self._note_latency(req)
                fut.set_result(({"granted": False, "infeasible": True}, ()))
            # WAIT: stays pending.
        for rid, req, fut in pg_grants:
            self._try_grant_pg(rid, req, fut)

    def _node_views(self) -> List[NodeView]:
        views = [NodeView(
            node_id=self.node_id.binary(), address=self.address,
            total=self.resources_total,
            available=dict(self.resources_available), is_local=True)]
        for nid, info in self.remote_nodes.items():
            views.append(NodeView(
                node_id=nid, address=info["address"],
                total=info["resources_total"],
                available=dict(info["resources_available"]), is_local=False))
        return views

    def _try_grant(self, req_id: int, req: PendingRequest, fut: asyncio.Future):
        worker = self._pop_idle_worker(req.env_hash)
        if worker is None:
            if self._alive_worker_count() + self._num_starting < self.max_workers:
                self._start_worker_process()
            return  # stays pending until a worker registers/frees
        worker.env_hash = req.env_hash
        self._pending.pop(req_id, None)
        self._note_latency(req)
        lease_id = next(self._lease_counter)
        for k, v in req.resources.items():
            self.resources_available[k] = self.resources_available.get(k, 0.0) - v
        worker.state = WORKER_LEASED
        worker.lease_id = lease_id
        worker.leased_at = time.monotonic()
        worker.lease_retriable = req.retriable
        client = getattr(fut, "client", None)
        lease = LeaseEntry(lease_id, worker, req.resources, client)
        self.leases[lease_id] = lease
        self._watch_lease_client(lease)
        self.num_leases_granted += 1
        self._note_lease_granted(req, worker)
        if faultpoints.armed and self._fault_lease_grant(lease):
            return
        fut.set_result(({"granted": True, "lease_id": lease_id,
                         "worker_address": worker.address,
                         "worker_id": worker.worker_id,
                         "node_id": self.node_id.binary()}, ()))

    def _fault_lease_grant(self, lease: LeaseEntry) -> bool:
        """Lease-grant crash window (point ``raylet.lease.grant``):
        the lease is fully booked but the reply never reaches the
        client. ``sever`` closes the client's connection — the
        owner-liveness watch must then reclaim the worker and the
        resources; ``kill``/``raise`` execute inside fire(). Returns
        True when the grant reply must not be sent."""
        act = faultpoints.fire("raylet.lease.grant",
                               lease_id=lease.lease_id, node=self._nid12)
        if act == "sever" and lease.client is not None:
            lease.client._mark_closed()
            return True
        if act == "drop":
            return True
        return False

    def _note_lease_granted(self, req, worker: WorkerHandle) -> None:
        if self.task_events.enabled and req.task_id:
            self.task_events.record(
                req.task_id, LEASE_GRANTED,
                {"node": self._nid12,
                 "worker": worker.worker_id.hex()[:12]})

    def _try_grant_pg(self, req_id: int, req: PendingRequest, fut: asyncio.Future):
        key = (req.pg_id, req.pg_bundle)
        bundle_avail = self._pg_available.get(key)
        if bundle_avail is None:
            self._pending.pop(req_id, None)
            fut.set_result(({"granted": False, "infeasible": True,
                             "reason": "no such placement group bundle here"}, ()))
            return
        if not all(bundle_avail.get(k, 0.0) + 1e-9 >= v
                   for k, v in req.resources.items() if v > 0):
            return  # wait for bundle capacity
        worker = self._pop_idle_worker(req.env_hash)
        if worker is None:
            if self._alive_worker_count() + self._num_starting < self.max_workers:
                self._start_worker_process()
            return
        worker.env_hash = req.env_hash
        self._pending.pop(req_id, None)
        self._note_latency(req)
        for k, v in req.resources.items():
            bundle_avail[k] = bundle_avail.get(k, 0.0) - v
        lease_id = next(self._lease_counter)
        worker.state = WORKER_LEASED
        worker.lease_id = lease_id
        worker.leased_at = time.monotonic()
        worker.lease_retriable = req.retriable
        lease = LeaseEntry(lease_id, worker, req.resources,
                           getattr(fut, "client", None))
        lease.pg_key = key  # type: ignore[attr-defined]
        self.leases[lease_id] = lease
        self._watch_lease_client(lease)
        self.num_leases_granted += 1
        self._note_lease_granted(req, worker)
        if faultpoints.armed and self._fault_lease_grant(lease):
            return
        fut.set_result(({"granted": True, "lease_id": lease_id,
                         "worker_address": worker.address,
                         "worker_id": worker.worker_id,
                         "node_id": self.node_id.binary()}, ()))

    async def handle_return_worker(self, conn, header, bufs):
        req = protocol.ReturnWorkerRequest.from_header(header)
        lease = self.leases.get(req.lease_id)
        if lease is not None and not req.get("worker_died", False):
            cw = getattr(lease, "credit_window", None)
            w = self._credit_windows.get(cw) if cw is not None else None
            if w is not None:
                # A VOLUNTARY credit return is the demand-decay signal:
                # the owner's queue drained (it never returns credit
                # workers while it has backlog), so the window must not
                # be topped back up from the just-freed worker — that
                # would churn grant/idle/return cycles until the demand
                # report went stale.
                w.demand = 0
                w.demand_ts = time.monotonic()
        self._release_lease(req.lease_id,
                            worker_alive=not req.get("worker_died", False))
        return protocol.ReturnWorkerReply(ok=True).to_header()

    async def handle_report_lease_demand(self, conn, header, bufs):
        """Owner -> raylet backlog refresh (one-way push, paced by the
        owner to ~2/stale-period per scheduling class): keeps a live
        window from going stale mid-drain and lets a queue that grew
        WITHOUT a legacy lease request still open a window."""
        if not self.config.lease_credits_enabled or \
                self.memory_monitor.pressure:
            return {}
        req = protocol.ReportLeaseDemandRequest.from_header(header)
        key = (id(conn), req.sched_class)
        w = self._credit_windows.get(key)
        if w is None:
            w = CreditWindow(conn, req.sched_class,
                             dict(req.get("resources") or {}),
                             req.get("env_hash", ""),
                             bool(req.get("retriable", False)))
            self._credit_windows[key] = w
            conn.on_disconnect.append(
                lambda c, k=key: self._credit_windows.pop(k, None))
        w.demand = int(req.get("backlog", 0))
        w.demand_ts = time.monotonic()
        # the refresh carries the CURRENT queue head's properties:
        # victim eligibility and env affinity must track the live
        # backlog, not whatever task bootstrapped the window
        # (scheduling classes key on (resources, fn_key) only —
        # max_retries and runtime_env vary within one class)
        w.env_hash = req.get("env_hash", w.env_hash)
        w.retriable = bool(req.get("retriable", w.retriable))
        self._schedule_credit_topup()
        return {}

    def _watch_lease_client(self, lease: LeaseEntry):
        """Reclaim a granted lease if its owner's connection drops.

        Without this a driver that exits while holding leases leaks the
        leased resources forever and every later lease WAITs — the
        reference ties worker leases to owner liveness the same way
        (node manager DisconnectClient → owned-worker teardown). The
        worker is killed, not recycled: it may be mid-task for the dead
        job, and a poisoned "idle" worker would stall its next lease."""
        conn = lease.client
        if conn is None:
            return

        def _on_client_drop(c, lid=lease.lease_id):
            entry = self.leases.get(lid)
            if entry is None:
                return
            logger.warning(
                "lease %d owner disconnected; reclaiming worker %s",
                lid, entry.worker.worker_id.hex()[:8])
            self._kill_worker(entry.worker)
            self._release_lease(lid, worker_alive=False)

        lease.on_client_drop = _on_client_drop  # type: ignore[attr-defined]
        conn.on_disconnect.append(_on_client_drop)

    def _release_lease(self, lease_id: int, worker_alive: bool = True):
        lease = self.leases.pop(lease_id, None)
        if lease is None:
            return
        cb = getattr(lease, "on_client_drop", None)
        if cb is not None and lease.client is not None and \
                cb in lease.client.on_disconnect:
            lease.client.on_disconnect.remove(cb)
        cw = getattr(lease, "credit_window", None)
        if cw is not None:
            win = self._credit_windows.get(cw)
            if win is not None:
                win.lease_ids.discard(lease_id)
        pg_key = getattr(lease, "pg_key", None)
        if pg_key is not None and pg_key in self._pg_available:
            for k, v in lease.resources.items():
                self._pg_available[pg_key][k] = \
                    self._pg_available[pg_key].get(k, 0.0) + v
        elif pg_key is None:
            for k, v in lease.resources.items():
                self.resources_available[k] = \
                    self.resources_available.get(k, 0.0) + v
        gang_id = getattr(lease, "gang_id", None)
        if gang_id is not None:
            # A member lease dying out from under a LIVE gang breaks the
            # whole incarnation (observability mirror of the owner-side
            # epoch fence: the owner sees the member conn drop and fails
            # the step; this keeps GetNodeStats truthful about it).
            rec = self.gangs.get(gang_id)
            if rec is not None and \
                    rec["epoch"] == getattr(lease, "gang_epoch", -1) \
                    and not worker_alive:
                rec["broken"] = True
                rec["dead_members"] += 1
            mem = self._gang_members.get(gang_id)
            if mem is not None:
                mem["lease_ids"].discard(lease_id)
                if not mem["lease_ids"]:
                    self._gang_members.pop(gang_id, None)
        w = lease.worker
        w.lease_id = None
        if worker_alive and w.state == WORKER_LEASED:
            w.state = WORKER_IDLE
        self._schedule_tick()
        # the freed slot may satisfy another window's deficit (no-op
        # when demand is stale or decayed — target tracks demand)
        self._schedule_credit_topup()

    # ------------------------------------------------- streaming leases

    def _note_credit_demand(self, conn, req: PendingRequest,
                            backlog) -> None:
        """Open/refresh the credit window a lease request's backlog
        describes. Old-protocol clients send no backlog — they simply
        never get a window (pure legacy behavior)."""
        if backlog is None or conn is None or conn.closed:
            return
        key = (id(conn), req.scheduling_class)
        w = self._credit_windows.get(key)
        if w is None:
            w = CreditWindow(conn, req.scheduling_class,
                             dict(req.resources), req.env_hash,
                             req.retriable)
            self._credit_windows[key] = w
            conn.on_disconnect.append(
                lambda c, k=key: self._credit_windows.pop(k, None))
        w.demand = int(backlog)
        w.demand_ts = time.monotonic()
        w.env_hash = req.env_hash
        w.retriable = req.retriable
        self._schedule_credit_topup()

    def _schedule_credit_topup(self) -> None:
        if self._credit_topup_scheduled or self._closing or \
                not self._credit_windows:
            return
        self._credit_topup_scheduled = True
        asyncio.get_event_loop().call_soon(self._credit_topup)

    def _credit_window_target(self, w: CreditWindow) -> Tuple[int, int]:
        """Window sizing from the REAL scheduler view. Returns
        ``(local, cluster)`` slot targets: the owner's breadth
        heuristic (~one worker per 8 queued tasks) clamped by the slot
        capacity for this resource shape on THIS node (what this raylet
        can stream) and across the whole cluster view (how many legacy
        requests the owner may park for spillback beyond the stream),
        both bounded by the per-window ceiling. Pressure or stale
        demand zeroes both — an owner that stopped reporting backlog
        must not keep slots."""
        if self.memory_monitor.pressure or w.demand <= 0:
            return 0, 0
        if time.monotonic() - w.demand_ts > \
                self.config.lease_credit_stale_s:
            return 0, 0

        def _slots(view: NodeView) -> int:
            per = None
            for k, need in w.resources.items():
                if need <= 0:
                    continue
                n = int(view.total.get(k, 0.0) / need + 1e-9)
                per = n if per is None else min(per, n)
            if per is None:  # zero-resource shape: CPU slots bound it
                per = int(view.total.get("CPU", 0.0)) or 1
            return per

        local = 0
        cluster = 0
        for v in self._node_views():
            n = _slots(v)
            cluster += n
            if v.is_local:
                local += n
        want = max(1, w.demand // 8)
        cap = self.config.lease_credit_window_max
        return (max(0, min(cap, want, local)),
                max(0, min(cap, want, cluster)))

    def _credit_topup(self) -> None:
        """Book credits up to each live window's target and stream them
        to the owner (one GrantLeaseCredits push per window per round,
        piggybacking the window target so the owner stops parking
        legacy lease requests beyond it). Every credit books a real
        worker + resources through the same accounting as _try_grant."""
        self._credit_topup_scheduled = False
        if self._closing or not self.config.lease_credits_enabled or \
                self.memory_monitor.pressure:
            return
        for key, w in list(self._credit_windows.items()):
            if w.conn is None or w.conn.closed:
                self._credit_windows.pop(key, None)
                continue
            target, cluster = self._credit_window_target(w)
            credits: List[dict] = []
            while len(w.lease_ids) < target:
                cr = self._grant_credit(w)
                if cr is None:
                    break
                credits.append(cr)
            deficit = target - len(w.lease_ids)
            if deficit > 0:
                # pool ramp-up parity with the legacy path (which
                # starts one worker per parked request): kick off a
                # spawn per unfilled slot NOW — _start_worker_process
                # no-ops at the cap, and each registration re-triggers
                # the topup. Serial one-spawn-per-beat ramping measured
                # 20% off the 1M-drain wall on a many-core box.
                for _ in range(deficit):
                    self._start_worker_process()
            if not credits and target == w.target:
                continue  # nothing new to announce
            w.target = target
            if faultpoints.armed and faultpoints.fire(
                    "lease.credit.grant", node=self._nid12,
                    sched_class=w.sched_class,
                    n=len(credits)) == "drop":
                # grant push lost: the leases stay booked against this
                # owner; the stale-revoke beat reconciles them (the
                # owner replies "released" for ids it never received)
                continue
            try:
                w.conn.push_nowait(
                    "GrantLeaseCredits",
                    protocol.GrantLeaseCreditsRequest(
                        sched_class=w.sched_class,
                        raylet_address=self.address,
                        window_target=target,
                        cluster_slots=cluster,
                        resources=w.resources,
                        credits=credits).to_header())
            except ConnectionError:
                pass  # disconnect callbacks reclaim the booked leases

    def _grant_credit(self, w: CreditWindow) -> Optional[dict]:
        """Book ONE credit: idle worker + resources -> LeaseEntry,
        exactly like _try_grant minus the pending request. Returns the
        wire credit dict, or None when the pool/capacity can't serve
        one right now (a worker spawn may be kicked off for later)."""
        for k, v in w.resources.items():
            if v > 0 and self.resources_available.get(k, 0.0) + 1e-9 < v:
                return None
        worker = self._pop_idle_worker(w.env_hash)
        if worker is None:
            if self._alive_worker_count() + self._num_starting < \
                    self.max_workers:
                self._start_worker_process()
            return None
        worker.env_hash = w.env_hash
        lease_id = next(self._lease_counter)
        for k, v in w.resources.items():
            self.resources_available[k] = \
                self.resources_available.get(k, 0.0) - v
        worker.state = WORKER_LEASED
        worker.lease_id = lease_id
        worker.leased_at = time.monotonic()
        worker.lease_retriable = w.retriable
        lease = LeaseEntry(lease_id, worker, dict(w.resources), w.conn)
        lease.credit_window = (id(w.conn), w.sched_class)  # type: ignore[attr-defined]
        self.leases[lease_id] = lease
        self._watch_lease_client(lease)
        w.lease_ids.add(lease_id)
        self.num_credit_grants += 1
        # Per-GRANT latency sample (credit grants included): how long
        # this window's current demand waited for the slot. Keeps the
        # grant_wait reservoirs reflecting the grant population instead
        # of the handful of legacy requests a credit-served drain makes.
        wait = time.monotonic() - w.demand_ts
        self._sched_latencies.append(wait)
        self._grant_waits.append(wait)
        return {"lease_id": lease_id,
                "worker_address": worker.address,
                "worker_id": worker.worker_id,
                "node_id": self.node_id.binary()}

    def _credit_beat(self) -> None:
        """Heartbeat-cadence window maintenance: prune dead-conn
        windows, zero + revoke everything under memory pressure (the
        watchdog's poll ran just before this), offer back the excess of
        over-target windows, and top up under-target ones."""
        if not self.config.lease_credits_enabled or \
                not self._credit_windows:
            return
        now = time.monotonic()
        pressure = self.memory_monitor.pressure
        for key, w in list(self._credit_windows.items()):
            if w.conn is None or w.conn.closed:
                self._credit_windows.pop(key, None)
                continue
            target = 0 if pressure else self._credit_window_target(w)[0]
            if pressure and w.target != 0:
                # tell the owner its window is gone so it falls back to
                # legacy requests (which get the typed backpressure
                # reply and spill/back off) instead of waiting on a
                # stream that will not flow
                w.target = 0
                # a pressure-driven window zeroing is a recovery action
                # worth a cluster event (per window, beat-paced —
                # routine stale-window resizes are not)
                self.events.emit(
                    "WARNING", "LEASE_CREDITS_REVOKED",
                    f"memory pressure zeroed a credit window "
                    f"({len(w.lease_ids)} credits outstanding)",
                    node=self._nid12, sched_class=w.sched_class,
                    outstanding=len(w.lease_ids))
                try:
                    w.conn.push_nowait(
                        "GrantLeaseCredits",
                        protocol.GrantLeaseCreditsRequest(
                            sched_class=w.sched_class,
                            raylet_address=self.address,
                            window_target=0,
                            cluster_slots=0,
                            resources=w.resources,
                            credits=[]).to_header())
                except ConnectionError:
                    continue
            excess = len(w.lease_ids) - target
            if w.lease_ids and not w.revoking and \
                    (pressure or now - w.last_revoke_ts >=
                     self.config.lease_credit_stale_s):
                # Offer the window's credits back on every stale
                # period — not just when over target. The owner keeps
                # what it is using; what comes back is the excess,
                # idle-with-no-backlog slots, AND any PHANTOM credits
                # a dropped grant push booked that the owner never
                # heard of (it confirms unknown ids as released) — the
                # reconciliation a lost push depends on, which a
                # demand-fresh at-target window would otherwise never
                # trigger.
                max_release = len(w.lease_ids) \
                    if (pressure or excess <= 0) else excess
                w.last_revoke_ts = now
                w.revoking = True
                rpc.spawn_logged(
                    self._revoke_credits(
                        w, list(w.lease_ids), max_release,
                        "memory_pressure" if pressure
                        else "window_resize"),
                    "raylet-revoke-credits")
            if excess < 0 and not pressure:
                self._schedule_credit_topup()

    async def _revoke_credits(self, w: CreditWindow, lease_ids: List[int],
                              max_release: int, reason: str) -> None:
        """Offer ``lease_ids`` back to the owner (which relinquishes up
        to ``max_release`` it is not using; under ``memory_pressure``
        it releases idle credits even with backlog — draining work off
        this node IS the recovery) and reclaim what came back. A lost
        or unanswered revoke is safe: the credits stay valid and a
        later beat re-offers them; a dead owner's credits come back
        through the lease-client liveness watch."""
        try:
            if faultpoints.armed and faultpoints.fire(
                    "lease.credit.revoke", node=self._nid12,
                    sched_class=w.sched_class, reason=reason,
                    n=len(lease_ids)) == "drop":
                return
            try:
                reply, _ = await w.conn.call(
                    "RevokeLeaseCredits",
                    protocol.RevokeLeaseCreditsRequest(
                        lease_ids=lease_ids,
                        max_release=max_release,
                        reason=reason).to_header(),
                    timeout=2.0)
            except (ConnectionError, asyncio.TimeoutError):
                return
            rep = protocol.RevokeLeaseCreditsReply.from_header(reply)
            for lid in rep.released:
                if lid in w.lease_ids and lid in self.leases:
                    self.num_credit_revoked += 1
                    self._release_lease(lid)
                else:
                    # an id the owner never received (dropped grant
                    # push) or already returned: reconcile the ledger
                    w.lease_ids.discard(lid)
                    if lid in self.leases:
                        self.num_credit_revoked += 1
                        self._release_lease(lid)
        finally:
            w.revoking = False

    def _credit_stats(self) -> dict:
        outstanding = sum(len(w.lease_ids)
                          for w in self._credit_windows.values())
        total = self.num_credit_grants + self.num_leases_granted
        return {
            "enabled": self.config.lease_credits_enabled,
            "windows": len(self._credit_windows),
            "outstanding": outstanding,
            "granted_total": self.num_credit_grants,
            "revoked_total": self.num_credit_revoked,
            "legacy_grants_total": self.num_leases_granted,
            # share of all lease grants that were streamed credits —
            # the raylet-side credit hit-rate (the owner-side per-TASK
            # dispatch split lives in CoreWorker.stats
            # credit_dispatches / legacy_dispatches)
            "credit_grant_rate": round(
                self.num_credit_grants / total, 4) if total else 0.0,
        }

    # ---------------------------------------------------- SPMD gang leases

    def _book_gang_local(self, gang_id: bytes, epoch: int, count: int,
                         resources: Dict[str, float], env_hash: str,
                         client) -> List[dict]:
        """Book up to ``count`` members from THIS node's idle pool —
        immediately, never waiting: gang placement is all-or-nothing,
        so a shortfall is reported (and rolled back) rather than parked.
        Each booking is an ordinary LeaseEntry (owner-liveness reclaim,
        ReturnWorker, the memory watchdog's victim ordering and the
        resource ledger all see it like any lease), tagged with the
        gang id + epoch so releases keep the gang record honest."""
        members: List[dict] = []
        while len(members) < count:
            if not all(self.resources_available.get(k, 0.0) + 1e-9 >= v
                       for k, v in resources.items() if v > 0):
                break
            worker = self._pop_idle_worker(env_hash)
            if worker is None:
                break
            worker.env_hash = env_hash
            lease_id = next(self._lease_counter)
            for k, v in resources.items():
                self.resources_available[k] = \
                    self.resources_available.get(k, 0.0) - v
            worker.state = WORKER_LEASED
            worker.lease_id = lease_id
            worker.leased_at = time.monotonic()
            # gang steps run with max_retries=0 (a dead member fails
            # the whole step) — never a watchdog retriable victim
            worker.lease_retriable = False
            lease = LeaseEntry(lease_id, worker, dict(resources), client)
            lease.gang_id = gang_id      # type: ignore[attr-defined]
            lease.gang_epoch = epoch     # type: ignore[attr-defined]
            self.leases[lease_id] = lease
            self._watch_lease_client(lease)
            self.num_leases_granted += 1
            members.append({"lease_id": lease_id,
                            "worker_address": worker.address,
                            "worker_id": worker.worker_id,
                            "node_id": self.node_id.binary()})
        return members

    async def _release_gang_remote(self, node_id: bytes, gang_id: bytes,
                                   epoch: int, lease_ids: List[int],
                                   kill: bool) -> None:
        info = await self._lookup_node(node_id)
        if info is None:
            return
        try:
            peer = await self._peer_conn(info["address"])
            await peer.call(
                "ReleaseGangMembers",
                protocol.ReleaseGangMembersRequest(
                    gang_id=gang_id, epoch=epoch,
                    lease_ids=lease_ids, kill=kill).to_header())
        # raylint: disable=exception-hygiene — best-effort: a dead peer's bookings die with it (owner-liveness reclaim)
        except Exception:
            pass

    async def _release_gang(self, gang_id: bytes, rec: dict,
                            kill: bool = False) -> None:
        """Release every member of one gang incarnation: local leases
        through _release_lease, remote bookings via ReleaseGangMembers
        fan-out. Pops the record first so a re-entrant release (owner
        drop racing an explicit ReleaseGangLease) is a no-op."""
        if self.gangs.get(gang_id) is rec:
            self.gangs.pop(gang_id, None)
        drop = rec.pop("owner_drop", None)
        conn = rec.pop("owner_conn", None)
        if drop is not None and conn is not None and \
                drop in conn.on_disconnect:
            conn.on_disconnect.remove(drop)
        me = self.node_id.binary()
        remote: Dict[bytes, List[int]] = {}
        for m in rec["members"]:
            if m["node_id"] == me:
                lease = self.leases.get(m["lease_id"])
                if lease is not None:
                    if kill:
                        self._kill_worker(lease.worker)
                    self._release_lease(m["lease_id"],
                                        worker_alive=not kill)
            else:
                remote.setdefault(m["node_id"], []).append(m["lease_id"])
        if remote:
            await asyncio.gather(*(
                self._release_gang_remote(nid, gang_id, rec["epoch"],
                                          lids, kill)
                for nid, lids in remote.items()))

    async def _rollback_gang_booking(self, gang_id: bytes, epoch: int,
                                     members: List[dict],
                                     peer_bookings: List[Tuple[bytes,
                                                               List[int]]]
                                     ) -> None:
        me = self.node_id.binary()
        for m in members:
            if m["node_id"] == me:
                self._release_lease(m["lease_id"], worker_alive=True)
        if peer_bookings:
            await asyncio.gather(*(
                self._release_gang_remote(nid, gang_id, epoch, lids,
                                          kill=False)
                for nid, lids in peer_bookings))

    async def handle_request_gang_lease(self, conn, header, bufs):
        """ONE lease round books N workers across the cluster, or none:
        the home raylet takes what its own pool serves, fans
        BookGangMembers out to peers for the remainder, and rolls the
        whole booking back on any shortfall (all-or-nothing — Tesserae-
        style gang placement on the PR11 lease machinery). Epoch-fenced
        like actor incarnations: a request at or below the live
        incarnation's epoch is rejected; a higher epoch releases the
        old incarnation before booking the new one."""
        req = protocol.RequestGangLeaseRequest.from_header(header)
        gang_id = req.gang_id
        epoch = int(req.epoch)
        count = int(req.count)
        resources = dict(req.get("resources") or {"CPU": 1.0})
        env_hash = runtime_env_mod.hash_runtime_env(
            req.get("runtime_env"))
        rec = self.gangs.get(gang_id)
        if rec is not None and epoch <= rec["epoch"]:
            self.num_gang_rejects += 1
            return {"granted": False, "stale_epoch": True,
                    "current_epoch": rec["epoch"]}
        if rec is not None:
            # re-formation: the new epoch invalidates the previous
            # incarnation BEFORE any booking, so a stale member can
            # never survive into the new gang
            await self._release_gang(gang_id, rec)
        if self.memory_monitor.pressure:
            return {"granted": False, "retry_later": True,
                    "reason": "node memory pressure"}
        members = self._book_gang_local(gang_id, epoch, count, resources,
                                        env_hash, conn)
        peer_bookings: List[Tuple[bytes, List[int]]] = []
        try:
            if len(members) < count:
                # widest-pool peers first: fewer fan-out hops per round
                candidates = sorted(
                    self.remote_nodes.items(),
                    key=lambda kv: -kv[1]["resources_available"].get(
                        "CPU", 0.0))
                for nid, info in candidates:
                    if len(members) >= count:
                        break
                    try:
                        peer = await self._peer_conn(info["address"])
                        reply, _ = await peer.call(
                            "BookGangMembers",
                            protocol.BookGangMembersRequest(
                                gang_id=gang_id, epoch=epoch,
                                count=count - len(members),
                                resources=resources,
                                env_hash=env_hash).to_header())
                    except (ConnectionError, asyncio.TimeoutError):
                        continue
                    got = reply.get("members") or []
                    if got:
                        peer_bookings.append(
                            (nid, [m["lease_id"] for m in got]))
                        members.extend(got)
        except asyncio.CancelledError:
            await self._rollback_gang_booking(gang_id, epoch, members,
                                              peer_bookings)
            raise
        if len(members) < count:
            deficit = count - len(members)
            await self._rollback_gang_booking(gang_id, epoch, members,
                                              peer_bookings)
            # prestart toward the deficit so a retry converges instead
            # of rediscovering the same empty pool
            for _ in range(deficit):
                if self._alive_worker_count() + self._num_starting < \
                        self.max_workers:
                    self._start_worker_process()
            self.num_gang_rejects += 1
            return {"granted": False, "retry_later": True,
                    "reason": f"booked {len(members)}/{count} workers"}
        for rank, m in enumerate(members):
            m["rank"] = rank
        rec = {"epoch": epoch, "members": members,
               "created": time.time(), "broken": False,
               "dead_members": 0}
        self.gangs[gang_id] = rec

        def _on_owner_drop(c, gid=gang_id, r=rec):
            if self.gangs.get(gid) is r:
                rpc.spawn_logged(self._release_gang(gid, r, kill=True),
                                 "raylet-release-gang")

        rec["owner_conn"] = conn
        rec["owner_drop"] = _on_owner_drop
        conn.on_disconnect.append(_on_owner_drop)
        self.num_gang_leases += 1
        _spmd_metrics()["gang_leases"].inc()
        self.events.emit(
            "INFO", "GANG_LEASE_GRANTED",
            f"gang {gang_id.hex()[:12]} epoch {epoch}: booked "
            f"{count} workers across "
            f"{len({m['node_id'] for m in members})} node(s)",
            node=self._nid12, size=count, epoch=epoch)
        return {"granted": True, "epoch": epoch,
                "members": [dict(m) for m in members]}

    async def handle_book_gang_members(self, conn, header, bufs):
        """Peer side of the gang fan-out: book what this node's idle
        pool serves RIGHT NOW (the home raylet enforces all-or-nothing
        globally and rolls back on shortfall). The booking's lease
        client is the home raylet's connection — a dead home raylet
        reclaims its bookings through the ordinary owner-liveness
        watch."""
        req = protocol.BookGangMembersRequest.from_header(header)
        gang_id = req.gang_id
        epoch = int(req.epoch)
        mem = self._gang_members.get(gang_id)
        if mem is not None and epoch < mem["epoch"]:
            return {"members": [], "stale_epoch": True}
        if self.memory_monitor.pressure:
            return {"members": [], "reason": "node memory pressure"}
        members = self._book_gang_local(
            gang_id, epoch, int(req.count),
            dict(req.get("resources") or {}),
            req.get("env_hash", ""), conn)
        if members:
            mem = self._gang_members.get(gang_id)
            if mem is None or epoch > mem["epoch"]:
                mem = self._gang_members[gang_id] = {
                    "epoch": epoch, "lease_ids": set()}
            mem["lease_ids"].update(m["lease_id"] for m in members)
        elif self._alive_worker_count() + self._num_starting < \
                self.max_workers:
            self._start_worker_process()  # converge a retry's shortfall
        return {"members": members}

    async def handle_release_gang_members(self, conn, header, bufs):
        req = protocol.ReleaseGangMembersRequest.from_header(header)
        gang_id = req.gang_id
        epoch = int(req.epoch)
        mem = self._gang_members.get(gang_id)
        if mem is not None and epoch < mem["epoch"]:
            # stale release from a previous incarnation must not tear
            # down a newer booking
            return {"ok": False, "stale_epoch": True}
        kill = bool(req.get("kill", False))
        for lid in req.get("lease_ids") or []:
            lease = self.leases.get(lid)
            if lease is None or \
                    getattr(lease, "gang_id", None) != gang_id:
                continue
            if kill:
                # broken-gang teardown: the member may be mid-step for
                # the dead incarnation — recycling it as "idle" would
                # poison its next lease
                self._kill_worker(lease.worker)
            self._release_lease(lid, worker_alive=not kill)
        return {"ok": True}

    async def handle_release_gang_lease(self, conn, header, bufs):
        """Owner -> home raylet gang teardown, epoch-fenced: a release
        carrying an older epoch than the live incarnation is the stale
        member's push after re-formation — rejected, never applied."""
        req = protocol.ReleaseGangLeaseRequest.from_header(header)
        gang_id = req.gang_id
        epoch = int(req.epoch)
        rec = self.gangs.get(gang_id)
        if rec is None:
            return {"ok": True, "already_released": True}
        if epoch < rec["epoch"]:
            self.num_gang_rejects += 1
            return {"ok": False, "stale_epoch": True,
                    "current_epoch": rec["epoch"]}
        await self._release_gang(gang_id, rec,
                                 kill=bool(req.get("kill", False)))
        return {"ok": True}

    def _gang_stats(self) -> dict:
        return {
            "homed": [{
                "gang_id": gid.hex(),
                "epoch": rec["epoch"],
                "size": len(rec["members"]),
                "nodes": sorted({m["node_id"].hex()[:12]
                                 for m in rec["members"]}),
                "broken": rec["broken"],
                "dead_members": rec["dead_members"],
                "created": rec["created"],
            } for gid, rec in self.gangs.items()],
            "member_bookings": [{
                "gang_id": gid.hex(),
                "epoch": mem["epoch"],
                "leases": len(mem["lease_ids"]),
            } for gid, mem in self._gang_members.items()],
            "num_gang_leases": self.num_gang_leases,
            "num_gang_rejects": self.num_gang_rejects,
        }

    # -------------------------------------------------------------- actors

    async def handle_schedule_actor_creation(self, conn, header, bufs):
        spec = header["spec"]
        # Idempotence by actor id: a GCS that restarted mid-creation may
        # re-send the request while the first worker is alive — a second
        # instance would split-brain the actor.
        for w in self.workers.values():
            if w.state == WORKER_ACTOR and w.actor_id == header["actor_id"]:
                return {"ok": True, "already_created": True}
        resources = spec.get("resources", {"CPU": 1.0})
        n_tpu = int(resources.get("TPU", 0))
        if n_tpu and (self._num_tpu_chips % n_tpu or (
                n_tpu < self._num_tpu_chips
                and n_tpu not in _TPU_PROCESS_BOUNDS)):
            # no aligned block of that size can be bound to one process
            await self.gcs_conn.call("ReportActorDeath", {
                "actor_id": header["actor_id"],
                "reason": f"cannot bind {n_tpu} of this host's "
                          f"{self._num_tpu_chips} TPU chips to one "
                          f"process (1, 2, 4 or the whole host)",
                "cause": {"kind": "CREATION_FAILED",
                          "node_id": self.node_id.hex()},
                "expected": True})
            return {"ok": True}
        pg_key = None
        # Reserve resources BEFORE any await: concurrent creations must not
        # both pass the availability check and oversubscribe the node.
        if spec.get("pg_id"):
            pg_key = (spec["pg_id"], spec.get("pg_bundle", 0))
            bundle_avail = self._pg_available.get(pg_key)
            if bundle_avail is None or not all(
                    bundle_avail.get(k, 0.0) + 1e-9 >= v
                    for k, v in resources.items() if v > 0):
                return {"ok": False, "reason": "pg bundle unavailable"}
            for k, v in resources.items():
                bundle_avail[k] = bundle_avail.get(k, 0.0) - v
        else:
            if not all(self.resources_available.get(k, 0.0) + 1e-9 >= v
                       for k, v in resources.items() if v > 0):
                return {"ok": False, "reason": "insufficient resources"}
            for k, v in resources.items():
                self.resources_available[k] = \
                    self.resources_available.get(k, 0.0) - v
        if n_tpu:
            worker = await self._start_tpu_worker(n_tpu)
        else:
            worker = self._pop_idle_worker()
            if worker is None:
                self._start_worker_process(force=True)
                deadline = time.time() + \
                    self.config.worker_register_timeout_s
                while worker is None and time.time() < deadline:
                    await asyncio.sleep(0.02)
                    worker = self._pop_idle_worker()
        if worker is None:
            self._give_back(resources, pg_key)
            reason = "no worker available" if not n_tpu else \
                "TPU chips still held by an exiting process, or their " \
                "worker did not register"
            return {"ok": False, "reason": reason}
        worker.state = WORKER_ACTOR
        worker.actor_id = header["actor_id"]
        worker.actor_resources = resources  # type: ignore[attr-defined]
        worker.actor_pg_key = pg_key        # type: ignore[attr-defined]
        try:
            reply, _ = await worker.conn.call(
                "CreateActor",
                {"actor_id": header["actor_id"], "spec": spec,
                 "incarnation": header.get("incarnation", 0)},
                bufs=bufs)
        except ConnectionError:
            return {"ok": False, "reason": "worker died during actor creation"}
        if not reply.get("ok"):
            worker.actor_id = b""
            if worker.tpu_chips:
                # it holds the chips until it is gone: never pooled
                self._kill_worker(worker)
                self.workers.pop(worker.worker_id, None)
            else:
                worker.state = WORKER_IDLE
            self._give_back(resources, pg_key)
            # Creation raised in __init__: actor is DEAD with the error.
            await self.gcs_conn.call("ReportActorDeath", {
                "actor_id": header["actor_id"],
                "reason": reply.get("error", "actor constructor failed"),
                "cause": {"kind": "CREATION_FAILED",
                          "node_id": self.node_id.hex(),
                          "worker_id": worker.worker_id.hex()},
                "expected": True})
            return {"ok": True}
        alive_reply, _ = await self.gcs_conn.call("ReportActorAlive", {
            "actor_id": header["actor_id"],
            "address": worker.address,
            "node_id": self.node_id.binary(),
            "incarnation": header.get("incarnation", 0)})
        if not alive_reply.get("ok"):
            # Superseded incarnation or killed-while-constructing: tear the
            # instance down instead of leaving a duplicate live actor.
            self._give_back(resources, pg_key)
            worker.actor_resources = {}
            self._kill_worker(worker)
            self.workers.pop(worker.worker_id, None)
            return {"ok": True}
        # Creation done: swap the hold to the actor's *lifetime* resources
        # (reference parity, python/ray/actor.py — default actors place
        # their creation with 1 CPU but hold 0 while alive). PG actors keep
        # the bundle reservation unchanged.
        lifetime = spec.get("lifetime_resources")
        if pg_key is None and lifetime is not None and lifetime != resources:
            self._give_back(resources, None)
            for k, v in lifetime.items():
                self.resources_available[k] = \
                    self.resources_available.get(k, 0.0) - v
            worker.actor_resources = lifetime
            self._schedule_tick()
        return {"ok": True}

    async def _start_tpu_worker(self, k: int) -> Optional[WorkerHandle]:
        """A registered worker process bound to ``k`` chips of its own,
        or None when no block of chips is free of its last holder yet
        or the process never registered (the GCS asks again)."""
        chips = self._claim_tpu_chips(k)
        if chips is None:
            return None
        worker = self._start_worker_process(force=True, tpu_chips=chips)
        deadline = time.time() + self.config.worker_register_timeout_s
        while worker.state == WORKER_STARTING and time.time() < deadline \
                and worker.proc.poll() is None:
            await asyncio.sleep(0.02)
        if worker.state == WORKER_IDLE:
            return worker
        if worker.state == WORKER_STARTING:
            self._num_starting = max(0, self._num_starting - 1)
        self._kill_worker(worker)
        self.workers.pop(worker.worker_id, None)
        return None

    def _give_back(self, resources, pg_key):
        if pg_key is not None:
            # Bundle-scoped resources return to the bundle; if the PG was
            # removed meanwhile, ReturnPGBundle already returned the whole
            # bundle to the node pool — crediting it again would inflate
            # node capacity.
            if pg_key in self._pg_available:
                for k, v in resources.items():
                    self._pg_available[pg_key][k] = \
                        self._pg_available[pg_key].get(k, 0.0) + v
            return
        for k, v in resources.items():
            self.resources_available[k] = \
                self.resources_available.get(k, 0.0) + v

    async def handle_kill_actor_worker(self, conn, header, bufs):
        actor_id = header["actor_id"]
        for w in list(self.workers.values()):
            if w.actor_id == actor_id and w.state == WORKER_ACTOR:
                self._give_back(getattr(w, "actor_resources", {}),
                                getattr(w, "actor_pg_key", None))
                w.actor_resources = {}
                self._kill_worker(w)
                self.workers.pop(w.worker_id, None)
                return {"ok": True}
        return {"ok": False, "reason": "actor worker not found"}

    async def handle_actor_exited(self, conn, header, bufs):
        """Graceful actor exit from the worker itself."""
        wid = conn.tags.get("worker_id")
        handle = self.workers.get(wid) if wid else None
        if handle is not None:
            self._give_back(getattr(handle, "actor_resources", {}),
                            getattr(handle, "actor_pg_key", None))
            handle.actor_resources = {}
        try:
            await self.gcs_conn.call("ReportActorDeath", {
                "actor_id": header["actor_id"],
                "reason": header.get("reason", "actor exited"),
                "expected": True})
        except ConnectionError:
            pass
        return {"ok": True}

    # --------------------------------------------------------- object plane

    async def handle_seal_object(self, conn, header, bufs):
        req = protocol.SealObjectRequest.from_header(header)
        oid = ObjectID(req.object_id)
        # "shard": DistributedArray placement attrs (rank / mesh
        # coords), folded into the SEALED object-plane record so
        # state.list_objects() shows where each shard landed
        ok = self.store.seal(oid, req.segment, req.size,
                             attrs=req.get("shard"))
        if ok and req.get("pin", False):
            self.store.pin(oid)
        owner_address = req.get("owner_address")
        if ok and owner_address:
            # leak-detector owner index: the sweep probes this owner's
            # live references against the stored segment
            self._object_owners[oid.binary()] = owner_address
        return protocol.SealObjectReply(
            ok=ok, node_id=self.node_id.binary()).to_header()

    async def handle_alloc_segment(self, conn, header, bufs):
        """Lease a recycled warm segment to a writing client (zero-copy
        put pipeline): the client fills it and SealObject returns it to
        the accounted tables. No lease -> the client creates a fresh
        segment, exactly as before this RPC existed."""
        # raylint: disable=shm-lifecycle — lease ownership transfers to the remote writer: SealObject/AbortSegment (or the stale sweep) closes it
        got = self.store.take_recycled(int(header["size"]))
        if got is None:
            return {"found": False}
        return {"found": True, "segment": got[0], "size": got[1]}

    async def handle_abort_segment(self, conn, header, bufs):
        """Abort half of the lease protocol: a writer whose fill failed
        hands the segment straight back (one-way push) instead of
        leaving it parked in _lent until the 600 s stale sweep."""
        self.store.abort_lease(header["segment"])
        return {"ok": True}

    async def handle_get_object_info(self, conn, header, bufs):
        oid = ObjectID(header["object_id"])
        segment = self.store.lookup(oid)
        if segment is None:
            return {"found": False}
        self.store.mark_exposed(oid)  # caller may mmap the segment
        return {"found": True, "segment": segment}

    async def handle_pin_object(self, conn, header, bufs):
        self.store.pin(ObjectID(header["object_id"]))
        return {"ok": True}

    async def handle_free_object(self, conn, header, bufs):
        oid = ObjectID(header["object_id"])
        if faultpoints.armed and faultpoints.fire(
                "object.free", oid=oid.hex(), node=self._nid12) == "drop":
            # free fault: the FreeObject is LOST before any state
            # changes — the store keeps the segment, the owner believes
            # it freed. Exactly the orphan class the leak detector's
            # sweep exists to catch (and reclaim).
            return {"ok": True}
        self._drop_object_bookkeeping(oid)
        self._free_local_object(oid)

        # Owner-supplied location list: forward the free to every other node
        # holding a copy (the owner has no raylet connections of its own).
        async def _free_on(nid: bytes):
            # _lookup_node, not remote_nodes: a replica on a peer this
            # raylet never saw register (the pubsub late-join gap) must
            # still be freed, exactly like it can be pulled from
            info = await self._lookup_node(nid)
            if info is None:
                return
            try:
                peer = await self._peer_conn(info["address"])
                await peer.call("FreeObject", {"object_id": oid.binary()})
            # raylint: disable=exception-hygiene — best-effort per peer; owner re-frees on next GC pass
            except Exception:
                pass

        peers = [nid for nid in header.get("locations", [])
                 if nid != self.node_id.binary()]
        if peers:
            await asyncio.gather(*[_free_on(nid) for nid in peers])
        return {"ok": True}

    async def handle_fetch_object_meta(self, conn, header, bufs):
        """Size + bulk-transfer endpoint probe that opens a pull: the
        puller learns total_size for admission/segment sizing and the
        data-channel address chunk requests should go to (empty = this
        node serves chunks over the control plane only)."""
        oid = ObjectID(header["object_id"])
        entry = self.store.entry(oid)
        if entry is None:
            return {"found": False}
        # A remote raylet is about to read chunks of this segment: it
        # must never enter the recycle pool mid-pull (same pin as the
        # chunk serve paths).
        self.store.mark_exposed(oid)
        return {"found": True, "total_size": entry[1],
                "data_address": self.data_address}

    async def _attach_serve_segment(self, segment: str):
        """Cached shared-memory attachment of a LOCAL segment for read
        serving (control-plane chunk serves + gather local-source
        copies). _QuietSharedMemory: cache eviction may race an
        in-flight chunk send whose memoryview still pins the mapping —
        deferred release absorbs that instead of leaking the fd on
        BufferError. Attached in an executor: the MAP_POPULATE remap of
        a GiB-scale segment must not stall the raylet loop."""
        shm = self._serve_attachments.get(segment)
        if shm is not None:
            return shm
        from ray_tpu._private.shm_store import _QuietSharedMemory
        new_shm = await asyncio.get_running_loop().run_in_executor(
            None, _QuietSharedMemory, segment)
        shm = self._serve_attachments.get(segment)
        if shm is not None:  # raced a concurrent first attach
            try:
                new_shm.close()
            except BufferError:
                pass
            return shm
        shm = new_shm
        # bounded cache: drop the oldest attachment beyond 16
        while len(self._serve_attachments) >= 16:
            oldest = next(iter(self._serve_attachments))
            old = self._serve_attachments.pop(oldest)
            try:
                old.close()
            except BufferError:
                pass  # a concurrent chunk read still holds it
        self._serve_attachments[segment] = shm
        return shm

    async def handle_fetch_object_chunk(self, conn, header, bufs):
        """Serve one chunk of a remote raylet's pull over the CONTROL
        plane (reference: the chunked Push path,
        src/ray/object_manager/push_manager.h). Retained as the
        fallback for peers whose puller runs with the data plane
        disabled (data_plane_stripes=0); striped pulls use the raw
        data channel (data_channel.py) instead."""
        oid = ObjectID(header["object_id"])
        segment = self.store.lookup(oid)
        if segment is None:
            return {"found": False}
        # a remote raylet is mid-pull: its future chunk reads must see
        # this exact data, so the segment can never enter the recycle
        # pool (an owner-side free mid-pull would otherwise let a new
        # writer overwrite the still-linked file under the puller)
        self.store.mark_exposed(oid)
        offset = header["offset"]
        length = header["length"]
        shm = await self._attach_serve_segment(segment)
        entry = self.store._objects.get(oid)  # noqa: SLF001
        total = entry[1] if entry is not None else shm.size
        end = min(offset + length, total)
        # zero-copy: the chunk rides to the socket as a live view of the
        # mapped segment — node-to-node pushes never flatten to bytes
        data = shm.buf[offset:end] if end > offset else b""
        return {"found": True, "total_size": total}, [data]

    async def handle_ensure_object_local(self, conn, header, bufs):
        """Pull an object into the local store from wherever it lives
        (reference: PullManager admission + ObjectManager::Pull)."""
        return await self._ensure_local(
            ObjectID(header["object_id"]), header.get("owner_address", ""))

    async def _ensure_local(self, oid: ObjectID, owner_address: str) -> dict:
        if self.store.contains(oid):
            segment = self.store.lookup(oid)
            if segment is not None:
                # the caller will mmap this segment: it can never be
                # recycled (zero-copy views may outlive the free)
                self.store.mark_exposed(oid)
                return {"ok": True, "segment": segment}
        # Dedupe concurrent pulls of the same object (reference:
        # PullManager bundles many requests for one object into one pull).
        pull = self._active_pulls.get(oid)
        if pull is None:
            pull = asyncio.get_running_loop().create_task(
                self._pull_object(oid, owner_address))
            self._active_pulls[oid] = pull
            pull.add_done_callback(
                lambda _: self._active_pulls.pop(oid, None))
        return await asyncio.shield(pull)

    async def _pull_object(self, oid: ObjectID, owner_address: str) -> dict:
        reason = "object not found at any location"
        attempts = max(0, self.config.pull_location_refresh_attempts)
        # floor at 1 ms: pull_location_refresh_backoff_s = 0 ("refresh
        # immediately") was valid before the backoff policy and must
        # stay valid — Backoff itself rejects a non-positive base
        base = max(self.config.pull_location_refresh_backoff_s, 1e-3)
        bo = backoff_mod.Backoff(
            base_s=base,
            cap_s=max(self.config.retry_backoff_cap_s, base),
            multiplier=self.config.retry_backoff_multiplier)
        for round_no in range(1 + attempts):
            if round_no:
                if not owner_address:
                    break  # nobody to re-ask for locations
                # Every known location failed (peer death / replica
                # freed mid-pull). Refresh the owner's location index
                # after a backoff (exponential-jitter across rounds,
                # pull_location_refresh_attempts of them): a replica
                # added meanwhile (e.g. by a concurrent pull elsewhere)
                # is found instead of erroring the get.
                await bo.sleep()
            locations = await self._query_locations(oid, owner_address)
            sources = await self._pull_sources(locations)
            if not sources:
                continue
            pulled = await self._pull_chunked(oid, sources)
            if pulled is None:
                continue
            name, total = pulled
            if not self.store.seal(oid, name, total):
                # distinct reason: the transfer SUCCEEDED — pointing
                # the operator at replica locations would hide the
                # real (local capacity) cause
                reason = "local store refused seal (capacity)"
                break  # retrying cannot help
            # Report the replica to the owner so its location index
            # stays complete and FreeObject reaches this node too
            # (reference: ObjectDirectory location adds).
            if owner_address:
                # leak-detector owner index: pulled replicas are judged
                # against the same owner the seal path records
                self._object_owners[oid.binary()] = owner_address
                async def _report(addr=owner_address):
                    try:
                        owner = await self._owner_conn(addr)
                        r, _ = await owner.call(
                            "AddObjectLocation", {
                                "object_id": oid.binary(),
                                "node_id": self.node_id.binary()})
                        if not r.get("ok"):
                            # owner already released the object —
                            # drop our replica
                            self.store.free(oid)
                    # raylint: disable=exception-hygiene — owner may be gone; replica already dropped
                    except Exception:
                        pass
                rpc.spawn_logged(_report(), "raylet-report-replica")
            self.store.mark_exposed(oid)  # caller is about to mmap
            return {"ok": True, "segment": name}
        return {"ok": False, "reason": reason}

    async def _query_locations(self, oid: ObjectID,
                               owner_address: str) -> List[bytes]:
        if not owner_address:
            return []
        try:
            owner = await self._owner_conn(owner_address)
            reply, _ = await owner.call(
                "GetObjectLocations",
                protocol.GetObjectLocationsRequest(
                    object_id=oid.binary()).to_header())
            return reply.get("locations", [])
        except ConnectionError:
            return []

    async def _lookup_node(self, nid: bytes) -> Optional[dict]:
        """Node info for the PULL/free path: the pubsub view first,
        then a GCS directory lookup for nodes that registered before
        this raylet subscribed (the late-join gap) — a pull must reach
        EVERY replica holder, not just peers whose alive event this
        raylet happened to see. Deliberately not fed into remote_nodes:
        the scheduler's spillback view stays pubsub-driven. Concurrent
        cache misses (a fan-out pull probing N locations at once) share
        ONE in-flight GetAllNodeInfo instead of stampeding the GCS."""
        info = self.remote_nodes.get(nid) or self._node_directory.get(nid)
        if info is not None:
            return info
        if self._node_dir_refresh is None or self._node_dir_refresh.done():
            self._node_dir_refresh = asyncio.get_running_loop() \
                .create_task(self._refresh_node_directory())
        # shield: this caller's cancellation must not kill the refresh
        # other concurrent lookups are waiting on
        await asyncio.shield(self._node_dir_refresh)
        return self.remote_nodes.get(nid) or self._node_directory.get(nid)

    async def _refresh_node_directory(self) -> None:
        try:
            reply, _ = await self.gcs_conn.call("GetAllNodeInfo", {})
        except ConnectionError:
            return
        for n in reply.get("nodes", []):
            if not n.get("alive") or n["node_id"] == self.node_id.binary():
                continue
            self._node_directory.setdefault(n["node_id"], {
                "address": n["address"],
                "data_address": n.get("data_address", ""),
                "resources_total": n.get("resources_total", {}),
                "resources_available": dict(
                    n.get("resources_available", {})),
            })

    @staticmethod
    async def _first_plus_grace(coros, grace: float = 0.5) -> list:
        """Run coroutines concurrently and return the truthy results —
        but once ANY of them yields one, give the stragglers only
        ``grace`` seconds before abandoning (cancelling) them. This is
        how every pull-setup fan-out is bounded: a dead peer's connect
        timeout must never gate the work the live peers can already do
        (it costs at most ``grace`` on top of the fastest success)."""
        tasks = [asyncio.ensure_future(c) for c in coros]
        results: list = []
        try:
            pending = set(tasks)
            while pending and not any(results):
                done, pending = await asyncio.wait(
                    pending, return_when=asyncio.FIRST_COMPLETED)
                for t in done:  # all done: these awaits return at once
                    results.append(await t)
            if pending:
                done, _ = await asyncio.wait(pending, timeout=grace)
                for t in done:
                    results.append(await t)
        finally:
            for t in tasks:
                t.cancel()
            # shield: if THIS task is cancelled during the reap, the
            # children must still be awaited — an abandoned gather
            # leaves their CancelledErrors unretrieved and any
            # half-open connections unreaped
            await asyncio.shield(
                asyncio.gather(*tasks, return_exceptions=True))
        return [r for r in results if r]

    async def _pull_sources(self, locations: List[bytes]
                            ) -> List[Tuple[rpc.Connection, str]]:
        """Reachable replica holders as (control conn, data_address).
        Connects run CONCURRENTLY, first success + grace: one dead peer
        never delays pulling from the live replicas."""
        async def _one(nid: bytes):
            info = await self._lookup_node(nid)
            if info is None:
                return None
            try:
                conn = await self._peer_conn(info["address"])
            except ConnectionError:
                return None
            return conn, info.get("data_address", "")

        candidates = [nid for nid in locations
                      if nid != self.node_id.binary()]
        if not candidates:
            return []
        return await self._first_plus_grace(_one(n) for n in candidates)

    def _pull_chunk_size(self, total: int, num_peers: int) -> int:
        """Adaptive data-plane chunk size. object_manager_chunk_size
        stays the FLOOR (and the exact size with the data plane off);
        large objects raise it toward data_plane_max_chunk_size so the
        transfer is copy-bound, not request-round-trip-bound — while
        keeping ~8 chunks per stripe so fan-out still balances."""
        floor = self.config.object_manager_chunk_size
        if self.config.data_plane_stripes <= 0:
            return floor
        lanes = self.config.data_plane_stripes * max(1, num_peers)
        target = -(-total // (8 * lanes))  # ceil div
        return min(max(floor, target),
                   max(floor, self.config.data_plane_max_chunk_size))

    async def _admit_pull(self, total: int, chunk: int) -> None:
        """Pull admission control (reference: pull_manager.h:47): wait
        — parked on the Condition, notified at every pull completion,
        no sleep-polling — until the in-flight byte budget has room.

        HONEST BUDGET: a single object LARGER than the whole budget can
        never fit under it, so it is admitted exactly when nothing else
        is in flight (``_pull_inflight_bytes == 0``) — oversized pulls
        serialize with everything else instead of deadlocking the
        admission queue (waiting for room that can never appear) or
        stampeding the store alongside admitted pulls."""
        budget = max(self.store.capacity // 4, chunk)
        async with self._pull_cond:
            await self._pull_cond.wait_for(
                lambda: self._pull_inflight_bytes == 0 or
                self._pull_inflight_bytes + total <= budget)
            self._pull_inflight_bytes += total

    def _notify_pull_done(self) -> None:
        """Wake admission waiters after ``_pull_inflight_bytes``
        dropped. The decrement itself runs synchronously in the
        caller's ``finally`` (a cancelled task must never leak budget);
        the Condition notify needs its lock held, so it rides a fresh
        task that cannot be cancelled with the pull."""
        async def _notify():
            async with self._pull_cond:
                self._pull_cond.notify_all()
        rpc.spawn_logged(_notify(), "raylet-pull-done-notify")

    async def _data_channel(self, address: str):
        """Cached striped data-channel client for one peer (reference:
        ObjectManager's per-peer transfer connections). Stripes dropped
        by failures or cancelled pulls are topped back up here, so a
        transient error never leaves the channel permanently degraded."""
        from ray_tpu._private.data_channel import DataChannelClient
        ch = self._data_channels.get(address)
        if ch is not None and ch.alive and \
                len(ch.stripes) < ch.num_stripes:
            await ch.ensure_stripes()
        if ch is None or not ch.alive:
            fresh = await DataChannelClient(
                address, self.config.data_plane_stripes).connect()
            ch = self._data_channels.get(address)
            if ch is not None and ch.alive:
                # raced a concurrent pull's connect during the await:
                # keep the cached client, close the loser's sockets
                await fresh.close()
            else:
                self._data_channels[address] = ch = fresh
        return ch

    async def _pull_fetchers(self, oid: ObjectID, found, chunk: int,
                             total: int, buf) -> list:
        """One fetch coroutine per transfer lane: every stripe of every
        replica-holding peer's data channel — chunk bytes land DIRECTLY
        in ``buf`` (the destination mapping) via the data plane's
        recv_into, one copy per chunk — or, for peers without a data
        channel, a window of control-plane FetchObjectChunk slots
        (socket -> bytes -> copy_into, the pre-data-plane path)."""
        from ray_tpu._private import native
        oid_b = oid.binary()

        async def _source_fetchers(conn, data_address):
            channel = None
            if data_address and self.config.data_plane_stripes > 0:
                try:
                    channel = await self._data_channel(data_address)
                except ConnectionError:
                    channel = None  # data port dead; control conn lives
            fetchers = []
            if channel is not None:
                for stripe in channel.stripes:
                    async def _fetch(off, _s=stripe, _ch=channel):
                        await _ch.fetch_chunk(
                            _s, oid_b, off, min(chunk, total - off),
                            buf, off)
                    fetchers.append(_fetch)
            else:
                async def _legacy(off, _conn=conn):
                    from ray_tpu._private.data_channel import \
                        note_control_chunk
                    # Control-plane lane: these frames SHARE the RPC
                    # stream with heartbeats and lease grants, so the
                    # adaptive data-plane chunk must never inflate them
                    # — sub-fetch at the fixed control-plane size,
                    # keeping the pre-data-plane bound (8 lanes x
                    # object_manager_chunk_size bytes in flight).
                    floor = self.config.object_manager_chunk_size
                    end = min(off + chunk, total)
                    sub = off
                    while sub < end:
                        want = min(floor, end - sub)
                        r, bufs2 = await _conn.call("FetchObjectChunk", {
                            "object_id": oid_b, "offset": sub,
                            "length": want})
                        if not r.get("found"):
                            raise ConnectionError(
                                "object vanished mid-pull")
                        if len(bufs2[0]) != want:
                            raise ConnectionError(
                                "short chunk from divergent replica")
                        native.copy_into(buf, sub, bufs2[0])
                        # counts the one intermediate bytes copy (the
                        # recv loop materialized this sub-chunk before
                        # copy_into) in pull_stats AND the Prometheus
                        # tier counters
                        note_control_chunk(want)
                        sub += want
                # the old pull window: 8 in-flight chunks per peer
                fetchers.extend([_legacy] * 8)
            return fetchers

        # Per-peer channel setup runs CONCURRENTLY, first success +
        # grace: a black-holed data port's stripe-dial timeout never
        # holds back lanes the reachable peers already have up —
        # stragglers are abandoned (their cancelled dials close their
        # own sockets) and the pull starts on the ready lanes.
        per_source = await self._first_plus_grace(
            _source_fetchers(c, d) for c, d in found)
        return [f for lanes in per_source for f in lanes]

    def _segment_reaper(self, alloc):
        """Done-callback for a segment-mapping executor future whose
        awaiter was cancelled. run_in_executor work cannot be
        interrupted: the thread still maps (and holds the recycled
        lease on) the segment after the cancel unwinds, so the
        eventual result is reaped HERE — close the mapping, re-park a
        recycled lease, unlink a fresh segment. Runs on the loop
        thread (executor futures schedule callbacks there), so store
        state is safe to touch."""
        from ray_tpu._private.shm_store import _close_segment_owner

        def _reap(fut):
            if fut.cancelled() or fut.exception() is not None:
                if alloc is not None:
                    self.store.abort_lease(alloc[0])
                return
            name, owner, buf = fut.result()
            _close_segment_owner(owner, buf)
            if alloc is not None and name == alloc[0]:
                self.store.abort_lease(name)
            else:
                self._unlink_segment(name)
        return _reap

    async def _pull_chunked(self, oid: ObjectID,
                            sources: List[Tuple[rpc.Connection, str]]
                            ) -> Optional[Tuple[str, int]]:
        """Striped, flow-controlled pull into a fresh local segment;
        returns (segment_name, total_size) or None when no source could
        serve the object. Chunk offsets fan out across every stripe of
        every replica-holding peer (data_channel.run_striped); a failed
        stripe hands its chunk to the survivors, so the pull outlives
        anything short of every source dying (reference: PushManager's
        chunk window + ObjectBufferPool chunk writes). Admission: total
        in-flight pull bytes are bounded so concurrent pulls cannot
        overcommit the store (reference: pull_manager.h:47)."""
        from collections import deque

        from ray_tpu._private import data_channel
        from ray_tpu._private.shm_store import (
            RECYCLE_MIN_BYTES, _close_segment_owner, acquire_segment)

        # Probe every source for size + bulk endpoint (concurrently,
        # first success + grace — a wedged-but-connected peer whose
        # call never answers must not park the pull); unreachable or
        # object-less sources drop out here.
        async def _probe(conn, data_address):
            try:
                reply, _ = await conn.call(
                    "FetchObjectMeta", protocol.FetchObjectMetaRequest(
                        object_id=oid.binary()).to_header())
            except ConnectionError:
                return None
            if not reply.get("found"):
                return None
            return (conn, reply.get("data_address") or data_address,
                    reply["total_size"])

        probes = await self._first_plus_grace(
            _probe(c, d) for c, d in sources)
        found: List[Tuple[rpc.Connection, str]] = []
        total = 0
        for conn, data_address, t in probes:
            if found and t != total:
                # divergent replica (size disagrees with the first
                # holder): chunks from it could seal wrong bytes —
                # drop the source, the strict per-chunk length check
                # is the backstop
                continue
            total = t
            found.append((conn, data_address))
        if not found:
            return None
        chunk = self._pull_chunk_size(total, len(found))
        await self._admit_pull(total, chunk)
        t_pull = time.monotonic()
        try:
            # Destination: a recycled warm segment when the local store
            # has one (page allocation dominates cold pull writes), else
            # a fresh MAP_POPULATE mapping; chunk payloads are received
            # straight into it.
            alloc = self.store.take_recycled(total) \
                if total >= RECYCLE_MIN_BYTES else None
            loop = asyncio.get_running_loop()
            # executor: a fresh multi-GiB MAP_POPULATE create would
            # otherwise stall the raylet loop for the whole zero-fill.
            # Shielded: the mapping thread cannot be interrupted, so a
            # cancel at this await must hand the eventual segment (and
            # the recycled lease) to the reaper instead of leaking both.
            fut = loop.run_in_executor(
                None, acquire_segment, alloc, max(total, 1))
            try:
                name, owner, buf = await asyncio.shield(fut)
            except asyncio.CancelledError:
                fut.add_done_callback(self._segment_reaper(alloc))
                raise

            def _discard():
                # run_striped cancelled AND awaited every in-flight
                # sibling before raising, so the segment can go away
                # now without an orphan receive landing in a closed
                # mmap.
                _close_segment_owner(owner, buf)
                self.store.release_lease(name)
                self._unlink_segment(name)

            try:
                offsets = deque(range(0, total, chunk))
                fetchers = await self._pull_fetchers(
                    oid, found, chunk, total, buf)
                if offsets:
                    await data_channel.run_striped(offsets, fetchers)
            except asyncio.CancelledError:
                # cancellation must UNWIND (a swallowed cancel would
                # roll into the location-refresh round and restart the
                # whole transfer on a cancelled task)
                _discard()
                raise
            except ConnectionError:
                _discard()
                return None
            _close_segment_owner(owner, buf)
            self.store.release_lease(name)  # sealed by the caller next
            wall = time.monotonic() - t_pull
            self._pull_rates.append(total / max(wall, 1e-9) / 1e9)
            data_channel.observe_pull(total, wall)
            if self.task_events.enabled:
                # timeline record: the pull interval on the wall clock
                # (ts = start), merged by ray_tpu.state.timeline() with
                # task states and tracing spans
                self.task_events.record(
                    b"", TRANSFER,
                    {"object_id": oid.hex(), "bytes": total,
                     "dur": wall, "node": self._nid12,
                     "sources": len(found)},
                    ts=time.time() - wall)
            if self.object_events.enabled:
                # object-plane twin of the TRANSFER record: this node
                # pulled a replica in (the seal that follows stamps
                # SEALED; PULLED carries the transfer shape)
                self.object_events.record(
                    oid.binary(), PULLED,
                    {"bytes": total, "dur": wall, "node": self._nid12,
                     "sources": len(found)},
                    ts=time.time() - wall)
            return name, total
        finally:
            self._pull_inflight_bytes -= total
            self._notify_pull_done()

    # ---------------------------------------------- shard collectives

    async def handle_gather_shards(self, conn, header, bufs):
        """Build ONE destination shard locally by scatter-gathering byte
        runs out of source shards cluster-wide — the collective data
        path behind DistributedArray reshard / all-gather / all-reduce.
        The header carries only the plan (per-source ``runs`` are
        [src_off, dst_off, length] triples relative to each shard's raw
        data frame); the bulk bytes ride the striped data plane with
        ``recv_into`` landing every chunk DIRECTLY in the destination
        segment — zero intermediate copies end to end. Local sources
        are GIL-releasing memcpys in the executor. Shares the pull
        path's admission budget, chunk sizing and discard discipline."""
        from ray_tpu._private.distributed_array import frame_plan
        from ray_tpu._private.shm_store import (
            RECYCLE_MIN_BYTES, _U32, _close_segment_owner, acquire_segment)

        req = protocol.GatherShardsRequest.from_header(header)
        oid = ObjectID(req.object_id)
        if self.store.contains(oid):
            segment = self.store.lookup(oid)
            if segment is not None:  # idempotent retry: already built
                self.store.mark_exposed(oid)
                return {"ok": True, "segment": segment,
                        "node_id": self.node_id.binary()}
        meta = req.meta
        payload = req.payload
        data_nbytes = int(req.data_nbytes)
        sources = req.sources
        # destination layout from sizes alone: [payload frame, data
        # frame], byte-identical to what plan_segment would produce
        hdr, offsets, total = frame_plan(
            meta, [len(payload), data_nbytes])
        me = self.node_id.binary()
        n_remote = len({s["node_id"] for s in sources
                        if s["node_id"] != me})
        chunk = self.config.reshard_chunk_bytes or \
            self._pull_chunk_size(data_nbytes, max(1, n_remote))
        await self._admit_pull(total, chunk)
        t0 = time.monotonic()
        try:
            alloc = self.store.take_recycled(total) \
                if total >= RECYCLE_MIN_BYTES else None
            loop = asyncio.get_running_loop()
            # shielded for the same reason as _pull_chunked: the
            # mapping thread survives the cancel, so its result must
            # be reaped, not dropped
            fut = loop.run_in_executor(
                None, acquire_segment, alloc, max(total, 1))
            try:
                name, owner, buf = await asyncio.shield(fut)
            except asyncio.CancelledError:
                fut.add_done_callback(self._segment_reaper(alloc))
                raise

            def _discard():
                _close_segment_owner(owner, buf)
                self.store.release_lease(name)
                self._unlink_segment(name)

            try:
                buf[0:4] = _U32.pack(len(hdr))
                buf[4:4 + len(hdr)] = hdr
                buf[offsets[0]:offsets[0] + len(payload)] = payload
                reduce_spec = req.get("reduce")
                if reduce_spec:
                    moved = await self._gather_reduce(
                        buf, offsets[1], data_nbytes, chunk, sources,
                        reduce_spec)
                else:
                    moved = await self._gather_runs(
                        buf, offsets[1], chunk, sources)
            except asyncio.CancelledError:
                # every gather job was cancelled AND awaited before the
                # re-raise reached here (see _gather_runs), so no
                # orphan receive can land in the unlinked mapping
                _discard()
                raise
            except (ConnectionError, OSError, ValueError) as e:
                # typed failure back to the driver: it falls back to
                # the naive get+assemble+put path (fallback matrix)
                _discard()
                return {"ok": False, "reason": str(e)}
            _close_segment_owner(owner, buf)
            self.store.release_lease(name)
            if not self.store.seal(oid, name, total,
                                   attrs=req.get("shard")):
                return {"ok": False,
                        "reason": "local store refused seal (capacity)"}
            if req.get("owner_address"):
                # leak-detector owner index, same as the seal/pull paths
                self._object_owners[oid.binary()] = \
                    req.owner_address
            self.store.mark_exposed(oid)  # a sibling gather may read it
            _spmd_metrics()["reshard_bytes"].inc(moved)
            if reduce_spec:
                # the fold twin of the ring path's per-step counter:
                # the two labels together make the bandwidth claim
                # assertable from telemetry alone
                _spmd_metrics()["collective_bytes"].inc(
                    moved, {"algo": "fold"})
                self._recent_collectives.append({
                    "collective": oid.hex()[:12], "rank": 0,
                    "algo": "fold", "op": reduce_spec.get("op", "sum"),
                    "wire_bytes": moved, "steps": len(sources),
                    "folds": max(0, len(sources) - 1), "ok": True})
            wall = time.monotonic() - t0
            if self.object_events.enabled:
                self.object_events.record(
                    oid.binary(), PULLED,
                    {"bytes": moved, "dur": wall, "node": self._nid12,
                     "sources": len(sources), "gather": True},
                    ts=time.time() - wall)
            return {"ok": True, "segment": name,
                    "node_id": self.node_id.binary()}
        finally:
            self._pull_inflight_bytes -= total
            self._notify_pull_done()

    async def _gather_runs(self, buf, data_off: int, chunk: int,
                           sources: List[dict]) -> int:
        """Execute a gather plan into ``buf``: per-source byte runs
        rebased to segment-absolute on the source side (``data_offset +
        src_off``) and destination-buffer-absolute on ours (``data_off
        + dst_off``). Remote nodes stream concurrently over every
        stripe of their data channel (or the legacy control lane);
        failure unwinds with every sibling job cancelled AND awaited,
        so the caller may unlink the destination mapping immediately.
        Returns total bytes moved."""
        from collections import deque

        from ray_tpu._private import data_channel, native

        me = self.node_id.binary()
        local: List[dict] = []
        by_node: Dict[bytes, List[dict]] = {}
        moved = 0
        for src in sources:
            for run in src["runs"]:
                moved += run[2]
            if src["node_id"] == me:
                local.append(src)
            else:
                by_node.setdefault(src["node_id"], []).append(src)
        loop = asyncio.get_running_loop()

        async def _local_job():
            for src in local:
                s_oid = ObjectID(src["oid"])
                segment = self.store.lookup(s_oid)
                if segment is None:
                    raise ConnectionError(
                        f"local shard {s_oid.hex()[:12]} vanished")
                # the gather reads this segment via a foreign-style
                # mapping: it must never enter the recycle pool mid-copy
                self.store.mark_exposed(s_oid)
                shm = await self._attach_serve_segment(segment)
                base = src["data_offset"]

                def _copy(runs=src["runs"], base=base, sbuf=shm.buf):
                    for s, d, ln in runs:
                        native.copy_into(buf, data_off + d,
                                         sbuf[base + s:base + s + ln])
                # one executor batch per source: GIL-releasing memcpys
                # off the raylet loop
                await loop.run_in_executor(None, _copy)

        async def _remote_job(nid: bytes, srcs: List[dict]):
            info = await self._lookup_node(nid)
            if info is None:
                raise ConnectionError(
                    f"shard holder node {nid.hex()[:12]} unknown")
            peer = await self._peer_conn(info["address"])
            work: deque = deque()
            data_address = ""
            for src in srcs:
                # the meta probe pins the source segment serve-side
                # (mark_exposed) and yields the bulk endpoint
                reply, _ = await peer.call(
                    "FetchObjectMeta", protocol.FetchObjectMetaRequest(
                        object_id=src["oid"]).to_header())
                if not reply.get("found"):
                    raise ConnectionError(
                        "source shard "
                        f"{src['oid'].hex()[:12]} not found on holder")
                data_address = reply.get("data_address") or \
                    info.get("data_address", "")
                base = src["data_offset"]
                for s, d, ln in src["runs"]:
                    off = 0
                    while off < ln:
                        n = min(chunk, ln - off)
                        work.append((src["oid"], base + s + off,
                                     data_off + d + off, n))
                        off += n
            channel = None
            if data_address and self.config.data_plane_stripes > 0:
                try:
                    channel = await self._data_channel(data_address)
                except ConnectionError:
                    channel = None  # data port dead; control conn lives
            fetchers = []
            if channel is not None:
                for stripe in channel.stripes:
                    async def _fetch(item, _s=stripe, _ch=channel):
                        ob, s_abs, d_abs, n = item
                        await _ch.fetch_chunk(_s, ob, s_abs, n,
                                              buf, d_abs)
                    fetchers.append(_fetch)
            else:
                async def _legacy(item, _conn=peer):
                    ob, s_abs, d_abs, n = item
                    floor = self.config.object_manager_chunk_size
                    sub = 0
                    while sub < n:
                        want = min(floor, n - sub)
                        r, bufs2 = await _conn.call(
                            "FetchObjectChunk", {
                                "object_id": ob, "offset": s_abs + sub,
                                "length": want})
                        if not r.get("found") or len(bufs2[0]) != want:
                            raise ConnectionError(
                                "short/missing chunk from shard holder")
                        native.copy_into(buf, d_abs + sub, bufs2[0])
                        data_channel.note_control_chunk(want)
                        sub += want
                fetchers.extend([_legacy] * 8)
            if work:
                await data_channel.run_striped(work, fetchers)

        jobs = []
        if local:
            jobs.append(loop.create_task(_local_job()))
        jobs.extend(loop.create_task(_remote_job(nid, srcs))
                    for nid, srcs in by_node.items())
        try:
            await asyncio.gather(*jobs)
        except BaseException:
            # cancel-and-AWAIT every sibling before unwinding: the
            # caller unlinks the destination mapping right after, and
            # an orphan recv_into must not land in a closed mmap
            for j in jobs:
                j.cancel()
            await asyncio.gather(*jobs, return_exceptions=True)
            raise
        return moved

    async def _gather_reduce(self, buf, data_off: int, data_nbytes: int,
                             chunk: int, sources: List[dict],
                             reduce_spec: dict) -> int:
        """All-reduce destination build, fold algorithm: the first
        source streams straight into the destination data frame; each
        further source streams into ONE reused scratch buffer and is
        folded in by the GIL-releasing ``native.reduce_into`` kernel in
        an executor — peak extra memory is one shard regardless of
        fan-in. The ring path (handle_ring_*) supersedes this for
        P >= 3; this stays as the ``collective_algorithm="fold"`` /
        2-rank / ring-failure fallback."""
        import numpy as np

        from ray_tpu._private import native

        op = reduce_spec.get("op", "sum")
        if op not in ("sum", "min", "max"):
            raise ValueError(f"unsupported reduce op: {op!r}")
        dtype = np.dtype(reduce_spec["dtype"])
        count = data_nbytes // dtype.itemsize

        def _fold(sbuf):
            # reduce_into's buffer exports live only inside this
            # executor call — an array view passed through (or returned
            # from) run_in_executor lingers in the work-item/future
            # plumbing and makes the caller's _close_segment_owner
            # fail with BufferError
            native.reduce_into(buf, data_off, sbuf, dtype, op)

        moved = await self._gather_runs(buf, data_off, chunk,
                                        sources[:1])
        if len(sources) > 1:
            scratch = np.empty(count, dtype=dtype)
            sbuf = memoryview(scratch).cast("B")
            loop = asyncio.get_running_loop()
            for src in sources[1:]:
                moved += await self._gather_runs(sbuf, 0, chunk, [src])
                await loop.run_in_executor(None, _fold, sbuf)
        return moved

    # ------------------------------------------------ ring collectives
    #
    # Bandwidth-optimal ring reduce-scatter + all-gather over the
    # striped data plane (plan math: distributed_array.ring_segments /
    # ring_reduce_schedule). The DRIVER orchestrates: one RingInit per
    # member, then one RingStep RPC per (member, schedule step) with a
    # barrier between rounds — so a step only ever reads peer segment
    # bytes its peer finished in the previous round — then RingFinish
    # seals every accumulator as the same result object. Per-rank wire
    # traffic: 2*(P-1)/P * N bytes (vs the fold path's (P-1)*N).
    #
    # A member's accumulator segment is store-LEASED (never sealed)
    # while the collective runs; ring peers read it mid-collective via
    # the data server's extra_entries side table, keyed by the 28-byte
    # member id. Admission: RingInit deliberately does NOT take the
    # pull-admission budget for the whole accumulator — P members of
    # one collective may share a node (single-driver runs), and the
    # driver's round barrier would deadlock against a held budget;
    # capacity is enforced at RingFinish's seal instead. Each RingStep
    # admits only its own segment's bytes (steps within a round are
    # mutually independent, so they serialize at worst, never
    # deadlock).

    def _discard_ring_member(self, member_id: bytes, rec: dict,
                             reason: str = "") -> None:
        """Release everything a ring member holds: the data-server
        serve entry, the segment mapping, the store lease and the
        /dev/shm file. Idempotent per member (callers pop the record
        first)."""
        from ray_tpu._private.shm_store import _close_segment_owner
        if self.data_server is not None:
            self.data_server.extra_entries.pop(member_id, None)
        try:
            _close_segment_owner(rec["owner"], rec["buf"])
        except BufferError:
            pass  # a straggling serve view closes with its unpin
        self.store.release_lease(rec["name"])
        self._unlink_segment(rec["name"])
        if reason:
            self._recent_collectives.append({
                "collective": rec["collective_id"].hex()[:12],
                "rank": rec["rank"], "algo": "ring", "op": rec["op"],
                "wire_bytes": rec["wire_bytes"], "steps": rec["steps"],
                "folds": rec["folds"], "ok": False, "reason": reason})

    def _sweep_ring_members(self) -> None:
        """Opportunistic TTL sweep (rides RingInit, no periodic task):
        discard members whose driver stopped stepping them — a crashed
        driver cannot send RingAbort, and a leaked lease would pin
        store capacity forever."""
        ttl = self.config.collective_member_ttl_s
        if ttl <= 0 or not self._ring_members:
            return
        now = time.monotonic()
        for mid, rec in list(self._ring_members.items()):
            if now - rec["touched"] > ttl:
                self._ring_members.pop(mid, None)
                self._discard_ring_member(mid, rec, reason="ttl expired")

    async def handle_ring_init(self, conn, header, bufs):
        """Create one ring member: lease + lay out the accumulator
        segment (same frame math as GatherShards), stream this rank's
        OWN source shard into it, and publish it to ring peers through
        the data server's side table. Replies with this node's data
        address so the driver can point the member's neighbours at it."""
        from ray_tpu._private.distributed_array import frame_plan
        from ray_tpu._private.shm_store import (
            RECYCLE_MIN_BYTES, _U32, _close_segment_owner, acquire_segment)

        self._sweep_ring_members()
        req = protocol.RingInitRequest.from_header(header)
        member_id = req.member_id
        rec = self._ring_members.get(member_id)
        if rec is not None:  # idempotent retry: member already built
            rec["touched"] = time.monotonic()
            return {"ok": True, "data_address": self.data_address,
                    "node_id": self.node_id.binary()}
        meta = req.meta
        payload = req.payload
        data_nbytes = int(req.data_nbytes)
        source = req.source
        hdr, offsets, total = frame_plan(
            meta, [len(payload), data_nbytes])
        chunk = self.config.reshard_chunk_bytes or \
            self._pull_chunk_size(data_nbytes, 1)
        alloc = self.store.take_recycled(total) \
            if total >= RECYCLE_MIN_BYTES else None
        loop = asyncio.get_running_loop()
        # shielded like the gather path: the mapping thread survives a
        # cancel, so its result must be reaped, not dropped
        fut = loop.run_in_executor(None, acquire_segment, alloc,
                                   max(total, 1))
        try:
            name, owner, buf = await asyncio.shield(fut)
        except asyncio.CancelledError:
            fut.add_done_callback(self._segment_reaper(alloc))
            raise

        def _discard():
            _close_segment_owner(owner, buf)
            self.store.release_lease(name)
            self._unlink_segment(name)

        try:
            buf[0:4] = _U32.pack(len(hdr))
            buf[4:4 + len(hdr)] = hdr
            buf[offsets[0]:offsets[0] + len(payload)] = payload
            await self._gather_runs(buf, offsets[1], chunk, [source])
        except asyncio.CancelledError:
            _discard()
            raise
        except (ConnectionError, OSError, ValueError) as e:
            _discard()
            return {"ok": False, "reason": str(e)}
        now = time.monotonic()
        self._ring_members[member_id] = {
            "collective_id": req.collective_id,
            "rank": int(req.rank),
            "nranks": int(req.nranks),
            "name": name, "owner": owner, "buf": buf,
            "total": total, "data_off": offsets[1],
            "data_nbytes": data_nbytes,
            "dtype": req.dtype, "op": req.get("op"),
            "oid": req.object_id,
            "owner_address": req.get("owner_address") or "",
            "shard": req.get("shard"),
            "chunk": chunk, "scratch": None,
            "wire_bytes": 0, "steps": 0, "folds": 0,
            "created": now, "touched": now,
        }
        if self.data_server is not None:
            self.data_server.extra_entries[member_id] = (name, total)
        return {"ok": True, "data_address": self.data_address,
                "node_id": self.node_id.binary()}

    async def handle_ring_step(self, conn, header, bufs):
        """Execute ONE ring step for one member: pull the named segment
        from the ring predecessor over the striped data plane and
        either fold it into the accumulator (reduce-scatter phase,
        pipelined through double-buffered scratch windows) or land it
        verbatim in the destination frame (all-gather phase — chunks
        recv_into the segment directly, zero intermediate copies).
        Layouts are identical on every rank, so the peer's absolute
        segment offsets equal this member's own."""
        from collections import deque

        from ray_tpu._private import data_channel

        req = protocol.RingStepRequest.from_header(header)
        rec = self._ring_members.get(req.member_id)
        if rec is None:
            return {"ok": False, "reason": "unknown ring member"}
        rec["touched"] = time.monotonic()
        seg_off = int(req.seg_off)
        seg_len = int(req.seg_len)
        step = int(req.get("step") or 0)
        if seg_len <= 0:  # P > element count: empty segment, no wire
            rec["steps"] += 1
            return {"ok": True}
        if seg_off < 0 or seg_off + seg_len > rec["data_nbytes"]:
            return {"ok": False,
                    "reason": f"ring segment out of bounds at step "
                              f"{step}"}
        peer_key = req.peer_member_id
        peer_addr = req.peer_data_address
        abs_off = rec["data_off"] + seg_off
        chunk = min(rec["chunk"], seg_len)
        await self._admit_pull(seg_len, chunk)
        try:
            try:
                channel = await self._data_channel(peer_addr)
                if req.get("reduce"):
                    rec["folds"] += await self._ring_reduce_fold(
                        rec, channel, peer_key, abs_off, seg_len, chunk)
                else:
                    buf = rec["buf"]
                    work: deque = deque()
                    off = 0
                    while off < seg_len:
                        n = min(chunk, seg_len - off)
                        work.append((abs_off + off, n))
                        off += n
                    fetchers = []
                    for stripe in channel.stripes:
                        async def _fetch(item, _s=stripe, _ch=channel):
                            o, n = item
                            await _ch.fetch_chunk(_s, peer_key, o, n,
                                                  buf, o)
                        fetchers.append(_fetch)
                    await data_channel.run_striped(work, fetchers)
            except asyncio.CancelledError:
                raise
            except (ConnectionError, OSError, ValueError) as e:
                # typed failure to the driver: it RingAborts every
                # member and falls back (fold, then naive)
                return {"ok": False, "reason": str(e)}
            rec["wire_bytes"] += seg_len
            rec["steps"] += 1
            _spmd_metrics()["collective_bytes"].inc(
                seg_len, {"algo": "ring"})
            return {"ok": True}
        finally:
            self._pull_inflight_bytes -= seg_len
            self._notify_pull_done()

    async def _ring_reduce_fold(self, rec: dict, channel, peer_key: bytes,
                                abs_off: int, seg_len: int,
                                chunk: int) -> int:
        """Pipelined recv+reduce for one reduce-scatter step: the
        segment streams through two scratch windows so window k folds
        (GIL-releasing ``native.reduce_into`` in an executor) while
        window k+1 is on the wire. Window reuse is safe by
        construction: the fetch into a window starts only after the
        previous fold FROM that window was awaited. Returns the number
        of window folds executed."""
        from collections import deque

        from ray_tpu._private import data_channel, native

        win = min(max(self.config.collective_scratch_bytes, chunk),
                  seg_len)
        scratch = rec.get("scratch")
        if scratch is None or len(scratch[0]) < win:
            scratch = rec["scratch"] = [bytearray(win), bytearray(win)]
        loop = asyncio.get_running_loop()
        nwin = -(-seg_len // win)
        dtype, op = rec["dtype"], rec["op"] or "sum"
        buf = rec["buf"]

        async def _fetch_window(w_idx: int, sbuf) -> int:
            w_off = w_idx * win
            w_len = min(win, seg_len - w_off)
            work: deque = deque()
            off = 0
            while off < w_len:
                n = min(chunk, w_len - off)
                work.append((w_off + off, n))
                off += n
            fetchers = []
            for stripe in channel.stripes:
                async def _fetch(item, _s=stripe, _ch=channel,
                                 _w=w_off):
                    o, n = item
                    await _ch.fetch_chunk(_s, peer_key, abs_off + o, n,
                                          sbuf, o - _w)
                fetchers.append(_fetch)
            await data_channel.run_striped(work, fetchers)
            return w_len

        folds = 0
        fold_fut: List[Any] = [None, None]
        fetch_task = loop.create_task(_fetch_window(0, scratch[0]))
        try:
            for k in range(nwin):
                w_len = await fetch_task
                if k + 1 < nwin:
                    nb = (k + 1) % 2
                    if fold_fut[nb] is not None:
                        # the window we are about to overwrite must be
                        # done folding before new bytes land in it
                        await fold_fut[nb]
                        fold_fut[nb] = None
                    fetch_task = loop.create_task(
                        _fetch_window(k + 1, scratch[nb]))

                def _fold(_sbuf=scratch[k % 2], _off=abs_off + k * win,
                          _n=w_len):
                    # views live only inside the executor call (the
                    # same BufferError discipline as _gather_reduce)
                    native.reduce_into(buf, _off,
                                       memoryview(_sbuf)[:_n],
                                       dtype, op)
                fold_fut[k % 2] = loop.run_in_executor(None, _fold)
                folds += 1
            for f in fold_fut:
                if f is not None:
                    await f
        except BaseException:
            # cancel-and-AWAIT before unwinding: an orphan recv/fold
            # must not land in buffers the abort path is about to
            # close (run_striped already awaits its own workers)
            fetch_task.cancel()
            await asyncio.gather(
                fetch_task, *(f for f in fold_fut if f is not None),
                return_exceptions=True)
            raise
        return folds

    async def handle_ring_finish(self, conn, header, bufs):
        """Seal one member's accumulator as the collective's result
        object and return its per-rank telemetry (wire bytes / steps /
        folds — the bench's bandwidth bound asserts on these)."""
        member_id = protocol.RingFinishRequest.from_header(header).member_id
        rec = self._ring_members.pop(member_id, None)
        if rec is None:
            return {"ok": False, "reason": "unknown ring member"}
        from ray_tpu._private.shm_store import _close_segment_owner
        if self.data_server is not None:
            self.data_server.extra_entries.pop(member_id, None)
        oid = ObjectID(rec["oid"])
        _close_segment_owner(rec["owner"], rec["buf"])
        self.store.release_lease(rec["name"])
        if not self.store.seal(oid, rec["name"], rec["total"],
                               attrs=rec["shard"]):
            self._unlink_segment(rec["name"])
            return {"ok": False,
                    "reason": "local store refused seal (capacity)"}
        if rec["owner_address"]:
            self._object_owners[oid.binary()] = rec["owner_address"]
        self.store.mark_exposed(oid)  # ring peers/gathers may read it
        self.num_ring_collectives += 1
        self._recent_collectives.append({
            "collective": rec["collective_id"].hex()[:12],
            "rank": rec["rank"], "algo": "ring", "op": rec["op"],
            "wire_bytes": rec["wire_bytes"], "steps": rec["steps"],
            "folds": rec["folds"], "ok": True})
        wall = time.monotonic() - rec["created"]
        if self.object_events.enabled:
            self.object_events.record(
                oid.binary(), PULLED,
                {"bytes": rec["wire_bytes"], "dur": wall,
                 "node": self._nid12, "sources": rec["nranks"],
                 "ring": True},
                ts=time.time() - wall)
        return {"ok": True, "node_id": self.node_id.binary(),
                "wire_bytes": rec["wire_bytes"], "steps": rec["steps"],
                "folds": rec["folds"]}

    async def handle_ring_abort(self, conn, header, bufs):
        """Tear one member down without sealing (driver-side failure
        fan-out, or cleanup after a peer died mid-collective).
        Idempotent: aborting an unknown/already-finished member is ok."""
        req = protocol.RingAbortRequest.from_header(header)
        rec = self._ring_members.pop(req.member_id, None)
        if rec is not None:
            self._discard_ring_member(
                req.member_id, rec,
                reason=req.get("reason") or "aborted")
        return {"ok": True}

    @staticmethod
    def _unlink_segment(name: str):
        from multiprocessing import shared_memory
        try:
            shm = shared_memory.SharedMemory(name=name)
            shm.close()
            shm.unlink()
        except OSError:
            pass  # segment already unlinked

    async def _peer_conn(self, address: str) -> rpc.Connection:
        conn = self._peer_raylets.get(address)
        if conn is None or conn.closed:
            conn = await rpc.connect(
                address, peer_name=f"raylet@{address}",
                timeout=self.config.rpc_connect_timeout_s)
            self._peer_raylets[address] = conn
        return conn

    async def _owner_conn(self, address: str) -> rpc.Connection:
        conn = self._owner_conns.get(address)
        if conn is None or conn.closed:
            conn = await rpc.connect(address, peer_name=f"owner@{address}")
            self._owner_conns[address] = conn
        return conn

    # ----------------------------------------------------- placement groups

    async def handle_prepare_pg_bundle(self, conn, header, bufs):
        key = (header["pg_id"], header["bundle_index"])
        resources = header["resources"]
        if not all(self.resources_available.get(k, 0.0) + 1e-9 >= v
                   for k, v in resources.items() if v > 0):
            return {"ok": False, "reason": "insufficient resources"}
        for k, v in resources.items():
            self.resources_available[k] = self.resources_available.get(k, 0.0) - v
        self._pg_prepared[key] = dict(resources)
        return {"ok": True}

    async def handle_commit_pg_bundle(self, conn, header, bufs):
        key = (header["pg_id"], header["bundle_index"])
        if key not in self._pg_prepared:
            return {"ok": False}
        self._pg_committed.add(key)
        self._pg_available[key] = dict(self._pg_prepared[key])
        return {"ok": True}

    async def handle_return_pg_bundle(self, conn, header, bufs):
        key = (header["pg_id"], header["bundle_index"])
        resources = self._pg_prepared.pop(key, None)
        self._pg_committed.discard(key)
        self._pg_available.pop(key, None)
        if resources:
            for k, v in resources.items():
                self.resources_available[k] = \
                    self.resources_available.get(k, 0.0) + v
        self._schedule_tick()
        return {"ok": True}

    # -------------------------------------------------------------- stats

    def _note_latency(self, req) -> None:
        now = time.monotonic()
        arrival = getattr(req, "arrival_ts", 0.0)
        if arrival:
            self._sched_latencies.append(now - arrival)
            first = getattr(req, "first_decision_ts", 0.0)
            if first:
                self._decision_latencies.append(first - arrival)
                self._grant_waits.append(now - first)

    @staticmethod
    def _pct_block(samples) -> dict:
        from ray_tpu._private.metrics import percentile

        lat = sorted(samples)
        if not lat:
            return {"count": 0}
        return {
            "count": len(lat),
            "p50_ms": round(percentile(lat, 0.50) * 1e3, 3),
            "p90_ms": round(percentile(lat, 0.90) * 1e3, 3),
            "p99_ms": round(percentile(lat, 0.99) * 1e3, 3),
            "max_ms": round(lat[-1] * 1e3, 3),
        }

    @staticmethod
    def _rate_block(samples) -> dict:
        """Percentile summary of a rate reservoir (units preserved —
        unlike _pct_block there is no seconds->ms scaling). Guards the
        empty case: metrics.percentile raises on empty input."""
        from ray_tpu._private.metrics import percentile

        rates = sorted(samples)
        if not rates:
            return {"count": 0}
        return {"count": len(rates),
                "p50": round(percentile(rates, 0.50), 3),
                "p90": round(percentile(rates, 0.90), 3),
                "max": round(rates[-1], 3)}

    def _latency_percentiles(self) -> dict:
        from ray_tpu._private.metrics import percentile

        out = self._pct_block(self._sched_latencies)
        # grant-population split: streamed credit grants vs legacy
        # request/grant round-trips (both feed the reservoirs above, so
        # the percentiles reflect the whole grant population — not just
        # the handful of legacy requests a credit-served drain makes)
        out["credit_grants"] = self.num_credit_grants
        out["legacy_grants"] = self.num_leases_granted
        if not out["count"]:
            return out
        # arrival->first-decision (kernel responsiveness) vs
        # first-decision->grant (resource/queue wait): reported apart so
        # a saturated node's backlog can't mask kernel regressions.
        out["decision"] = self._pct_block(self._decision_latencies)
        out["grant_wait"] = self._pct_block(self._grant_waits)
        ticks = list(self._tick_durations)
        if ticks:
            durs = sorted(t for _, t in ticks)
            out["tick"] = {
                "count": len(ticks),
                "p50_ms": round(percentile(durs, 0.50) * 1e3, 3),
                "p99_ms": round(percentile(durs, 0.99) * 1e3, 3),
                "max_queue": max(n for n, _ in ticks),
                "max_ms": round(durs[-1] * 1e3, 3),
            }
        return out

    async def handle_set_resource(self, conn, header, bufs):
        """Dynamic custom resources (reference:
        experimental/dynamic_resources.py set_resource → raylet-side
        capacity update): adjust total AND available by the same delta
        so in-flight leases keep their accounting; capacity 0 deletes.
        The next tick dispatches anything the new capacity unblocks."""
        name = header["name"]
        capacity = float(header["capacity"])
        if name == "CPU":
            return {"ok": False, "reason": "CPU capacity is fixed"}
        old_total = self.resources_total.get(name, 0.0)
        delta = capacity - old_total
        new_avail = self.resources_available.get(name, 0.0) + delta
        if capacity <= 0.0:
            self.resources_total.pop(name, None)
            # available moves by the SAME delta (possibly negative:
            # in-flight leases still owe their release), so a later
            # re-create can never oversubscribe
            if new_avail == 0.0:
                self.resources_available.pop(name, None)
            else:
                self.resources_available[name] = new_avail
        else:
            self.resources_total[name] = capacity
            self.resources_available[name] = new_avail
        self._schedule_tick()
        return {"ok": True, "total": self.resources_total.get(name, 0.0)}

    async def handle_dump_worker_stacks(self, conn, header, bufs):
        """Aggregate all-thread stack dumps from every live worker on
        this node (reference: `ray stack`, scripts.py:1393 — py-spy
        over local pids; here each worker self-reports over RPC)."""
        out = []
        for w in list(self.workers.values()):
            if w.conn is None or w.conn.closed or w.state == WORKER_DEAD:
                continue
            try:
                reply, _ = await w.conn.call("DumpStack", {}, timeout=5.0)
                reply["worker_id"] = w.worker_id.hex() \
                    if isinstance(w.worker_id, bytes) else w.worker_id
                out.append(reply)
            except (ConnectionError, asyncio.TimeoutError):
                out.append({"pid": w.pid, "error": "unreachable"})
        return {"node_id": self.node_id.binary(), "workers": out}

    async def handle_get_logs(self, conn, header, bufs):
        """List / tail this node's session log files (reference:
        dashboard log module, dashboard/modules/log — per-node file
        serving; here the raylet serves its own session dir)."""
        log_dir = os.path.join(self.session_dir, "logs")
        name = header.get("name") or ""
        try:
            tail = int(header.get("tail") or 200)
        except (TypeError, ValueError):
            tail = 200
        try:
            files = sorted(os.listdir(log_dir))
        except OSError:
            files = []
        if not name:
            out = []
            for fname in files:
                try:
                    out.append({"name": fname, "size": os.path.getsize(
                        os.path.join(log_dir, fname))})
                except OSError:
                    continue
            return {"files": out}
        matches = [f for f in files if name in f]
        if not matches:
            return {"error": f"no log file matching {name!r}",
                    "files": [{"name": f} for f in files]}
        path = os.path.join(log_dir, matches[0])
        try:
            data = await asyncio.get_running_loop().run_in_executor(
                None, _read_file_tail, path, 256 * 1024)
            lines = data.decode("utf-8", errors="replace") \
                .splitlines()[-tail:]
        except OSError as e:
            return {"error": str(e)}
        return {"name": matches[0], "lines": lines}

    # ----------------------------------------------------- leak detector

    def _free_local_object(self, oid: ObjectID) -> None:
        """Free a store-held object AND release this raylet's serving
        state for its segment (cached serve attachment, data-plane
        source) — a free that skips the attachment close leaves the
        unlinked segment's pages pinned by the open mmap."""
        entry = self.store._objects.get(oid)  # noqa: SLF001
        if entry is not None:
            att = self._serve_attachments.pop(entry[0], None)
            if att is not None:
                try:
                    att.close()
                except BufferError:
                    pass
            if self.data_server is not None:
                self.data_server.drop_source(entry[0])
        self.store.free(oid)

    def _drop_object_bookkeeping(self, oid: ObjectID) -> None:
        """An object legitimately left this store (FreeObject, owner
        release): forget its owner entry and any leak verdict — a
        late-but-arrived free is a recovery, and the leaked gauge must
        drop with it."""
        k = oid.binary()
        self._object_owners.pop(k, None)
        self._leak_suspects.pop(k, None)
        self._leaked.discard(k)

    def _maybe_start_leak_sweep(self) -> None:
        """Interval gate + single-flight spawn for the leak sweep: the
        heartbeat loop calls this every beat; an actual sweep runs as
        its own task so slow/dead-owner probes never delay a beat. A
        sweep still in flight (wedged owner) is simply not doubled."""
        interval = self.config.leak_sweep_interval_s
        if interval <= 0 or self._closing:
            return
        now = time.monotonic()
        if now - self._last_leak_sweep < interval:
            return
        if self._leak_sweep_task is not None and \
                not self._leak_sweep_task.done():
            return
        self._last_leak_sweep = now
        self.leak_sweeps += 1
        self._leak_sweep_task = asyncio.get_running_loop().create_task(
            self._leak_sweep())

    async def _leak_sweep(self) -> None:
        """Cross-check store-held segments against live owner
        references (reference intent: the plasma store's unreferenced-
        object accounting, surfaced as `ray memory`'s LOST_OBJECT
        class; here it is an active probe because the owner table IS
        the ground truth in ownership-based memory management).

        Cadence: ``leak_sweep_interval_s`` (0 disables), spawned off
        the heartbeat loop by _maybe_start_leak_sweep. Verdict
        protocol: an object older than one interval whose owner says
        ``live=False`` (or whose owner is GONE — dial refused/timed
        out) accumulates one dead vote per sweep — the SECOND vote
        flags it LEAKED (objects_leaked gauge, leaked=True in
        list_objects(), a LEAKED event), the THIRD reclaims it
        (store.free -> FREED + LEAK_RECLAIMED, gauge back to 0). A
        live verdict at any point clears the votes and retracts an
        already-raised flag (LEAK_CLEARED). Owners that cannot
        be judged (probe unsupported, or a CONNECTED owner whose call
        times out — a GIL-stalled driver must never be judged dead)
        are skipped — never a verdict.
        """
        interval = self.config.leak_sweep_interval_s
        try:
            await self._leak_sweep_inner(interval)
        except asyncio.CancelledError:
            raise
        except Exception:  # noqa: BLE001 — missed sweep < broken raylet
            logger.exception("object leak sweep failed")

    async def _leak_sweep_inner(self, interval: float) -> None:
        cutoff = time.time() - interval
        held: Set[bytes] = set()
        by_owner: Dict[str, List[ObjectID]] = {}
        for oid, sealed_ts in self.store.held_objects():
            held.add(oid.binary())
            if sealed_ts > cutoff:
                continue  # too young to judge (seal/free may be racing)
            owner = self._object_owners.get(oid.binary())
            if owner:
                by_owner.setdefault(owner, []).append(oid)
        # prune bookkeeping for objects that left the store sideways
        # (eviction, watchdog relief) so the index can't grow unbounded
        for k in [k for k in self._object_owners if k not in held]:
            self._object_owners.pop(k, None)
            self._leak_suspects.pop(k, None)
            self._leaked.discard(k)
        for owner, oids in by_owner.items():
            if self._closing:
                return
            try:
                # wait_for caps the dial: rpc.connect retries a refused
                # socket for its full 10s budget, and a dead owner must
                # cost this background sweep seconds, not the default
                # timeout per owner per sweep
                conn = await asyncio.wait_for(
                    self._owner_conn(owner), timeout=5.0)
            except (ConnectionError, asyncio.TimeoutError):
                # owner GONE (SIGKILLed driver — refused dial, or a
                # black-holed endpoint): every object it owned here
                # gets a dead vote. Reclaim still needs the multi-sweep
                # confirmation, so a restarting owner's transient
                # outage never costs data by itself.
                for o in oids:
                    self._judge_object(o, False, owner)
                continue
            try:
                reply, _ = await conn.call(
                    "ProbeObjectLiveness",
                    {"object_ids": [o.binary() for o in oids]},
                    timeout=5.0)
                live = reply.get("live") or []
            except ConnectionError:
                for o in oids:  # conn dropped mid-call: owner gone
                    self._judge_object(o, False, owner)
                continue
            except asyncio.TimeoutError:
                # CONNECTED but slow (a GIL-stalled driver under
                # load): cannot be judged — never a dead vote
                continue
            except Exception:  # noqa: BLE001 — probe-incapable owner: no verdict
                logger.debug("leak probe to %s failed; skipping verdict",
                             owner, exc_info=True)
                continue
            for o, alive in zip(oids, live):
                self._judge_object(o, bool(alive), owner)

    def _judge_object(self, oid: ObjectID, alive: bool,
                      owner: str) -> None:
        k = oid.binary()
        if alive:
            self._leak_suspects.pop(k, None)
            if k in self._leaked:
                self._leaked.discard(k)
                # retract the flag in the GCS table too — without this
                # the record's current state stays LEAKED and
                # summary_objects()["leaked"] reports a phantom leak
                # for as long as the (healthy) owner keeps its reference
                if self.object_events.enabled:
                    self.object_events.record(
                        k, LEAK_CLEARED,
                        {"node": self._nid12, "owner": owner})
            return
        if k not in self._object_owners:
            # a legitimate FreeObject landed while the probe was in
            # flight (_drop_object_bookkeeping cleared the entry): the
            # verdict is stale — re-creating a suspect entry here would
            # leak it forever (nothing prunes keys outside the index)
            return
        votes = self._leak_suspects.get(k, 0) + 1
        self._leak_suspects[k] = votes
        if votes == 2 and k not in self._leaked:
            self._leaked.add(k)
            logger.warning(
                "leak detector: object %s held in store but owner %s "
                "has no reference (lost FreeObject?)", oid.hex()[:16],
                owner)
            if self.object_events.enabled:
                self.object_events.record(
                    k, LEAKED, {"node": self._nid12, "owner": owner})
        elif votes >= 3:
            # flagged a full sweep ago and still dead: reclaim. free()
            # stamps FREED; LEAK_RECLAIMED names the cause.
            self._free_local_object(oid)
            self._drop_object_bookkeeping(oid)
            self.leak_reclaims += 1
            if self.object_events.enabled:
                self.object_events.record(
                    k, LEAK_RECLAIMED,
                    {"node": self._nid12, "owner": owner})
            self.events.emit(
                "WARNING", "OBJECT_LEAK_RECLAIMED",
                f"leak detector reclaimed object {oid.hex()[:16]} "
                f"(owner {owner} held no reference)",
                node=self._nid12, object_id=oid.hex()[:16])

    def object_plane_stats(self) -> dict:
        """Public object-plane snapshot — the chaos invariants assert
        on THIS (lent leases drained, admission budget at zero, nothing
        leaked) instead of peeking private fields."""
        s = self.store.stats()
        return {
            "lent_segments": s["recycle_lent_segments"],
            "pull_inflight_bytes": self._pull_inflight_bytes,
            "leaked": len(self._leaked),
            "leak_suspects": len(self._leak_suspects),
            "leak_reclaims": self.leak_reclaims,
            "leak_sweeps": self.leak_sweeps,
            "owners_tracked": len(self._object_owners),
        }

    async def handle_get_node_stats(self, conn, header, bufs):
        from ray_tpu._private import native
        from ray_tpu._private.data_channel import pull_stats, serve_stats
        from ray_tpu._private.rpc import handler_stats, telemetry
        return {
            "data_plane": {
                "data_address": self.data_address,
                "stripes": self.config.data_plane_stripes,
                "pull": dict(pull_stats),
                "serve": dict(serve_stats),
                "recv_tiers": dict(native.recv_stats),
                "pull_inflight_bytes": self._pull_inflight_bytes,
                # per-pull throughput percentiles (GB/s) from the
                # bounded reservoir; {"count": 0} before any pull
                "pull_throughput_gb_per_s": self._rate_block(
                    self._pull_rates),
            },
            "schedule_latency": self._latency_percentiles(),
            "rpc_handlers": handler_stats.snapshot(),
            # the full flight recorder: per-method server/client
            # reservoir percentiles, queue-vs-exec split, bytes,
            # errors, in-flight — plus THIS raylet loop's lag probe
            "rpc": telemetry.snapshot(probe="raylet"),
            "node_id": self.node_id.binary(),
            "address": self.address,
            "resources_total": self.resources_total,
            "resources_available": self.resources_available,
            "num_workers": self._alive_worker_count(),
            "workers": [{
                "worker_id": w.worker_id, "pid": w.pid, "state": w.state,
                "actor_id": w.actor_id,
                # last watchdog poll's RSS sample (0 before any poll)
                "rss_bytes": self.memory_monitor.workers_rss.get(
                    w.worker_id.hex()[:12], 0),
            } for w in self.workers.values()],
            "num_pending_leases": len(self._pending),
            "num_leases_granted": self.num_leases_granted,
            "num_spillbacks": self.num_spillbacks,
            # streaming-lease window state + credit hit-rate
            "lease_credits": self._credit_stats(),
            # SPMD gang leases: incarnations homed here + member
            # bookings this node holds for gangs homed elsewhere
            "gangs": self._gang_stats(),
            # ring collectives: members currently accumulating on this
            # node + the bounded per-member finish/abort history (wire
            # bytes, steps, folds — the bench asserts its 2*(P-1)/P*N
            # bandwidth bound from these, not from timing)
            "collectives": {
                "active_members": len(self._ring_members),
                "finished": self.num_ring_collectives,
                "recent": list(self._recent_collectives),
            },
            "store": self.store.stats(),
            # per-process writer mapping cache (zero-copy put tier;
            # meaningful where writers share this process, i.e. the
            # in-process head)
            "writer_map_cache": _map_cache_stats(),
            # leak detector + lease/admission truth, public form
            "object_plane": self.object_plane_stats(),
            # watchdog state: per-worker RSS, pressure flag, cumulative
            # kill/backpressure counts + last-64 action history
            "memory_monitor": self.memory_monitor.snapshot(),
        }
