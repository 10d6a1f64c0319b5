"""Core worker: the per-process ownership facade.

Role parity: reference CoreWorker (src/ray/core_worker/core_worker.h) —
embedded in every driver and worker process. Owns:

  * the in-process memory store (small objects) and the shm-store client
  * the reference counter (ownership + borrowing)
  * the task manager (pending tasks, retries, error objects)
  * the direct task submitter (leases from raylets, pipelined pushes
    straight to leased workers — reference: transport/direct_task_transport.h)
  * the direct actor submitter (per-actor ordered queues with sequence
    numbers — reference: transport/direct_actor_transport.h)
  * the owner RPC services other processes call: GetObject,
    GetObjectLocations, AddBorrower/RemoveBorrower

The synchronous public API (get/put/wait/submit) marshals onto a dedicated
asyncio IO loop, the analog of the reference core worker's io_service.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import itertools
import logging
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ray_tpu import exceptions as exc
from ray_tpu._private import protocol, rpc
from ray_tpu._private.config import RayTpuConfig
from ray_tpu._private.function_manager import FunctionManager
from ray_tpu._private.ids import (
    ACTOR_ID_SIZE, TASK_ID_SIZE, ActorID, JobID, ObjectID, TaskID,
    WorkerID, make_task_id_bytes, return_object_id_bytes,
)
from ray_tpu._private.memory_store import IN_PLASMA, MemoryStore
from ray_tpu._private.object_events import (
    LINEAGE_RELEASED, ObjectEventBuffer,
)
from ray_tpu._private.object_ref import ObjectRef
from ray_tpu._private.reference_count import Reference, ReferenceCounter
from ray_tpu._private.serialization import (
    META_ERROR, SerializationContext, SerializedObject,
)
from ray_tpu._private.shm_store import (
    RECYCLE_MIN_BYTES, AttachedObject, plan_segment, write_segment,
)
from ray_tpu._private.task_events import (
    CREDIT_DISPATCHED, DISPATCHED, FAILED, PENDING_ARGS, RETRY, SUBMITTED,
    TaskEventBuffer,
)
from ray_tpu._private.task_spec import (
    ARG_REF, ARG_VALUE, REPLY_ACTOR_RESTARTING, REPLY_ERROR, REPLY_STOLEN,
    TASK_ACTOR, TASK_ACTOR_CREATION, TASK_NORMAL, TaskArg, TaskSpec,
)

logger = logging.getLogger(__name__)


_tracing_mod = None


def _check_tpu_demand(resources: Optional[Dict[str, float]],
                      actor: bool) -> None:
    """A chip belongs to one process from TPU start-up until that
    process exits, so ``TPU`` is leased only to an actor — the raylet
    starts a process of its own for it — and only in whole chips. A
    task would run on a pool worker, which is pinned to CPU jax."""
    k = (resources or {}).get("TPU", 0)
    if not k:
        return
    if not actor:
        raise ValueError(
            "a task cannot hold TPU: the chip goes to one dedicated "
            "process for that process's lifetime. Put the work in an "
            "actor created with num_tpus=k")
    if k != int(k) or k < 0:
        raise ValueError(
            f"num_tpus counts whole chips bound to one process, got {k}")


def _trace_ctx():
    """Span context for a submission, or None when tracing is off.

    Off-path cost is one global read: ``ray_tpu.util.tracing`` REGISTERS
    itself into ``_tracing_mod`` at import time (the prior sys.modules
    probe here cost ~0.4us/task on the submit hot path) — enable()
    imports it in the driver, CoreWorker.__init__ imports it when
    RAY_TPU_TRACE=1 was set in the environment, and workers import it in
    ``_exec_span`` the moment a traced spec arrives, before any nested
    submission."""
    m = _tracing_mod
    if m is None:
        return None
    return m.inject_context() if m.enabled() else None


class PendingTaskEntry:
    """Owner-side record of one submitted task (reference: TaskManager's
    pending-task table, src/ray/core_worker/task_manager.h)."""

    __slots__ = ("spec", "num_retries_left", "return_ids", "dep_ids",
                 "lineage_pinned", "recovery_waiter", "oom_retries_left",
                 "oom_backoff")

    def __init__(self, spec: TaskSpec, return_ids: List[ObjectID]):
        self.spec = spec
        self.num_retries_left = spec.max_retries
        self.return_ids = return_ids
        # args=() is the submit hot path: skip the dependency scan.
        self.dep_ids = [ObjectID(b) for b in spec.dependency_ids()] \
            if spec.args else ()
        self.lineage_pinned = False
        # Future resolved on the next completion of this task (set by
        # object recovery while it waits for the re-execution).
        self.recovery_waiter = None
        # Dedicated memory-watchdog retry budget (config
        # task_oom_retries), SEPARATE from num_retries_left: an OOM
        # kill is the node's fault, not the task's. None = not yet
        # initialized — the first OOM kill fills it from config, and
        # the C fastpath (cpp/fastpath.c) leaves these two slots UNSET
        # entirely, so every reader goes through getattr defaults.
        self.oom_retries_left = None
        self.oom_backoff = None


class LeasedWorker:
    __slots__ = ("address", "lease_id", "node_id", "conn", "inflight",
                 "raylet_address", "worker_id", "idle_timer",
                 "via_credit", "on_drop", "gang")

    def __init__(self, address, lease_id, node_id, conn, raylet_address, worker_id):
        self.address = address
        self.lease_id = lease_id
        self.node_id = node_id
        self.conn = conn
        self.raylet_address = raylet_address
        self.worker_id = worker_id
        self.inflight = 0
        # cancellable keepalive TimerHandle while idle (exactly one)
        self.idle_timer = None
        # True when this worker arrived as a streamed lease credit
        # (GrantLeaseCredits) rather than a RequestWorkerLease grant —
        # its dispatches count as credit hits and stamp
        # CREDIT_DISPATCHED, and RevokeLeaseCredits may reclaim it.
        self.via_credit = False
        # the on_disconnect callback registered for this worker, kept
        # so deliberate teardown (idle return, credit revocation) can
        # unregister it — a revoked credit must not fire the
        # worker-died retry path against a healthy worker
        self.on_drop = None
        # owning SpmdGang when this lease is a gang member (rank-pinned
        # dispatch; never idle-returned — the gang release path owns
        # the lease lifetime, see _schedule_idle_return)
        self.gang = None


class SchedulingKeyState:
    """Per scheduling-class submission state (reference: SchedulingKey
    queues in direct_task_transport.h)."""

    __slots__ = ("queue", "workers", "pending_lease", "resources",
                 "steal_pending", "reassigned", "last_grant_ts",
                 "credit_target", "cluster_slots", "last_demand_ts",
                 "activating")

    def __init__(self, resources):
        self.queue: deque[TaskSpec] = deque()
        self.workers: List[LeasedWorker] = []
        self.pending_lease = 0
        self.resources = resources
        # Streaming-lease window target announced by the raylet
        # (GrantLeaseCredits.window_target): the breadth this class may
        # hold. -1 = unknown (probe with ONE legacy request — it
        # carries the backlog that opens the window). Bounds how many
        # legacy lease requests the pump parks at the raylet; parked
        # requests beyond the cluster's capacity were exactly the
        # 200-700ms grant_wait tail streaming leases exist to kill.
        self.credit_target = -1
        # cluster-wide slot bound from the same push: how many legacy
        # requests may park at the raylet for spillback BEYOND the
        # streamed local slots (remote capacity still flows through
        # the existing spill machinery)
        self.cluster_slots = -1
        # last ReportLeaseDemand push (paced refresh, see the pump)
        self.last_demand_ts = 0.0
        # credits announced for this class whose worker dial is still
        # in flight: counted as expected breadth by the pump so a
        # racing legacy request doesn't grab the same pool slot
        self.activating = 0
        # Work stealing (reference: direct_task_transport.h:57): at most
        # one outstanding StealTasks per key. ``reassigned`` maps a
        # stolen task_id -> a multiset (list, repeats allowed) of VICTIM
        # worker_ids — a task stolen twice has two victim slots, and
        # both steals can even be from the same worker. Each victim's
        # batch slot (stolen marker, or victim death) must be skipped
        # exactly once, but a THIEF dying while executing the stolen
        # task must still retry.
        self.steal_pending = False
        self.reassigned: Dict[bytes, List[bytes]] = {}
        # when the last lease grant landed (breadth/depth phase signal)
        self.last_grant_ts = 0.0


class ActorQueueState:
    """Per-actor client-side queue (reference:
    CoreWorkerDirectActorTaskSubmitter per-actor state)."""

    __slots__ = ("actor_id", "seqno", "conn", "address", "state", "buffer",
                 "inflight", "resolving", "incarnation", "death_cause",
                 "death_info", "max_pending", "creation_arg_holds")

    def __init__(self, actor_id: bytes):
        self.actor_id = actor_id
        self.seqno = 0
        self.conn: Optional[rpc.Connection] = None
        self.address = ""
        self.state = "UNRESOLVED"
        # (spec, seqno) awaiting send
        self.buffer: deque[Tuple[TaskSpec, int]] = deque()
        self.inflight: Dict[int, Tuple[TaskSpec, int]] = {}  # seqno -> (spec, retries)
        self.resolving = False
        self.incarnation = -1
        self.death_cause = ""
        # structured death cause from the GCS actor table (see
        # exceptions.ActorDiedError.cause) — attached to every
        # ActorDiedError this queue raises
        self.death_info: dict = {}
        self.max_pending = -1


class SpmdGang:
    """Driver handle to a gang-scheduled SPMD worker group.

    Formation books ``world_size`` workers across the cluster in ONE
    all-or-nothing lease round (``RequestGangLease`` — the home raylet
    fans peer bookings out itself, so rpc telemetry shows exactly one
    gang-lease call, never N ``RequestWorkerLease``s). Members are
    adopted as rank-pinned :class:`LeasedWorker`s: ``run()`` pushes one
    ``max_retries=0`` task per rank straight to its member, so a step
    is deterministic in placement and a dead member fails its task with
    :class:`~ray_tpu.exceptions.WorkerCrashedError` instead of silently
    re-running elsewhere. Incarnations are epoch-fenced like actors: a
    member death marks the gang broken; ``reform()`` books epoch+1 and
    the raylet rejects any stale push from the previous incarnation."""

    def __init__(self, core: "CoreWorker", world_size: int,
                 resources: Dict[str, float], runtime_env):
        self.core = core
        self.gang_id = os.urandom(16)
        self.world_size = world_size
        self.resources = resources
        self.runtime_env = runtime_env
        self.epoch = 0
        self.broken = False
        self.members: List[LeasedWorker] = []  # rank-ordered
        self._released = False
        # private dispatch state, NEVER registered in scheduling_keys:
        # the pump must not see gang members as general-purpose breadth
        self._state = SchedulingKeyState(dict(resources))

    # -- formation ------------------------------------------------------

    async def _form(self) -> "SpmdGang":
        from ray_tpu._private import backoff as backoff_mod

        core = self.core
        cfg = core.config
        epoch = self.epoch + 1
        base = max(cfg.gang_lease_retry_backoff_s, 1e-3)
        bo = backoff_mod.Backoff(
            base_s=base, cap_s=max(cfg.retry_backoff_cap_s, base),
            multiplier=cfg.retry_backoff_multiplier)
        reply: dict = {}
        for attempt in range(1 + max(0, cfg.gang_lease_retry_attempts)):
            if attempt:
                await bo.sleep()
            reply, _ = await core.raylet_conn.call(
                "RequestGangLease",
                protocol.RequestGangLeaseRequest(
                    gang_id=self.gang_id, epoch=epoch,
                    count=self.world_size,
                    resources=dict(self.resources),
                    runtime_env=self.runtime_env).to_header())
            if reply.get("granted"):
                break
            if reply.get("stale_epoch"):
                # another incarnation of this gang_id advanced past us
                # — unreachable through the public API (epochs only
                # move through this handle) but fenced anyway
                raise exc.GangPlacementError(
                    f"gang epoch {epoch} is stale (raylet has "
                    f"{reply.get('current_epoch')})")
        else:
            raise exc.GangPlacementError(
                f"could not book {self.world_size} workers in one "
                f"round after {1 + max(0, cfg.gang_lease_retry_attempts)}"
                f" attempts: {reply.get('reason', 'unknown')}")

        async def _dial(m: dict) -> LeasedWorker:
            conn = await rpc.connect(
                m["worker_address"], peer_name="gang-member",
                timeout=cfg.gang_member_dial_timeout_s)
            lw = LeasedWorker(m["worker_address"], m["lease_id"],
                              m["node_id"], conn, core.raylet_address,
                              m["worker_id"])
            lw.gang = self

            def _on_drop(c, _lw=lw):
                self._member_died(_lw)

            lw.on_drop = _on_drop
            conn.on_disconnect.append(_on_drop)
            return lw

        members = sorted(reply["members"], key=lambda m: m["rank"])
        dials = [asyncio.ensure_future(_dial(m)) for m in members]
        results = await asyncio.gather(*dials, return_exceptions=True)
        failed = [r for r in results if isinstance(r, BaseException)]
        if failed:
            # all-or-nothing extends to adoption: kill-release the
            # whole booking (a member that died before its first dial
            # may be mid-fork wreckage) and close the dials that DID
            # land
            for r in results:
                if isinstance(r, LeasedWorker):
                    await self._close_member(r)
            try:
                await core.raylet_conn.call(
                    "ReleaseGangLease",
                    protocol.ReleaseGangLeaseRequest(
                        gang_id=self.gang_id, epoch=epoch,
                        kill=True).to_header())
            except ConnectionError:
                pass  # raylet gone; owner-liveness watch reclaims
            raise exc.GangPlacementError(
                f"gang member adoption failed: {failed[0]}")
        self.epoch = epoch
        self.broken = False
        self._released = False
        self.members = list(results)
        self._state.workers = list(self.members)
        return self

    def _member_died(self, lw: LeasedWorker) -> None:
        # a dead member invalidates the WHOLE step: in-flight push
        # futures on its conn error out and fail their tasks with
        # WorkerCrashedError (max_retries=0); surviving ranks' results
        # still land, but the epoch fence stops any further steps
        if not self._released:
            self.broken = True

    # -- steps ----------------------------------------------------------

    def run(self, fn, args_per_rank: Optional[Sequence] = None,
            name: Optional[str] = None) -> List[ObjectRef]:
        """Run ``fn`` once per rank, pinned to the gang's members.

        ``args_per_rank[rank]`` (a tuple/list) becomes the call args for
        that rank; with the default None each rank is called as
        ``fn(rank)``. Returns the rank-ordered list of result refs.
        Step tasks run with ``max_retries=0``: a dead member fails its
        slot with WorkerCrashedError and breaks the gang."""
        if args_per_rank is not None and \
                len(args_per_rank) != self.world_size:
            raise ValueError(
                f"args_per_rank has {len(args_per_rank)} entries for a "
                f"{self.world_size}-rank gang")
        # export on the CALLER thread (export_prepickled round-trips
        # the GCS through the sync KV facade, illegal from the loop) —
        # exactly where remote_function does it for pumped tasks
        fn_key, pickled = self.core.function_manager.prepare(fn)
        self.core.function_manager.export_prepickled(fn_key, pickled, fn)
        return self.core._run(
            self._run_step(fn, fn_key, args_per_rank, name))

    async def _run_step(self, fn, fn_key, args_per_rank, name):
        if self._released:
            raise exc.GangBrokenError("gang already released")
        if self.broken:
            raise exc.GangBrokenError(
                f"gang epoch {self.epoch} lost a member; reform() "
                f"books a fresh incarnation")
        core = self.core
        per_rank = [list(args_per_rank[r]) if args_per_rank is not None
                    else [r] for r in range(self.world_size)]
        # owned-arg readiness, as _submit_when_ready does for pumped
        # tasks (borrowed args resolve at the executing worker)
        for args in per_rank:
            for a in args:
                if isinstance(a, ObjectRef) and \
                        core.reference_counter.is_owned(a.object_id):
                    try:
                        await core.memory_store.get(a.object_id)
                    # raylint: disable=exception-hygiene — errored deps surface at the executing worker
                    except Exception:
                        pass
        return core._submit_gang_step(
            self, fn_key, name or getattr(fn, "__name__", "gang_step"),
            per_rank)

    # -- teardown / re-formation ---------------------------------------

    async def _close_member(self, lw: LeasedWorker) -> None:
        if lw.on_drop is not None and not lw.conn.closed and \
                lw.on_drop in lw.conn.on_disconnect:
            lw.conn.on_disconnect.remove(lw.on_drop)
        if not lw.conn.closed:
            await lw.conn.close()

    def reform(self) -> "SpmdGang":
        """Book a fresh incarnation at epoch+1. The raylet releases the
        previous incarnation's bookings first (kill-releasing broken
        members — they may be mid-step wreckage) and fences every stale
        push from the old epoch."""
        return self.core._run(self._reform_async())

    async def _reform_async(self) -> "SpmdGang":
        for lw in self.members:
            await self._close_member(lw)
        self.members = []
        self._state.workers = []
        return await self._form()

    def release(self) -> None:
        """Tear the gang down: one ReleaseGangLease to the home raylet
        releases every member cluster-wide (kill when broken — a
        possibly mid-step worker must not be recycled as idle)."""
        self.core._run(self._release_async())

    shutdown = release

    async def _release_async(self) -> None:
        if self._released:
            return
        self._released = True
        for lw in self.members:
            await self._close_member(lw)
        try:
            await self.core.raylet_conn.call(
                "ReleaseGangLease",
                protocol.ReleaseGangLeaseRequest(
                    gang_id=self.gang_id, epoch=self.epoch,
                    kill=self.broken).to_header())
        except ConnectionError:
            pass  # raylet gone; its teardown reclaimed everything
        self.members = []
        self._state.workers = []


class CoreWorker:
    def __init__(self, mode: str, config: RayTpuConfig,
                 gcs_address: str, raylet_address: str,
                 session_dir: str, job_id: bytes = b"",
                 worker_id: bytes = b"", node_id: bytes = b"",
                 loop: Optional[asyncio.AbstractEventLoop] = None,
                 log_to_driver: bool = False):
        assert mode in ("driver", "worker")
        if os.environ.get("RAY_TPU_TRACE", "") not in ("", "0"):
            # same truthiness predicate as tracing.enabled()
            # honor env-var-only activation (tracing.py's documented
            # contract): importing registers the module into
            # _tracing_mod, arming _trace_ctx without putting
            # os.environ on the hot path
            from ray_tpu.util import tracing  # noqa: F401
        self.mode = mode
        self.log_to_driver = log_to_driver
        self.config = config
        self.gcs_address = gcs_address
        self.raylet_address = raylet_address
        self.session_dir = session_dir
        self.worker_id = worker_id or WorkerID.from_random().binary()
        self.node_id = node_id
        self.job_id = job_id

        if loop is None:
            self._loop_thread = rpc.EventLoopThread(f"rtpu-{mode}-io")
            self.loop = self._loop_thread.loop
        else:
            self._loop_thread = None
            self.loop = loop

        # Warm the native copy tier at process boot (copy_into itself
        # never builds — a cold-cache compile must not reach any event
        # loop; here we are still on the constructing thread).
        from ray_tpu._private import native as _native
        _native.load_fastpath()

        self.memory_store = MemoryStore()
        self.reference_counter = ReferenceCounter()
        self.serialization_context = SerializationContext()
        self.serialization_context.set_object_ref_reducer(
            self._serialize_ref, self._deserialize_ref)
        self.serialization_context.set_actor_handle_reducer(
            self._serialize_actor_handle, self._deserialize_actor_handle)

        self.pending_tasks: Dict[bytes, PendingTaskEntry] = {}
        self.scheduling_keys: Dict[int, SchedulingKeyState] = {}
        self.actor_queues: Dict[bytes, ActorQueueState] = {}
        self.actor_handles: Dict[bytes, Any] = {}

        self.gcs_conn: Optional[rpc.Connection] = None
        self._gcs_reconnect_lock = asyncio.Lock()
        self.raylet_conn: Optional[rpc.Connection] = None
        # worker_id -> (monotonic ts, structured WORKER_OOM cause),
        # recorded by a raylet's WorkerOOMKilled call before its memory
        # watchdog kills a worker this owner leased (the ack-then-kill
        # ordering means the cause is here before the worker socket
        # drops). Bounded, and time-bounded at lookup: a kill the
        # raylet's re-grant guard ABORTED leaves an entry with no
        # matching death — without the age check, that worker's later
        # unrelated crash would be misclassified as an OOM kill.
        self._oom_worker_kills: Dict[bytes, tuple] = {}
        self._server = rpc.RpcServer(self._owner_handlers(), name=f"cw-{mode}")
        self.address = ""
        self._owner_conns: Dict[str, rpc.Connection] = {}
        # Cached control-plane connections to REMOTE raylets hosting ring
        # collective members (the local raylet rides raylet_conn). Keyed
        # by raylet address; closed with the owner connections.
        self._ring_conns: Dict[str, rpc.Connection] = {}
        self._attached: Dict[ObjectID, AttachedObject] = {}
        self._attached_lock = threading.Lock()
        self.function_manager = FunctionManager(self._kv_put_sync, self._kv_get_sync)
        # runtime envs: job-level default + per-driver upload dedupe cache
        self.job_runtime_env: Optional[Dict] = None
        self._uploaded_working_dirs: Dict[str, str] = {}
        self._task_counter = itertools.count(1)
        self._put_counter = itertools.count(1)
        # Submission batching: the caller thread appends specs here and
        # schedules ONE loop wakeup per burst instead of one
        # run_coroutine_threadsafe per task (the round-1 hot-path cost).
        self._submit_buffer: deque = deque()
        self._submit_scheduled = False
        # Batched local-ref decrefs: ObjectRef.__del__ is a per-object
        # hot path (dropping a list of 1M refs); it appends here
        # (GIL-atomic) and the loop drains under ONE lock round trip.
        self._decref_buffer: deque = deque()
        self._decref_scheduled = False
        self._current_task_id: bytes = b""
        # Cached cluster node table for locality lease targeting.
        self._node_table: Dict[bytes, str] = {}
        self._node_table_ts = -1e9
        self._shutdown = False
        self.task_executor = None   # set in worker mode by worker_main
        # Task-lifecycle recorder (task_events.py): owner-side
        # transitions land here and flush with the metrics report loop.
        # The executor (worker mode) records RUNNING/FINISHED/FAILED
        # into the same buffer.
        self.task_events = TaskEventBuffer(
            config.task_events_buffer_size,
            enabled=config.task_events_enabled)
        # Object-lifecycle recorder (object_events.py): the reference
        # counter stamps CREATED/BORROWED/CONTAINED/location/
        # OUT_OF_SCOPE transitions into this buffer; flushed with the
        # same metrics-report cadence (AddObjectEvents).
        self.object_events = ObjectEventBuffer(
            config.object_events_buffer_size,
            enabled=config.object_events_enabled)
        self.reference_counter.events = self.object_events
        # Cluster-event plane (events.py): this process's emitter feeds
        # a bounded buffer flushed on the metrics-report cadence
        # (AddClusterEvents) — driver/worker-side structured events
        # reach the GCS table without their own RPC.
        from ray_tpu._private.events import ClusterEventBuffer, EventEmitter
        self.cluster_events = ClusterEventBuffer(
            getattr(config, "cluster_event_buffer_size", 4096))
        self.events = EventEmitter(
            mode, os.path.join(session_dir, "logs")
            if config.event_log_enabled else None,
            buffer=self.cluster_events)
        # Control-plane flight recorder config for this process
        # (per-method RPC telemetry + loop-lag probe, rpc.py).
        rpc.telemetry.configure(config)
        self._task_events: List[dict] = []
        self._profile_flush_task = None
        self._metrics_report_task = None
        # Set by the actor module so the core worker can build handles
        # without import cycles.
        self._actor_handle_factory: Optional[Callable] = None

        self.stats = {"tasks_submitted": 0, "tasks_finished": 0,
                      "tasks_retried": 0, "tasks_stolen": 0,
                      "actor_tasks_submitted": 0,
                      "puts": 0, "gets": 0,
                      # streaming leases: per-task dispatch split (the
                      # owner-side credit hit-rate) + window traffic
                      "credit_dispatches": 0, "legacy_dispatches": 0,
                      "lease_credits_received": 0,
                      "lease_credits_activated": 0,
                      "lease_credits_revoked": 0}
        # lease_ids of credits whose worker connect is still in flight:
        # a concurrent RevokeLeaseCredits must not report these as
        # released (the raylet would re-lease the worker under us)
        self._activating_credits: set = set()

        # Native fused submit path (cpp/fastpath.c), created lazily on
        # the first template submission (needs self.address, i.e. post-
        # connect). None until then; False-y sentinel on init failure.
        self._fast_ctx = None
        self._fast_ctx_failed = False

    # ------------------------------------------------------------ lifecycle

    def connect(self):
        self._run(self._connect_async())

    async def _connect_async(self):
        sock_dir = os.path.join(self.session_dir, "sockets")
        os.makedirs(sock_dir, exist_ok=True)
        self.address = await self._server.listen(
            f"unix://{sock_dir}/cw-{WorkerID(self.worker_id).hex()[:12]}")
        self.reference_counter.own_address = self.address
        self.reference_counter.add_release_callback(self._on_object_released)
        self.reference_counter.add_borrow_removed_callback(self._on_borrow_removed)
        self.gcs_conn = await rpc.connect(
            self.gcs_address,
            handlers={"Published": self._handle_published},
            peer_name="gcs")
        if self.mode == "driver":
            reply, _ = await self.gcs_conn.call("AddJob", {
                "driver_address": self.address})
            self.job_id = reply["job_id"]
        # Share the server's handler dict: the raylet pushes CreateActor /
        # PushTask over this connection (workers), and the TaskExecutor
        # registers its handlers into the same dict.
        self.raylet_conn = await rpc.connect(
            self.raylet_address, handlers=self._server.handlers,
            peer_name="raylet")
        await self.gcs_conn.call("Subscribe", {"channel": "ACTOR"})
        if self.mode == "driver" and self.log_to_driver:
            await self.gcs_conn.call("Subscribe", {"channel": "LOGS"})
        self._driver_task_id = TaskID.for_driver(JobID(self.job_id)) \
            if self.job_id else TaskID.from_random()
        # cached lineage prefix for the raw-bytes submit hot path
        self._task_lineage_prefix = \
            self._driver_task_id.binary()[:ACTOR_ID_SIZE]
        if self.config.profiling_enabled:
            self._profile_flush_task = self.loop.create_task(
                self._profile_flush_loop())
        # Claim the process's shipper role BEFORE the first report
        # period elapses: an in-process raylet's early heartbeats would
        # otherwise ship the shared process telemetry/registry under a
        # second (node-) reporter id for the first period.
        from ray_tpu._private import metrics as metrics_mod
        metrics_mod.mark_core_reporter()
        self._metrics_report_task = self.loop.create_task(
            self._metrics_report_loop())

    def shutdown(self):
        if self._shutdown:
            return
        self._shutdown = True
        try:
            self._run(self._shutdown_async(), timeout=5)
        except Exception:
            logger.debug("async shutdown incomplete", exc_info=True)
        if self._loop_thread is not None:
            self._loop_thread.stop()

    async def _shutdown_async(self):
        if self._profile_flush_task:
            self._profile_flush_task.cancel()
        if getattr(self, "_metrics_report_task", None):
            self._metrics_report_task.cancel()
        if self.gcs_conn and not self.gcs_conn.closed:
            # last task-event flush: terminal transitions observed since
            # the previous periodic flush should outlive this process
            # independent try blocks: a hung task-event flush must not
            # also cost the object-event batch (and vice versa)
            try:
                await asyncio.wait_for(self._flush_task_events(), timeout=2)
            except (asyncio.TimeoutError, ConnectionError):
                pass
            try:
                await asyncio.wait_for(self._flush_object_events(),
                                       timeout=2)
            except Exception:  # noqa: BLE001 — shutdown must reach MarkJobFinished
                logger.debug("object-event flush at shutdown failed",
                             exc_info=True)
            try:
                await asyncio.wait_for(self._flush_cluster_events(),
                                       timeout=2)
            except Exception:  # noqa: BLE001 — shutdown must reach MarkJobFinished
                logger.debug("cluster-event flush at shutdown failed",
                             exc_info=True)
        if self.mode == "driver" and self.gcs_conn and not self.gcs_conn.closed:
            try:
                await self.gcs_conn.call("MarkJobFinished",
                                         {"job_id": self.job_id}, timeout=2)
            except Exception:
                logger.debug("MarkJobFinished at shutdown failed",
                             exc_info=True)
        for key_state in self.scheduling_keys.values():
            for lw in key_state.workers:
                try:
                    await self._return_lease(lw)
                except Exception:
                    logger.debug("lease return at shutdown failed",
                                 exc_info=True)
        await self._server.close()
        for conn in list(self._owner_conns.values()):
            await conn.close()
        for conn in list(self._ring_conns.values()):
            await conn.close()
        if self.gcs_conn:
            await self.gcs_conn.close()
        if self.raylet_conn:
            await self.raylet_conn.close()
        with self._attached_lock:
            for att in self._attached.values():
                att.close()
            self._attached.clear()

    def _run(self, coro, timeout=None):
        """Run a coroutine on the IO loop from any thread (never from the
        loop thread itself — that would deadlock)."""
        try:
            running = asyncio.get_running_loop()
        except RuntimeError:
            running = None
        if running is self.loop:
            coro.close()
            raise RuntimeError("sync API called from the IO loop thread")
        fut = asyncio.run_coroutine_threadsafe(coro, self.loop)
        return fut.result(timeout)

    async def _gcs_call(self, method: str, header=None, bufs=(),
                        timeout=None):
        """GCS RPC with transparent redial: a restarted GCS (journal
        replay) drops every connection; callers should not fail for that
        (reference: workers re-resolve the GCS address on failover,
        core_worker/gcs_server_address_updater.cc). Retried methods must
        be idempotent server-side (RegisterActor dedupes by actor id).
        Redial attempts repeat within ``gcs_reconnect_timeout_s``: a
        SIGKILLed GCS's listen socket can still accept for a beat, so a
        single reconnect may land on the dying process and lose its
        retried call too — keep going until the budget, not one shot."""
        try:
            return await self.gcs_conn.call(method, header, bufs=bufs,
                                            timeout=timeout)
        except ConnectionError:
            if self._shutdown:
                raise
            loop = asyncio.get_running_loop()
            deadline = loop.time() + max(
                self.config.gcs_reconnect_timeout_s, 0.1)
            while True:
                try:
                    # One reconnect at a time: concurrent failures reuse
                    # the winner's connection instead of each dialing
                    # (and double-subscribing) their own.
                    async with self._gcs_reconnect_lock:
                        if self.gcs_conn is None or self.gcs_conn.closed:
                            conn = await rpc.connect(
                                self.gcs_address,
                                handlers={
                                    "Published": self._handle_published},
                                peer_name="gcs")
                            await conn.call("Subscribe",
                                            {"channel": "ACTOR"})
                            self.gcs_conn = conn
                    return await self.gcs_conn.call(method, header,
                                                    bufs=bufs,
                                                    timeout=timeout)
                except ConnectionError:
                    if self._shutdown or loop.time() >= deadline:
                        raise
                    await asyncio.sleep(0.1)

    # ------------------------------------------------------------ KV helpers

    def gcs_call_sync(self, method: str, header: dict) -> dict:
        """Generic blocking GCS RPC from API threads (state dumps)."""
        reply, _ = self._run(self._gcs_call(method, header))
        return reply

    def _kv_put_sync(self, key: bytes, value: bytes):
        self._run(self._gcs_call(
            "KVPut", protocol.KVPutRequest(key=key).to_header(),
            bufs=[value]))

    def _kv_get_sync(self, key: bytes) -> Optional[bytes]:
        header, bufs = self._run(self._gcs_call(
            "KVGet", protocol.KVGetRequest(key=key).to_header()))
        return bufs[0] if header.get("found") else None

    # --------------------------------------------------------- ref reducers

    def _serialize_ref(self, ref: ObjectRef):
        owner = ref.owner_address or \
            self.reference_counter.owner_address_of(ref.object_id) or self.address
        return (ref.object_id.binary(), owner)

    def _deserialize_ref(self, state):
        oid_b, owner = state
        oid = ObjectID(oid_b)
        # Record the borrow BEFORE constructing the ObjectRef: the ctor
        # increments local_refs, which would defeat add_borrowed_object's
        # first-borrow detection and the AddBorrower RPC would never fire.
        if owner and owner != self.address:
            first = self.reference_counter.add_borrowed_object(oid, owner)
            if first:
                self._fire_and_forget(self._notify_add_borrower(oid, owner))
        return ObjectRef(oid, owner_address=owner, worker=self)

    def _serialize_actor_handle(self, handle):
        return handle._serialization_state()

    def _deserialize_actor_handle(self, state):
        if self._actor_handle_factory is None:
            raise RuntimeError("actor handle factory not registered")
        return self._actor_handle_factory(self, state)

    async def _notify_add_borrower(self, oid: ObjectID, owner: str):
        try:
            conn = await self._get_owner_conn(owner)
            await conn.call("AddBorrower", {"object_id": oid.binary(),
                                            "borrower": self.address})
        except ConnectionError:
            pass

    def _fire_and_forget(self, coro):
        if self.loop.is_running():
            asyncio.run_coroutine_threadsafe(coro, self.loop)
        else:
            coro.close()  # interpreter teardown: drop without a warning

    def kv_put_nowait(self, key: bytes, value: bytes) -> None:
        """Fire-and-forget internal-KV put (tracing/telemetry export —
        must never block or fail the caller's thread)."""
        self._fire_and_forget(self._gcs_call(
            "KVPut",
            protocol.KVPutRequest(key=key, overwrite=True).to_header(),
            bufs=[value]))

    async def _get_owner_conn(self, address: str) -> rpc.Connection:
        if address == self.address:
            raise RuntimeError("attempted self-connection for owner RPC")
        conn = self._owner_conns.get(address)
        if conn is None or conn.closed:
            # Share the server's handler dict (same as raylet_conn): a
            # REMOTE raylet this owner leased from must be able to call
            # back over this pipe — e.g. WorkerOOMKilled before a
            # watchdog kill, which classifies the death as a retriable
            # OutOfMemoryError instead of a generic worker crash.
            conn = await rpc.connect(address,
                                     handlers=self._server.handlers,
                                     peer_name=f"owner@{address}")
            self._owner_conns[address] = conn
        return conn

    # --------------------------------------------------------- owner services

    def _owner_handlers(self):
        handlers = {
            "GetObject": self._handle_get_object,
            "GetObjectLocations": self._handle_get_object_locations,
            "AddObjectLocation": self._handle_add_object_location,
            "AddBorrower": self._handle_add_borrower,
            "RemoveBorrower": self._handle_remove_borrower,
            "WorkerOOMKilled": self._handle_worker_oom_killed,
            "ProbeObjectLiveness": self._handle_probe_object_liveness,
            "GrantLeaseCredits": self._handle_grant_lease_credits,
            "RevokeLeaseCredits": self._handle_revoke_lease_credits,
            "Ping": self._handle_ping,
        }
        return handlers

    # ------------------------------------------------- streaming leases

    async def _handle_grant_lease_credits(self, conn, header, bufs):
        """Raylet push: pre-granted worker slots for one scheduling
        class plus the window target. Each credit is activated (worker
        socket dialed) EAGERLY here, off the submit path — by the time
        the pump dispatches against it there is zero control-plane work
        left, which is the whole point of the stream."""
        if self._shutdown:
            return {}
        req = protocol.GrantLeaseCreditsRequest.from_header(header)
        sc = req.sched_class
        state = self.scheduling_keys.get(sc)
        if state is None:
            state = self.scheduling_keys[sc] = SchedulingKeyState(
                req.get("resources") or {})
        if req.raylet_address == self.raylet_address:
            # Only the HOME raylet's window sizes the pump's stream
            # floor and legacy-band clamp: in spillback clusters a
            # remote raylet pushes its own (differently-sized) window
            # each beat, and last-push-wins would flap the breadth
            # every heartbeat. Remote credits still activate below —
            # they just don't steer the local policy.
            state.credit_target = int(req.window_target)
            state.cluster_slots = int(req.get(
                "cluster_slots", req.window_target))
        for cr in req.get("credits", ()):
            self.stats["lease_credits_received"] += 1
            self._activating_credits.add(cr["lease_id"])
            state.activating += 1
            rpc.spawn_logged(
                self._activate_credit(sc, state, cr,
                                      req.raylet_address),
                "worker-activate-credit")
        return {}

    async def _activate_credit(self, sc: int, state: SchedulingKeyState,
                               cr: dict, raylet_address: str) -> None:
        lid = cr["lease_id"]
        try:
            try:
                wconn = await rpc.connect(cr["worker_address"],
                                          peer_name="leased-worker")
            except ConnectionError:
                state.activating = max(0, state.activating - 1)
                if state.queue:
                    # the expected breadth shrank: re-evaluate (the
                    # pump may now fire a legacy fallback request)
                    self._pump_scheduling_key(sc, state)
                # dead worker (or its whole node): hand the slot back
                # so it isn't parked; a dead raylet makes this a no-op
                # and its conn-drop already reclaimed everything.
                # worker_died=True: the dial failed, so this is a death
                # report, NOT a voluntary return — it must neither
                # decay the window's demand (the backlog is still
                # there) nor mark a dead worker idle for re-grant.
                self._activating_credits.discard(lid)
                try:
                    if raylet_address == self.raylet_address:
                        rconn = self.raylet_conn
                    else:
                        rconn = await self._get_owner_conn(raylet_address)
                    await rconn.call(
                        "ReturnWorker",
                        protocol.ReturnWorkerRequest(
                            lease_id=lid,
                            worker_died=True).to_header())
                except (ConnectionError, RuntimeError):
                    pass
                return
            if lid not in self._activating_credits or self._shutdown:
                # revoked (or shutting down) while the dial was in
                # flight: don't adopt a worker the raylet reclaimed
                state.activating = max(0, state.activating - 1)
                await wconn.close()
                return
            state.activating = max(0, state.activating - 1)
            lw = LeasedWorker(cr["worker_address"], lid, cr["node_id"],
                              wconn, raylet_address, cr["worker_id"])
            lw.via_credit = True
            state.workers.append(lw)
            state.last_grant_ts = time.monotonic()

            def _on_drop(c, _lw=lw):
                self._on_leased_worker_died(sc, state, _lw)

            lw.on_drop = _on_drop
            wconn.on_disconnect.append(_on_drop)
            self.stats["lease_credits_activated"] += 1
            if state.queue:
                self._pump_scheduling_key(sc, state)
            elif not self._try_steal(sc, state):
                self._schedule_idle_return(sc, state, lw)
        finally:
            self._activating_credits.discard(lid)

    async def _handle_revoke_lease_credits(self, conn, header, bufs):
        """Raylet call: give back up to ``max_release`` of the listed
        credits. Only credits NOT in use are relinquished — in-flight
        batches finish and busy workers stay leased (the raylet
        re-offers on a later beat). Under ``memory_pressure`` idle
        credits are released even when this class still has backlog:
        the queue falls back to legacy requests, which the pressured
        raylet answers with spill/retry-later — draining work off the
        hot node is the recovery, so the owner must not cling to its
        slots there. Ids we never saw (a chaos-dropped grant push) or
        already returned are confirmed released so the raylet's ledger
        reconciles."""
        req = protocol.RevokeLeaseCreditsRequest.from_header(header)
        ids = set(req.lease_ids)
        try:
            max_release = int(req.get("max_release", len(ids)))
        except (TypeError, ValueError):
            max_release = len(ids)
        aggressive = req.get("reason") == "memory_pressure"
        released: List[int] = []
        seen: set = set()
        # snapshot: the awaited conn.close below yields to the loop,
        # where a first-submit of a new remote function may create a
        # scheduling class mid-iteration
        for sc, state in list(self.scheduling_keys.items()):
            for lw in list(state.workers):
                if lw.lease_id not in ids or not lw.via_credit:
                    continue
                seen.add(lw.lease_id)
                if len(released) >= max_release or lw.inflight > 0:
                    continue
                if state.queue and not aggressive:
                    continue  # about to be used; keep it
                if not aggressive and lw.idle_timer is not None:
                    # inside its idle-keepalive grace: the keepalive's
                    # own ReturnWorker (or the next burst) decides,
                    # exactly like a legacy lease — the raylet's
                    # periodic reconcile offer must not defeat
                    # warm-lease reuse for sync-loop callers
                    continue
                state.workers.remove(lw)
                if lw.idle_timer is not None:
                    lw.idle_timer.cancel()
                    lw.idle_timer = None
                # unregister the death watch FIRST: this close is a
                # revocation, not a worker death — firing the retry
                # path would double-return the lease as worker_died
                # and strand a healthy worker in the LEASED state
                if lw.on_drop is not None and \
                        lw.on_drop in lw.conn.on_disconnect:
                    lw.conn.on_disconnect.remove(lw.on_drop)
                await lw.conn.close()
                released.append(lw.lease_id)
        for lid in ids - seen:
            if lid not in self._activating_credits and \
                    len(released) < max_release:
                released.append(lid)
        self.stats["lease_credits_revoked"] += len(released)
        return protocol.RevokeLeaseCreditsReply(
            released=released).to_header()

    async def _handle_worker_oom_killed(self, conn, header, bufs):
        """Raylet push: the node memory watchdog is killing a worker
        this owner leased. Recording the cause BEFORE the worker socket
        drops lets _retry_or_fail_after_worker_death classify the death
        as a retriable OutOfMemoryError (dedicated task_oom_retries
        budget) instead of a generic worker crash."""
        cause = header.get("cause") or {"kind": "WORKER_OOM"}
        self._oom_worker_kills[header["worker_id"]] = \
            (time.monotonic(), cause)
        while len(self._oom_worker_kills) > 64:
            self._oom_worker_kills.pop(
                next(iter(self._oom_worker_kills)))
        return {}

    async def _handle_ping(self, conn, header, bufs):
        return {"ok": True, "mode": self.mode}

    async def _handle_probe_object_liveness(self, conn, header, bufs):
        """Raylet leak-detector probe: for each object id, does this
        owner still hold ANY reference (local/submitted/borrowed)?
        ``False`` means the owner released it — a store still holding
        its segment missed the FreeObject and is leaking. One batched
        call per (raylet, owner) per sweep; has_reference is a
        GIL-atomic dict probe, so a large batch is cheap."""
        has = self.reference_counter.has_reference
        return {"live": [bool(has(ObjectID(b)))
                         for b in header.get("object_ids", ())]}

    async def _handle_get_object(self, conn, header, bufs):
        oid = ObjectID(header["object_id"])
        timeout = header.get("timeout", 60.0)
        try:
            obj = await self.memory_store.get(oid, timeout=timeout)
        except asyncio.TimeoutError:
            return {"found": False}
        if obj is IN_PLASMA:
            return {"found": True, "in_plasma": True,
                    "locations": sorted(
                        self.reference_counter.get_locations(oid))}
        assert isinstance(obj, SerializedObject)
        # SNAPSHOT: a locally-put small value's frames alias the
        # caller's buffers, which user code may mutate while the
        # coalesced reply flush is pending — live views could send
        # torn bytes. Small objects only (large ones are IN_PLASMA).
        meta, frames = obj.to_wire()
        return {"found": True, "in_plasma": False, "metadata": meta,
                "contained": [r.binary() for r in obj.contained_refs]}, frames

    async def _handle_get_object_locations(self, conn, header, bufs):
        oid = ObjectID(header["object_id"])
        return {"locations": sorted(self.reference_counter.get_locations(oid))}

    async def _handle_add_object_location(self, conn, header, bufs):
        """A raylet pulled a replica: keep the owner's location index
        complete so release-time frees reach every copy. Replies
        ok=False if the ref was already released (the report lost the
        race with the final release) so the raylet frees its copy."""
        ok = self.reference_counter.add_location_if_tracked(
            ObjectID(header["object_id"]), header["node_id"])
        return {"ok": ok}

    async def _handle_add_borrower(self, conn, header, bufs):
        self.reference_counter.add_borrower(
            ObjectID(header["object_id"]), header["borrower"])
        return {"ok": True}

    async def _handle_remove_borrower(self, conn, header, bufs):
        self.reference_counter.remove_borrower(
            ObjectID(header["object_id"]), header["borrower"])
        return {"ok": True}

    # -------------------------------------------------------- release paths

    def _on_object_released(self, oid: ObjectID, record):
        """Last reference anywhere dropped: delete the value everywhere.
        ``record`` is the popped Reference — the live table no longer has
        this id, so ownership/locations must come from the record."""
        self.memory_store.delete(oid)
        with self._attached_lock:
            att = self._attached.pop(oid, None)
        if att is not None:
            att.close()
        if record.owned:
            self._release_lineage(oid)
            if record.in_plasma and record.pinned_lineage and \
                    self.object_events.enabled:
                # lineage-pin transition, plasma returns only (a 1M
                # drain of small returns must not flood the buffer):
                # the creating task's lineage retention just ended
                self.object_events.record(
                    oid.binary(), LINEAGE_RELEASED,
                    {"task": oid.binary()[:TASK_ID_SIZE].hex()})
        if record.owned and record.in_plasma:
            locations = sorted(record.locations or ())
            self._fire_and_forget(self._free_remote(oid, locations))

    def _release_lineage(self, oid: ObjectID) -> None:
        """Last reference to an owned return object dropped: release the
        creating task's lineage once NO return of that task can still
        need reconstruction (reference:
        TaskManager::RemoveLineageReference,
        src/ray/core_worker/task_manager.cc). PendingTaskEntry's
        ``lineage_pinned`` is the lifecycle flag: False = in flight,
        True = completed + retained only for lineage, None = in flight
        but all returns already dead (completion drops the entry)."""
        me = oid.binary()
        tid_b = me[:TASK_ID_SIZE]  # release path is per-call hot
        entry = self.pending_tasks.get(tid_b)
        if entry is None:
            return
        if len(entry.return_ids) > 1:
            for rid in entry.return_ids:
                if rid.binary() != me and \
                        self.reference_counter.has_reference(rid):
                    return  # a sibling return is still reachable
        if entry.lineage_pinned:
            self.pending_tasks.pop(tid_b, None)
        elif entry.lineage_pinned is False:
            entry.lineage_pinned = None

    async def _free_remote(self, oid: ObjectID, locations):
        # Primary copy may live on remote nodes too: the local raylet frees
        # its own copy and forwards FreeObject to every listed location
        # (reference: ReferenceCounter release → plasma delete on all nodes).
        try:
            if self.raylet_conn and not self.raylet_conn.closed:
                await self.raylet_conn.call("FreeObject", {
                    "object_id": oid.binary(),
                    "locations": sorted(locations) if locations else []})
        except ConnectionError:
            pass

    def _on_borrow_removed(self, oid: ObjectID, owner_address: str):
        async def _notify():
            try:
                conn = await self._get_owner_conn(owner_address)
                await conn.call("RemoveBorrower", {
                    "object_id": oid.binary(), "borrower": self.address})
            except (ConnectionError, RuntimeError):
                pass
        self._fire_and_forget(_notify())

    # ---------------------------------------------------------------- put

    def put(self, value: Any, _owner_ref: Optional[ObjectRef] = None) -> ObjectRef:
        serialized = self.serialization_context.serialize(value)
        oid = self._next_put_id()
        self.stats["puts"] += 1
        if serialized.total_bytes() <= self.config.max_direct_call_object_size:
            # Small object: entirely in-process — no IO-loop round trip.
            self.reference_counter.add_owned_with_local_ref(oid)
            if serialized.contained_refs:
                self.reference_counter.add_contained_refs(
                    oid, serialized.contained_refs)
            self.memory_store.put(oid, serialized)
            return ObjectRef(oid, owner_address=self.address, worker=self,
                             call_site="put", skip_adding_local_ref=True)
        self._run(self._put_serialized(oid, serialized))
        return ObjectRef(oid, owner_address=self.address, worker=self,
                         call_site="put")

    def put_async(self, value: Any):
        """Non-blocking put for async-actor callers — the HTTP proxy's
        zero-copy ingress. ``put`` blocks its calling thread on the IO
        loop's seal round trip, which inside an async actor would stall
        the user loop and every other request coroutine on it; here the
        serialize happens on the calling thread (bytes bodies are
        META_RAW: no copy) and the AllocSegment-lease write + seal are
        scheduled onto the IO loop. Returns ``(ref, done)`` where
        ``done`` is a concurrent.futures.Future the caller must await
        (``asyncio.wrap_future``) before shipping the ref — a failed
        seal (store full) surfaces there, typed."""
        serialized = self.serialization_context.serialize(value)
        oid = self._next_put_id()
        self.stats["puts"] += 1
        if serialized.total_bytes() <= \
                self.config.max_direct_call_object_size:
            self.reference_counter.add_owned_with_local_ref(oid)
            if serialized.contained_refs:
                self.reference_counter.add_contained_refs(
                    oid, serialized.contained_refs)
            self.memory_store.put(oid, serialized)
            done: "concurrent.futures.Future" = concurrent.futures.Future()
            done.set_result(None)
            return ObjectRef(oid, owner_address=self.address, worker=self,
                             call_site="put",
                             skip_adding_local_ref=True), done
        done = asyncio.run_coroutine_threadsafe(
            self._put_serialized(oid, serialized), self.loop)
        return ObjectRef(oid, owner_address=self.address, worker=self,
                         call_site="put"), done

    def _next_put_id(self) -> ObjectID:
        # Put ids live in the current task's index space after returns
        # (reference: ObjectID::FromIndex with put_index offset).
        base = TaskID(self._current_task_id) if self._current_task_id \
            else self._driver_task_id
        return base.object_id(100_000 + next(self._put_counter))

    async def _put_serialized(self, oid: ObjectID, serialized: SerializedObject,
                              pin: bool = True):
        self.reference_counter.add_owned_object(oid)
        if serialized.contained_refs:
            self.reference_counter.add_contained_refs(
                oid, serialized.contained_refs)
        if serialized.total_bytes() <= self.config.max_direct_call_object_size:
            self.memory_store.put(oid, serialized)
            return
        segment, size = await self._write_segment_async(serialized)
        # owner_address feeds the raylet's leak detector: the sweep
        # probes this owner's live references against the stored
        # segment (object_events.py).
        reply, _ = await self.raylet_conn.call(
            "SealObject", protocol.SealObjectRequest(
                object_id=oid.binary(), segment=segment, size=size,
                pin=pin, owner_address=self.address).to_header())
        if not reply.get("ok"):
            raise exc.ObjectStoreFullError(
                f"object {oid.hex()} ({size} bytes) does not fit in the store")
        self.reference_counter.add_location(oid, reply["node_id"], size)
        self.memory_store.put(oid, IN_PLASMA)

    async def _write_segment_async(self, serialized: SerializedObject):
        """Zero-copy segment write: lease a recycled warm segment from
        the raylet when one fits (AllocSegment — fresh tmpfs pages are
        the dominant cost of a cold large put), and run the fill in an
        executor thread so the IO loop keeps pumping while the
        (GIL-releasing, striped) memcpy of a huge object runs. The plan
        is computed once and shared with write_segment."""
        plan = plan_segment(serialized)
        size = plan[3]
        alloc = None
        if size >= RECYCLE_MIN_BYTES and self.raylet_conn is not None:
            try:
                reply, _ = await self.raylet_conn.call(
                    "AllocSegment", {"size": size}, timeout=5)
                if reply.get("found"):
                    alloc = (reply["segment"], reply["size"])
            except (ConnectionError, asyncio.TimeoutError):
                pass  # fresh segment below — the lease is an optimization
        try:
            if size >= RECYCLE_MIN_BYTES:
                return await asyncio.get_running_loop().run_in_executor(
                    None, write_segment, serialized, alloc, plan)
            return write_segment(serialized, alloc, plan)
        except BaseException:
            # Seal-or-abort: a failed fill must hand the lease back, or
            # its pages sit in the store's _lent table until the stale
            # sweep (raylint shm-lifecycle). Best-effort one-way push —
            # the sweep remains the backstop if the raylet is gone.
            if alloc is not None and self.raylet_conn is not None \
                    and not self.raylet_conn.closed:
                try:
                    await self.raylet_conn.push(
                        "AbortSegment", {"segment": alloc[0]})
                except (ConnectionError, OSError):
                    pass  # raylet gone; stale-lease sweep reclaims
            raise

    def write_segment_sync(self, serialized: SerializedObject):
        """Blocking variant for executor-pool callers (task returns in
        the worker): same AllocSegment lease + direct-write pipeline."""
        return self._run(self._write_segment_async(serialized))

    # ---------------------------------------------------------------- get

    def get(self, refs: Sequence[ObjectRef], timeout: float | None = None):
        self.stats["gets"] += len(refs)
        # Fast path: every value already local and in-process — deserialize
        # on the caller thread, skipping the IO-loop round trip.
        objs = []
        for ref in refs:
            obj = self.memory_store.get_if_exists(ref.object_id)
            if obj is None or obj is IN_PLASMA:
                objs = None
                break
            objs.append(obj)
        if objs is not None:
            return [self._deserialize_obj(o) for o in objs]
        return self._run(self.get_objects_async(refs, timeout=timeout))

    def get_async(self, ref: ObjectRef) -> asyncio.Future:
        """Future on the IO loop (for ``await ref`` inside async actors)."""
        return asyncio.run_coroutine_threadsafe(
            self._get_one(ref, None), self.loop)

    # concurrent.futures alias used by ObjectRef.future().
    get_future = get_async

    async def get_objects_async(self, refs: Sequence[ObjectRef],
                                timeout: float | None = None):
        deadline = None if timeout is None else time.monotonic() + timeout
        # Bulk barrier: for OWNED ids still in flight, one future covers
        # the whole batch (memory_store.wait_many) instead of a future +
        # wait_for per ref — the 1M-drain get side was ~3us/task of
        # per-ref coroutine machinery.  Non-owned / plasma ids take the
        # per-ref path below as before.
        store_get = self.memory_store.get_if_exists
        is_owned = self.reference_counter.is_owned
        waitable = [ref.object_id for ref in refs
                    if store_get(ref.object_id) is None
                    and is_owned(ref.object_id)]
        if waitable:
            try:
                await self.memory_store.wait_many(
                    waitable,
                    None if deadline is None
                    else max(0.0, deadline - time.monotonic()))
            except asyncio.TimeoutError:
                raise exc.GetTimeoutError(
                    f"get() timed out waiting for "
                    f"{len(waitable)} objects") from None
        out = []
        deserialize = self.serialization_context.deserialize
        for ref in refs:
            obj = store_get(ref.object_id)
            if obj is not None and obj is not IN_PLASMA:
                out.append(deserialize(obj.metadata, obj.frames))
                continue
            remaining = None if deadline is None else deadline - time.monotonic()
            if remaining is not None and remaining <= 0:
                raise exc.GetTimeoutError(
                    f"get() timed out waiting for {ref.hex()}")
            out.append(await self._get_one(ref, remaining))
        return out

    async def _get_one(self, ref: ObjectRef, timeout: float | None):
        oid = ref.object_id
        owned = self.reference_counter.is_owned(oid)
        if owned or self.memory_store.contains(oid):
            try:
                obj = await self.memory_store.get(oid, timeout=timeout)
            except asyncio.TimeoutError:
                raise exc.GetTimeoutError(
                    f"get() timed out waiting for {oid.hex()}") from None
            if obj is IN_PLASMA:
                return await self._get_from_plasma(oid, ref.owner_address)
            return self._deserialize_obj(obj)
        # Borrowed: ask the owner.
        owner = ref.owner_address or self.reference_counter.owner_address_of(oid)
        if not owner:
            raise exc.ObjectLostError(oid.hex(), "no owner known",
                                      cause={"kind": "NO_OWNER"})
        try:
            conn = await self._get_owner_conn(owner)
            header, frames = await conn.call(
                "GetObject", {"object_id": oid.binary(),
                              "timeout": timeout if timeout is not None else 3600.0},
                timeout=timeout)
        except ConnectionError:
            raise exc.ObjectLostError(
                oid.hex(), f"owner {owner} unreachable",
                cause={"kind": "OWNER_UNREACHABLE"}) from None
        except asyncio.TimeoutError:
            raise exc.GetTimeoutError(
                f"get() timed out waiting for {oid.hex()}") from None
        if not header.get("found"):
            raise exc.ObjectLostError(oid.hex(), "owner no longer has object",
                                      cause={"kind": "OWNER_RELEASED"})
        if header.get("in_plasma"):
            return await self._get_from_plasma(oid, owner)
        obj = SerializedObject(header["metadata"], frames)
        # Cache small borrowed values locally for repeat gets.
        self.memory_store.put(oid, obj)
        return self._deserialize_obj(obj)

    async def _get_from_plasma(self, oid: ObjectID, owner_address: str):
        with self._attached_lock:
            att = self._attached.get(oid)
        if att is None:
            reply, _ = await self.raylet_conn.call(
                "EnsureObjectLocal",
                {"object_id": oid.binary(), "owner_address": owner_address})
            if not reply.get("ok") and not reply.get("segment"):
                recovered = await self._try_recover(oid)
                if not recovered:
                    raise exc.ObjectLostError(
                        oid.hex(), reply.get("reason", "pull failed"),
                        cause={"kind": "PULL_FAILED"})
                # The re-executed task may have returned the value (or an
                # error object) inline this time — prefer the memory store
                # over another plasma round trip.
                obj = self.memory_store.get_if_exists(oid)
                if obj is not None and obj is not IN_PLASMA:
                    return self._deserialize_obj(obj)
                reply, _ = await self.raylet_conn.call(
                    "EnsureObjectLocal",
                    {"object_id": oid.binary(), "owner_address": owner_address})
                if not reply.get("segment"):
                    raise exc.ObjectLostError(oid.hex(), "recovery failed",
                                              cause={"kind":
                                                     "RECOVERY_FAILED"})
            att = await asyncio.get_running_loop().run_in_executor(
                None, AttachedObject, reply["segment"])
            with self._attached_lock:
                self._attached[oid] = att
        obj = SerializedObject(att.metadata, att.frames)
        return self._deserialize_obj(obj)

    def _deserialize_obj(self, obj: SerializedObject):
        return self.serialization_context.deserialize(obj.metadata, obj.frames)

    async def _try_recover(self, oid: ObjectID) -> bool:
        """Lineage reconstruction: resubmit the creating task (reference:
        ObjectRecoveryManager, src/ray/core_worker/object_recovery_manager.h)."""
        if not self.config.lineage_reconstruction_enabled:
            return False
        entry = self.pending_tasks.get(oid.task_id().binary())
        if entry is None:
            return False
        logger.info("reconstructing %s by resubmitting task %s",
                    oid.hex()[:16], entry.spec.name)
        # The memory store still holds the stale IN_PLASMA marker, so
        # polling it would return immediately — wait for the actual task
        # completion instead. One shared waiter per entry: concurrent
        # recoveries of sibling returns resubmit the task ONCE and all
        # await the same future (shield: one caller timing out must not
        # cancel it for the rest).
        if entry.recovery_waiter is None:
            entry.recovery_waiter = self.loop.create_future()
            self.stats["tasks_retried"] += 1
            if self.task_events.enabled:
                self.task_events.record(entry.spec.task_id, RETRY,
                                        {"reason": "lineage reconstruction"})
            self._queue_spec(entry.spec)
        waiter = entry.recovery_waiter
        try:
            await asyncio.wait_for(asyncio.shield(waiter), timeout=30.0)
        except asyncio.TimeoutError:
            return False
        # raylint: disable=async-blocking — awaited above: a done future's result() is a non-blocking read
        return bool(waiter.result())

    # ---------------------------------------------------------------- wait

    def wait(self, refs: Sequence[ObjectRef], num_returns: int = 1,
             timeout: float | None = None, fetch_local: bool = True):
        return self._run(self._wait_async(refs, num_returns, timeout))

    async def _wait_async(self, refs, num_returns, timeout):
        pending = list(refs)
        ready: List[ObjectRef] = []

        async def _await_ready(ref):
            try:
                await self._object_available(ref)
            # raylint: disable=exception-hygiene — errored objects count as ready (get will raise)
            except Exception:
                pass
            return ref

        tasks = {asyncio.ensure_future(_await_ready(r)): r for r in pending}
        deadline = None if timeout is None else time.monotonic() + timeout
        try:
            while len(ready) < num_returns and tasks:
                remaining = None if deadline is None else \
                    max(0.0, deadline - time.monotonic())
                done, _ = await asyncio.wait(
                    tasks.keys(), timeout=remaining,
                    return_when=asyncio.FIRST_COMPLETED)
                if not done:
                    break
                for d in done:
                    ready.append(tasks.pop(d))
        finally:
            for t in tasks:
                t.cancel()
        ready_set = set(ready)
        ready_in_order = [r for r in refs if r in ready_set][:num_returns]
        in_order_set = set(ready_in_order)
        not_ready = [r for r in refs if r not in in_order_set]
        return ready_in_order, not_ready

    async def _object_available(self, ref: ObjectRef):
        oid = ref.object_id
        if self.reference_counter.is_owned(oid) or self.memory_store.contains(oid):
            await self.memory_store.get(oid)
            return
        owner = ref.owner_address
        conn = await self._get_owner_conn(owner)
        await conn.call("GetObject", {"object_id": oid.binary(),
                                      "timeout": 3600.0})

    # ------------------------------------------------------- SPMD gangs

    def create_gang(self, world_size: int,
                    resources: Optional[Dict[str, float]] = None,
                    runtime_env: Optional[Dict] = None) -> SpmdGang:
        """Book an SPMD gang: ``world_size`` workers across the cluster
        in ONE all-or-nothing lease round. See :class:`SpmdGang`."""
        if world_size < 1:
            raise ValueError("world_size must be >= 1")
        _check_tpu_demand(resources, actor=False)
        gang = SpmdGang(self, world_size, resources or {"CPU": 1.0},
                        self._resolve_runtime_env(runtime_env))
        return self._run(gang._form())

    def _submit_gang_step(self, gang: SpmdGang, fn_key: str, name: str,
                          per_rank_args: List[list]) -> List[ObjectRef]:
        """Loop thread: register + push one rank-pinned spec per gang
        member. Bypasses the scheduling pump entirely — placement was
        decided at gang formation, so each spec goes straight to its
        rank's worker conn with max_retries=0 (a dead member is a step
        failure, never a silent re-placement)."""
        if self.mode == "driver":
            prefix = self._task_lineage_prefix
        else:
            prefix = (self._current_task_id or
                      self._driver_task_id.binary())[:ACTOR_ID_SIZE]
        refs: List[ObjectRef] = []
        ev = self.task_events
        for rank, (lw, args) in enumerate(
                zip(gang.members, per_rank_args)):
            prepared_args, arg_holds = self._prepare_args(args) \
                if args else ((), None)
            spec = TaskSpec(
                task_id=make_task_id_bytes(prefix), job_id=self.job_id,
                task_type=TASK_NORMAL, name=f"{name}:{rank}",
                fn_key=fn_key, args=prepared_args, num_returns=1,
                resources=dict(gang.resources), max_retries=0,
                retry_exceptions=False, owner_address=self.address,
                owner_worker_id=self.worker_id,
                runtime_env=gang.runtime_env, trace_ctx=_trace_ctx())
            refs.extend(self._register_task(spec, arg_holds))
            if ev.enabled:
                ev.record(spec.task_id, SUBMITTED,
                          {"name": spec.name,
                           "gang": gang.gang_id.hex()[:12],
                           "rank": rank, "epoch": gang.epoch})
            lw.inflight += 1
            self._push_task_batch_nowait(
                spec.scheduling_class, gang._state, lw, [spec])
        return refs

    # ------------------------------------------------ distributed arrays

    def put_sharded(self, array, mesh, spec):
        """Shard ``array`` over ``mesh`` with ``spec`` and put every
        shard as a first-class shm object carrying placement metadata.
        Returns a :class:`~ray_tpu._private.distributed_array
        .DistributedArray`; the shard set is registered as ONE lineage
        unit (ReferenceCounter.add_shard_group) — dropping the handle
        frees every shard segment together or not at all."""
        return self._run(self._put_sharded_async(array, mesh, spec))

    async def _put_sharded_async(self, array, mesh, spec):
        import numpy as np

        from ray_tpu._private import distributed_array as da

        arr = np.ascontiguousarray(array)
        if arr.dtype == object:
            raise TypeError("object-dtype arrays cannot be sharded")
        da._validate(arr.shape, mesh, spec)
        shards: List[da.ShardInfo] = []
        for rank in range(mesh.nranks):
            box = da._rank_box(arr.shape, mesh, spec, rank)
            shard = np.ascontiguousarray(
                arr[tuple(slice(a, b) for a, b in box)])
            serialized = self.serialization_context.serialize(shard)
            _hdr, raw_frames, offsets, _total = plan_segment(serialized)
            if len(raw_frames) != 2:
                raise TypeError(
                    "sharded put requires the 2-frame ndarray wire "
                    f"shape, got {len(raw_frames)} frames")
            oid = self._next_put_id()
            attrs = {"rank": rank, "coords": list(mesh.coords(rank)),
                     "mesh": list(mesh.shape),
                     "array_shape": list(arr.shape)}
            node_id = await self._put_shard_async(oid, serialized, attrs)
            shards.append(da.ShardInfo(
                ref=ObjectRef(oid, owner_address=self.address,
                              worker=self, call_site="put_sharded"),
                rank=rank, node_id=node_id, data_offset=offsets[1],
                nbytes=raw_frames[1].nbytes, shape=shard.shape))
        self.reference_counter.add_shard_group(
            [s.ref.object_id for s in shards])
        return da.DistributedArray(mesh, spec, arr.shape, str(arr.dtype),
                                   shards)

    async def _put_shard_async(self, oid: ObjectID,
                               serialized: SerializedObject,
                               shard_attrs: dict) -> bytes:
        """Always-plasma put for one shard: shard-group lineage and the
        GatherShards collectives need a real segment even when the
        shard is small enough for the in-process store. ``shard_attrs``
        ride the SealObject frame into the SEALED object-plane record
        (state.list_objects() placement surface)."""
        self.reference_counter.add_owned_object(oid)
        segment, size = await self._write_segment_async(serialized)
        reply, _ = await self.raylet_conn.call(
            "SealObject", protocol.SealObjectRequest(
                object_id=oid.binary(), segment=segment, size=size,
                pin=True, owner_address=self.address,
                shard=shard_attrs).to_header())
        if not reply.get("ok"):
            raise exc.ObjectStoreFullError(
                f"shard {oid.hex()} ({size} bytes) does not fit in the "
                f"store")
        self.reference_counter.add_location(oid, reply["node_id"], size)
        self.memory_store.put(oid, IN_PLASMA)
        return reply["node_id"]

    def get_shard(self, darr, rank: int):
        """Fetch one shard's value (zero-copy attach when local)."""
        return self.get([darr.shards[rank].ref])[0]

    def assemble(self, darr):
        """Materialize the full array driver-side by pasting every
        shard into place (pulls remote shards through the normal
        striped pull path)."""
        return self._run(self._assemble_async(darr))

    async def _assemble_async(self, darr):
        import numpy as np

        from ray_tpu._private import distributed_array as da

        out = np.empty(darr.shape, dtype=np.dtype(darr.dtype_str))
        slices = da.shard_slices(darr.shape, darr.mesh, darr.spec)
        for shard in darr.shards:
            val = await self._get_one(shard.ref, None)
            out[slices[shard.rank]] = val
        return out

    def reshard(self, darr, mesh_dst, spec_dst):
        """Re-partition a DistributedArray onto a new mesh/spec. Every
        destination shard is built by ONE GatherShards collective whose
        bulk bytes ride the striped data plane straight into the
        destination segment (zero intermediate copies); on any typed
        collective failure the slice falls back to the naive
        get+assemble+put path (fallback matrix in the README)."""
        return self._run(self._reshard_async(darr, mesh_dst, spec_dst))

    async def _reshard_async(self, darr, mesh_dst, spec_dst):
        import numpy as np

        from ray_tpu._private import distributed_array as da

        dtype = np.dtype(darr.dtype_str)
        da._validate(darr.shape, mesh_dst, spec_dst)
        plan = da.gather_plan(darr.shape, dtype.itemsize, darr.mesh,
                              darr.spec, mesh_dst, spec_dst)
        shards: List[da.ShardInfo] = []
        for dst_rank in range(mesh_dst.nranks):
            shape = da.shard_shape(darr.shape, mesh_dst, spec_dst,
                                   dst_rank)
            attrs = {"rank": dst_rank,
                     "coords": list(mesh_dst.coords(dst_rank)),
                     "mesh": list(mesh_dst.shape),
                     "array_shape": list(darr.shape)}
            sources = [{
                "oid": darr.shards[src_rank].ref.object_id.binary(),
                "node_id": darr.shards[src_rank].node_id,
                "data_offset": darr.shards[src_rank].data_offset,
                "runs": runs,
            } for src_rank, runs in plan[dst_rank]]
            info = await self._gather_shard(shape, dtype, attrs, sources)
            if info is None:
                # fallback matrix: any dest slice the collective can't
                # build routes the WHOLE reshard through the naive path
                # (the already-built slices' refs drop with this list —
                # no group was registered yet, so they free normally)
                del shards
                arr = await self._assemble_async(darr)
                return await self._put_sharded_async(arr, mesh_dst,
                                                     spec_dst)
            shards.append(da.ShardInfo(
                ref=info[0], rank=dst_rank, node_id=info[1],
                data_offset=info[2], nbytes=info[3], shape=shape))
        self.reference_counter.add_shard_group(
            [s.ref.object_id for s in shards])
        return da.DistributedArray(mesh_dst, spec_dst, darr.shape,
                                   darr.dtype_str, shards)

    async def _gather_shard(self, shape, dtype, attrs: dict,
                            sources: List[dict], reduce_spec=None):
        """Ask the local raylet to build one destination shard via
        GatherShards. Returns (ref, node_id, data_offset, nbytes) or
        None on a typed collective failure (caller falls back)."""
        import numpy as np

        from ray_tpu._private import distributed_array as da

        # zeros template: np.zeros never touches the calloc'd pages, so
        # this payload is byte-identical to the real shard's regardless
        # of content — the destination raylet lays the segment out from
        # (meta, payload, data_nbytes) alone
        template = np.zeros(shape, dtype=dtype)
        serialized = self.serialization_context.serialize(template)
        _hdr, raw_frames, offsets, total = plan_segment(serialized)
        if len(raw_frames) != 2:
            return None
        oid = self._next_put_id()
        try:
            reply, _ = await self.raylet_conn.call(
                "GatherShards",
                protocol.GatherShardsRequest(
                    object_id=oid.binary(),
                    meta=serialized.metadata,
                    payload=bytes(raw_frames[0]),
                    data_nbytes=raw_frames[1].nbytes,
                    owner_address=self.address,
                    shard=attrs, sources=sources,
                    reduce=reduce_spec).to_header())
        except ConnectionError:
            reply = {"ok": False, "reason": "raylet unreachable"}
        if not reply.get("ok"):
            # nothing sealed, nothing registered: the minted id simply
            # goes unused and the caller takes the fallback path
            logger.warning("GatherShards for %s failed (%s); falling "
                           "back to naive path", oid.hex()[:16],
                           reply.get("reason"))
            return None
        self.reference_counter.add_owned_object(oid)
        self.reference_counter.add_location(oid, reply["node_id"], total)
        self.memory_store.put(oid, IN_PLASMA)
        ref = ObjectRef(oid, owner_address=self.address, worker=self,
                        call_site="reshard")
        return ref, reply["node_id"], offsets[1], raw_frames[1].nbytes

    # ------------------------------------------------------ ring collectives
    #
    # Driver-orchestrated ring engine. The driver never moves array
    # bytes: it mints one member identity per rank, asks each shard's
    # raylet to stage a full-size accumulator (RingInit), then issues
    # one RingStep RPC per (rank, step) — P concurrent calls per round
    # with a barrier between rounds, so a rank only ever pulls a
    # segment its upstream peer finished in the previous round. Bulk
    # bytes move peer-to-peer over the striped data plane; per-rank
    # wire traffic is 2*(P-1)/P * N for all_reduce (the bandwidth
    # optimum) vs (P-1)*N for the fold path's single sink.

    def _ring_applicable(self, darr) -> bool:
        """Ring engages only when configured, with enough ranks for
        the ring to beat the fold sink (P >= 3), and with a data plane
        to carry the peer-to-peer segment traffic."""
        return (self.config.collective_algorithm == "ring"
                and darr.mesh.nranks >= 3
                and self.config.data_plane_stripes > 0)

    async def _collective_raylet_conn(self, node_id: bytes):
        """Control-plane connection to the raylet hosting one ring
        member (the local raylet for local shards; cached dials for
        remote peers)."""
        if not node_id or node_id == self.node_id:
            return self.raylet_conn
        addr = await self._node_address_of(node_id)
        if not addr:
            raise exc.CollectiveError(
                f"no raylet address for node {node_id.hex()[:12]}")
        if addr == self.raylet_address:
            return self.raylet_conn
        conn = self._ring_conns.get(addr)
        if conn is None or conn.closed:
            conn = await rpc.connect(
                addr, peer_name=f"ring-raylet@{addr}",
                timeout=self.config.rpc_connect_timeout_s)
            self._ring_conns[addr] = conn
        return conn

    async def _ring_abort(self, members, reason: str):
        """Best-effort RingAbort fan-out: every member's raylet drops
        its accumulator segment and serve entry. Idempotent on the
        raylet side, so members that never finished RingInit are fine."""
        async def _one(m):
            try:
                await m["conn"].call(
                    "RingAbort",
                    protocol.RingAbortRequest(
                        member_id=m["mid"],
                        reason=reason[:200]).to_header(),
                    timeout=5)
            except (ConnectionError, OSError, asyncio.TimeoutError):
                pass
        await asyncio.gather(*(_one(m) for m in members),
                             return_exceptions=True)

    async def _ring_collective(self, darr, segments, schedules, sources,
                               op, attrs: dict, call_site: str):
        """Run one ring collective to completion and return the result
        ObjectRef. ``segments`` is the [(seg_off, seg_len)] tiling of
        the result's data frame, ``schedules[rank]`` the per-rank step
        list from distributed_array.ring_*_schedule, ``sources[rank]``
        the GatherShards-style source dict each member seeds its
        accumulator from. Raises CollectiveError after aborting every
        member on any round failure."""
        import numpy as np

        from ray_tpu._private import faultpoints

        nranks = darr.mesh.nranks
        dtype = np.dtype(darr.dtype_str)
        # identical zeros template on every rank: all members share one
        # frame layout, so a peer's absolute segment offset equals our
        # own data_off + seg_off (the pull model depends on this)
        template = np.zeros(darr.shape, dtype=dtype)
        serialized = self.serialization_context.serialize(template)
        _hdr, raw_frames, offsets, total = plan_segment(serialized)
        if len(raw_frames) != 2:
            raise exc.CollectiveError(
                "template does not serialize to the 2-frame ndarray "
                "wire shape")
        data_nbytes = raw_frames[1].nbytes
        oid = self._next_put_id()
        members = []
        try:
            for rank in range(nranks):
                conn = await self._collective_raylet_conn(
                    darr.shards[rank].node_id)
                # member ids ride the put-id minter: 28 bytes, globally
                # unique, disjoint from any sealed object's id
                members.append({"mid": self._next_put_id().binary(),
                                "conn": conn, "data_address": ""})
        except ConnectionError as e:
            raise exc.CollectiveError(
                f"ring peer raylet unreachable: {e}") from e
        meta = serialized.metadata
        payload = bytes(raw_frames[0])
        try:
            inits = await asyncio.gather(*(
                m["conn"].call(
                    "RingInit",
                    protocol.RingInitRequest(
                        collective_id=oid.binary(),
                        member_id=m["mid"], rank=rank, nranks=nranks,
                        object_id=oid.binary(), meta=meta,
                        payload=payload, data_nbytes=data_nbytes,
                        source=sources[rank], dtype=darr.dtype_str,
                        op=op, owner_address=self.address,
                        shard=attrs).to_header())
                for rank, m in enumerate(members)),
                return_exceptions=True)
            for m, rep in zip(members, inits):
                if isinstance(rep, BaseException):
                    raise rep
                reply, _ = rep
                if not reply.get("ok"):
                    raise exc.CollectiveError(
                        f"RingInit failed: {reply.get('reason')}")
                m["data_address"] = reply.get("data_address") or ""
                if not m["data_address"]:
                    raise exc.CollectiveError(
                        "ring peer runs without a data plane")
            nsteps = len(schedules[0])
            for step in range(nsteps):
                if faultpoints.armed:
                    await faultpoints.async_fire(
                        "collective.ring_step", step=step,
                        nsteps=nsteps, collective=oid.hex())
                calls = []
                for rank, m in enumerate(members):
                    st = schedules[rank][step]
                    seg_off, seg_len = segments[st["seg"]]
                    peer = members[st["recv_peer"]]
                    calls.append(m["conn"].call(
                        "RingStep",
                        protocol.RingStepRequest(
                            member_id=m["mid"],
                            peer_member_id=peer["mid"],
                            peer_data_address=peer["data_address"],
                            seg_off=seg_off, seg_len=seg_len,
                            reduce=bool(st["reduce"]),
                            step=step).to_header()))
                replies = await asyncio.gather(*calls,
                                               return_exceptions=True)
                for rep in replies:
                    if isinstance(rep, BaseException):
                        raise rep
                    reply, _ = rep
                    if not reply.get("ok"):
                        raise exc.CollectiveError(
                            f"ring step {step} failed: "
                            f"{reply.get('reason')}")
            fins = await asyncio.gather(*(
                m["conn"].call(
                    "RingFinish",
                    protocol.RingFinishRequest(
                        member_id=m["mid"]).to_header())
                for m in members), return_exceptions=True)
            node_ids = []
            for rep in fins:
                if isinstance(rep, BaseException):
                    raise rep
                reply, _ = rep
                if not reply.get("ok"):
                    raise exc.CollectiveError(
                        f"RingFinish failed: {reply.get('reason')}")
                node_ids.append(reply["node_id"])
        except BaseException as e:
            # abort EVERY member (not just survivors): RingAbort is
            # idempotent and this is the only thing standing between a
            # failed round and P leaked full-size segments
            await self._ring_abort(members, str(e) or type(e).__name__)
            if isinstance(e, (exc.CollectiveError,
                              asyncio.CancelledError)):
                raise
            raise exc.CollectiveError(
                f"ring collective {oid.hex()[:16]} failed: {e!r}") from e
        self.reference_counter.add_owned_object(oid)
        for nid in set(node_ids):
            self.reference_counter.add_location(oid, nid, total)
        self.memory_store.put(oid, IN_PLASMA)
        return ObjectRef(oid, owner_address=self.address, worker=self,
                         call_site=call_site)

    def _ring_gather_layout(self, darr, contribs, data_nbytes: int):
        """(segments, sources) for a ring all-gather, or None when the
        source layout is not a rank-ordered contiguous tiling of the
        destination (rank r's ring segment must be exactly its own
        shard's bytes, laid out in rank order — true for every 1-D
        sharding and for row-major leading-axis shardings; anything
        else takes the fold path)."""
        if len(contribs) != darr.mesh.nranks:
            return None
        segments, sources = [], []
        expect = 0
        for idx, (src_rank, runs) in enumerate(contribs):
            if src_rank != idx or len(runs) != 1:
                return None
            s_off, d_off, length = runs[0]
            if (s_off != 0 or d_off != expect
                    or length != darr.shards[src_rank].nbytes):
                return None
            segments.append((d_off, length))
            sources.append({
                "oid": darr.shards[src_rank].ref.object_id.binary(),
                "node_id": darr.shards[src_rank].node_id,
                "data_offset": darr.shards[src_rank].data_offset,
                "runs": [[0, d_off, length]],
            })
            expect += length
        if expect != data_nbytes:
            return None
        return segments, sources

    def all_gather(self, darr) -> ObjectRef:
        """Materialize the FULL array as one new object via a single
        GatherShards collective (striped data plane); returns its ref.
        Falls back to assemble+put when the collective fails."""
        return self._run(self._all_gather_async(darr))

    async def _all_gather_async(self, darr):
        import numpy as np

        from ray_tpu._private import distributed_array as da

        dtype = np.dtype(darr.dtype_str)
        mesh1 = da.Mesh((1,), ("gather",))
        plan = da.gather_plan(darr.shape, dtype.itemsize, darr.mesh,
                              darr.spec, mesh1, da.PartitionSpec())
        if self._ring_applicable(darr):
            nbytes = (int(np.prod(darr.shape, dtype=np.int64))
                      * dtype.itemsize)
            layout = self._ring_gather_layout(darr, plan[0], nbytes)
            if layout is not None:
                segments, ring_sources = layout
                schedules = [
                    da.ring_gather_schedule(r, darr.mesh.nranks)
                    for r in range(darr.mesh.nranks)]
                try:
                    return await self._ring_collective(
                        darr, segments, schedules, ring_sources, None,
                        {"gather": True, "ring": True}, "all_gather")
                except exc.CollectiveError as e:
                    logger.warning(
                        "ring all_gather failed (%s); falling back to "
                        "the fold path", e)
        sources = [{
            "oid": darr.shards[src_rank].ref.object_id.binary(),
            "node_id": darr.shards[src_rank].node_id,
            "data_offset": darr.shards[src_rank].data_offset,
            "runs": runs,
        } for src_rank, runs in plan[0]]
        info = await self._gather_shard(
            darr.shape, dtype, {"gather": True}, sources)
        if info is None:
            arr = await self._assemble_async(darr)
            oid = self._next_put_id()
            await self._put_serialized(
                oid, self.serialization_context.serialize(arr))
            return ObjectRef(oid, owner_address=self.address,
                             worker=self, call_site="all_gather")
        return info[0]

    def all_reduce(self, darr, op: str = "sum") -> ObjectRef:
        """Fold every rank's full-shape partial into one summed array
        (each shard must be the full global shape — a replicated spec).
        The destination raylet streams the first partial straight into
        the result segment and folds the rest through one reused
        scratch buffer; returns the result's ref. Falls back to
        get-all + np.sum when the collective fails."""
        return self._run(self._all_reduce_async(darr, op))

    async def _all_reduce_async(self, darr, op: str):
        import numpy as np

        from ray_tpu._private import distributed_array as da

        # typed rejection BEFORE any bytes move: both fold tiers and the
        # native kernel only know these ops, and reducing non-numeric
        # dtypes (strings, objects) is meaningless on raw frames
        if op not in ("sum", "min", "max"):
            raise ValueError(
                f"all_reduce op must be 'sum', 'min' or 'max', got "
                f"{op!r}")
        dtype = np.dtype(darr.dtype_str)
        if dtype.kind not in "fiu":
            raise TypeError(
                "all_reduce supports float/int/uint dtypes only, got "
                f"{darr.dtype_str}")
        nbytes = int(np.prod(darr.shape, dtype=np.int64)) * dtype.itemsize
        for s in darr.shards:
            if tuple(s.shape) != tuple(darr.shape):
                raise ValueError(
                    "all_reduce needs full-shape partials on every rank "
                    f"(rank {s.rank} holds {s.shape}, global is "
                    f"{darr.shape})")
        sources = [{
            "oid": s.ref.object_id.binary(), "node_id": s.node_id,
            "data_offset": s.data_offset,
            "runs": [[0, 0, nbytes]],
        } for s in darr.shards]
        if self._ring_applicable(darr):
            segments = da.ring_segments(nbytes, dtype.itemsize,
                                        darr.mesh.nranks)
            schedules = [da.ring_reduce_schedule(r, darr.mesh.nranks)
                         for r in range(darr.mesh.nranks)]
            try:
                return await self._ring_collective(
                    darr, segments, schedules, sources, op,
                    {"reduce": op, "ring": True}, "all_reduce")
            except exc.CollectiveError as e:
                logger.warning(
                    "ring all_reduce failed (%s); falling back to the "
                    "fold path", e)
        info = await self._gather_shard(
            darr.shape, dtype, {"reduce": op}, sources,
            reduce_spec={"op": op, "dtype": darr.dtype_str})
        if info is not None:
            return info[0]
        vals = [await self._get_one(s.ref, None) for s in darr.shards]
        out = vals[0].copy()
        ufunc = {"sum": np.add, "min": np.minimum,
                 "max": np.maximum}[op]
        for v in vals[1:]:
            ufunc(out, v, out)
        oid = self._next_put_id()
        await self._put_serialized(
            oid, self.serialization_context.serialize(out))
        return ObjectRef(oid, owner_address=self.address, worker=self,
                         call_site="all_reduce")

    # -------------------------------------------------------- runtime envs

    def set_job_runtime_env(self, runtime_env: Optional[Dict]) -> None:
        """Job-level default env (ray.init(runtime_env=...)): uploaded
        once, merged under every task/actor env (reference: JobConfig
        runtime_env, python/ray/job_config.py). Persisted in the GCS KV
        so WORKERS of this job merge it into their nested submissions
        too (the reference ships JobConfig inside the job table)."""
        from ray_tpu._private import runtime_env as runtime_env_mod

        self.job_runtime_env = self._prepare_runtime_env(runtime_env)
        if self.job_runtime_env and self.job_id:
            import json as _json
            self._kv_put_sync(
                runtime_env_mod.JOB_ENV_KEY_PREFIX + self.job_id,
                _json.dumps(self.job_runtime_env).encode())

    def adopt_job_runtime_env(self, job_id: bytes) -> None:
        """Worker side: when adopting a job, pick up its job-level
        runtime env so nested task/actor submissions inherit it."""
        from ray_tpu._private import runtime_env as runtime_env_mod

        if self.job_runtime_env is not None or not job_id:
            return
        try:
            raw = self._kv_get_sync(
                runtime_env_mod.JOB_ENV_KEY_PREFIX + job_id)
        except Exception:  # noqa: BLE001 — GCS restarting; best effort
            return
        import json as _json
        # {} on miss: caches "no job env" so this is one KV read per
        # worker, not one per task.
        self.job_runtime_env = _json.loads(raw) if raw else {}

    def _prepare_runtime_env(self, runtime_env: Optional[Dict]):
        if not runtime_env:
            return runtime_env
        from ray_tpu._private import runtime_env as runtime_env_mod
        return runtime_env_mod.prepare_runtime_env(
            runtime_env, self._kv_get_sync, self._kv_put_sync,
            self._uploaded_working_dirs)

    def _resolve_runtime_env(self, runtime_env: Optional[Dict]):
        """Prepare (validate/upload) a per-task env and merge the job
        default under it. Task env_vars overlay the job's; a task-level
        working_dir wins over the job's."""
        prepared = self._prepare_runtime_env(runtime_env)
        job = self.job_runtime_env
        if not job:
            return prepared
        if not prepared:
            return dict(job)
        merged = dict(job)
        merged.update({k: v for k, v in prepared.items()
                       if k != "env_vars"})
        env_vars = dict(job.get("env_vars") or {})
        env_vars.update(prepared.get("env_vars") or {})
        if env_vars:
            merged["env_vars"] = env_vars
        return merged

    # ------------------------------------------------------- task submission

    def submit_task(self, fn_key: str, name: str, args: List[Any],
                    num_returns: int = 1, resources: Dict[str, float] | None = None,
                    max_retries: int | None = None,
                    retry_exceptions: bool = False,
                    placement_group_id: bytes = b"",
                    placement_group_bundle_index: int = -1,
                    scheduling_strategy: str = "DEFAULT",
                    runtime_env: Dict | None = None) -> List[ObjectRef]:
        _check_tpu_demand(resources, actor=False)
        # Hot path: raw-bytes task id (lineage prefix + random suffix)
        # instead of TaskID/ActorID wrapper churn — ~4 object
        # constructions per submit otherwise.
        if self.mode == "driver":
            prefix = self._task_lineage_prefix
        else:
            prefix = (self._current_task_id or
                      self._driver_task_id.binary())[:ACTOR_ID_SIZE]
        task_id_b = make_task_id_bytes(prefix)
        prepared_args, arg_holds = self._prepare_args(args) \
            if args else ((), None)
        spec = TaskSpec(
            task_id=task_id_b, job_id=self.job_id,
            task_type=TASK_NORMAL, name=name, fn_key=fn_key,
            args=prepared_args,
            num_returns=num_returns,
            resources=resources or {"CPU": 1.0},
            max_retries=self.config.task_max_retries_default
            if max_retries is None else max_retries,
            retry_exceptions=retry_exceptions,
            owner_address=self.address, owner_worker_id=self.worker_id,
            placement_group_id=placement_group_id,
            placement_group_bundle_index=placement_group_bundle_index,
            scheduling_strategy=scheduling_strategy,
            runtime_env=self._resolve_runtime_env(runtime_env),
            trace_ctx=_trace_ctx())
        return self._register_and_submit(spec, arg_holds)

    def make_task_template(self, fn_key: str, name: str,
                           num_returns: int = 1,
                           resources: Dict[str, float] | None = None,
                           max_retries: int | None = None,
                           retry_exceptions: bool = False,
                           placement_group_id: bytes = b"",
                           placement_group_bundle_index: int = -1,
                           scheduling_strategy: str = "DEFAULT",
                           runtime_env: Dict | None = None) -> TaskSpec:
        """Prototype TaskSpec for repeated submissions of the same
        remote function: runtime env resolved and scheduling class
        interned ONCE, per-call work reduced to id generation + arg
        prep + a slot-copy clone (see TaskSpec.clone_for)."""
        _check_tpu_demand(resources, actor=False)
        proto = TaskSpec(
            task_id=b"", job_id=self.job_id,
            task_type=TASK_NORMAL, name=name, fn_key=fn_key, args=[],
            num_returns=num_returns,
            resources=resources or {"CPU": 1.0},
            max_retries=self.config.task_max_retries_default
            if max_retries is None else max_retries,
            retry_exceptions=retry_exceptions,
            owner_address=self.address, owner_worker_id=self.worker_id,
            placement_group_id=placement_group_id,
            placement_group_bundle_index=placement_group_bundle_index,
            scheduling_strategy=scheduling_strategy,
            runtime_env=self._resolve_runtime_env(runtime_env))
        proto.scheduling_class  # intern now, off the per-call path
        return proto

    def submit_task_from_template(self, proto: TaskSpec,
                                  args: List[Any]) -> List[ObjectRef]:
        if self.mode == "driver":
            prefix = self._task_lineage_prefix
        else:
            prefix = (self._current_task_id or
                      self._driver_task_id.binary())[:ACTOR_ID_SIZE]
        if not args and proto.num_returns == 1:
            # The dominant microbenchmark shape (arg-less, one return):
            # one C call fuses mint + clone + refcount + ObjectRef +
            # pending entry + queue append (cpp/fastpath.c).
            ctx = self._fast_ctx
            if ctx is None and not self._fast_ctx_failed:
                ctx = self._make_fast_ctx()
            if ctx is not None:
                # SUBMITTED is recorded loop-side by
                # _drain_submit_buffer (the C path enqueues the cloned
                # spec there like every other submission): the caller
                # thread pays nothing for recording.
                return ctx.submit(proto, prefix, _trace_ctx())
            prepared_args, arg_holds = (), None
        elif args:
            prepared_args, arg_holds = self._prepare_args(args)
        else:
            prepared_args, arg_holds = (), None
        spec = proto.clone_for(make_task_id_bytes(prefix), prepared_args,
                               trace_ctx=_trace_ctx())
        return self._register_and_submit(spec, arg_holds)

    def _make_fast_ctx(self):
        """Bind a native fused-submit context to this worker (or mark
        the attempt failed and stay on the pure-Python path forever)."""
        try:
            from ray_tpu._private.native import load_fastpath

            mod = load_fastpath()
            if mod is None or not self.address:
                raise RuntimeError("native module or address unavailable")
            self._fast_ctx = mod.Ctx(
                worker=self,
                refs_dict=self.reference_counter._refs,
                pending_dict=self.pending_tasks,
                submit_buffer=self._submit_buffer,
                stats_dict=self.stats,
                own_address=self.address,
                call_soon_threadsafe=self.loop.call_soon_threadsafe,
                drain_fn=self._drain_submit_buffer,
                taskspec_cls=TaskSpec,
                objectid_cls=ObjectID,
                objectref_cls=ObjectRef,
                reference_cls=Reference,
                entry_cls=PendingTaskEntry,
                serialized_cls=SerializedObject,
                seed=os.urandom(16),
            )
            return self._fast_ctx
        except Exception as e:  # noqa: BLE001 — perf tier, never correctness
            logger.debug("fast submit path unavailable: %s", e)
            self._fast_ctx_failed = True
            return None

    def _register_and_submit(self, spec: TaskSpec,
                             arg_holds: Optional[List[ObjectRef]] = None
                             ) -> List[ObjectRef]:
        refs = self._register_task(spec, arg_holds)
        # SUBMITTED recorded loop-side by _drain_submit_buffer
        self._enqueue_submit("task", spec)
        return refs

    def _register_task(self, spec: TaskSpec,
                       arg_holds: Optional[List[ObjectRef]] = None
                       ) -> List[ObjectRef]:
        tid_b = spec.task_id
        if spec.num_returns == 1:
            # Hot path (the reference's microbenchmarks are all
            # single-return): no list comprehension frames.
            oid = ObjectID(return_object_id_bytes(tid_b, 1))
            self.reference_counter.add_owned_with_local_ref(
                oid, pin_lineage=True)
            refs = [ObjectRef(oid, owner_address=self.address, worker=self,
                              call_site=spec.name,
                              skip_adding_local_ref=True)]
            return_ids = [oid]
        else:
            return_ids = [
                ObjectID(return_object_id_bytes(tid_b, i + 1))
                for i in range(spec.num_returns)]
            refs = []
            for oid in return_ids:
                self.reference_counter.add_owned_with_local_ref(
                    oid, pin_lineage=True)
                refs.append(ObjectRef(oid, owner_address=self.address,
                                      worker=self, call_site=spec.name,
                                      skip_adding_local_ref=True))
        entry = PendingTaskEntry(spec, return_ids)
        self.pending_tasks[tid_b] = entry
        if entry.dep_ids:
            self.reference_counter.update_submitted_task_references(
                entry.dep_ids)
        del arg_holds  # promoted args now pinned by submitted-ref counts
        self.stats["tasks_submitted"] += 1
        return refs

    def queue_local_decref(self, object_id: ObjectID):
        """Deferred remove_local_reference (called from ObjectRef.__del__,
        any thread): batch the lock + release side effects onto the loop."""
        self._decref_buffer.append(object_id)
        if not self._decref_scheduled:
            self._decref_scheduled = True
            try:
                self.loop.call_soon_threadsafe(self._drain_decrefs)
            except RuntimeError:  # loop closed: shutting down
                self._decref_scheduled = False

    def _drain_decrefs(self):
        self._decref_scheduled = False
        buf = self._decref_buffer
        remove = self.reference_counter.remove_local_reference
        # Chunked: dropping a 1M-ref list must not freeze the IO loop
        # for the whole backlog — yield after a slice and reschedule.
        for _ in range(20000):
            try:
                oid = buf.popleft()
            except IndexError:
                return
            remove(oid)
        if buf and not self._decref_scheduled:
            self._decref_scheduled = True
            self.loop.call_soon(self._drain_decrefs)

    def _enqueue_submit(self, kind: str, spec: TaskSpec):
        """Queue a spec for submission and wake the IO loop at most once
        per burst (reference analog: the submitter queue pump in
        direct_task_transport.cc, but batched for the caller thread).
        Lock-free: deque.append is GIL-atomic, and the drain clears the
        scheduled flag BEFORE popping, so the worst interleaving is one
        spurious extra wakeup — never a stranded spec."""
        self._submit_buffer.append((kind, spec))
        if not self._submit_scheduled:
            self._submit_scheduled = True
            self.loop.call_soon_threadsafe(self._drain_submit_buffer)

    def _drain_submit_buffer(self):
        """Loop thread: move buffered submissions into per-key / per-actor
        queues, then pump each touched queue once."""
        self._submit_scheduled = False
        items = []
        buf = self._submit_buffer
        while True:
            try:
                items.append(buf.popleft())
            except IndexError:
                break
        ev = self.task_events
        # SUBMITTED stamps for the whole burst, grouped by task name
        # (one record_many per distinct template): the caller thread
        # pays nothing, and the grouping is FUSED into the routing loop
        # below — one pass over the burst, not a separate stamping pass
        # (bench.py task_events_overhead pins the submit-path cost).
        recording = bool(ev.enabled and items)
        # Stamp ts taken BEFORE the loop: PENDING_ARGS records fired
        # mid-loop must sort after their task's SUBMITTED event.
        now = time.time() if recording else 0.0
        by_name: Dict[str, list] = {}
        touched_keys: Dict[int, SchedulingKeyState] = {}
        touched_actors: Dict[bytes, ActorQueueState] = {}
        for kind, spec in items:
            if recording:
                tids = by_name.get(spec.name)
                if tids is None:
                    tids = by_name[spec.name] = []
                tids.append(spec.task_id)
            if kind == "task":
                # args check first: the dominant argless submit skips
                # the dependency_ids() call entirely
                if spec.args and spec.dependency_ids():
                    # Owned args may be pending: resolve asynchronously.
                    if self.task_events.enabled:
                        self.task_events.record(spec.task_id, PENDING_ARGS)
                    rpc.spawn_logged(self._submit_when_ready(spec),
                                     "worker-submit-when-ready",
                                     loop=self.loop)
                    continue
                sc = spec._sched  # interned at template creation
                if sc < 0:
                    sc = spec.scheduling_class
                state = self.scheduling_keys.get(sc)
                if state is None:
                    state = self.scheduling_keys[sc] = \
                        SchedulingKeyState(spec.resources)
                state.queue.append(spec)
                touched_keys[sc] = state
            else:
                q = self.actor_queues.get(spec.actor_id)
                if q is None:
                    q = self.actor_queues[spec.actor_id] = \
                        ActorQueueState(spec.actor_id)
                if q.state == "DEAD":
                    self._store_error_for_task(
                        spec, exc.ActorDiedError(
                            q.death_cause or "actor is dead",
                            cause=q.death_info))
                    continue
                # Seqnos assigned in buffer order == submission order (the
                # receiver executes strictly by seqno per caller).
                seqno = q.seqno
                q.seqno += 1
                q.buffer.append((spec, seqno))
                touched_actors[spec.actor_id] = q
        if by_name:
            for tname, tids in by_name.items():
                ev.record_many(tids, SUBMITTED, tname, ts=now)
        for sc, state in touched_keys.items():
            self._pump_scheduling_key(sc, state)
        for q in touched_actors.values():
            self._pump_actor_queue(q)

    def _prepare_args(self, args: List[Any]):
        """Inline small values; pass ObjectRefs and big values by reference
        (reference: prepare_args in _raylet.pyx — the
        max_direct_call_object_size threshold). Returns (task_args, holds):
        ``holds`` keeps promoted-arg ObjectRefs alive until the caller has
        registered submitted-task references for them."""
        holds: List[ObjectRef] = []
        out: List[TaskArg] = []
        for a in args:
            if isinstance(a, ObjectRef):
                owner = a.owner_address or \
                    self.reference_counter.owner_address_of(a.object_id) or \
                    self.address
                out.append(TaskArg(ARG_REF, object_id=a.object_id.binary(),
                                   owner_address=owner))
                continue
            serialized = self.serialization_context.serialize(a)
            if serialized.total_bytes() <= self.config.max_direct_call_object_size \
                    and not serialized.contained_refs:
                meta, frames = serialized.to_wire()
                out.append(TaskArg(ARG_VALUE, metadata=meta, frames=frames))
            else:
                # Too big (or carries refs needing ownership tracking):
                # promote to a put + by-reference arg.
                ref = self.put(a)
                out.append(TaskArg(ARG_REF, object_id=ref.object_id.binary(),
                                   owner_address=self.address))
                holds.append(ref)
        return out, holds

    async def _submit_when_ready(self, spec: TaskSpec):
        """Local dependency resolution (reference: LocalDependencyResolver):
        wait until every owned arg is available before asking for a lease;
        borrowed args resolve at the executing worker."""
        for dep in spec.dependency_ids():
            oid = ObjectID(dep)
            if self.reference_counter.is_owned(oid):
                try:
                    await self.memory_store.get(oid)
                # raylint: disable=exception-hygiene — errored deps surface at the executing worker
                except Exception:
                    pass
        self._queue_spec(spec)

    def _queue_spec(self, spec: TaskSpec):
        """Loop thread: queue a dependency-free spec and pump."""
        sc = spec.scheduling_class
        state = self.scheduling_keys.get(sc)
        if state is None:
            state = self.scheduling_keys[sc] = SchedulingKeyState(spec.resources)
        state.queue.append(spec)
        self._pump_scheduling_key(sc, state)

    def _pump_scheduling_key(self, sc: int, state: SchedulingKeyState):
        """Breadth-first lease acquisition, depth only when breadth is
        exhausted: leases are requested in proportion to the queue (one
        per ~8 queued tasks, bounded), and each worker's batch is sized
        to an even split across the workers we have or expect — NOT to
        the full pipeline cap. The cap (deep, for wire batching) only
        bites when the cluster can't give us more workers, so a 100-task
        job on an 8-CPU node parallelizes instead of serializing into
        one 512-deep pipeline (reference: per-scheduling-key lease
        requests bounded by backlog, direct_task_transport.h)."""
        cap = self.config.max_tasks_in_flight_per_worker
        max_pending = self.config.max_pending_leases_per_scheduling_class
        credits_on = self.config.lease_credits_enabled
        stale_s = self.config.lease_credit_stale_s
        while state.queue:
            qlen = len(state.queue)
            # target worker count for this backlog (breadth first)
            want = min(max(1, qlen // 8), max_pending)
            floor = 0
            if credits_on:
                # Streaming leases. Until the raylet announces a window
                # (credit_target < 0), probe with ONE legacy request —
                # it carries the backlog that opens the window and
                # keeps locality-aware targeting intact. After that:
                #   * breadth is clamped to the raylet's cluster-wide
                #     slot bound — parking legacy requests beyond real
                #     capacity WAS the 200-700ms grant_wait tail;
                #   * the first min(want, window_target) slots are
                #     RESERVED for the credit stream while it is live
                #     (credits activating, workers held, or a grant
                #     within the stale period) — the stream fills them
                #     with zero request/grant round-trips;
                #   * legacy requests fire only for the remainder
                #     (remote capacity, reached through the existing
                #     park-and-spill machinery) or when the stream has
                #     gone silent (raylet restarted, pressure zeroed
                #     the window, grant push lost) — the fallback lane.
                tgt = state.credit_target
                if tgt < 0:
                    want = min(want, 1)
                else:
                    want = min(want, max(1, state.cluster_slots))
                    stream_live = state.activating > 0 or \
                        bool(state.workers) or \
                        time.monotonic() - state.last_grant_ts < stale_s
                    if stream_live:
                        floor = min(want, tgt)
                now = time.monotonic()
                expected0 = len(state.workers) + state.activating
                if tgt >= 0 and \
                        self.raylet_conn is not None and \
                        not self.raylet_conn.closed and \
                        (now - state.last_demand_ts > stale_s / 2 or
                         (expected0 == 0 and
                          now - state.last_demand_ts > 0.01)):
                    # paced backlog refresh (kept off the per-task
                    # path): renews the window mid-drain, and a
                    # zero-worker burst start kicks it immediately so
                    # the stream restarts without waiting out the pace
                    state.last_demand_ts = now
                    head = state.queue[0]
                    from ray_tpu._private import runtime_env as _re
                    try:
                        self.raylet_conn.push_nowait(
                            "ReportLeaseDemand",
                            protocol.ReportLeaseDemandRequest(
                                sched_class=sc, backlog=qlen,
                                resources=state.resources,
                                # same env key the legacy summary
                                # carries: a window (re)created from
                                # this push must keep the warm-pool
                                # runtime-env affinity
                                env_hash=_re.hash_runtime_env(
                                    head.runtime_env),
                                retriable=head.max_retries != 0,
                            ).to_header())
                    except ConnectionError:
                        pass  # raylet gone; lease path handles retries
            while True:
                expected = len(state.workers) + state.pending_lease + \
                    state.activating
                if expected >= want or \
                        state.pending_lease >= want - floor:
                    # enough breadth, or the legacy band is full: only
                    # (want - floor) legacy requests may be in flight —
                    # the stream owns the floor, and a partially-filled
                    # stream must not block the remote-spill band
                    break
                state.pending_lease += 1
                rpc.spawn_logged(
                    self._request_lease(sc, state, self.raylet_address),
                    "worker-request-lease", loop=self.loop)
            worker = min((w for w in state.workers if w.inflight < cap),
                         key=lambda w: w.inflight, default=None)
            if worker is None:
                if state.pending_lease == 0 and state.activating == 0:
                    if floor:
                        # deferred to the stream with nothing in
                        # flight: guard against a silent stream (lost
                        # demand push / raylet restart) — re-pump after
                        # the stale period, by when stream_live has
                        # expired and the legacy fallback fires
                        if not self._shutdown:
                            self.loop.call_later(
                                stale_s, self._pump_scheduling_key,
                                sc, state)
                    else:
                        state.pending_lease += 1
                        rpc.spawn_logged(
                            self._request_lease(sc, state,
                                                self.raylet_address),
                            "worker-request-lease", loop=self.loop)
                return
            # Batch sizing: fair share over current+expected workers
            # while grants are ARRIVING (breadth phase); once they stop
            # — saturated node, or a single-worker box whose extra
            # lease requests just sit pending — deepen to the cap so
            # wire batches stay large (tail batches shrinking with the
            # fair share measured a ~20% throughput loss).
            growing = (state.pending_lease > 0 or state.activating > 0) \
                and time.monotonic() - state.last_grant_ts < 0.05
            if growing:
                share = qlen // max(
                    1, len(state.workers) + state.pending_lease +
                    state.activating)
                target = min(cap, max(8, share))
            else:
                target = cap
            if worker.inflight >= target:
                # growing: breadth pending, wait for grants;
                # otherwise: every worker at the cap, wait for replies
                return
            n = min(qlen, target - worker.inflight)
            batch = [state.queue.popleft() for _ in range(n)]
            worker.inflight += n
            if worker.idle_timer is not None:
                worker.idle_timer.cancel()
                worker.idle_timer = None
            self._push_task_batch_nowait(sc, state, worker, batch)

    def _dep_info(self, spec: TaskSpec) -> List[dict]:
        """Owner-side locality data per by-ref arg: size + known replica
        locations from the reference counter (reference: LocalityData fed
        into lease_policy.h)."""
        out = []
        for a in spec.args:
            if a.kind != ARG_REF:
                continue
            size, locations = self.reference_counter.location_info(
                ObjectID(a.object_id))
            out.append({"oid": a.object_id,
                        "owner": a.owner_address or self.address,
                        "size": size, "locations": locations})
        return out

    async def _node_address_of(self, node_id: bytes) -> str:
        """node_id -> raylet address via a cached GCS node table."""
        now = time.monotonic()
        if now - self._node_table_ts > 5.0:
            try:
                reply, _ = await self._gcs_call("GetAllNodeInfo", {})
            except (ConnectionError, asyncio.TimeoutError):
                return ""
            # Re-sample after the await: a concurrent refresher may
            # have landed a NEWER table during our RPC — overwriting it
            # with this (older) reply would roll the cache backwards.
            if self._node_table_ts <= now:
                self._node_table = {n["node_id"]: n["address"]
                                    for n in reply["nodes"] if n["alive"]}
                self._node_table_ts = now
        return self._node_table.get(node_id, "")

    async def _best_locality_raylet(self, dep_info: List[dict]) -> str:
        """Locality-aware lease targeting (reference: lease_policy.h
        LocalityAwareLeasePolicy::GetBestNodeForTask): request the lease
        from the node already holding the most argument bytes."""
        per_node: Dict[bytes, int] = {}
        for d in dep_info:
            for nid in d["locations"]:
                per_node[nid] = per_node.get(nid, 0) + d["size"]
        if not per_node:
            return ""
        best_node = max(per_node, key=per_node.get)
        if per_node[best_node] <= 0:
            return ""
        addr = await self._node_address_of(best_node)
        return addr if addr and addr != self.raylet_address else ""

    async def _request_lease(self, sc: int, state: SchedulingKeyState,
                             raylet_address: str, depth: int = 0):
        try:
            def _build_summary():
                sample = state.queue[0] if state.queue else None
                if sample is None:
                    return {
                        "task_id": b"", "scheduling_class": sc,
                        "resources": state.resources, "deps": [],
                        "strategy": "DEFAULT", "pg_id": b"",
                        "pg_bundle": -1, "runtime_env": None,
                        "depth": 0, "name": "", "retriable": False,
                        "backlog": 0}
                s = sample.lease_summary()
                s["dep_info"] = self._dep_info(sample)
                # streaming leases: the backlog opens/refreshes this
                # owner's credit window at the serving raylet
                s["backlog"] = len(state.queue)
                return s

            summary = _build_summary()
            dep_info = summary.get("dep_info")
            if dep_info and depth == 0 and \
                    raylet_address == self.raylet_address:
                target = await self._best_locality_raylet(dep_info)
                if target:
                    raylet_address = target
            if raylet_address == self.raylet_address:
                conn = self.raylet_conn
            else:
                conn = await self._get_owner_conn(raylet_address)
            bo = None
            while True:
                reply, _ = await conn.call(
                    "RequestWorkerLease",
                    protocol.RequestWorkerLeaseRequest(
                        summary=summary).to_header())
                if not reply.get("retry_later"):
                    break
                # Typed lease backpressure: the raylet is above its
                # memory threshold and admits no new work. Back off
                # with jitter and re-request while this scheduling
                # class still has backlog (pressure clears when the
                # watchdog frees memory or the work drains elsewhere);
                # once the queue empties, stop asking.
                if self._shutdown or not state.queue:
                    state.pending_lease -= 1
                    return
                if bo is None:
                    from ray_tpu._private import backoff as backoff_mod
                    bo = backoff_mod.from_config(self.config)
                await bo.sleep()
                # re-sample the CURRENT queue head: the task sampled
                # before the backoff may have completed (stolen,
                # cancelled) — its task-events and retriable flag must
                # not be stamped onto whatever runs next
                summary = _build_summary()
        except asyncio.CancelledError:
            # settle the ledger, but stay cancelled: swallowing here
            # made `task.cancel(); await task` report success with the
            # lease request half-done
            state.pending_lease -= 1
            raise
        except ConnectionError:
            state.pending_lease -= 1
            return
        if reply.get("granted"):
            try:
                wconn = await rpc.connect(reply["worker_address"],
                                          peer_name="leased-worker")
            except ConnectionError:
                state.pending_lease -= 1
                return
            lw = LeasedWorker(reply["worker_address"], reply["lease_id"],
                              reply["node_id"], wconn, raylet_address,
                              reply["worker_id"])
            state.workers.append(lw)
            state.pending_lease -= 1
            state.last_grant_ts = time.monotonic()
            lw.on_drop = \
                lambda c: self._on_leased_worker_died(sc, state, lw)
            wconn.on_disconnect.append(lw.on_drop)
            if state.queue:
                self._pump_scheduling_key(sc, state)
            elif not self._try_steal(sc, state):
                # Stale grant: the queue drained while this request was
                # pending at the raylet and no sibling has stealable
                # backlog. Hand the worker straight back — keeping it
                # starves other scheduling classes.
                state.workers.remove(lw)
                await self._return_lease(lw)
        elif reply.get("spill") and depth < 4:
            await self._request_lease(sc, state, reply["spill"], depth + 1)
        elif reply.get("spill"):
            # Spill chain exhausted — e.g. mutually memory-pressured
            # nodes bouncing the request between each other (each zeroes
            # only its OWN availability in the backpressure view). The
            # old silent drop left the queue stranded with
            # pending_lease=0 and nothing to re-pump it. Back off, then
            # start over from the HOME raylet: pressure clears and the
            # home node re-admits (or re-spills somewhere healthy).
            state.pending_lease -= 1
            if state.queue and not self._shutdown:
                from ray_tpu._private import backoff as backoff_mod
                await backoff_mod.from_config(self.config).sleep()
                if state.queue and not self._shutdown:
                    self._pump_scheduling_key(sc, state)
        elif reply.get("infeasible"):
            state.pending_lease -= 1
            self._fail_queued_tasks(state, exc.RaySystemError(
                f"task requires infeasible resources {state.resources}"))
        else:
            state.pending_lease -= 1

    def _schedule_idle_return(self, sc: int, state: SchedulingKeyState,
                              lw: "LeasedWorker") -> None:
        """Keep an idle leased worker warm for a grace period before
        returning it — a sync-loop caller (submit, get, repeat) reuses
        the lease instead of paying a raylet round trip per task. One
        cancellable timer per worker: re-arming replaces the old timer,
        and the pump cancels it when work lands, so a stale timer can
        never return a lease that went back into use."""
        if lw.gang is not None:
            # gang-pinned lease: rank identity must survive between
            # steps — only the gang's release/teardown path (or the
            # raylet's owner-liveness watch) ends it
            return

        def _maybe_return():
            lw.idle_timer = None
            if lw not in state.workers or lw.inflight > 0 or state.queue:
                return  # back in use
            state.workers.remove(lw)
            rpc.spawn_logged(self._return_lease(lw),
                             "worker-return-lease", loop=self.loop)

        if lw.idle_timer is not None:
            lw.idle_timer.cancel()
        lw.idle_timer = self.loop.call_later(
            self.config.idle_lease_keepalive_s, _maybe_return)

    def _try_steal(self, sc: int, state: SchedulingKeyState) -> bool:
        """Initiate work stealing when a worker sits idle while a
        sibling has a deep pipeline (reference:
        direct_task_transport.h:57 StealTasks). Returns True if a steal
        was started (the idle worker should be kept leased)."""
        if state.steal_pending or state.queue:
            return False
        victim = max((w for w in state.workers if w.inflight >= 2),
                     key=lambda w: w.inflight, default=None)
        if victim is None or not any(
                w is not victim and w.inflight == 0 for w in state.workers):
            return False
        state.steal_pending = True
        rpc.spawn_logged(self._steal_tasks(sc, state, victim),
                         "worker-steal-tasks", loop=self.loop)
        return True

    async def _steal_tasks(self, sc: int, state: SchedulingKeyState,
                           victim: LeasedWorker):
        try:
            reply, rbufs = await victim.conn.call(
                "StealTasks", {"max_n": victim.inflight - 1})
        except ConnectionError:
            reply, rbufs = {"tasks": []}, []
        finally:
            state.steal_pending = False
        protos = [TaskSpec.from_tail_wire(t) for t in reply.get("protos", ())]
        for pidx, task_id, args_wire, fstart, nframes, trace_ctx in \
                reply["tasks"]:
            spec = protos[pidx].clone_for(
                task_id,
                TaskSpec._args_from_wire(
                    args_wire, list(rbufs[fstart:fstart + nframes])),
                trace_ctx=tuple(trace_ctx) if trace_ctx else None)
            state.reassigned.setdefault(spec.task_id, []).append(
                victim.worker_id)
            state.queue.append(spec)
            self.stats["tasks_stolen"] += 1
        if state.queue:
            self._pump_scheduling_key(sc, state)
        # thieves the steal couldn't feed idle out through the normal
        # keepalive (an immediate return would defeat the warm lease)
        for w in [w for w in state.workers if w.inflight == 0]:
            if state.queue:
                break
            self._schedule_idle_return(sc, state, w)

    def _fail_queued_tasks(self, state: SchedulingKeyState, error: BaseException):
        for spec in state.queue:
            self._store_error_for_task(spec, error)
        state.queue.clear()

    def _on_leased_worker_died(self, sc, state, lw: LeasedWorker):
        if lw in state.workers:
            state.workers.remove(lw)
        self._fire_and_forget(self._return_lease(lw, worker_died=True))

    async def _return_lease(self, lw: LeasedWorker, worker_died: bool = False):
        try:
            if lw.raylet_address == self.raylet_address:
                conn = self.raylet_conn
            else:
                conn = await self._get_owner_conn(lw.raylet_address)
            await conn.call(
                "ReturnWorker",
                protocol.ReturnWorkerRequest(
                    lease_id=lw.lease_id,
                    worker_died=worker_died).to_header())
        except ConnectionError:
            pass
        if not lw.conn.closed:
            # deliberate return: unhook the death watch first so the
            # close doesn't fire a spurious worker-died ReturnWorker
            if lw.on_drop is not None and \
                    lw.on_drop in lw.conn.on_disconnect:
                lw.conn.on_disconnect.remove(lw.on_drop)
            await lw.conn.close()

    def _push_task_batch_nowait(self, sc: int, state: SchedulingKeyState,
                                lw: LeasedWorker, batch: List[TaskSpec]):
        """Loop thread: write ONE PushTasks frame carrying the whole batch
        and attach completion handling to the reply future — no per-task
        coroutine, no per-task syscall. Static spec fields ride once per
        distinct prototype (TaskSpec.tail_wire), not once per task."""
        ctx = self._fast_ctx
        if ctx is not None:
            # C wire assembly also hands back the task-id list so the
            # dispatch stamp below needs no Python per-spec loop
            tails, theaders, frames, tids = ctx.build_push(batch)
        else:
            tails_l: List[list] = []
            tail_idx: Dict[int, int] = {}
            theaders_l: List[list] = []
            frames_l: List[bytes] = []
            tids = []
            for spec in batch:
                proto = spec._proto or spec
                tids.append(spec.task_id)
                pidx = tail_idx.get(id(proto))
                if pidx is None:
                    pidx = tail_idx[id(proto)] = len(tails_l)
                    tails_l.append(proto.tail_wire())
                if not spec.args and spec.trace_ctx is None:
                    theaders_l.append([pidx, spec.task_id])  # compact
                    continue
                args_wire, afr = spec._args_wire()
                theaders_l.append([pidx, spec.task_id, args_wire,
                                   len(frames_l), len(afr), spec.trace_ctx])
                frames_l.extend(afr)
            tails, theaders, frames = tails_l, theaders_l, frames_l
        # owner-side credit hit-rate: per-task dispatch split between
        # streamed credits and legacy request/grant leases
        self.stats["credit_dispatches" if lw.via_credit
                   else "legacy_dispatches"] += len(batch)
        ev = self.task_events
        if ev.enabled:
            # CREDIT_DISPATCHED marks the hop that replaced the lease
            # round-trip — grant_wait stays honestly measured (a credit
            # hit is visible as such, never passed off as a zero-wait
            # legacy grant)
            ev.record_many(tids,
                           CREDIT_DISPATCHED if lw.via_credit
                           else DISPATCHED,
                           {"worker": lw.worker_id.hex()[:12]})
        try:
            fut = lw.conn.call_nowait("PushTasks",
                                      {"protos": tails, "tasks": theaders},
                                      bufs=frames)
        except ConnectionError:
            lw.inflight -= len(batch)
            for spec in batch:
                self._retry_or_fail_after_worker_death(spec, lw.worker_id)
            return
        fut.add_done_callback(
            lambda f: self._on_push_batch_done(f, sc, state, lw, batch))

    def _retry_or_fail_after_worker_death(self, spec: TaskSpec,
                                          via_worker_id: bytes = b""):
        state = self.scheduling_keys.get(spec.scheduling_class)
        if state is not None and \
                via_worker_id in state.reassigned.get(spec.task_id, ()):
            # the VICTIM of a steal died before its batch reply; the
            # task already runs elsewhere — only this worker's copy is
            # skipped (a thief's death still retries below)
            victims = state.reassigned[spec.task_id]
            victims.remove(via_worker_id)
            if not victims:
                del state.reassigned[spec.task_id]
            return
        entry = self.pending_tasks.get(spec.task_id)
        # OOM classification is only trusted close to the notify: the
        # SIGKILL follows the owner's ack within ~1s, so a much older
        # entry means the kill was aborted (re-grant guard) and THIS
        # death has some other cause.
        rec = self._oom_worker_kills.get(via_worker_id) \
            if via_worker_id else None
        oom_cause = rec[1] if rec is not None and \
            time.monotonic() - rec[0] < 5.0 else None
        if oom_cause is not None:
            self._retry_or_fail_after_oom_kill(spec, entry, oom_cause)
            return
        if entry is not None and entry.num_retries_left != 0:
            if entry.num_retries_left > 0:
                entry.num_retries_left -= 1
            self.stats["tasks_retried"] += 1
            if self.task_events.enabled:
                self.task_events.record(spec.task_id, RETRY,
                                        {"reason": "worker died"})
            logger.info("retrying task %s after worker death", spec.name)
            self._queue_spec(spec)
        else:
            self._store_error_for_task(
                spec, exc.WorkerCrashedError(
                    f"worker died executing {spec.name}"))

    def _retry_or_fail_after_oom_kill(self, spec: TaskSpec, entry,
                                      cause: dict):
        """Worker was killed by a node's memory watchdog: retry under
        the DEDICATED ``task_oom_retries`` budget (an OOM kill is the
        node's pressure, not the task's bug — the generic worker-crash
        budget survives), paced by the shared exponential-jitter
        backoff so a genuinely ballooning task can't hot-loop
        kill/retry against a node that is still at the threshold.
        Exhausted budget — or a non-retriable task — surfaces a typed
        :class:`~ray_tpu.exceptions.OutOfMemoryError` carrying the
        watchdog's cause (node/worker ids + per-worker RSS snapshot)."""
        left = getattr(entry, "oom_retries_left", None) \
            if entry is not None else None
        if left is None:
            # first OOM for this task (or a C-fastpath entry whose
            # slots were never initialized): budget comes from config
            left = self.config.task_oom_retries
        if entry is not None and spec.max_retries != 0 and left != 0:
            entry.oom_retries_left = left - 1 if left > 0 else left
            self.stats["tasks_retried"] += 1
            if self.task_events.enabled:
                self.task_events.record(spec.task_id, RETRY,
                                        {"reason": "worker OOM-killed"})
            bo = getattr(entry, "oom_backoff", None)
            if bo is None:
                from ray_tpu._private import backoff as backoff_mod
                bo = entry.oom_backoff = backoff_mod.from_config(
                    self.config)
            delay = bo.next_delay()
            logger.info("retrying task %s in %.2fs after watchdog OOM "
                        "kill", spec.name, delay)
            self.loop.call_later(delay, self._queue_spec, spec)
        else:
            self._store_error_for_task(
                spec, exc.OutOfMemoryError(
                    f"worker running {spec.name} was killed by the "
                    f"node memory watchdog", cause=cause))

    def _on_push_batch_done(self, fut: asyncio.Future, sc: int,
                            state: SchedulingKeyState, lw: LeasedWorker,
                            batch: List[TaskSpec]):
        lw.inflight -= len(batch)
        err = fut.exception() if not fut.cancelled() else None
        if fut.cancelled() or err is not None:
            for spec in batch:
                self._retry_or_fail_after_worker_death(spec, lw.worker_id)
            return
        reply, rbufs = fut.result()
        # Fast path for the dominant reply shape (ok, one inline
        # return, no deps/contained refs): batch every memory-store
        # landing under ONE lock via put_many.  The shape split runs in
        # C when the native ctx exists (cpp/fastpath.c complete_fast);
        # the Python fallback implements the identical
        # (pairs, finished, slow-indices) contract, so the stolen-reply
        # handling and the lease tail exist exactly once.
        replies = reply["replies"]
        keep_lineage = self.config.lineage_reconstruction_enabled
        ctx = self._fast_ctx
        if ctx is not None:
            put_pairs, finished, slow = ctx.complete_fast(
                batch, replies, rbufs, keep_lineage)
        else:
            put_pairs, finished, slow = self._complete_batch_py(
                batch, replies, rbufs, keep_lineage)
        for i in slow:
            spec = batch[i]
            rheader, fstart, nframes = replies[i]
            if rheader[0] == REPLY_STOLEN:
                # relinquished by THIS worker via StealTasks; the steal
                # reply already requeued it elsewhere. Consume only this
                # victim's entry — a second steal's victim keeps its own.
                victims = state.reassigned.get(spec.task_id)
                if victims is not None and lw.worker_id in victims:
                    victims.remove(lw.worker_id)
                    if not victims:
                        del state.reassigned[spec.task_id]
                continue
            self._complete_task(spec, rheader, rbufs[fstart:fstart + nframes])
        if put_pairs:
            self.memory_store.put_many(put_pairs)
        if finished:  # lineage-skip completions carry no put pair
            self.stats["tasks_finished"] += finished
        # Reuse the lease, steal for it, or (after a grace) return it.
        if state.queue:
            self._pump_scheduling_key(sc, state)
        elif lw.inflight == 0:
            if not self._try_steal(sc, state):
                self._schedule_idle_return(sc, state, lw)

    def _complete_batch_py(self, batch, replies, rbufs, keep_lineage):
        """Pure-Python twin of the native complete_fast: split a reply
        batch into memory-store pairs for the dominant shape plus slow
        indices for everything else."""
        pending = self.pending_tasks
        put_pairs: List[tuple] = []
        slow: List[int] = []
        finished = 0
        for i, (spec, (rheader, fstart, _nframes)) in enumerate(
                zip(batch, replies)):
            rets = rheader[1]
            if rheader[0] == 0 and not spec.args and len(rets) == 1:
                ret0 = rets[0]
                compact = len(ret0) == 2
                if not compact and (ret0[1] or ret0[5]):
                    slow.append(i)  # plasma / contained refs
                    continue
                entry = pending.get(spec.task_id)
                if entry is None:
                    continue
                if entry.recovery_waiter is not None:
                    slow.append(i)
                    continue
                if entry.lineage_pinned is None:
                    # returns all released in flight: skip the store
                    # put (it would orphan — the release-path delete
                    # already ran, and put_many lands AFTER the
                    # _finish_pending_entry cleanup) and drop the
                    # record, same contract as the C path's skip
                    # branch. Applies with lineage on OR off.
                    pending.pop(spec.task_id, None)
                    finished += 1
                    continue
                if compact:
                    # [meta, frames], oid derived from the task id
                    oid_b = return_object_id_bytes(spec.task_id, 1)
                    meta, frames = ret0
                else:
                    oid_b, _ip, meta, start, n, _cont = ret0[:6]
                    if len(ret0) > 6:
                        # inline return: payload frames decoded with
                        # the reply header (INLINE_RETURN_MAX)
                        frames = ret0[6]
                    else:
                        # `start` is task-relative; `fstart` locates
                        # this task's frames in the batch buffer
                        base = fstart + start
                        frames = rbufs[base:base + n]
                put_pairs.append((ObjectID(oid_b), SerializedObject(
                    meta, frames)))
                finished += 1
                self._finish_pending_entry(spec, entry, keep_lineage)
                continue
            slow.append(i)
        return put_pairs, finished, slow

    def _complete_task(self, spec: TaskSpec, reply: list, rbufs: List[bytes]):
        """Handle a task reply: land return values in the memory store /
        record plasma locations (reference: TaskManager::CompletePendingTask).
        ``reply`` is the compact [status, returns] list (task_spec.py)."""
        entry = self.pending_tasks.get(spec.task_id)
        if entry is None:
            return
        if reply[0] == REPLY_ERROR and spec.retry_exceptions and \
                entry.num_retries_left != 0:
            if entry.num_retries_left > 0:
                entry.num_retries_left -= 1
            self.stats["tasks_retried"] += 1
            if self.task_events.enabled:
                self.task_events.record(spec.task_id, RETRY,
                                        {"reason": "application error"})
            self._queue_spec(spec)
            return
        for ret in reply[1]:
            if len(ret) == 2:
                # compact single-return row [meta, frames]: the return
                # oid is derived (task id + index 1)
                self.memory_store.put(
                    return_object_id_bytes(spec.task_id, 1),
                    SerializedObject(ret[0], ret[1]))
                continue
            oid_b, in_plasma, meta, start, n, contained_b = ret[:6]
            oid = ObjectID(oid_b)
            if in_plasma:
                # plasma entry: meta=node_id, start=size. if_tracked:
                # refs released while the task ran must not be
                # resurrected by the location report — free the
                # replica instead (it has zero owners)
                if self.reference_counter.add_location_if_tracked(
                        oid, meta, start):
                    self.memory_store.put(oid, IN_PLASMA)
                else:
                    self._fire_and_forget(self._free_remote(oid, [meta]))
            else:
                frames = ret[6] if len(ret) > 6 \
                    else rbufs[start:start + n]
                obj = SerializedObject(meta, frames)
                if contained_b:
                    contained = [ObjectID(b) for b in contained_b]
                    self.reference_counter.add_contained_refs(oid, contained)
                    obj.contained_refs = contained
                self.memory_store.put(oid, obj)
        self.stats["tasks_finished"] += 1
        if spec.args and not spec.is_actor_task():
            self.reference_counter.update_finished_task_references(
                spec.dependency_ids())
        self._finish_pending_entry(
            spec, entry, self.config.lineage_reconstruction_enabled)

    def _finish_pending_entry(self, spec: TaskSpec, entry,
                              keep_lineage: bool) -> None:
        """Completion tail shared by _complete_task and the batched
        fast path: wake any recovery waiter, and drop the pending entry
        unless lineage reconstruction needs it."""
        waiter = entry.recovery_waiter
        if waiter is not None:
            entry.recovery_waiter = None
            if not waiter.done():
                waiter.set_result(True)
        if not keep_lineage or entry.lineage_pinned is None:
            # lineage off, or every return was already released while
            # the task ran (_release_lineage) — nobody can reconstruct
            self.pending_tasks.pop(spec.task_id, None)
            if entry.lineage_pinned is None:
                # the refs died before the values landed, so the
                # release path's memory_store.delete already ran —
                # drop the just-stored orphans (fire-and-forget tasks)
                for rid in entry.return_ids:
                    self.memory_store.delete(rid)
        else:
            # completed: the entry now lives only for lineage; the last
            # return's release pops it (_release_lineage)
            entry.lineage_pinned = True

    def _store_error_for_task(self, spec: TaskSpec, error: BaseException):
        if self.task_events.enabled:
            # owner-observed failures (worker death, cancellation,
            # infeasibility, dead actor): the worker never ran the task,
            # so the terminal FAILED is stamped here
            attrs = {"reason": type(error).__name__,
                     "message": str(error)[:200]}
            cause = getattr(error, "cause_info", None)
            if cause:
                # structured death cause (ActorDiedError /
                # ObjectLostError): state.list_tasks() shows node death
                # vs worker crash vs restarts-exhausted, with ids
                attrs["cause"] = {k: cause[k] for k in
                                  ("kind", "node_id", "worker_id",
                                   "last_failure")
                                  if cause.get(k)}
            self.task_events.record(spec.task_id, FAILED, attrs)
        serialized = self.serialization_context.serialize_error(error)
        task_id = TaskID(spec.task_id)
        for i in range(spec.num_returns):
            self.memory_store.put(task_id.object_id(i + 1), serialized)
        # A recovery waiting on this task must learn the outcome NOW (the
        # error value landed in the memory store) rather than time out;
        # the entry then follows the normal completion lifecycle so
        # errored tasks don't pin their records forever.
        entry = self.pending_tasks.get(spec.task_id)
        if entry is not None:
            self._finish_pending_entry(
                spec, entry, self.config.lineage_reconstruction_enabled)
        self.reference_counter.update_finished_task_references(
            spec.dependency_ids())

    # ------------------------------------------------------------- actors

    def register_actor_handle_factory(self, factory):
        self._actor_handle_factory = factory

    def create_actor(self, fn_key: str, name: str, args: List[Any],
                     actor_name: str = "", namespace: str = "",
                     max_restarts: int = 0, max_concurrency: int = 1,
                     resources: Dict[str, float] | None = None,
                     lifetime_resources: Dict[str, float] | None = None,
                     is_asyncio: bool = False,
                     placement_group_id: bytes = b"",
                     placement_group_bundle_index: int = -1,
                     max_pending_calls: int = -1,
                     runtime_env: Dict | None = None) -> bytes:
        _check_tpu_demand(resources, actor=True)
        actor_id = ActorID.of(JobID(self.job_id)).binary()
        prepared_args, arg_holds = self._prepare_args(args)
        spec = TaskSpec(
            task_id=TaskID.of(ActorID(actor_id)).binary(), job_id=self.job_id,
            task_type=TASK_ACTOR_CREATION, name=name, fn_key=fn_key,
            args=prepared_args, num_returns=0,
            resources=resources or {"CPU": 1.0},
            owner_address=self.address, owner_worker_id=self.worker_id,
            actor_id=actor_id,
            runtime_env=self._resolve_runtime_env(runtime_env),
            actor_creation={"max_restarts": max_restarts,
                            "max_concurrency": max_concurrency,
                            "is_asyncio": is_asyncio,
                            "name": actor_name, "namespace": namespace},
            placement_group_id=placement_group_id,
            placement_group_bundle_index=placement_group_bundle_index)
        header, frames = spec.to_wire_dict()
        header["lifetime_resources"] = lifetime_resources
        header["pg_id"] = placement_group_id
        header["pg_bundle"] = placement_group_bundle_index
        self._run(self._gcs_call("RegisterActor", {
            "actor_id": actor_id, "spec": header,
            "name": actor_name, "namespace": namespace,
            "max_restarts": max_restarts, "job_id": self.job_id,
        }, bufs=frames))
        q = ActorQueueState(actor_id)
        q.max_pending = max_pending_calls
        self.actor_queues[actor_id] = q
        # Actor-creation args stay pinned for the actor's restarts: keep the
        # holds on the queue state (freed when the queue is dropped).
        q.creation_arg_holds = arg_holds  # type: ignore[attr-defined]
        return actor_id

    def submit_actor_task(self, actor_id: bytes, fn_key: str, name: str,
                          args: List[Any], num_returns: int = 1,
                          max_task_retries: int = 0) -> List[ObjectRef]:
        # (4) backpressure: enforce max_pending_calls before queueing.
        self._check_actor_backpressure(actor_id)
        task_id = TaskID.of(ActorID(actor_id))
        prepared_args, arg_holds = self._prepare_args(args)
        spec = TaskSpec(
            task_id=task_id.binary(), job_id=self.job_id,
            task_type=TASK_ACTOR, name=name, fn_key=fn_key,
            args=prepared_args, num_returns=num_returns,
            resources={}, max_retries=max_task_retries,
            owner_address=self.address, owner_worker_id=self.worker_id,
            actor_id=actor_id, trace_ctx=_trace_ctx())
        return self._register_and_submit_actor(spec, arg_holds)

    def make_actor_template(self, actor_id: bytes, fn_key: str, name: str,
                            num_returns: int = 1,
                            max_task_retries: int = 0) -> TaskSpec:
        """Prototype spec for repeated calls of one actor method (the
        actor-side twin of make_task_template): per-call work drops to
        id mint + clone — or the native fused submit."""
        return TaskSpec(
            task_id=b"", job_id=self.job_id,
            task_type=TASK_ACTOR, name=name, fn_key=fn_key,
            args=[], num_returns=num_returns,
            resources={}, max_retries=max_task_retries,
            owner_address=self.address, owner_worker_id=self.worker_id,
            actor_id=actor_id)

    def _check_actor_backpressure(self, actor_id: bytes) -> None:
        q = self.actor_queues.get(actor_id)
        if q is not None and q.max_pending >= 0 and \
                len(q.buffer) + len(q.inflight) >= q.max_pending:
            raise exc.PendingCallsLimitExceeded(
                f"actor has {len(q.buffer) + len(q.inflight)} pending calls "
                f"(max_pending_calls={q.max_pending})")

    def submit_actor_from_template(self, proto: TaskSpec
                                   ) -> List[ObjectRef]:
        """Arg-less actor call on a cached template (backpressure
        checked, then the fused native path when built — single-return
        only, same gate as submit_task_from_template)."""
        actor_id = proto.actor_id
        self._check_actor_backpressure(actor_id)
        if proto.num_returns == 1:
            ctx = self._fast_ctx
            if ctx is None and not self._fast_ctx_failed:
                ctx = self._make_fast_ctx()
            if ctx is not None:
                # SUBMITTED recorded loop-side (_drain_submit_buffer)
                return ctx.submit(proto, actor_id, _trace_ctx(), True)
        spec = proto.clone_for(make_task_id_bytes(actor_id), (),
                               trace_ctx=_trace_ctx())
        return self._register_and_submit_actor(spec, None)

    def _register_and_submit_actor(self, spec: TaskSpec, arg_holds
                                   ) -> List[ObjectRef]:
        task_id = TaskID(spec.task_id)
        num_returns = spec.num_returns
        return_ids = [task_id.object_id(i + 1) for i in range(num_returns)]
        refs = []
        for oid in return_ids:
            self.reference_counter.add_owned_with_local_ref(oid)
            refs.append(ObjectRef(oid, owner_address=self.address, worker=self,
                                  call_site=spec.name,
                                  skip_adding_local_ref=True))
        entry = PendingTaskEntry(spec, return_ids)
        self.pending_tasks[spec.task_id] = entry
        if entry.dep_ids:
            self.reference_counter.update_submitted_task_references(
                entry.dep_ids)
        del arg_holds
        self.stats["actor_tasks_submitted"] += 1
        # SUBMITTED recorded loop-side by _drain_submit_buffer
        # Seqno assignment happens at drain time in buffer order, which is
        # submission order (the receiver executes strictly by seqno). By-ref
        # args resolve at the executing worker — the owner's GetObject blocks
        # until the value exists — so no client-side dependency wait is
        # needed, and ordering can't be inverted by slow dependencies.
        self._enqueue_submit("actor", spec)
        return refs

    def _pump_actor_queue(self, q: ActorQueueState):
        if q.state == "DEAD":
            for spec, _ in q.buffer:
                self._store_error_for_task(
                    spec, exc.ActorDiedError(q.death_cause or "actor is dead",
                                             cause=q.death_info))
            q.buffer.clear()
            return
        if q.conn is None or q.conn.closed:
            if not q.resolving:
                q.resolving = True
                rpc.spawn_logged(self._resolve_actor(q),
                                 "worker-resolve-actor", loop=self.loop)
            return
        if not q.buffer:
            return
        # Drain the whole buffer into ONE wire message (same batching as
        # the normal-task path); seqnos stay per-task for the receiver's
        # reorder buffer.
        theaders: List[list] = []
        frames: List[bytes] = []
        batch: List[Tuple[TaskSpec, int]] = []
        ev = self.task_events
        ev_attrs = {"actor": q.actor_id.hex()[:12]} if ev.enabled else None
        while q.buffer:
            spec, seqno = q.buffer.popleft()
            q.inflight[seqno] = (spec, 0)
            if ev_attrs is not None:
                ev.record(spec.task_id, DISPATCHED, ev_attrs)
            tw, tfr = spec.to_wire()
            theaders.append([tw, seqno, len(frames), len(tfr)])
            frames.extend(tfr)
            batch.append((spec, seqno))
        try:
            fut = q.conn.call_nowait(
                "PushActorTasks",
                {"tasks": theaders, "incarnation": q.incarnation},
                bufs=frames)
        except ConnectionError:
            # Conn-lost handler requeues the inflight entries.
            return
        fut.add_done_callback(
            lambda f, batch=batch: self._on_actor_batch_done(f, q, batch))

    async def _resolve_actor(self, q: ActorQueueState):
        from ray_tpu._private import backoff as backoff_mod

        try:
            deadline = time.monotonic() + 120.0
            # exponential-jitter retry pacing (backoff.py): starts at
            # the old 0.05 s fast path, backs off toward the cap while
            # the actor is restarting / the GCS is down — no more
            # fixed-interval polling storms from every holder of a
            # handle to a restarting actor
            bo = backoff_mod.from_config(self.config)
            while time.monotonic() < deadline:
                if q.conn is not None and not q.conn.closed and \
                        q.state == "ALIVE":
                    return  # a concurrent resolve already connected
                if self._shutdown:
                    return
                # _gcs_call redials a restarting GCS — do NOT bail on a
                # closed gcs_conn here, or buffered actor calls would
                # hang with no retry timer.
                try:
                    reply, _ = await self._gcs_call(
                        "GetActorInfo", {"actor_id": q.actor_id})
                except ConnectionError:
                    await bo.sleep()  # GCS still down; keep trying
                    continue
                if not reply.get("found"):
                    await bo.sleep()
                    continue
                if reply["state"] == "ALIVE" and \
                        reply["incarnation"] != q.incarnation:
                    try:
                        q.conn = await rpc.connect(
                            reply["address"], peer_name="actor",
                            handlers={"ActorTaskResult":
                                      self._actor_result_handler(q)})
                    except ConnectionError:
                        await bo.sleep()
                        continue
                    q.address = reply["address"]
                    q.state = "ALIVE"
                    restarted = q.incarnation != -1
                    q.incarnation = reply["incarnation"]
                    if restarted:
                        # Fresh worker expects seqno 0: renumber the stream
                        # (reference: the submitter resets sequence state on
                        # actor restart, direct_actor_transport.h).
                        q.buffer = deque(
                            (spec, i)
                            for i, (spec, _) in enumerate(q.buffer))
                        q.seqno = len(q.buffer)
                    q.conn.on_disconnect.append(
                        lambda c, q=q: self._on_actor_conn_lost(q, c))
                    self._pump_actor_queue(q)
                    return
                if reply["state"] == "DEAD":
                    q.state = "DEAD"
                    q.death_cause = reply.get("death_cause", "actor died")
                    q.death_info = reply.get("death_info") or {}
                    self._pump_actor_queue(q)
                    return
                await bo.sleep()
            q.state = "DEAD"
            q.death_cause = "timed out resolving actor location"
            q.death_info = {"kind": "RESOLVE_TIMEOUT"}
            self._pump_actor_queue(q)
        finally:
            q.resolving = False

    def _on_actor_conn_lost(self, q: ActorQueueState,
                            conn: Optional[rpc.Connection] = None):
        """Actor worker connection dropped: requeue retryable inflight tasks
        and re-resolve (the actor may be restarting). Tasks without retries
        fail with ActorDiedError (reference: max_task_retries semantics in
        direct_actor_transport.h)."""
        if conn is not None and q.conn is not conn:
            return  # stale disconnect from a pre-restart connection
        q.conn = None
        q.state = "RESOLVING"
        inflight = sorted(q.inflight.items())
        q.inflight.clear()
        requeue = []
        for seqno, (spec, _) in inflight:
            entry = self.pending_tasks.get(spec.task_id)
            retries_left = entry.num_retries_left if entry else 0
            if retries_left != 0:
                if entry and entry.num_retries_left > 0:
                    entry.num_retries_left -= 1
                self.stats["tasks_retried"] += 1
                if self.task_events.enabled:
                    self.task_events.record(
                        spec.task_id, RETRY,
                        {"reason": "actor connection lost"})
                requeue.append((spec, seqno))
            else:
                self._store_error_for_task(spec, exc.ActorDiedError(
                    "actor worker died before the call completed",
                    cause=q.death_info or {"kind": "WORKER_DIED"}))
        q.buffer.extendleft(reversed(requeue))
        self._pump_actor_queue(q)

    def _on_actor_batch_done(self, fut: asyncio.Future, q: ActorQueueState,
                             batch: List[Tuple[TaskSpec, int]]):
        if fut.cancelled() or fut.exception() is not None:
            # Connection lost: the conn-lost handler requeues inflight.
            return
        reply, rbufs = fut.result()
        if reply.get("streamed"):
            # Concurrent actor: per-task results arrive as
            # ActorTaskResult pushes (see _actor_result_handler);
            # entries stay inflight until theirs lands.
            return
        requeue: List[Tuple[TaskSpec, int]] = []
        for (spec, seqno), (rheader, fstart, nframes) in zip(
                batch, reply["replies"]):
            q.inflight.pop(seqno, None)
            if rheader[0] == REPLY_ACTOR_RESTARTING:
                requeue.append((spec, seqno))
                continue
            self._complete_task(spec, rheader, rbufs[fstart:fstart + nframes])
            if spec.args:
                self.reference_counter.update_finished_task_references(
                    spec.dependency_ids())
        if requeue:
            q.buffer.extendleft(reversed(requeue))

    def _actor_result_handler(self, q: ActorQueueState):
        """Push handler resolving one streamed actor-task result
        (concurrent actors reply per task, not per batch)."""
        async def handler(conn, header, bufs):
            if q.conn is not conn:
                return  # stale pre-restart connection
            seqno = header["seqno"]
            entry = q.inflight.get(seqno)
            if entry is None:
                return  # already requeued by a conn-loss race
            spec, _ = entry
            rheader = header["reply"]
            q.inflight.pop(seqno, None)
            if rheader[0] == REPLY_ACTOR_RESTARTING:
                q.buffer.append((spec, seqno))
                self._pump_actor_queue(q)
                return
            self._complete_task(spec, rheader, list(bufs))
            if spec.args:
                self.reference_counter.update_finished_task_references(
                    spec.dependency_ids())
        return handler

    def cancel(self, ref: ObjectRef, force: bool = False):
        """Best-effort task cancel (reference: CoreWorker::CancelTask):
        drop it from the local queue if not yet pushed, else ask every
        leased worker of the scheduling class to cancel."""
        self._run(self._cancel_async(ref))

    async def _cancel_async(self, ref: ObjectRef):
        entry = self.pending_tasks.get(ref.object_id.task_id().binary())
        if entry is None:
            return
        state = self.scheduling_keys.get(entry.spec.scheduling_class)
        if state is None:
            return
        if entry.spec in state.queue:
            state.queue.remove(entry.spec)
            self._store_error_for_task(
                entry.spec, exc.TaskCancelledError(entry.spec.name))
            return
        for lw in state.workers:
            try:
                await lw.conn.call("CancelTask",
                                   {"task_id": entry.spec.task_id})
            except ConnectionError:
                pass

    def kill_actor(self, actor_id: bytes, no_restart: bool = True):
        self._run(self._gcs_call("KillActor", {
            "actor_id": actor_id, "no_restart": no_restart}))

    async def _metrics_report_loop(self):
        """Ship this process's user-metric registry AND buffered
        task-lifecycle events to the GCS on a timer (reference:
        per-process OpenCensus exporter → metrics agent,
        stats/metric.h + metrics_agent.py; TaskEventBuffer's periodic
        GCS flush, task_event_buffer.h). Task events ride this existing
        cadence — never a per-transition RPC."""
        from ray_tpu._private import metrics as metrics_mod

        period = self.config.metrics_report_period_ms / 1000.0
        reporter = f"{self.mode}-{WorkerID(self.worker_id).hex()[:12]}"
        # This CoreWorker ships the process-global registry; an
        # in-process raylet (head node) must not ship it again.
        metrics_mod.mark_core_reporter()
        while not self._shutdown:
            await asyncio.sleep(period)
            # loop-lag probe rides this existing cadence (the
            # instrumented_io_context tick for worker/driver loops)
            rpc.telemetry.loop_probe("core").tick()
            snap = metrics_mod.global_registry().snapshot()
            if rpc.telemetry.enabled:
                # per-method RPC latency histograms merge into the same
                # registry shipment (real Prometheus histograms on the
                # GCS endpoint, no new transport)
                snap.update(rpc.telemetry.prom_snapshot())
            if snap:
                try:
                    await self._gcs_call("ReportMetrics", {
                        "reporter_id": reporter, "snapshot": snap})
                except (ConnectionError, asyncio.TimeoutError):
                    pass  # GCS restarting; next period retries
            await self._flush_rpc_telemetry(reporter)
            await self._flush_task_events()
            await self._flush_object_events()
            await self._flush_cluster_events()

    async def _flush_rpc_telemetry(self, reporter: str):
        """Ship this process's flight-recorder snapshot + drained slow
        calls (claiming the process's reporter role — an in-process
        raylet skips its heartbeat copy via metrics.core_reporter, the
        same single-shipper rule the metric registry uses)."""
        if not rpc.telemetry.enabled:
            return
        slow, dropped = rpc.telemetry.drain_slow_calls()
        try:
            await self._gcs_call(
                "ReportRpcTelemetry",
                protocol.ReportRpcTelemetryRequest(
                    reporter_id=reporter,
                    snapshot=rpc.telemetry.wire(probe="core"),
                    slow_calls=slow,
                    slow_calls_dropped=dropped).to_header())
        except (ConnectionError, asyncio.TimeoutError):
            pass  # GCS restarting; gauges re-ship next period
        except Exception:  # noqa: BLE001
            # a not-yet-upgraded GCS without the handler (rolling
            # upgrade): the wire error must not kill the metrics loop
            logger.debug("ReportRpcTelemetry flush failed", exc_info=True)

    async def _flush_cluster_events(self):
        """Drain the cluster-event buffer to the GCS event table (same
        contract as _flush_task_events: bounded batch, a flush lost to
        a restarting GCS is bounded loss by design)."""
        events, dropped = self.cluster_events.drain()
        if not events and not dropped:
            return
        try:
            await self._gcs_call(
                "AddClusterEvents",
                protocol.AddClusterEventsRequest(
                    events=events, dropped=dropped).to_header())
        except (ConnectionError, asyncio.TimeoutError):
            pass  # GCS restarting; bounded loss
        except Exception:  # noqa: BLE001
            # a not-yet-upgraded GCS without the AddClusterEvents
            # handler must not kill the metrics-report loop
            logger.debug("AddClusterEvents flush failed", exc_info=True)

    async def _flush_object_events(self):
        """Drain the object-event buffer to the GCS object table (same
        contract as _flush_task_events: bounded batch, a flush lost to
        a restarting GCS is bounded loss by design)."""
        events, dropped = self.object_events.drain_wire()
        if not events and not dropped:
            return
        try:
            await self._gcs_call(
                "AddObjectEvents",
                protocol.AddObjectEventsRequest(
                    events=events, dropped=dropped).to_header())
        except (ConnectionError, asyncio.TimeoutError):
            pass  # GCS restarting; bounded loss
        except Exception:  # noqa: BLE001
            # e.g. a not-yet-upgraded GCS without the AddObjectEvents
            # handler (rolling upgrade): the error re-raised off the
            # wire must not escape the metrics-report loop and kill
            # metrics + task-event shipping for the worker's lifetime
            logger.debug("AddObjectEvents flush failed", exc_info=True)

    async def _flush_task_events(self):
        """Drain the task-event buffer to the GCS task table (the
        batch is bounded by the buffer capacity; a flush lost to a
        restarting GCS is bounded event loss, by design —
        observability never blocks or retries forever)."""
        events, dropped = self.task_events.drain_wire()
        if not events and not dropped:
            return
        try:
            await self._gcs_call(
                "AddTaskEvents",
                protocol.AddTaskEventsRequest(
                    events=events, dropped=dropped,
                    job_id=self.job_id).to_header())
        except (ConnectionError, asyncio.TimeoutError):
            pass  # GCS restarting; bounded loss

    async def _handle_published(self, conn, header, bufs):
        if header["channel"] == "LOGS":
            msg = header["msg"]
            prefix = f"(pid={msg['pid']}, {msg['ip']})"
            for line in msg["lines"]:
                print(f"{prefix} {line}", flush=True)
            return {}
        if header["channel"] == "ACTOR":
            msg = header["msg"]
            q = self.actor_queues.get(msg["actor_id"])
            if q is None:
                return {}
            if msg["state"] == "ALIVE" and msg["incarnation"] != q.incarnation:
                if not q.resolving:
                    q.resolving = True
                    rpc.spawn_logged(self._resolve_actor(q),
                                     "worker-resolve-actor")
            elif msg["state"] == "DEAD":
                q.state = "DEAD"
                q.death_cause = msg.get("reason", "actor died")
                q.death_info = msg.get("death_info") or {}
                self._pump_actor_queue(q)
            elif msg["state"] == "RESTARTING":
                q.state = "RESOLVING"
        return {}

    # ------------------------------------------------------------ profiling

    def add_task_event(self, event: dict):
        if self.config.profiling_enabled:
            self._task_events.append(event)

    def add_exec_event(self, name: str, task_id: bytes,
                       start: float, end: float):
        """Hot-path execution event: append a TUPLE; the dict form (with
        hex ids) is built lazily at flush time, off the per-task path."""
        self._task_events.append(("task:execute", name, task_id, start, end))

    async def _profile_flush_loop(self):
        period = self.config.metrics_report_period_ms / 1000.0
        while not self._shutdown:
            await asyncio.sleep(period)
            # reap shm mappings whose last zero-copy consumer view has
            # been garbage-collected since the store detached (the
            # park-and-sweep half of the view-release discipline —
            # shm_store._QuietSharedMemory)
            try:
                from ray_tpu._private import shm_store
                shm_store.sweep_zombies()
            # raylint: disable=exception-hygiene — maintenance loop must not die
            except Exception:
                pass
            if self._task_events and self.gcs_conn and not self.gcs_conn.closed:
                events, self._task_events = self._task_events, []
                wid = self.worker_id.hex()
                events = [
                    {"event": e[0], "name": e[1], "task_id": e[2].hex(),
                     "start": e[3], "end": e[4], "worker_id": wid}
                    if type(e) is tuple else e
                    for e in events]
                try:
                    await self.gcs_conn.call("AddProfileEvents",
                                             {"events": events})
                except ConnectionError:
                    return
