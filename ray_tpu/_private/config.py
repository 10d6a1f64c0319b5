"""Typed runtime config registry.

Equivalent of the reference's RAY_CONFIG flag registry
(reference: src/ray/common/ray_config_def.h): every tunable is a typed entry
with a default, overridable by (priority order) an explicit
``_system_config`` dict passed to ``init()``/process argv, then the
``RAY_TPU_<NAME>`` environment variable.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field, fields
from typing import Any, Dict


def _env_override(name: str, typ, default):
    raw = os.environ.get(f"RAY_TPU_{name.upper()}")
    if raw is None:
        return default
    if typ is bool:
        return raw.lower() in ("1", "true", "yes", "on")
    if typ is int:
        return int(raw)
    if typ is float:
        return float(raw)
    return raw


@dataclass
class RayTpuConfig:
    # --- object plane ---
    # Values at or below this size are returned/passed inline through the
    # owner's in-process memory store rather than the shared-memory store
    # (reference: max_direct_call_object_size, ray_config_def.h).
    max_direct_call_object_size: int = 100 * 1024
    # Size of the shared-memory object store arena per node, bytes.
    object_store_memory: int = 512 * 1024 * 1024
    # Fraction of the store that may be used before create requests block.
    object_store_full_delay_ms: int = 10
    # Enable spilling objects to disk when the store fills.
    object_spilling_enabled: bool = True
    spill_path: str = ""
    # External spill target (reference: external_storage.py S3 via
    # smart_open): a workflow-storage URL (file:///shared, kv://, or
    # s3://bucket/prefix) that overrides the local spill dir.
    spill_external_storage_url: str = ""
    # Chunk size for node-to-node object transfer. This is the FLOOR of
    # the data plane's adaptive chunking (and the fixed chunk of the
    # legacy control-plane pull): large objects scale their chunk up to
    # data_plane_max_chunk_size so per-chunk request overhead amortizes.
    object_manager_chunk_size: int = 1024 * 1024
    # Striped raw-socket data channels per peer for cross-node object
    # pulls (the bulk transport under the msgpack control plane; see
    # data_channel.py). Chunks fan out across the stripes — and across
    # every replica-holding peer — and land directly in the destination
    # shm mapping (one copy per chunk). 0 disables the data plane
    # entirely: pulls fall back to chunked FetchObjectChunk RPCs on the
    # shared control connection (the pre-data-plane path).
    data_plane_stripes: int = 4
    # Ceiling of the adaptive per-chunk size on the striped data plane.
    # object_manager_chunk_size stays the floor; multi-GiB objects use
    # chunks up to this size so the transfer is syscall-bound, not
    # round-trip-bound.
    data_plane_max_chunk_size: int = 8 * 1024 * 1024
    # When every known location of an object fails mid-pull, the raylet
    # re-queries the owner's location index after a backoff — a replica
    # added meanwhile (e.g. by a concurrent pull elsewhere) is found
    # instead of erroring the get. This is the BASE delay of the
    # exponential-jitter policy (backoff.py); the refresh is attempted
    # pull_location_refresh_attempts times.
    pull_location_refresh_backoff_s: float = 0.2
    # How many location-refresh rounds a failing pull gets before the
    # get errors (1 preserves the original one-shot refresh; each extra
    # round backs off exponentially from
    # pull_location_refresh_backoff_s up to retry_backoff_cap_s).
    pull_location_refresh_attempts: int = 1

    # --- scheduling ---
    # Pipeline depth CEILING for pushing tasks to a leased worker before
    # waiting for replies (reference: max_tasks_in_flight_per_worker;
    # far deeper here — the batched submit/reply path amortizes bursts:
    # measured 16.7k/s at 32, plateau 22.2k/s at 512 on the task
    # microbenchmark). The transport fills BREADTH-first: batches are
    # sized to an even split over current+pending workers, and this cap
    # only bites once the cluster stops granting leases.
    max_tasks_in_flight_per_worker: int = 512
    # Outstanding lease requests per scheduling class (reference:
    # max_pending_lease_requests_per_scheduling_category); requested in
    # proportion to the backlog, ~one per 8 queued tasks.
    max_pending_leases_per_scheduling_class: int = 16
    # How long an idle leased worker is kept before returning it to the
    # pool. Returning instantly makes every sync-loop task pay a fresh
    # lease round trip through the raylet (~500us of the sync row).
    idle_lease_keepalive_s: float = 0.2
    # Hybrid policy: prefer the local/first node until its utilization
    # exceeds this threshold, then spread (reference: scheduler_spread_threshold).
    scheduler_spread_threshold: float = 0.5
    # Which scheduler backend the raylet uses: "host" (dict/heap reference
    # implementation) or "tpu_batched" (JAX batched frontier/scoring kernel).
    scheduler_backend: str = "host"
    # What happens to a task no node can currently satisfy: "fail" the
    # lease (fast feedback) or "wait" in the queue until capacity
    # appears — dynamic resources / autoscaled nodes (the reference
    # keeps infeasible tasks pending and warns).
    infeasible_task_policy: str = "fail"
    # Max tasks the batched backend scores per tick.
    scheduler_batch_size: int = 4096
    # Lease reuse: keep an idle leased worker this long before returning it.
    idle_worker_lease_timeout_ms: int = 2000

    # --- streaming lease credits ---
    # Master switch for streaming leases. On (the default) the raylet
    # pre-grants each owner a revocable CREDIT WINDOW of worker slots
    # per scheduling class — leases as a flow-controlled stream instead
    # of a per-lease request/grant ping-pong. The owner's submit path
    # (including the C fastpath) dispatches tasks against local credits
    # with zero control-plane round-trips on the hot path and falls
    # back to the legacy RequestWorkerLease path when credits are
    # exhausted, revoked, or this knob is off. Wire frames:
    # GrantLeaseCredits (raylet -> owner push: credits + window target,
    # issued on demand registration and renewed on the heartbeat
    # cadence) and RevokeLeaseCredits (raylet -> owner call: the owner
    # relinquishes the listed credits it is not using; in-use ones are
    # kept and reconciled on a later beat). Memory pressure (PR10)
    # zeroes and revokes windows BEFORE lease backpressure engages —
    # revocation is a first-class recovery path, chaos-soaked by the
    # credit_revoke schedule.
    lease_credits_enabled: bool = True
    # Ceiling on credit worker-slots outstanding per (owner connection,
    # scheduling class). The actual window is sized from the owner's
    # reported backlog and the REAL scheduler view (cluster slot
    # capacity for the window's resource shape), clamped by this.
    lease_credit_window_max: int = 64
    # Unused-credit reclaim cadence: a window whose demand report is
    # older than this gets its outstanding credits offered back via
    # RevokeLeaseCredits on the next heartbeat (the owner keeps the
    # ones it is actively using). Bounds how long an idle owner can
    # park pool slots it no longer needs.
    lease_credit_stale_s: float = 2.0

    # --- SPMD gangs & distributed arrays ---
    # How many times the driver re-asks for a gang lease after an
    # all-or-nothing booking round came back short (retry_later). Each
    # rejection prestarts workers toward the deficit on the raylets
    # that ran dry, so retries converge instead of re-probing the same
    # empty pool; the wait between rounds follows the shared
    # exponential-jitter policy (backoff.py) starting from
    # gang_lease_retry_backoff_s. 0 = a single attempt, fail fast.
    gang_lease_retry_attempts: int = 20
    # BASE delay between gang-lease booking rounds (exponential-jitter
    # up to retry_backoff_cap_s). Short by default: the common cause of
    # a short round is workers still forking, which resolves in tens of
    # milliseconds.
    gang_lease_retry_backoff_s: float = 0.1
    # Per-member worker-socket dial timeout when the driver adopts a
    # freshly granted gang. A member that cannot be dialed inside this
    # window fails the formation (the whole gang is released — all-or-
    # nothing extends to adoption, not just booking).
    gang_member_dial_timeout_s: float = 5.0
    # Per-run override of the striped chunk size for GatherShards
    # collective transfers (reshard / all-gather / all-reduce). 0 (the
    # default) keeps the pull path's adaptive sizing:
    # object_manager_chunk_size floor, data_plane_max_chunk_size
    # ceiling, ~8 chunks per stripe lane.
    reshard_chunk_bytes: int = 0
    # Which algorithm all_reduce / all_gather use when every precondition
    # holds: "ring" (the default — bandwidth-optimal reduce-scatter +
    # all-gather, per-rank wire traffic 2*(P-1)/P*N bytes) or "fold"
    # (the PR15 single-destination GatherShards path, (P-1)*N per
    # destination). Ring silently falls back to fold when it cannot
    # apply: fewer than 3 ranks, data plane off
    # (data_plane_stripes=0), or a source layout whose segments the
    # ring math cannot partition (see the README fallback matrix).
    collective_algorithm: str = "ring"
    # Per-member scratch WINDOW size for the pipelined ring fold: each
    # reduce step double-buffers two windows of this size so segment
    # bytes for window k+1 stream off the wire while window k folds in
    # an executor thread. Bigger windows amortize per-window overhead;
    # smaller ones overlap sooner and cap the fold's cache footprint.
    # Segments smaller than the window use one exact-size buffer pair.
    collective_scratch_bytes: int = 16 * 1024 * 1024
    # How long a ring-collective member record (and its leased
    # accumulator segment) may sit idle before the raylet's
    # opportunistic sweep discards it. Members are normally freed by
    # RingFinish/RingAbort; the TTL only catches a driver that died
    # between rounds without aborting.
    collective_member_ttl_s: float = 120.0

    # --- worker pool ---
    # Hard cap on workers started per node (0 = num_cpus).
    max_workers_per_node: int = 0
    # Workers prestarted at node boot. -1 = auto: one per CPU (the
    # reference's PrestartWorkers heuristic, worker_pool.h:94 — cold
    # leases then never pay process-start latency). 0 disables.
    num_prestart_workers: int = -1
    worker_register_timeout_s: float = 30.0
    # Zygote worker factory (zygote.py): one forkserver-style template
    # process per raylet pre-imports the worker module graph and
    # pre-builds the native fastpath, then fork()s per spawn request —
    # worker/actor startup and post-kill recovery become milliseconds
    # instead of a full interpreter boot (bench.py worker_spawn row).
    # Takes effect only where forking is safe: Linux, and only for pool
    # workers, which are pinned to CPU jax. A worker started for a
    # `TPU` lease is always a cold Popen: an accelerator client must
    # never be forked. Cold Popen is also the automatic fallback when
    # the template dies mid-session.
    worker_zygote_enabled: bool = True
    # Comma list of EXTRA modules the zygote pre-imports on top of the
    # default worker graph (core_worker, task_executor, rpc,
    # serialization, worker_main + the ray_tpu package). Keep entries
    # fork-safe: no threads, no event loops, no accelerator backends at
    # import time (jax is deliberately absent from the default list).
    zygote_preload_modules: str = ""

    # --- memory watchdog (memory_monitor.py) ---
    # Master switch for the raylet-side node memory watchdog. On (the
    # default) the raylet polls node memory on its heartbeat cadence
    # and, above memory_usage_threshold, runs the ordered degradation
    # sequence: store spill/evict pressure relief, then SIGKILL of the
    # most-recently-started retriable task's worker (surfaced to the
    # owner as a retriable OutOfMemoryError), plus lease backpressure
    # (new lease requests spill to other nodes or get a typed
    # retry-later) — instead of letting the kernel OOM killer shoot a
    # random process (often the raylet or GCS) and take the node down.
    memory_monitor_enabled: bool = True
    # Node-memory usage fraction above which the watchdog engages
    # (reference: RAY_memory_usage_threshold, default 0.95). Usage is
    # cgroup-aware: a container's memory limit wins over the host
    # total, so the threshold tracks the boundary the kernel OOM
    # killer actually enforces.
    memory_usage_threshold: float = 0.95
    # Minimum seconds between watchdog evaluations. The poll rides the
    # raylet heartbeat loop (no extra thread/timer), so the effective
    # cadence is max(this, raylet_heartbeat_period_ms). Each poll does
    # a handful of µs-scale procfs reads; bench.py's
    # memory_monitor_overhead row pins the cost under 2%.
    memory_monitor_interval_s: float = 0.5
    # Dedicated retry budget for watchdog OOM kills, SEPARATE from
    # max_retries: a task killed for memory pressure did nothing wrong
    # and shouldn't burn its worker-crash budget, but unbounded OOM
    # retries of a genuinely ballooning task would thrash the node
    # forever. Retries are paced with the shared exponential-jitter
    # backoff (backoff.py). 0 = never retry OOM kills; -1 = unlimited.
    # Non-retriable tasks (max_retries=0) always surface
    # OutOfMemoryError immediately.
    task_oom_retries: int = 3

    # --- liveness / fault tolerance ---
    raylet_heartbeat_period_ms: int = 250
    # 10s of silence marks a node dead (reference default ≈3s; wider
    # here because an in-process head under full single-host task load
    # can delay the heartbeat coroutine by seconds — GIL + loop
    # occupancy — and a false node death kills the whole bench).
    num_heartbeats_timeout: int = 40
    task_max_retries_default: int = 3
    actor_max_restarts_default: int = 0
    # Enable lineage-based reconstruction of lost shared-memory objects.
    lineage_reconstruction_enabled: bool = True
    lineage_max_bytes: int = 64 * 1024 * 1024

    # --- rpc ---
    rpc_connect_timeout_s: float = 10.0
    rpc_frame_max_bytes: int = 1 << 31
    gcs_port: int = 0
    # Append-only metadata journal for GCS restart recovery ("" = off)
    # (reference: GcsTableStorage persistence + GcsInitData reload).
    gcs_journal_path: str = ""
    # How long a raylet keeps retrying to reach a restarting GCS.
    gcs_reconnect_timeout_s: float = 60.0
    # Shared retry/backoff policy (backoff.py): every reconnect /
    # re-resolve loop (raylet->GCS redial, actor re-resolution, pull
    # location refresh) backs off exponentially with full jitter from
    # this base up to this cap, so failure storms never produce
    # fixed-interval thundering herds. Multiplier is the growth factor
    # per attempt.
    retry_backoff_base_s: float = 0.05
    retry_backoff_cap_s: float = 2.0
    retry_backoff_multiplier: float = 2.0

    # --- serving (ray_tpu/serve) ---
    # Request/response bodies at or above this size (bytes) cross the
    # proxy->replica boundary BY REFERENCE: the HTTP proxy writes the
    # body straight into shm through the AllocSegment lease path
    # (core_worker.put_async — the same recycled-segment pipeline as
    # any large put) and ships an ObjectRef, so a 100 MB upload costs
    # one shm fill instead of riding the pickle lane through the
    # control plane. Bodies below the threshold stay inline (a ref
    # round trip costs more than a small copy). 0 disables the shm
    # ingress path entirely. Large replica RETURNS need no knob: the
    # task-return plane already seals them into the store.
    serve_ingress_shm_threshold: int = 64 * 1024
    # Per-replica queue-depth cap, enforced replica-side on top of the
    # router's max_concurrent_queries flow control: a replica that
    # somehow accumulates more than max_concurrent_queries +
    # serve_max_queue_depth in-flight calls (several independent
    # routers, a handle that bypassed flow control) sheds the excess
    # with the typed ServeOverloadedError instead of queueing without
    # bound. Also the default queue cap of a DecodeScheduler built by
    # a replica that doesn't pass its own.
    serve_max_queue_depth: int = 16
    # How long a replica's constructor may take before its deployment
    # fails (the controller kills the replica and ``deploy()`` raises):
    # the bound on a constructor that hangs. A replica that leases a
    # TPU gets ten times it: it starts a device client and makes or
    # loads its model's weights there (9 GB of weights drawn from a
    # seed took over a minute on a v5e chip whose programs were not
    # compiled yet).
    serve_replica_startup_timeout_s: float = 60.0
    # The proxy's admission-controller queue budget, as a multiple of
    # the deployment's dispatch capacity (replicas x
    # max_concurrent_queries): once waiting + in-flight requests reach
    # capacity x this factor, new requests are shed at the door with
    # 503 + Retry-After (the serving analog of the lease plane's
    # retry_later) instead of joining a backlog the replicas can never
    # drain. 2.0 = allow one full batch queued behind the one in
    # flight. Must be >= 1; larger values trade shed rate for queueing
    # latency.
    serve_shed_queue_factor: float = 2.0
    # Optional latency half of the SLO budget (seconds; 0 = queue-only
    # shedding): when set, the proxy also sheds while the deployment's
    # observed p99 (rolling per-proxy reservoir, fed to the metrics
    # registry as ray_tpu_serve_request_seconds) exceeds this budget
    # AND every replica slot is busy — a saturated deployment with
    # degraded tails sheds before the backlog doubles the damage.
    serve_shed_p99_budget_s: float = 0.0
    # Floor (seconds) of the Retry-After hint on shed responses. The
    # proxy scales the hint with the observed backlog (queue depth x
    # mean latency / capacity, capped at 30 s); this knob is the
    # minimum — and the whole hint when no latency samples exist yet.
    serve_retry_after_s: float = 1.0

    # --- observability ---
    event_log_enabled: bool = True
    metrics_report_period_ms: int = 2000
    # Task-lifecycle event recording (task_events.py): every task gets
    # a recorded state machine (SUBMITTED -> PENDING_LEASE ->
    # DISPATCHED -> RUNNING -> FINISHED|FAILED plus retry/spillback
    # annotations) surfaced by ray_tpu.state.list_tasks()/timeline().
    # ON by default — the history must exist when the straggler
    # happens; bench.py's task_events_overhead row pins the submit-path
    # cost under 5%.
    task_events_enabled: bool = True
    # Per-process event buffer capacity (events, not bytes). When full,
    # NEW transitions are dropped and counted (TaskEventBuffer.dropped
    # -> GCS dropped_events) — memory stays flat, the hot path never
    # blocks on observability. Also bounds the per-flush wire batch
    # (the whole buffer ships each reporting period): 16384 events ~=
    # 1.5 MB worst case.
    task_events_buffer_size: int = 16384
    # GCS task-table cap per job: oldest-seen tasks are evicted first
    # and the eviction is COUNTED per job (GetTaskSummary
    # evicted_tasks), so a truncated view always reports as truncated.
    task_events_max_tasks_per_job: int = 8192
    # Object-lifecycle event recording (object_events.py): the
    # object-plane twin of task_events — every plasma/borrowed/
    # contained object's lifecycle (CREATED -> SEALED/PINNED ->
    # BORROWED/PULLED/locations -> OUT_OF_SCOPE/FREED, plus
    # eviction/spill/restore and the leak-detector verdicts) recorded
    # at the layer that owns each transition and surfaced by
    # ray_tpu.state.list_objects() / summary_objects() /
    # memory_summary() / timeline(). ON by default; bench.py's
    # object_events_overhead row pins the put/get cost under 5%.
    object_events_enabled: bool = True
    # Per-process object-event buffer capacity (events, not bytes).
    # Same honest-truncation contract as task_events_buffer_size: when
    # full, NEW transitions are dropped and counted — memory stays
    # flat, the put/free hot paths never block on observability.
    object_events_buffer_size: int = 16384
    # GCS object-table cap per job (the job is read off the object id
    # prefix): oldest-seen objects are evicted first and the eviction
    # is COUNTED per job (GetObjectSummary evicted_objects) — a
    # truncated view always reports as truncated.
    object_events_max_objects_per_job: int = 8192
    # Leak-detector sweep cadence (seconds; 0 disables). Each sweep the
    # raylet cross-checks store-held segments against live owner
    # references (one batched ProbeObjectLiveness per owner): an object
    # whose owner holds no reference — a dropped FreeObject, a
    # SIGKILLed owner — is flagged LEAKED (objects_leaked gauge,
    # leaked=True in list_objects()) on its second dead verdict and
    # reclaimed (freed + LEAK_RECLAIMED, counter back to 0) one sweep
    # later. Objects younger than one interval, and objects whose
    # owner cannot be judged (probe unsupported / transient error),
    # are never touched.
    leak_sweep_interval_s: float = 5.0
    # Per-method RPC telemetry (rpc.py RpcTelemetry): the control-plane
    # flight recorder. ON by default — server side records exec-time
    # percentiles, queueing delay (frame arrival -> handler start),
    # bytes in/out, in-flight and error counts per method; client side
    # records per-method call latency, timeout/redial counts and push
    # bytes; the loop-lag probe rides the existing periodic loops. All
    # bounded and drop-counted; surfaced by ray_tpu.state.list_rpc() /
    # summary_rpc(), /api/rpc, Prometheus per-method histograms, and
    # timeline() cat="rpc" slices. bench.py's rpc_telemetry_overhead
    # row pins the submit-path cost under 2%. Off = no recording at
    # all (the note paths are one bool check).
    rpc_telemetry_enabled: bool = True
    # Bounded per-(side, method) latency reservoir size (samples, not
    # bytes). Reservoirs drop OLDEST when full — percentiles are
    # recency-biased by design — and the drop count is reported
    # honestly (count - samples) in every snapshot.
    rpc_telemetry_reservoir: int = 512
    # Width (seconds) of the rotating max window behind every reported
    # max_ms (RPC telemetry AND the legacy rpc_handlers block): the max
    # covers the worst of the last one-to-two windows, so dashboards
    # reflect recent behavior instead of an all-time high-water mark
    # from a cold start a week ago.
    rpc_stats_window_s: float = 60.0
    # Slow-callback / slow-call threshold (milliseconds), the
    # instrumented-io-context analog: an RPC handler exceeding it logs
    # a WARNING naming the handler and counts into slow_callbacks; a
    # loop-lag probe sample exceeding it logs the loop occupancy; and
    # any server/client call above it becomes a bounded slow-call
    # record that timeline() renders as a cat="rpc" slice on the same
    # wall clock as tasks/objects/pulls.
    loop_slow_callback_threshold_ms: float = 200.0
    # Per-process cluster-event buffer capacity (events, not bytes):
    # EventEmitter emissions (node/worker death, OOM kills, leak
    # reclaims, credit revokes, backpressure engage/clear, zygote
    # fallbacks...) buffer here and ship piggybacked on the heartbeat
    # (raylets) or the metrics-report loop (workers/drivers). When
    # full, NEW events are dropped and counted — the hot path never
    # blocks on observability.
    cluster_event_buffer_size: int = 4096
    # GCS ClusterEventTable cap: beyond it the OLDEST events are
    # evicted and the eviction is COUNTED (GetClusterEvents summary) —
    # a truncated event feed always reports as truncated. Events carry
    # a GCS-assigned monotonic seq, so ordering survives reporter
    # clock skew.
    cluster_events_max: int = 10_000
    # Cluster-KV span cap for util/tracing.py exports: beyond this many
    # stored spans the GCS evicts the OLDEST whole trace (and counts
    # the drop in the __rtpu_trace_dropped__ KV key /
    # tracing.dropped_span_count()) so long-running clusters with
    # RAY_TPU_TRACE=1 don't leak the KV and its journal. 0 = unbounded
    # (the pre-cap behavior).
    tracing_max_spans: int = 100_000
    # Prometheus text endpoint on the GCS host (0 = auto-assign; the
    # bound address lands in the KV key __rtpu_metrics_address__).
    metrics_export_port: int = 0
    profiling_enabled: bool = True
    debug_dump_period_ms: int = 10000

    extra: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def create(cls, system_config: Dict[str, Any] | None = None) -> "RayTpuConfig":
        cfg = cls()
        for f in fields(cls):
            if f.name == "extra":
                continue
            setattr(cfg, f.name, _env_override(f.name, f.type if isinstance(f.type, type) else type(getattr(cfg, f.name)), getattr(cfg, f.name)))
        if system_config:
            known = {f.name for f in fields(cls)}
            for k, v in system_config.items():
                if k in known:
                    setattr(cfg, k, v)
                else:
                    cfg.extra[k] = v
        return cfg

    def to_json(self) -> str:
        d = {f.name: getattr(self, f.name) for f in fields(self) if f.name != "extra"}
        d.update(self.extra)
        return json.dumps(d)

    @classmethod
    def from_json(cls, s: str) -> "RayTpuConfig":
        return cls.create(json.loads(s))


_global_config: RayTpuConfig | None = None


def get_config() -> RayTpuConfig:
    global _global_config
    if _global_config is None:
        _global_config = RayTpuConfig.create()
    return _global_config


def set_config(cfg: RayTpuConfig) -> None:
    global _global_config
    _global_config = cfg
