"""Public exception types raised by the runtime.

Parity targets (reference: python/ray/exceptions.py): RayError,
RayTaskError, WorkerCrashedError, ActorDiedError / RayActorError,
ObjectLostError, GetTimeoutError, TaskCancelledError, ObjectStoreFullError,
RuntimeEnvSetupError.
"""

from __future__ import annotations


class RayTpuError(Exception):
    """Base class for all framework errors."""


class RayTaskError(RayTpuError):
    """A task raised an exception on a remote worker.

    The remote traceback is captured as a string and re-raised at every
    ``get`` of any object whose lineage includes the failed task.
    """

    def __init__(self, function_name: str = "", traceback_str: str = "",
                 cause: Exception | None = None):
        self.function_name = function_name
        self.traceback_str = traceback_str
        self.cause = cause
        # Exception directly, NOT super(): as_instanceof_cause builds a
        # (RayTaskError, cause_cls) diamond, and the cooperative chain
        # would feed these two positional strings into cause_cls.__init__
        # (ValueError from dict("traceback...") for cause-bearing types).
        Exception.__init__(self, function_name, traceback_str)

    def __str__(self):
        msg = f"task {self.function_name} failed"
        if self.traceback_str:
            msg += f"\n{self.traceback_str}"
        return msg

    def as_instanceof_cause(self) -> Exception:
        """Return an exception that is also an instance of the cause's type,
        so ``except UserError`` works across process boundaries."""
        cause = self.cause
        if cause is None or isinstance(cause, RayTaskError):
            return self
        cause_cls = type(cause)
        if cause_cls is RayTaskError:
            return self
        try:
            derived = type(
                "RayTaskError(" + cause_cls.__name__ + ")",
                (RayTaskError, cause_cls),
                {"__init__": RayTaskError.__init__, "__str__": RayTaskError.__str__},
            )
            err = derived(self.function_name, self.traceback_str, cause)
            # The wrapper IS an instance of the cause's type, so it
            # must answer for its attributes too (cause_info /
            # cause_kind / object_id_hex ...): cause_cls.__init__ never
            # ran on it, so graft the cause's state across.
            for k, v in vars(cause).items():
                err.__dict__.setdefault(k, v)
            return err
        except TypeError:
            return self


class TaskCancelledError(RayTpuError):
    """The task was cancelled before or during execution."""


class WorkerCrashedError(RayTpuError):
    """The worker process executing the task died unexpectedly."""


def _format_cause(cause: dict) -> str:
    """Render a structured death cause for the message tail:
    ``[WORKER_DIED node=ab12cd worker=ef34..]``."""
    if not cause:
        return ""
    parts = [str(cause.get("kind", "UNKNOWN"))]
    for key, label in (("node_id", "node"), ("worker_id", "worker"),
                       ("last_failure", "after"), ("restarts", "restarts")):
        v = cause.get(key)
        if v not in (None, "", 0) or (key == "restarts" and v == 0 and
                                      cause.get("kind") ==
                                      "RESTARTS_EXHAUSTED"):
            parts.append(f"{label}={v}")
    return " [" + " ".join(parts) + "]"


class ActorDiedError(RayTpuError):
    """The actor is dead: creation failed, it exhausted restarts, or its
    node/worker died and max_restarts was 0.

    ``cause`` is the structured death cause recorded by the GCS actor
    table (and stamped into the task-event FAILED record shown by
    ``ray_tpu.state.list_tasks()``)::

        {"kind": "NODE_DIED" | "WORKER_DIED" | "RESTARTS_EXHAUSTED"
                 | "CREATION_FAILED" | "ACTOR_EXITED" | "KILLED",
         "node_id": hex, "worker_id": hex, "message": str,
         "restarts": int, "max_restarts": int,
         "last_failure": str}   # RESTARTS_EXHAUSTED: the final straw
    """

    def __init__(self, reason: str = "actor died", cause: dict | None = None):
        self.reason = reason
        self.cause_info = dict(cause or {})
        super().__init__(reason + _format_cause(self.cause_info))

    @property
    def cause_kind(self) -> str:
        return str(self.cause_info.get("kind", ""))


# Alias matching the reference's name.
RayActorError = ActorDiedError


class ObjectLostError(RayTpuError):
    """All copies of the object were lost and reconstruction failed or was
    disabled.

    ``cause`` mirrors :class:`ActorDiedError`'s structured death cause,
    with object-plane kinds: ``NO_OWNER`` / ``OWNER_UNREACHABLE`` /
    ``OWNER_RELEASED`` / ``PULL_FAILED`` / ``RECOVERY_FAILED``."""

    def __init__(self, object_id_hex: str = "", reason: str = "",
                 cause: dict | None = None):
        self.object_id_hex = object_id_hex
        self.reason = reason
        self.cause_info = dict(cause or {})
        super().__init__(f"object {object_id_hex} lost: {reason}"
                         + _format_cause(self.cause_info))

    @property
    def cause_kind(self) -> str:
        return str(self.cause_info.get("kind", ""))


class OutOfMemoryError(RayTpuError):
    """The node memory watchdog killed the worker executing the task.

    Raised at ``get`` once the task's dedicated OOM retry budget
    (``task_oom_retries``) is exhausted — or immediately for a
    non-retriable task (``max_retries=0``). Unlike a kernel OOM kill,
    this is an *ordered* eviction: store spill/evict pressure relief ran
    first, the raylet and GCS survive, and the kill is retriable.

    ``cause`` mirrors :class:`ActorDiedError`'s structured death cause::

        {"kind": "WORKER_OOM", "node_id": hex, "worker_id": hex,
         "usage_fraction": float, "threshold": float,
         "workers_rss": {worker_id12: rss_bytes, ...},  # at kill time
         "message": str}
    """

    def __init__(self, reason: str = "worker killed by the node memory "
                 "watchdog", cause: dict | None = None):
        self.reason = reason
        self.cause_info = dict(cause or {})
        super().__init__(reason + _format_cause(self.cause_info))

    @property
    def cause_kind(self) -> str:
        return str(self.cause_info.get("kind", ""))


class ObjectStoreFullError(RayTpuError):
    """The shared-memory object store cannot fit the object even after
    eviction and spilling."""


class GetTimeoutError(RayTpuError, TimeoutError):
    """``get`` timed out before the object was available."""


class RuntimeEnvSetupError(RayTpuError):
    """Setting up the task/actor runtime environment failed."""


class RaySystemError(RayTpuError):
    """Internal system failure (e.g. a control-plane process died)."""


class PendingCallsLimitExceeded(RayTpuError):
    """Actor max_pending_calls exceeded."""


class ServeOverloadedError(RayTpuError):
    """Serve shed the request at admission instead of queueing it.

    The serving plane's typed analog of the lease protocol's
    ``retry_later`` backpressure verdict: a replica's queue-depth cap or
    the proxy's SLO budget (queue depth x observed latency) was
    exceeded, so the request was refused AT THE DOOR — the in-flight
    decode batch keeps its cadence instead of collapsing under a
    backlog it can never drain. ``retry_after_s`` is the server's
    backoff hint; the HTTP proxy renders this error as
    ``503 Service Unavailable`` with a ``Retry-After`` header.
    """

    def __init__(self, reason: str = "serve overloaded",
                 retry_after_s: float = 1.0):
        self.reason = reason
        self.retry_after_s = float(retry_after_s)
        super().__init__(reason)


class SlotStateLostError(RayTpuError):
    """A serving program failed after it had taken the slot cache, so
    the state of EVERY slot is lost, not one request's.

    ``models/decode.py``'s two programs consume the cache they are
    given (it is donated: the result is the same memory, written in
    place), so a call that raises after dispatch leaves no cache to go
    on from. ``JaxSlotEngine`` then starts over from an empty cache and
    raises this, with the program's own error as ``__cause__``; the
    decode scheduler fails every in-flight request with it and frees
    every slot, and its queue and loop go on. A request that fails with
    it was not served wrong: it can be sent again as it was."""

    def __init__(self, reason: str = "slot cache lost"):
        self.reason = reason
        super().__init__(reason)


class AsyncioActorExit(RayTpuError):
    """Raised inside an async actor to exit it gracefully."""


class GangPlacementError(RayTpuError):
    """An all-or-nothing SPMD gang lease could not be satisfied.

    Raised when the home raylet's booking round (RequestGangLease) came
    back short after every configured retry — no partial gang is ever
    adopted, so nothing was leased when this surfaces."""


class GangBrokenError(RayTpuError):
    """The SPMD gang lost a member and the incarnation is invalid.

    A dead member invalidates the WHOLE step (epoch fence, like actor
    incarnations): in-flight step tasks fail with
    :class:`WorkerCrashedError`, and further ``run()`` calls raise this
    until ``reform()`` books a fresh incarnation at epoch+1."""


class CollectiveError(RayTpuError):
    """A DistributedArray ring collective failed mid-flight.

    Raised by the driver-side ring engine when any rank's RingInit /
    RingStep / RingFinish round fails (peer raylet death, data-plane
    failure, store capacity): every surviving member was sent RingAbort
    first, so no partial accumulator segment outlives this. The
    collective verbs catch it and take the fold/naive fallback; it
    surfaces to user code only when every fallback is exhausted."""
