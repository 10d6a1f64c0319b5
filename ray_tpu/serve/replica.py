"""Replica actor: hosts one copy of a deployment's callable.

Parity target: the reference's RayServeWrappedReplica / RayServeReplica
(reference: python/ray/serve/backend_worker.py). An async actor so many
requests interleave up to the deployment's max_concurrent_queries (the
hard cap is enforced caller-side by the ReplicaSet; the replica-side
counter exists for draining — plus a hard overload cap: multiple
routers each honor max_concurrent_queries LOCALLY, so their sum can
oversubscribe one replica. Past
``max_concurrent_queries + serve_max_queue_depth`` concurrent requests
(a replica that hosts a decode loop counts from the loop's own capacity
where that is larger: ``concurrency``) the replica sheds with the typed
:class:`~ray_tpu.exceptions.ServeOverloadedError`, which the proxy
renders as ``503 + Retry-After``).

Zero-copy ingress lands here too: an :class:`HTTPRequest` carrying
``body_ref`` (shm ObjectRef) has its body resolved on the replica's
event loop before user code runs — deployment code always sees
``request.body`` as plain bytes.
"""

from __future__ import annotations

import asyncio
import inspect
import time
from typing import Any

from ray_tpu.exceptions import ServeOverloadedError


# How long a draining replica must have seen no new request before it
# counts as drained.
DRAIN_QUIET_S = 0.1


class Replica:
    """Generic wrapper instantiated by the controller for every replica."""

    def __init__(self, callable_def: Any, init_args: tuple,
                 init_kwargs: dict, max_concurrent_queries: int = 100):
        if inspect.isclass(callable_def):
            self._obj = callable_def(*init_args, **init_kwargs)
        else:
            self._obj = callable_def  # plain function deployment
        self._inflight = 0
        self._last_request_at = time.monotonic()
        self._shed = 0
        self._draining = False
        queue_depth = 16
        retry_after = 1.0
        try:
            import ray_tpu.worker as worker_mod
            cfg = worker_mod.global_worker.core.config
            queue_depth = int(cfg.serve_max_queue_depth)
            retry_after = float(cfg.serve_retry_after_s)
        except Exception:  # noqa: BLE001 — unit harness without a
            pass           # worker: keep the defaults
        # A continuous-batching decode loop bounds itself: so many
        # requests decode at once, so many wait, and its own submit
        # sheds the rest. Where that is more than the deployment's cap,
        # the loop's bound is this replica's, or the routers would hold
        # back requests its batch has slots for.
        sched = getattr(self._obj, "decode_scheduler", None)
        self._concurrency = max(int(max_concurrent_queries),
                                int(getattr(sched, "capacity", 0)))
        self._max_inflight = self._concurrency + max(0, queue_depth)
        self._retry_after_s = max(0.0, retry_after)

    async def ready(self) -> str:
        """Health check the controller awaits before routing traffic."""
        return "ok"

    async def concurrency(self) -> int:
        """What a router may keep in flight here: the deployment's
        ``max_concurrent_queries``, or the hosted decode loop's own
        capacity where that is larger. The controller publishes it to
        the routers with the replica set."""
        return self._concurrency

    async def stats(self) -> dict:
        """Load signal for the controller's autoscaler (reference:
        autoscaling_policy.py scale() consumes per-router queue lens —
        here the replica self-reports concurrency). A deployment
        hosting a continuous-batching decode loop exposes it as
        ``self.decode_scheduler``; its occupancy/queue counters ride
        along for /api/serve."""
        out = {"inflight": self._inflight, "shed": self._shed}
        sched = getattr(self._obj, "decode_scheduler", None)
        if sched is not None:
            try:
                out["decode"] = sched.stats()
                # the compiled step's table of some thousand entries
                # (DecodeScheduler.stats) does not ride every poll
                out["decode"].pop("parts", None)
            except Exception:  # noqa: BLE001 — stats must never fail
                pass           # the autoscaler poll
        return out

    async def handle_request(self, method: str, args: tuple,
                             kwargs: dict):
        # Note: a DRAINING replica still serves — a router that raced
        # the rolling update may send a few stragglers after the
        # controller switched the snapshot, and failing them would
        # surface errors for requests the user did nothing wrong with.
        # Drain completion just waits a little longer.
        if self._inflight >= self._max_inflight:
            self._shed += 1
            raise ServeOverloadedError(
                f"replica at capacity ({self._inflight} in flight, cap "
                f"{self._max_inflight})",
                retry_after_s=self._retry_after_s)
        self._inflight += 1
        self._last_request_at = time.monotonic()
        try:
            # Zero-copy ingress: resolve a by-reference body before the
            # user's callable sees the request.
            for a in args:
                ref = getattr(a, "body_ref", None)
                if ref is not None and hasattr(a, "body"):
                    a.body = bytes(await ref.as_future())
                    a.body_ref = None  # borrow ends; shm seg can free
            # Class deployments: bound-method lookup; function
            # deployments: the function's own __call__.
            fn = getattr(self._obj, method)
            result = fn(*args, **kwargs)
            if inspect.iscoroutine(result):
                result = await result
            return result
        finally:
            self._inflight -= 1

    async def drain(self) -> int:
        """Stop accepting work, wait for in-flight requests to finish.

        Returns the number of requests that were in flight when the
        drain began (for controller bookkeeping/tests).
        """
        self._draining = True
        started_with = self._inflight
        # Drained is idle for a moment from here on, not idle at this
        # instant: a router that has not seen the controller's new
        # snapshot yet may send a straggler, and the controller kills
        # this replica as soon as drain returns.
        self._last_request_at = time.monotonic()
        while self._inflight > 0 or (time.monotonic() - self._last_request_at
                                     < DRAIN_QUIET_S):
            await asyncio.sleep(0.005)
        sched = getattr(self._obj, "decode_scheduler", None)
        if sched is not None:
            try:
                await sched.aclose()
            except Exception:  # noqa: BLE001 — a wedged decode loop
                pass           # must not block the roll
        return started_with

    async def reconfigure(self, user_config: Any) -> None:
        """Push a new user_config without restarting the replica."""
        fn = getattr(self._obj, "reconfigure", None)
        if fn is not None:
            result = fn(user_config)
            if inspect.iscoroutine(result):
                await result
