"""ServeController: the cluster-singleton control plane for serving.

Parity target: the reference's ServeController + BackendState
(reference: python/ray/serve/controller.py:38, backend_state.py). One
named async actor owns all deployment goal-state, reconciles replica
actors toward it (scale up/down, rolling version updates with drain),
and pushes membership snapshots to routers through the LongPollHost.
"""

from __future__ import annotations

import asyncio
import logging
from typing import Any, Dict, List, Optional

from ray_tpu.serve.long_poll import LongPollHost
from ray_tpu.serve.replica import Replica

logger = logging.getLogger(__name__)

CONTROLLER_NAME = "SERVE_CONTROLLER"
SNAPSHOT_KEY = "replicas:{name}"  # long-poll key per deployment
ROUTES_KEY = "routes"             # long-poll key for the HTTP route table
# Cadence of the replica health loop (a crashed replica is detected,
# dropped from router membership, and replaced within ~one period).
HEALTH_CHECK_PERIOD_S = 0.5
# GCS internal-KV key the controller publishes its deployment/replica
# view under, so the dashboard's /api/serve renders without an RPC to
# this actor (the GCS process has no worker to call actors with).
SERVE_STATE_KEY = b"serve:state"
# A replica that leases a TPU may take this many times the start-up
# limit of one that does not (device client, weights, first compiles).
TPU_STARTUP_FACTOR = 10.0


def _startup_timeout_s(actor_options: dict) -> float:
    """How long a replica's constructor may take before its deployment
    fails: the session's ``serve_replica_startup_timeout_s``, and ten
    times it for a replica that leases a TPU (_private/config.py)."""
    from ray_tpu._private.config import get_config

    try:
        import ray_tpu.worker as worker_mod
        cfg = worker_mod.global_worker.core.config
    except Exception:  # noqa: BLE001 — unit harness without a worker
        cfg = get_config()
    leases_tpu = actor_options.get("num_tpus") or (
        actor_options.get("resources") or {}).get("TPU")
    return float(cfg.serve_replica_startup_timeout_s) * (
        TPU_STARTUP_FACTOR if leases_tpu else 1.0)


async def _as_coro(ref):
    """asyncio.wait_for needs a coroutine/task, not a bare awaitable."""
    return await ref


class ServeController:
    """Async actor. All methods run interleaved on one event loop, so
    state mutations need no locks (single-loop discipline, the same
    posture as the rest of the runtime)."""

    def __init__(self):
        self._host = LongPollHost()
        # goal state per deployment
        self._configs: Dict[str, dict] = {}
        # live replicas: name -> [{"id": str, "handle": ActorHandle,
        #                          "version": str}]
        self._replicas: Dict[str, List[dict]] = {}
        self._next_replica_id = 0
        self._reconciling: Dict[str, asyncio.Lock] = {}
        # autoscaling: per-deployment consecutive-decision counters
        # (reference: autoscaling_policy.py BasicAutoscalingPolicy)
        self._scale_counters: Dict[str, int] = {}
        self._autoscale_task: Optional[asyncio.Task] = None
        self._health_task: Optional[asyncio.Task] = None

    # ---- long-poll host passthrough (routers call this) ----

    async def listen_for_change(self, known: Dict[str, int]):
        return await self._host.listen_for_change(known)

    # ---- deployment API (called by serve.api) ----

    async def deploy(self, name: str, callable_def: Any,
                     init_args: tuple, init_kwargs: dict,
                     num_replicas: int = 1,
                     max_concurrent_queries: int = 100,
                     version: Optional[str] = None,
                     user_config: Any = None,
                     ray_actor_options: Optional[dict] = None,
                     route_prefix: Optional[str] = "__default__",
                     autoscaling_config: Optional[dict] = None) -> None:
        """Create or update a deployment and reconcile to the new goal."""
        version = version or "1"
        if route_prefix == "__default__":
            route_prefix = f"/{name}"
        if route_prefix:
            for other, cfg in self._configs.items():
                if other != name and cfg.get("route_prefix") == route_prefix:
                    raise ValueError(
                        f"route_prefix {route_prefix!r} is already used "
                        f"by deployment {other!r}")
        if callable_def is None:
            # Config-only redeploy (scale / reconfigure via
            # serve.get_deployment): keep the stored callable.
            existing = self._configs.get(name)
            if existing is None:
                raise ValueError(
                    f"deployment {name!r} has no stored callable")
            callable_def = existing["callable_def"]
        self._configs[name] = {
            "name": name,
            "callable_def": callable_def,
            "init_args": tuple(init_args or ()),
            "init_kwargs": dict(init_kwargs or {}),
            "num_replicas": int(num_replicas),
            "max_concurrent_queries": int(max_concurrent_queries),
            "version": version,
            "user_config": user_config,
            "ray_actor_options": dict(ray_actor_options or {}),
            "route_prefix": route_prefix,
            "autoscaling_config": dict(autoscaling_config)
            if autoscaling_config else None,
        }
        self._scale_counters.pop(name, None)  # fresh hysteresis per deploy
        if autoscaling_config:
            cfg = self._configs[name]
            lo, hi = self._bounds(autoscaling_config)
            cfg["num_replicas"] = max(lo, min(cfg["num_replicas"], hi))
            if self._autoscale_task is None or self._autoscale_task.done():
                self._autoscale_task = asyncio.get_running_loop().\
                    create_task(self._autoscale_loop())
        if self._health_task is None or self._health_task.done():
            self._health_task = asyncio.get_running_loop().create_task(
                self._health_loop())
        # Reconcile BEFORE announcing the route: when the proxy learns a
        # new route and bootstraps its replica snapshot, replicas must
        # already be serving (reference ordering: backend_state goal
        # completion precedes endpoint-table publication).
        await self._reconcile(name)
        await self._notify_routes()

    async def delete_deployment(self, name: str) -> None:
        self._configs.pop(name, None)
        self._scale_counters.pop(name, None)
        await self._notify_routes()
        await self._reconcile(name)

    async def get_routes(self) -> Dict[str, str]:
        """HTTP route table: {route_prefix: deployment_name} (reference:
        python/ray/serve/api.py route management + http_proxy routing)."""
        return {
            cfg["route_prefix"]: name
            for name, cfg in self._configs.items()
            if cfg.get("route_prefix")
        }

    async def _notify_routes(self) -> None:
        await self._host.notify_changed(ROUTES_KEY, await self.get_routes())
        self._publish_state()

    def _publish_state(self) -> None:
        """Mirror the deployment/replica view into the GCS internal KV
        (fire-and-forget). The dashboard's /api/serve reads it there and
        joins it with the serve metrics — same pattern as tracing's span
        export (util/tracing.py)."""
        import json

        try:
            import ray_tpu.worker as worker_mod
            core = worker_mod.global_worker.core
        except Exception:  # noqa: BLE001 — unit harness without a
            return         # worker: nothing to publish to
        state = {
            "routes": {cfg["route_prefix"]: name
                       for name, cfg in self._configs.items()
                       if cfg.get("route_prefix")},
            "deployments": {
                name: {
                    "num_replicas": cfg["num_replicas"],
                    "max_concurrent_queries":
                        cfg["max_concurrent_queries"],
                    "version": cfg["version"],
                    "route_prefix": cfg.get("route_prefix"),
                    "autoscaling": bool(cfg.get("autoscaling_config")),
                    "replicas": [r["id"] for r in
                                 self._replicas.get(name, [])],
                } for name, cfg in self._configs.items()
            },
        }
        try:
            core.kv_put_nowait(SERVE_STATE_KEY,
                               json.dumps(state).encode())
        except Exception:  # noqa: BLE001 — telemetry export must never
            pass           # fail a deploy/reconcile

    async def get_deployment_info(self, name: str) -> Optional[dict]:
        cfg = self._configs.get(name)
        if cfg is None:
            return None
        return {k: v for k, v in cfg.items() if k != "callable_def"}

    async def list_deployments(self) -> List[str]:
        return sorted(self._configs)

    async def get_replica_snapshot(self, name: str) -> dict:
        """One-shot snapshot (handles bootstrap before long-poll arms)."""
        return self._snapshot(name)

    async def shutdown(self) -> None:
        for name in list(self._configs):
            self._configs.pop(name, None)
            await self._reconcile(name)
        await self._notify_routes()

    # ---- reconciliation ----

    def _snapshot(self, name: str) -> dict:
        cfg = self._configs.get(name)
        replicas = self._replicas.get(name, [])
        # what a router may keep in flight at one replica: the
        # deployment's cap, or the replicas' own where they state a
        # larger one (a decode loop's slots and queue: replica.py)
        cap = cfg["max_concurrent_queries"] if cfg else 1
        if cfg and replicas:
            cap = max(cap, min(r.get("concurrency", cap)
                               for r in replicas))
        return {
            "max_concurrent_queries": cap,
            "replicas": [
                {"id": r["id"], "handle": r["handle"]}
                for r in replicas
            ],
        }

    async def _notify(self, name: str) -> None:
        await self._host.notify_changed(
            SNAPSHOT_KEY.format(name=name), self._snapshot(name))
        self._publish_state()

    async def _reconcile(self, name: str) -> None:
        # Serialize reconciles per deployment; concurrent deploy() calls
        # otherwise interleave replica starts and double-count.
        lock = self._reconciling.setdefault(name, asyncio.Lock())
        async with lock:
            await self._reconcile_locked(name)

    async def _reconcile_locked(self, name: str) -> None:
        import ray_tpu

        cfg = self._configs.get(name)
        live = self._replicas.setdefault(name, [])

        if cfg is None:  # deleted: drain everything, then kill
            victims = list(live)
            self._replicas[name] = []
            await self._notify(name)  # routers stop sending first
            await self._drain_and_kill(victims)
            self._replicas.pop(name, None)
            return

        version = cfg["version"]
        current = [r for r in live if r["version"] == version]
        outdated = [r for r in live if r["version"] != version]

        # Scale up to goal with new-version replicas.
        want = cfg["num_replicas"]
        starting = []
        for _ in range(want - len(current)):
            self._next_replica_id += 1
            rid = f"{name}#{version}#{self._next_replica_id}"
            opts = dict(cfg["ray_actor_options"])
            # the replica sheds above its own cap (replica.py); the
            # actor's limit only has to stay out of its way and leave
            # ready/stats/drain room beside the requests
            opts.setdefault("max_concurrency", 1000)
            handle = ray_tpu.remote(Replica).options(**opts).remote(
                cfg["callable_def"], cfg["init_args"], cfg["init_kwargs"],
                max_concurrent_queries=cfg["max_concurrent_queries"])
            starting.append({"id": rid, "handle": handle,
                             "version": version})
        # Health-gate: route no traffic to a replica that can't init.
        # A failing/hanging constructor must not leak the batch or
        # wedge the reconcile lock forever.
        try:
            timeout = _startup_timeout_s(cfg["ray_actor_options"])
            for r in starting:
                await asyncio.wait_for(
                    _as_coro(r["handle"].ready.remote()), timeout=timeout)
                r["concurrency"] = int(
                    await r["handle"].concurrency.remote())
                current.append(r)
        except BaseException:
            for r in starting:
                if r not in current:
                    try:
                        ray_tpu.kill(r["handle"])
                    except Exception:  # noqa: BLE001
                        pass
            # keep serving whatever came healthy; surface the failure
            self._replicas[name] = current
            await self._notify(name)
            raise

        # Scale down extra same-version replicas (newest first).
        extra = current[want:]
        current = current[:want]

        if cfg["user_config"] is not None:
            for r in current:
                await r["handle"].reconfigure.remote(cfg["user_config"])

        self._replicas[name] = current
        await self._notify(name)  # switch routers to the new set...
        await self._drain_and_kill(outdated + extra)  # ...then drain old

    # ---- replica health (a crashed replica — SIGKILL, OOM — must come
    # OUT of router membership and back UP to the replica goal without
    # waiting for the next deploy; reference: backend_state.py's
    # actor-death handling in the controller loop) ----

    async def _health_loop(self) -> None:
        from ray_tpu import exceptions as exc_mod

        while self._configs:
            await asyncio.sleep(HEALTH_CHECK_PERIOD_S)
            for name in list(self._configs):
                live = self._replicas.get(name, [])
                if not live:
                    continue
                checks = await asyncio.gather(
                    *[asyncio.wait_for(_as_coro(r["handle"].ready.remote()),
                                       timeout=10.0) for r in live],
                    return_exceptions=True)
                dead = [r for r, c in zip(live, checks)
                        if isinstance(c, exc_mod.ActorDiedError)]
                # only a DEAD actor counts: a slow/timed-out ready()
                # (replica busy under load) must not get it replaced
                if not dead:
                    continue
                dead_ids = {r["id"] for r in dead}
                logger.warning("replica(s) %s of %s died; replacing",
                               sorted(dead_ids), name)
                self._replicas[name] = [r for r in live
                                        if r["id"] not in dead_ids]
                await self._notify(name)  # routers stop picking it NOW
                try:
                    await self._reconcile(name)  # scale back to goal
                except Exception:  # noqa: BLE001 — node still sick;
                    # retry next period
                    logger.exception("replacing dead replicas of %s "
                                     "failed", name)
        self._health_task = None

    # ---- autoscaling (reference: serve/autoscaling_policy.py
    # BasicAutoscalingPolicy driven from the controller loop) ----

    async def _autoscale_loop(self) -> None:
        while any(cfg.get("autoscaling_config")
                  for cfg in self._configs.values()):
            await asyncio.sleep(0.25)
            for name in list(self._configs):
                try:
                    await self._autoscale_one(name)
                except Exception:  # noqa: BLE001 — loop must survive
                    logger.exception("autoscale of %s failed", name)
        self._autoscale_task = None

    @staticmethod
    def _bounds(ac: dict) -> tuple:
        """(min, max) replica bounds; max_replicas <= 0 = unbounded."""
        lo = int(ac.get("min_replicas", 1))
        hi = ac.get("max_replicas", -1)
        return lo, (int(hi) if hi and int(hi) > 0 else 10**9)

    async def _autoscale_one(self, name: str) -> None:
        cfg = self._configs.get(name)
        ac = cfg.get("autoscaling_config") if cfg else None
        if not ac:
            return
        replicas = self._replicas.get(name, [])
        if not replicas:
            return
        # concurrent polls: one slow replica must not serialize the
        # pass (and through the shared loop, every OTHER deployment)
        results = await asyncio.gather(
            *[asyncio.wait_for(_as_coro(r["handle"].stats.remote()),
                               timeout=5.0) for r in replicas],
            return_exceptions=True)
        inflight = 0
        responsive = 0
        for res in results:
            if isinstance(res, BaseException):
                continue  # unresponsive != idle: excluded entirely
            responsive += 1
            inflight += int(res.get("inflight", 0))
        if responsive == 0:
            return  # no signal this round: never scale blind
        avg = inflight / responsive
        # the router caps replica concurrency at max_concurrent_queries,
        # so a threshold above the cap could never fire — saturation
        # must always count as scale-up pressure
        up_thresh = min(float(ac.get("scale_up_threshold", 5)),
                        float(cfg["max_concurrent_queries"]))
        down_thresh = float(ac.get("scale_down_threshold", 1))
        counter = self._scale_counters.get(name, 0)
        if avg >= up_thresh:
            counter = max(1, counter + 1)
        elif avg <= down_thresh:
            counter = min(-1, counter - 1)
        else:
            counter = 0
        lo, hi = self._bounds(ac)
        want = cfg["num_replicas"]
        if counter >= int(ac.get("scale_up_consecutive_periods", 2)):
            want = min(hi, want + int(ac.get("scale_up_num_replicas", 1)))
            counter = 0
        elif -counter >= int(ac.get("scale_down_consecutive_periods", 5)):
            want = max(lo, want - int(ac.get("scale_down_num_replicas", 1)))
            counter = 0
        self._scale_counters[name] = counter
        if want != cfg["num_replicas"]:
            logger.info("autoscaling %s: %d -> %d replicas (avg load %.2f)",
                        name, cfg["num_replicas"], want, avg)
            cfg["num_replicas"] = want
            await self._reconcile(name)

    async def _drain_and_kill(self, replicas: List[dict]) -> None:
        import ray_tpu

        for r in replicas:
            try:
                await r["handle"].drain.remote()
            except Exception:  # noqa: BLE001 — already dead is fine
                pass
            try:
                ray_tpu.kill(r["handle"])
            except Exception:  # noqa: BLE001
                pass
