"""Model serving on the actor runtime.

Parity target: the reference's Serve control/data plane
(reference: python/ray/serve/ — ServeController controller.py:38,
Router/ReplicaSet router.py:45,177, RayServeHandle handle.py:44,
@serve.deployment api.py:610,865, LongPollClient/Host long_poll.py).
Handle-based calls are first-class (they compose with the task graph);
HTTP ingress is served by the HTTPProxy actor (http_proxy.py, parity
with python/ray/serve/http_proxy.py:162): every deployment gets a
route (default ``/<name>``, opt out with ``route_prefix=None``) and
receives an ``HTTPRequest`` when invoked over HTTP.

Usage::

    from ray_tpu import serve

    serve.start()

    @serve.deployment(num_replicas=2, max_concurrent_queries=4)
    class Model:
        def __call__(self, x):
            return x * 2

    Model.deploy()
    handle = Model.get_handle()
    ray_tpu.get(handle.remote(21))  # 42
"""

from __future__ import annotations

import uuid
from typing import Any, Callable, Dict, List, Optional

import ray_tpu
from ray_tpu.serve.batching import batch  # noqa: F401
from ray_tpu.serve.controller import CONTROLLER_NAME, ServeController
from ray_tpu.serve.decode_scheduler import (DecodeScheduler,  # noqa: F401
                                            JaxSlotEngine)
from ray_tpu.serve.handle import DeploymentHandle
from ray_tpu.serve.http_proxy import (HTTPProxy, HTTPRequest, HTTPResponse,
                                      PROXY_NAME)

__all__ = [
    "start", "shutdown", "deployment", "get_deployment",
    "list_deployments", "DeploymentHandle", "HTTPRequest", "HTTPResponse",
    "get_http_address", "batch", "DecodeScheduler", "JaxSlotEngine",
]

_controller = None
_http_address = None


def start(detached: bool = False, http: bool = True,
          http_host: str = "127.0.0.1", http_port: int = 0):
    """Start (or connect to) the serve control plane.

    ``detached=True`` keeps the controller alive past this driver, like
    the reference's serve.start(detached=True). ``http=True`` (default)
    also starts the HTTP ingress proxy (reference:
    python/ray/serve/http_proxy.py); ``http_port=0`` binds an ephemeral
    port — read it back with :func:`get_http_address`.
    """
    global _controller, _http_address
    if _controller is not None:
        return _controller
    opts = {"name": CONTROLLER_NAME, "get_if_exists": True,
            "max_concurrency": 1000}
    if detached:
        opts["lifetime"] = "detached"
    _controller = ray_tpu.remote(ServeController).options(**opts).remote()
    if http:
        popts = {"name": PROXY_NAME, "get_if_exists": True,
                 "max_concurrency": 10000, "num_cpus": 0}
        if detached:
            popts["lifetime"] = "detached"
        proxy = ray_tpu.remote(HTTPProxy).options(**popts).remote(
            _controller, http_host, http_port)
        _http_address = ray_tpu.get(proxy.ready.remote())
    return _controller


def get_http_address() -> Optional[str]:
    """'host:port' of the HTTP ingress, or None if HTTP is off."""
    global _http_address
    if _http_address is None:
        try:
            proxy = ray_tpu.get_actor(PROXY_NAME)
            _http_address = ray_tpu.get(proxy.ready.remote())
        except Exception:
            return None
    return _http_address


def _get_controller():
    global _controller
    if _controller is None:
        try:
            _controller = ray_tpu.get_actor(CONTROLLER_NAME)
        except Exception:
            raise RuntimeError(
                "serve.start() must be called first") from None
    return _controller


def shutdown() -> None:
    """Tear down every deployment, the HTTP proxy, and the controller."""
    global _controller, _http_address
    if _controller is None:
        try:
            _controller = ray_tpu.get_actor(CONTROLLER_NAME)
        except Exception:
            return
    try:
        proxy = ray_tpu.get_actor(PROXY_NAME)
        ray_tpu.get(proxy.drain.remote())
        ray_tpu.kill(proxy)
    except Exception:
        pass
    ray_tpu.get(_controller.shutdown.remote())
    ray_tpu.kill(_controller)
    _controller = None
    _http_address = None


class Deployment:
    """Declarative deployment: callable + config, bound by deploy()."""

    def __init__(self, func_or_class: Callable, name: str,
                 num_replicas: int = 1,
                 max_concurrent_queries: int = 100,
                 version: Optional[str] = None,
                 user_config: Any = None,
                 ray_actor_options: Optional[Dict] = None,
                 init_args: tuple = (), init_kwargs: Optional[dict] = None,
                 route_prefix: Optional[str] = "__default__",
                 autoscaling_config: Optional[Dict] = None):
        self._func_or_class = func_or_class
        self.name = name
        self.num_replicas = num_replicas
        self.max_concurrent_queries = max_concurrent_queries
        self.version = version
        self.user_config = user_config
        self.ray_actor_options = ray_actor_options or {}
        self.init_args = init_args
        self.init_kwargs = init_kwargs or {}
        # "__default__" → /<name>; None → not HTTP-routable (handle-only)
        self.route_prefix = route_prefix
        # reference: autoscaling_policy.py BasicAutoscalingPolicy keys
        # (min/max_replicas, scale_up/down_threshold, *_consecutive_
        # periods, scale_up/down_num_replicas); None = fixed replicas
        self.autoscaling_config = autoscaling_config

    def options(self, **overrides) -> "Deployment":
        cfg = {
            "name": self.name, "num_replicas": self.num_replicas,
            "max_concurrent_queries": self.max_concurrent_queries,
            "version": self.version, "user_config": self.user_config,
            "ray_actor_options": dict(self.ray_actor_options),
            "init_args": self.init_args,
            "init_kwargs": dict(self.init_kwargs),
            "route_prefix": self.route_prefix,
            "autoscaling_config": self.autoscaling_config,
        }
        cfg.update(overrides)
        return Deployment(self._func_or_class, **cfg)

    def deploy(self, *init_args, **init_kwargs) -> None:
        """Create or roll the deployment to this config (blocking)."""
        controller = _get_controller()
        ray_tpu.get(controller.deploy.remote(
            self.name, self._func_or_class,
            init_args or self.init_args,
            init_kwargs or self.init_kwargs,
            num_replicas=self.num_replicas,
            max_concurrent_queries=self.max_concurrent_queries,
            # an unversioned redeploy always rolls: fresh token
            version=self.version or uuid.uuid4().hex,
            user_config=self.user_config,
            ray_actor_options=self.ray_actor_options,
            route_prefix=self.route_prefix,
            autoscaling_config=self.autoscaling_config))
        _wait_http_route(self.name, self.route_prefix)

    def delete(self) -> None:
        controller = _get_controller()
        ray_tpu.get(controller.delete_deployment.remote(self.name))
        _wait_http_route(self.name, None)

    def get_handle(self) -> DeploymentHandle:
        return DeploymentHandle(_get_controller(), self.name)

    def __call__(self, *a, **kw):
        raise RuntimeError(
            "deployments are invoked via .get_handle().remote(), not "
            "called directly")


def _wait_http_route(name: str, route_prefix) -> None:
    """Best-effort: block until the HTTP proxy applied the new route
    table (the long-poll push is async; without this the first request
    after deploy() races the table update and can 404)."""
    try:
        proxy = ray_tpu.get_actor(PROXY_NAME)
    except Exception:  # noqa: BLE001 — http=False or detached teardown
        return
    try:
        ray_tpu.get(proxy.wait_for_route.remote(name, route_prefix),
                    timeout=15)
    except Exception:  # noqa: BLE001 — readiness is advisory
        pass


def deployment(_func_or_class=None, *, name: Optional[str] = None,
               num_replicas: int = 1, max_concurrent_queries: int = 100,
               version: Optional[str] = None, user_config: Any = None,
               ray_actor_options: Optional[Dict] = None,
               route_prefix: Optional[str] = "__default__",
               autoscaling_config: Optional[Dict] = None):
    """``@serve.deployment`` decorator (bare or with options).
    ``max_concurrent_queries`` caps what a router keeps in flight at one
    replica. A replica that hosts a continuous-batching decode loop
    (``self.decode_scheduler``) states its own cap where that is larger:
    the loop's slots and its queue (serve/replica.py)."""
    def wrap(func_or_class):
        return Deployment(
            func_or_class,
            name or func_or_class.__name__,
            num_replicas=num_replicas,
            max_concurrent_queries=max_concurrent_queries,
            version=version, user_config=user_config,
            ray_actor_options=ray_actor_options,
            route_prefix=route_prefix,
            autoscaling_config=autoscaling_config)

    if _func_or_class is not None:
        return wrap(_func_or_class)
    return wrap


def get_deployment(name: str) -> Deployment:
    """Fetch a live deployment's config as a re-deployable object."""
    controller = _get_controller()
    info = ray_tpu.get(controller.get_deployment_info.remote(name))
    if info is None:
        raise KeyError(f"no deployment named {name!r}")
    dep = Deployment(
        None, name,
        num_replicas=info["num_replicas"],
        max_concurrent_queries=info["max_concurrent_queries"],
        version=info["version"], user_config=info["user_config"],
        ray_actor_options=info["ray_actor_options"],
        init_args=info["init_args"], init_kwargs=info["init_kwargs"],
        route_prefix=info.get("route_prefix"),
        autoscaling_config=info.get("autoscaling_config"))
    return dep


def list_deployments() -> List[str]:
    return ray_tpu.get(_get_controller().list_deployments.remote())
