"""Distributed task tracing: span context propagated through task
submission, spans exported to the GCS.

Parity target: the reference's OpenTelemetry integration
(reference: python/ray/util/tracing/tracing_helper.py —
``_inject_tracing_into_function`` propagates the caller's span context
inside task metadata; ``_function_span_consumer_name`` names the
server-side span). This implementation is dependency-free: spans are
plain records, the context rides :attr:`TaskSpec.trace_ctx`, and
finished spans are exported to the cluster KV, where
:func:`get_trace` reassembles the tree from any driver.

Tracing is OFF by default (zero overhead on the submit hot path
beyond one falsy check); enable with ``RAY_TPU_TRACE=1`` or
:func:`enable`.

A loop that turns every few milliseconds (the decode step) is too hot
for a span with a uuid, a wall clock and a KV export: :func:`phase` is
the span for it, always on, summed in the process and read as deltas
through :func:`phase_totals`. It lives in ``ray_tpu.util.phases``, which
imports nothing of ``ray_tpu``, and is offered from here too.

Usage::

    from ray_tpu.util import tracing

    tracing.enable()
    with tracing.trace("my pipeline"):
        out = ray_tpu.get(step.remote(x))    # worker spans auto-link

    spans = tracing.get_trace(trace_id)       # the whole tree
    tracing.to_chrome_trace(spans)            # chrome://tracing JSON
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import os
import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu.util.phases import (  # noqa: F401 — offered beside the spans
    phase, phase_add, phase_totals, recording)

_KV_PREFIX = b"__traces__/"

_current: contextvars.ContextVar[Optional["Span"]] = \
    contextvars.ContextVar("ray_tpu_span", default=None)
_enabled: Optional[bool] = None


def enabled() -> bool:
    global _enabled
    if _enabled is None:
        _enabled = os.environ.get("RAY_TPU_TRACE", "") not in ("", "0")
    return _enabled


def enable() -> None:
    """Turn tracing on for this process AND future workers (the env var
    propagates through worker spawn)."""
    global _enabled
    _enabled = True
    os.environ["RAY_TPU_TRACE"] = "1"


def disable() -> None:
    global _enabled
    _enabled = False
    os.environ["RAY_TPU_TRACE"] = "0"


@dataclass
class Span:
    trace_id: str
    span_id: str
    parent_id: Optional[str]
    name: str
    kind: str = "internal"          # internal | producer | consumer
    start_ns: int = 0
    end_ns: int = 0
    attributes: Dict[str, Any] = field(default_factory=dict)
    status: str = "ok"

    def to_json(self) -> bytes:
        return json.dumps(self.__dict__, default=str).encode()

    @classmethod
    def from_json(cls, data: bytes) -> "Span":
        return cls(**json.loads(data))


def current_context() -> Optional[Tuple[str, str]]:
    """(trace_id, span_id) of the active span, or None."""
    span = _current.get()
    return (span.trace_id, span.span_id) if span is not None else None


@contextlib.contextmanager
def trace(name: str, kind: str = "internal",
          parent_ctx: Optional[Tuple[str, str]] = None,
          attributes: Optional[Dict[str, Any]] = None):
    """Open a span. Nested ``trace``/task submissions become children.
    Yields the span (its ``trace_id`` is how you fetch the tree)."""
    parent = _current.get()
    if parent_ctx is not None:
        trace_id, parent_id = parent_ctx
    elif parent is not None:
        trace_id, parent_id = parent.trace_id, parent.span_id
    else:
        trace_id, parent_id = uuid.uuid4().hex, None
    span = Span(trace_id=trace_id, span_id=uuid.uuid4().hex[:16],
                parent_id=parent_id, name=name, kind=kind,
                start_ns=time.time_ns(), attributes=attributes or {})
    token = _current.set(span)
    try:
        yield span
    except BaseException as e:
        span.status = f"error: {type(e).__name__}"
        raise
    finally:
        span.end_ns = time.time_ns()
        _current.reset(token)
        _export(span)


def inject_context(attributes: Optional[Dict[str, Any]] = None
                   ) -> Optional[Tuple[str, str]]:
    """What the submit path stamps into TaskSpec.trace_ctx: a producer
    span is recorded for the submission and its context propagated
    (reference: tracing_helper.py _tracing_task_invocation)."""
    if not enabled():
        return None
    ctx = current_context()
    if ctx is None:
        # root: a submission outside any span still gets a trace
        return (uuid.uuid4().hex, "")
    return ctx


@contextlib.contextmanager
def task_execution_span(spec_name: str, task_id_hex: str,
                        trace_ctx: Optional[Tuple[str, str]]):
    """Worker-side consumer span around task execution (reference:
    tracing_helper.py _inject_tracing_into_function's server span).
    No-op when the submission carried no context."""
    if not trace_ctx:
        yield None
        return
    trace_id, parent_id = trace_ctx
    with trace(f"execute {spec_name}", kind="consumer",
               parent_ctx=(trace_id, parent_id or None),
               attributes={"task_id": task_id_hex,
                           "pid": os.getpid()}) as span:
        yield span


# ------------------------------------------------------------- export

def _export(span: Span) -> None:
    """Finished spans go to the cluster KV (fire-and-forget off the
    caller's thread)."""
    try:
        import ray_tpu.worker as worker_mod

        w = worker_mod.global_worker
        if w is not None and w.core is not None:
            key = (_KV_PREFIX + span.trace_id.encode() + b"/" +
                   span.span_id.encode())
            w.core.kv_put_nowait(key, span.to_json())
    except Exception:  # noqa: BLE001 — tracing must never break tasks
        pass


def _spans_under(prefix: bytes) -> List[Span]:
    """All spans stored under ``prefix``, start-time ordered. ONE bulk
    GCS round-trip (KVGetPrefix): a per-key get loop over up to
    tracing_max_spans entries would issue 100k sequential RPCs. Falls
    back to the per-key path where the bulk RPC is unavailable
    (ray:// thin-client cores route the experimental KV API only)."""
    import ray_tpu
    import ray_tpu.worker as worker_mod

    try:
        core = worker_mod._require_connected().core
        reply = core.gcs_call_sync("KVGetPrefix", {"prefix": prefix})
        datas = [v for _k, v in reply.get("pairs", [])]
    except Exception:  # noqa: BLE001 — client mode / old GCS: fall back
        datas = [ray_tpu.experimental_internal_kv_get(key)
                 for key in ray_tpu.experimental_internal_kv_list(prefix)]
    spans = [Span.from_json(data) for data in datas if data]
    spans.sort(key=lambda s: s.start_ns)
    return spans


def get_trace(trace_id: str) -> List[Span]:
    """All exported spans of a trace, start-time ordered."""
    return _spans_under(_KV_PREFIX + trace_id.encode() + b"/")


def all_spans() -> List[Span]:
    """Every exported span across all traces, start-time ordered (the
    timeline export merges these with task states and data-plane
    transfer events — see ray_tpu.state.timeline)."""
    return _spans_under(_KV_PREFIX)


def dropped_span_count() -> int:
    """Spans evicted by the GCS span cap (config ``tracing_max_spans``)
    since cluster start — the honest counter behind oldest-trace
    eviction."""
    import ray_tpu

    raw = ray_tpu.experimental_internal_kv_get(b"__rtpu_trace_dropped__")
    return int(raw) if raw else 0


def clear_trace(trace_id: str) -> int:
    """Delete one trace's spans from the cluster KV. Span storage is
    bounded by the GCS ``tracing_max_spans`` cap (oldest-trace eviction,
    counted by :func:`dropped_span_count`); clearing traces you have
    consumed (or calling :func:`clear_all` periodically) still keeps
    the retained window focused on live work."""
    import ray_tpu

    n = 0
    prefix = _KV_PREFIX + trace_id.encode() + b"/"
    for key in ray_tpu.experimental_internal_kv_list(prefix):
        n += bool(ray_tpu.experimental_internal_kv_del(key))
    return n


def clear_all() -> int:
    """Delete every exported span (see :func:`clear_trace`)."""
    import ray_tpu

    n = 0
    for key in ray_tpu.experimental_internal_kv_list(_KV_PREFIX):
        n += bool(ray_tpu.experimental_internal_kv_del(key))
    return n


def to_chrome_trace(spans: List[Span]) -> List[dict]:
    """chrome://tracing 'X' events (complements the runtime's existing
    profile-event timeline)."""
    return [{
        "name": s.name, "cat": s.kind, "ph": "X",
        "ts": s.start_ns / 1e3, "dur": max(0, s.end_ns - s.start_ns) / 1e3,
        "pid": s.attributes.get("pid", 0), "tid": 0,
        "args": {**s.attributes, "trace_id": s.trace_id,
                 "span_id": s.span_id, "parent_id": s.parent_id,
                 "status": s.status},
    } for s in spans]


# Importing this module ARMS the submit-path trace hook: core_worker's
# _trace_ctx reads one global instead of probing sys.modules per task
# (see core_worker._trace_ctx docstring for the activation contract).
import sys as _sys

from ray_tpu._private import core_worker as _core_worker_mod

_core_worker_mod._tracing_mod = _sys.modules[__name__]
