"""Continuous batching for KV-cached decode: one in-flight batch,
slot admission at step boundaries.

The ``@serve.batch`` decorator forms batches with a static window —
requests wait up to ``batch_wait_timeout_s`` for peers, the batch runs
to completion, and a request arriving one tick after the flush waits a
FULL generation before its tokens start. Under ragged arrivals that
leaves most of the model's decode ceiling on the floor (the scheduling
gap PAPERS.md [1] measures: batch-formation policy, not kernel speed,
dominates accelerator goodput).

:class:`DecodeScheduler` replaces the window with ONE long-lived decode
batch over a per-slot KV cache (``models/decode.py``
``init_slot_cache`` / ``slot_prefill`` / ``slot_decode_step``):

* the loop runs one batched decode step per iteration for every
  ACTIVE slot;
* a newly arrived request is admitted into any open slot at the next
  step boundary — its prompt prefills into that cache row while the
  other rows' positions are untouched, and its first step joins the
  very next batch;
* a finished sequence (eos / max_tokens) frees its slot IMMEDIATELY
  and the head of the queue takes it — the batch never drains to empty
  just to let a waiter in;
* past ``max_queue_depth`` waiting requests, ``submit`` sheds with the
  typed :class:`~ray_tpu.exceptions.ServeOverloadedError` (the serving
  analog of the lease plane's ``retry_later``) instead of queueing
  work the decode loop can never catch up on.

The scheduler is ENGINE-AGNOSTIC: anything with ``slots``,
``prefill(slot, prompt) -> first_token`` and
``step({slot: last_token}) -> {slot: next_token}`` drives it, so the
admission policy is unit-testable without jax (tests/
test_decode_scheduler.py uses a fake engine); :class:`JaxSlotEngine`
adapts the real per-slot cache. Engine calls run in the default
executor — a jitted decode step must not block the replica's asyncio
loop, which keeps accepting/queueing requests mid-step.

Where the time of a step goes is recorded from inside, always, by
``util.phases.phase`` (a host clock into the scheduler's own table, and
a span on the device trace's clock while a profiler session is open).
``stats()["phases"]`` is that table, cumulative ``[count, seconds]`` a
name, to be read as deltas:

* loop thread: ``serve.admit`` (one ``_admit`` that took a request off
  the queue) holds a ``serve.prefill`` per request (executor submit to
  resumed); ``serve.step`` is the decode step from executor submit to
  resumed; ``serve.emit`` the bookkeeping after it (tokens appended,
  futures resolved). Two are sums without a span:
  ``serve.admit_stall``, the part of ``serve.admit`` during which
  active slots stood still for a prefill, and ``serve.hop``, what
  ``serve.step`` and ``serve.prefill`` took beyond the engine call as
  timed on the executor thread (hand-off, GIL, loop lag);
* executor thread, inside :class:`JaxSlotEngine`'s ``step``:
  ``serve.engine.check`` / ``.put`` / ``.dispatch`` / ``.wait`` /
  ``.read``. ``check`` is host only: the capacity check against the
  engine's own mirror of the slots' positions, and the ``tok``/``act``
  lists. ``wait`` is the step's one device-to-host transfer, the whole
  argmax row fetched at once: it waits for the device and carries the
  transfer. ``read`` builds the returned dict from that host array.
  The engine's ``prefill`` has no spans of its own: ``serve.prefill``
  less its hop is the call. Of a model with expert layers the same
  fetch brings three counts of the step, each summed over its expert
  layers, kept as sums under ``serve.engine.experts_hit`` (held experts
  that got a row), ``serve.engine.expert_rows`` (rows routed to held
  experts) and ``serve.engine.expert_rows_max`` (the fullest expert's
  rows): ``[steps, sum]``, not seconds.
"""

from __future__ import annotations

import asyncio
import contextlib
import logging
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from ray_tpu._private import rpc
from ray_tpu.exceptions import ServeOverloadedError, SlotStateLostError
from ray_tpu.util.phases import phase, phase_add, phase_totals, recording

logger = logging.getLogger(__name__)

# what a step of a model with expert layers counts, in the order of
# ``cache["load"]`` (models/decode.py): sums, kept beside the phases
EXPERT_COUNTS = ("serve.engine.experts_hit", "serve.engine.expert_rows",
                 "serve.engine.expert_rows_max")


@dataclass
class _Request:
    prompt: Any
    max_tokens: int
    eos_token: Optional[int]
    future: asyncio.Future
    t_submit: float         # time.perf_counter() at submit
    tokens: List[int] = field(default_factory=list)
    joined_mid_batch: bool = False


class DecodeScheduler:
    """One in-flight decode batch; admission at step boundaries.

    ``submit`` is awaited per request and resolves with the generated
    token list. The background loop starts lazily on the first submit
    and parks (zero cycles) whenever queue and batch are both empty.
    """

    def __init__(self, engine, *, max_queue_depth: Optional[int] = None,
                 retry_after_s: float = 1.0):
        if int(engine.slots) <= 0:
            raise ValueError("engine must expose at least one slot")
        if max_queue_depth is None:
            # room for a whole batch of waiters and as many again: a
            # closed loop of more callers than slots queues that many
            # at once when it starts
            max_queue_depth = max(64, 2 * int(engine.slots))
        self._engine = engine
        self._free: List[int] = list(range(engine.slots))
        self._queue: deque[_Request] = deque()
        self._active: Dict[int, _Request] = {}
        self._max_queue_depth = int(max_queue_depth)
        self._retry_after_s = float(retry_after_s)
        self._wakeup = asyncio.Event()
        self._loop_task: Optional[asyncio.Task] = None
        self._closed = False
        # counters surfaced by stats() (and the replica's stats() ->
        # autoscaler/admission view)
        self.steps = 0
        self.slot_steps = 0          # sum of batch occupancy per step
        self.completed = 0
        self.shed = 0
        self.admitted = 0
        self.admitted_mid_batch = 0
        self.tokens_generated = 0
        # per-request sums since submit, in seconds: until taken for a
        # slot and until the first token (over ``admitted``; a prefill
        # that raised adds no first token), until resolved (over
        # ``completed``)
        self.queue_wait_s = 0.0
        self.first_token_s = 0.0
        self.request_s = 0.0
        # name -> [count, seconds] of every phase of this scheduler's
        # loop and of the engine calls it makes (module docstring)
        self._phases: Dict[str, list] = {}

    # ------------------------------------------------------------ public

    async def submit(self, prompt, *, max_tokens: int,
                     eos_token: Optional[int] = None) -> List[int]:
        """Queue one prompt; resolves with its generated tokens.

        Sheds (typed, never queues) once ``max_queue_depth`` requests
        are already waiting for a slot — the per-replica half of the
        SLO contract; the proxy's admission controller is the cluster
        half."""
        if self._closed:
            raise ServeOverloadedError("decode scheduler is closed",
                                       retry_after_s=self._retry_after_s)
        if len(self._queue) >= self._max_queue_depth:
            self.shed += 1
            raise ServeOverloadedError(
                f"decode queue full ({len(self._queue)} waiting, cap "
                f"{self._max_queue_depth})",
                retry_after_s=self._retry_after_s)
        req = _Request(prompt, int(max_tokens), eos_token,
                       asyncio.get_running_loop().create_future(),
                       time.perf_counter())
        self._queue.append(req)
        self._wakeup.set()
        if self._loop_task is None or self._loop_task.done():
            self._loop_task = rpc.spawn_logged(self._run(),
                                               "serve-decode-loop")
        return await req.future

    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def capacity(self) -> int:
        """Requests this loop holds at once before ``submit`` sheds:
        one a slot and ``max_queue_depth`` waiting. The replica that
        hosts the loop states it to the routers as its own cap."""
        return int(self._engine.slots) + self._max_queue_depth

    def stats(self) -> dict:
        return {
            "queue_depth": len(self._queue),
            "active_slots": len(self._active),
            "free_slots": len(self._free),
            "steps": self.steps,
            "slot_steps": self.slot_steps,
            "mean_occupancy": (self.slot_steps / self.steps
                               if self.steps else 0.0),
            "completed": self.completed,
            "shed": self.shed,
            "admitted": self.admitted,
            "admitted_mid_batch": self.admitted_mid_batch,
            "tokens_generated": self.tokens_generated,
            "queue_wait_s": self.queue_wait_s,
            "first_token_s": self.first_token_s,
            "request_s": self.request_s,
            "phases": phase_totals(table=self._phases),
        }

    async def aclose(self) -> None:
        """Stop the loop; fail queued and in-flight requests typed."""
        self._closed = True
        task, self._loop_task = self._loop_task, None
        if task is not None:
            task.cancel()
            try:
                await task
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass
        err = ServeOverloadedError("decode scheduler closed",
                                   retry_after_s=self._retry_after_s)
        for req in list(self._queue) + list(self._active.values()):
            if not req.future.done():
                req.future.set_exception(err)
        self._queue.clear()
        self._active.clear()
        self._free = list(range(self._engine.slots))

    # ------------------------------------------------------------- loop

    async def _engine_call(self, name: str, fn, *args):
        """One engine call under the span ``name``: awaited where the
        engine is a coroutine, else run on the executor (which records
        into this scheduler's table for as long), and then what the
        span took beyond the call itself goes to ``serve.hop``."""
        span = phase(name)
        if asyncio.iscoroutinefunction(fn):
            with span:
                return await fn(*args)
        ran = None

        def timed():
            nonlocal ran
            t0 = time.perf_counter()
            try:
                with recording(self._phases):
                    return fn(*args)
            finally:
                ran = time.perf_counter() - t0

        try:
            with span:
                return await asyncio.get_running_loop().run_in_executor(
                    None, timed)
        finally:
            if ran is not None:     # None: cancelled before the call ended
                phase_add("serve.hop", span.seconds - ran)

    async def _prefill(self, slot: int, req: _Request) -> None:
        first = await self._engine_call(
            "serve.prefill", self._engine.prefill, slot, req.prompt)
        req.tokens.append(int(first))
        self.first_token_s += time.perf_counter() - req.t_submit
        self.tokens_generated += 1

    def _finish(self, slot: int, req: _Request) -> None:
        self._active.pop(slot, None)
        self._free.append(slot)
        self.completed += 1
        self.request_s += time.perf_counter() - req.t_submit
        if not req.future.done():
            req.future.set_result(req.tokens)

    def _fail_active(self, e: Exception, what: str) -> None:
        """Fail every in-flight request with ``e`` and free its slot."""
        logger.error("%s failed: %r", what, e, exc_info=e)
        for slot, req in list(self._active.items()):
            del self._active[slot]
            self._free.append(slot)
            if not req.future.done():
                req.future.set_exception(e)

    def _done(self, req: _Request) -> bool:
        return (len(req.tokens) >= req.max_tokens or
                (req.eos_token is not None and req.tokens and
                 req.tokens[-1] == req.eos_token))

    async def _admit(self) -> None:
        """Fill open slots from the queue head (step boundary only)."""
        if not (self._free and self._queue):
            return
        stalled = 0.0       # of this call, with active slots waiting
        with phase("serve.admit"):
            while self._free and self._queue:
                req = self._queue.popleft()
                if req.future.done():   # caller gave up while queued
                    continue
                slot = self._free.pop()
                req.joined_mid_batch = bool(self._active)
                self.admitted += 1
                t_taken = time.perf_counter()
                self.queue_wait_s += t_taken - req.t_submit
                if req.joined_mid_batch:
                    self.admitted_mid_batch += 1
                try:
                    await self._prefill(slot, req)
                except Exception as e:  # noqa: BLE001 — one bad prompt
                    # must not kill the batch: fail ITS future, free the
                    # slot, keep decoding everyone else. That holds for
                    # whatever is refused before the program is
                    # dispatched (a prompt too long, an error while
                    # tracing or compiling): the cache is untouched.
                    self._free.append(slot)
                    if not req.future.done():
                        req.future.set_exception(e)
                    if isinstance(e, SlotStateLostError):
                        # the program took the cache and then failed:
                        # the engine starts from an empty one, so what
                        # every active slot held is gone with it. As a
                        # failed step: they fail typed, the queue and
                        # the loop go on. Never a token off a zeroed row.
                        self._fail_active(e, "prefill")
                    continue
                finally:
                    if req.joined_mid_batch:
                        stalled += time.perf_counter() - t_taken
                if self._done(req):
                    self._finish(slot, req)
                else:
                    self._active[slot] = req
        phase_add("serve.admit_stall", stalled)

    async def _run(self) -> None:
        with recording(self._phases):   # this task's phases
            await self._loop()

    async def _loop(self) -> None:
        while not self._closed:
            await self._admit()
            if not self._active:
                self._wakeup.clear()
                if not self._queue:
                    await self._wakeup.wait()
                continue
            tokens = {slot: req.tokens[-1]
                      for slot, req in self._active.items()}
            try:
                out = await self._engine_call(
                    "serve.step", self._engine.step, tokens)
            except Exception as e:  # noqa: BLE001 — a failed device
                # step fails the IN-FLIGHT requests typed; the loop and
                # the queue survive (shed at the door, never collapse)
                self._fail_active(e, "decode step")
                continue
            with phase("serve.emit"):
                self.steps += 1
                self.slot_steps += len(tokens)
                for slot, tok in out.items():
                    req = self._active.get(slot)
                    if req is None:
                        continue
                    req.tokens.append(int(tok))
                    self.tokens_generated += 1
                    if self._done(req):
                        self._finish(slot, req)


class JaxSlotEngine:
    """Adapts the per-slot KV cache (models/decode.py) to the
    scheduler's engine protocol. Greedy decoding; prompts are int
    token-id sequences. One compiled prefill program per distinct
    prompt length, one compiled step program total.

    A decode step makes one device-to-host transfer, and none before
    its dispatch. The slots' positions are mirrored on the host
    (``_pos``: ``prefill`` sets a slot's entry to the prompt's length,
    ``step`` adds one per active slot, each once the program has
    returned and ``_cache`` is reassigned, so a call that raises leaves
    the mirror where the cache is); the device's ``cache["pos"]`` stays
    what the programs compute from and the mirror never writes it.
    ``step``'s phases: ``check`` reads the mirror (host only), ``wait``
    fetches the whole argmax row once, ``read`` builds the dict from
    that host array. No ``block_until_ready`` here or in ``prefill``:
    the fetch waits for the device, and its own enqueue overlaps the
    step (an explicit wait before it cost 0.9 ms a step on a v5e:
    PERF.md, PR 25).

    The cache is one device buffer for the engine's life: both programs
    take it donated and return it written in place, so ``_cache`` is
    reassigned to the same memory and the array handed in is dead
    after the call. What a failed call leaves: one refused before
    dispatch (the prompt's length, a full slot, an error while tracing
    or compiling) raises as it is and leaves cache and mirror as they
    were; one that raises after the buffers are gone (the program, or
    the fetch of its result) leaves an EMPTY cache, a zeroed mirror and
    :class:`~ray_tpu.exceptions.SlotStateLostError` — every slot has to
    be prefilled again, and the scheduler fails what was in flight."""

    def __init__(self, params, cfg, *, slots: int, max_len: int):
        import jax  # deferred: scheduler users without a
        import jax.numpy as jnp  # model never pay
        from ray_tpu.models import decode as decode_mod

        self._jax = jax
        self._jnp = jnp
        self._decode = decode_mod
        self._params = params
        self._cfg = cfg
        self.slots = int(slots)
        self.max_len = int(max_len)
        self._cache = decode_mod.init_slot_cache(cfg, slots, max_len)
        self._pos = [0] * self.slots    # host mirror of _cache["pos"]

    @contextlib.contextmanager
    def _giving_the_cache(self):
        """Yields the cache for a program to consume. Where the program
        or the fetch of its result raises: the error passes as it is if
        the cache still lives (nothing was taken: a refusal while
        tracing or compiling; donation takes the buffers at dispatch).
        Else every slot's K/V went with it: start over from an empty
        cache and say so, typed."""
        given = self._cache
        try:
            yield given
        except Exception as e:  # noqa: BLE001 — typed if the cache went
            if not given["k"][0].is_deleted():
                raise
            self._cache = None      # a result half made goes first
            self._cache = self._decode.init_slot_cache(
                self._cfg, self.slots, self.max_len)
            self._pos = [0] * self.slots
            raise SlotStateLostError(
                f"the slot cache was consumed by a call that failed "
                f"({e!r}): the state of all {self.slots} slots is lost"
            ) from e

    def prefill(self, slot: int, prompt) -> int:
        jnp = self._jnp
        tokens = jnp.asarray(prompt, jnp.int32)[None, :]
        if tokens.shape[1] >= self.max_len:
            raise ValueError(
                f"prompt ({tokens.shape[1]}) >= slot max_len "
                f"({self.max_len})")
        with self._giving_the_cache() as cache:
            logits, self._cache = self._decode.slot_prefill(
                self._params, tokens, cache, jnp.int32(slot), self._cfg)
            self._pos[slot] = tokens.shape[1]
            return int(jnp.argmax(logits[0]))

    def step(self, tokens: Dict[int, int]) -> Dict[int, int]:
        jnp = self._jnp
        with phase("serve.engine.check"):
            tok = [0] * self.slots
            act = [False] * self.slots
            for slot, t in tokens.items():
                # a slot at capacity would silently clamp its cache
                # write; refuse loudly (the scheduler's max_tokens bound
                # plus the engine's prompt-length check make this
                # unreachable)
                if self._pos[slot] >= self.max_len:
                    raise ValueError(f"slot {slot} KV cache full")
                tok[slot], act[slot] = int(t), True
        with phase("serve.engine.put"):
            tok = jnp.asarray(tok, jnp.int32)
            act = jnp.asarray(act)
        with self._giving_the_cache() as cache:
            with phase("serve.engine.dispatch"):
                logits, self._cache = self._decode.slot_decode_step(
                    self._params, cache, tok, act, self._cfg)
                for slot in tokens:
                    self._pos[slot] += 1
                nxt = jnp.argmax(logits, axis=-1)
                load = self._cache.get("load")
                if load is not None:
                    # the expert layers' counts ride in the same row
                    nxt = jnp.concatenate([nxt.astype(load.dtype), load])
            with phase("serve.engine.wait"):
                # the step's one transfer: waits for the device, then
                # brings the whole int32[slots] row
                row = self._jax.device_get(nxt)
        with phase("serve.engine.read"):
            row = row.tolist()
            if load is not None:
                for name, n in zip(EXPERT_COUNTS, row[self.slots:]):
                    phase_add(name, n)
            return {slot: row[slot] for slot in tokens}
