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
adapts the real per-slot cache. ``step`` may answer fewer slots than
it was given: an engine that keeps a step in flight, as
:class:`JaxSlotEngine` does, answers a slot that joined with a call in
the next one, and is handed that slot's last token again until it has.
Engine calls run in the default
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
  engine's own mirror of the slots' positions, and the one int32 row
  that steers the step. ``put`` sends that row, ``dispatch`` enqueues
  the step. ``wait`` is the call's one device-to-host transfer, the
  whole row of picks of the step dispatched a call EARLIER: it waits
  for what is left of that step, which has run since, and carries the
  transfer. ``read`` builds the returned dict from that host array.
  The engine's ``prefill`` has no span of its own on a trace
  (``serve.prefill`` less its hop is the call) and keeps one sum
  without a span, as ``serve.hop`` is: ``serve.engine.prefill``,
  ``[calls, seconds]`` of the call from the prompt's dispatch to its
  first token fetched on the host, for which the decoding rows stand
  still. Sums beside the phases, ``[calls, sum]`` and not seconds:
  ``serve.engine.prefill_tokens`` (a prefill's prompt length),
  ``serve.engine.ahead`` (1 for a call answered from
  a step already in flight, 0 for one that had to dispatch and wait)
  and ``serve.engine.rows_wasted`` (rows stepped that no request was
  owed: a finished request's one step more). From the host mirror of
  the slots' positions alone, once a dispatched step:
  ``serve.engine.kv_rows_read`` (over the slots it steps, the cache
  rows a full-attention layer fetches for them: whole chunks up to the
  one written to, the chunk ``ops.attention.decode_attention``'s
  kernel copies for the cache's shape; all ``max_len`` where its XLA
  form runs) and
  ``serve.engine.kv_rows_held`` (stepped slots x ``max_len``): their
  ratio is how far the bounded read engages. Both count growing caches
  only; a window layer's ring is not in them. Of a model whose layers
  keep a summary a slot (Mamba, retention), likewise once a dispatched
  step: ``serve.engine.state_rows`` (the rows in it, each of which has
  its whole state read and written, owed an answer or not). Of a
  model with expert
  layers the same fetch brings three counts of the step it fetched
  (wasted rows included), each summed over its expert layers:
  ``serve.engine.experts_hit`` (held experts that got a row),
  ``serve.engine.expert_rows`` (rows routed to held experts) and
  ``serve.engine.expert_rows_max`` (the fullest expert's rows).

Where the DEVICE's time of a decode step goes is recorded in the
compiled step itself, as names: every operation of it is traced under
the ``jax.named_scope`` of its part (``models.transformer.PARTS``:
``embed``, ``qkv``, ``full_attention`` / ``window_attention``,
``attn_out``, ``mamba_mixer`` with ``ssm_step`` inside it, ``router``,
``experts``, ``mlp``, ``head``) and every run of alike layers under
``run<i>``; what a run's loop holds outside every part (the scan's
slice of each stacked weight and the copies XLA hangs on it) reads as
that run's ``layer_weights``, and what the compiler made outside every
run and gave no name as in no part. The scopes are metadata of the
compiled program (``op_name``): XLA fuses as without them and a step
pays nothing. ``stats()["parts"]`` is ``{"slot_decode_step":
{instruction: [run, part]}}`` where the engine offers ``parts()``
(:class:`JaxSlotEngine`: made once, by its first step on its own
thread while the device runs that step, from the very executable that
runs, with no compile; None until then, and where the executable's
scopes are another tree's), and the key is absent for an engine that
compiles nothing. A prefill carries the block's scopes too, and nothing
reads them yet. A profiler names a device event by its
HLO instruction, so an operator joins the two:

    table = scheduler.stats()["parts"]["slot_decode_step"]
    # events of a jax.profiler trace's "XLA Ops" line on the device
    # plane, inside a run of the module jit_slot_decode_step(...):
    name = event.name.split(" = ")[0].split("(")[0].strip().lstrip("%")
    run, part = table.get(name, (None, None))    # a `while` is not listed

(``benchmarks/inside_parts.py`` is that join over a traced slice.)
"""

from __future__ import annotations

import asyncio
import contextlib
import functools
import logging
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, NamedTuple, Optional, Set

from ray_tpu._private import rpc
from ray_tpu.exceptions import ServeOverloadedError, SlotStateLostError
from ray_tpu.util.phases import phase, phase_add, phase_totals, recording

logger = logging.getLogger(__name__)

# what a step of a model with expert layers counts, in the order of
# ``cache["load"]`` (models/decode.py): sums, kept beside the phases
EXPERT_COUNTS = ("serve.engine.experts_hit", "serve.engine.expert_rows",
                 "serve.engine.expert_rows_max")
# the decode step's program, as a device trace names it (the jitted
# function's name: models/decode.py)
DECODE_PROGRAM = "slot_decode_step"


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
    token list. The background loop starts lazily on a submit and ends
    whenever queue and batch are both empty; the next submit starts it
    again. An idle scheduler therefore holds no task: one that nobody
    refers to any more is collected, and its engine's device cache
    with it (a parked task would keep both alive for the process's
    life: 4.4 GB of state beside whatever the owner runs next).
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
        """The loop's counters and phase sums and, where the engine
        offers ``parts()``, ``"parts": {program: {instruction: [run,
        part]}}`` of the decode step it runs (module docstring): the
        engine's own table, handed on, nothing computed here.

        TEMPORARY: an engine without ``parts`` is asked for the one it
        wraps as ``inner``. That is the attribute of the benchmark's
        ``benchmarks/worker.py::TimedEngine``, which stands between
        this loop and its ``JaxSlotEngine`` and which no PR but a
        ``benchmark`` PR may edit. The PR that enters the parts'
        metrics gives ``TimedEngine`` a ``parts`` of its own and takes
        this walk out (ROADMAP M15)."""
        out = {
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
        engine = self._engine
        while engine is not None and not hasattr(engine, "parts"):
            engine = getattr(engine, "inner", None)     # temporary: above
        if engine is not None:
            out["parts"] = {DECODE_PROGRAM: engine.parts()}
        return out

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
                if not self._queue:
                    return  # idle: ``submit`` starts the loop again
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
                # a slot that joined with this call is answered by the
                # next (JaxSlotEngine keeps one step in flight)
                self.slot_steps += len(out)
                for slot, tok in out.items():
                    req = self._active.get(slot)
                    if req is None:
                        continue
                    req.tokens.append(int(tok))
                    self.tokens_generated += 1
                    if self._done(req):
                        self._finish(slot, req)


class _Flight(NamedTuple):
    """A decode step dispatched and not yet fetched."""
    row: Any            # its picks (and counts), still on the device
    owed: Set[int]      # the slots it owes an answer; prefill takes its own out
    rode: int           # the rows in it


class JaxSlotEngine:
    """Adapts the per-slot cache (models/decode.py: an attention
    layer's K/V rows, a Mamba layer's recurrent state, a retention
    layer's matrix state) to the scheduler's engine protocol. Greedy
    decoding; prompts are int
    token-id sequences. One compiled prefill program per distinct
    prompt length, one compiled step program total.

    **One decode step is in flight.** ``step(tokens)`` dispatches the
    step for ``tokens``' slots first and only then fetches the row of
    the step that the call before dispatched, and answers from that: a
    call's answer is one step behind what the device is running, and
    between two calls (emit, executor hops, check, put) the device
    works on a step that is already queued. The token a row picked
    stays on the device (``cache["tok"]``) and the next step reads it
    there; only a slot that is not in the step in flight (just
    prefilled) is fed the host's token. So:

    * ``out`` holds the slots of ``tokens`` that were in the step in
      flight. A slot that joins with this call gets its first decode
      token from the next call; a call that finds no slot of its own in
      flight (the first ever, after an idle spell) dispatches twice and
      fetches the first, so it never answers nothing.
    * For a slot that continues, ``tokens`` must hand back the engine's
      own last answer (it is not sent up again): another value is
      refused before dispatch. A slot in the step in flight that a call
      leaves out has left: its row there is waste (a finished request
      rides one step more, no time), and it steps again only after a
      ``prefill``.
    * ``prefill`` forgets its slot's place in the step in flight, so a
      new request is answered only from steps dispatched after it: the
      old row's token never reaches it.

    **What a slot's state may suffer**, for the two kinds of state a
    layer keeps (models/decode.py). A row left out of a step (``IDLE``:
    not in ``tokens``, or sitting out at ``max_len``) keeps its state
    as it was: its K/V rows may take garbage at its frozen position,
    which the next write there covers before anyone attends it, and its
    recurrent state, which has no such position to cover later, is
    written back bit for bit. A finished request's one step more does
    advance its dead slot, rows and recurrent state alike; that slot is
    only ever re-entered through ``prefill``, which makes its whole
    state the new prompt's (rows [0, T0) and the position; the
    recurrent state computed from zeros), so no request sees its
    predecessor. ``max_len`` bounds a slot by its attention layers'
    rows; recurrent state does not grow, and for a model of Mamba or
    retention layers alone ``max_len`` sizes no buffer but the rope's
    table: the capacity check stays, since a position past the table
    has no rotation. Such a model's step moves each stepped row's whole
    state whoever is owed its token, so the rows a step moved are
    counted beside the answers it owed: ``serve.engine.state_rows``
    (``[steps dispatched, rows in them]``, from the host's mirror, no
    device read) against the ``rows`` a caller keeps of its own calls.

    A call makes one device-to-host transfer, and none before its
    dispatch. The slots' positions are mirrored on the host: ``_pos``
    is what has been dispatched (``prefill`` sets a slot's entry to the
    prompt's length, every dispatched step adds one for each row in it,
    wasted rows too), which is what the device's ``cache["pos"]`` reads
    once it has run what is queued; the mirror never writes it. A slot
    whose mirror stands at ``max_len`` is left out of a step dispatched
    ahead of its answer; asked for in earnest it raises before
    dispatch. ``step``'s phases: ``check`` reads the mirror and builds
    the row that steers the step (host only), ``put`` sends that one
    int32 row, ``wait`` fetches the row of picks of the step before,
    ``read`` builds the dict from that host array; ``serve.engine.ahead``,
    ``.rows_wasted``, ``.kv_rows_read``, ``.kv_rows_held`` (each times
    the layers that read a held layer's rows, where cross layers share
    one cache), ``.state_rows`` and, of a model whose prefill goes on
    at the last position alone behind its last mixing layer,
    ``.prefill_cross_rows`` (``[prefills, positions the layers behind
    it ran over]``) count beside them (module docstring). No
    ``block_until_ready``: the fetch
    waits for the device.

    The cache is one device buffer for the engine's life: both programs
    take it donated and return it written in place, so the step in
    flight holds the only cache. What a failed call leaves: one refused
    before dispatch (the prompt's length, a full slot, a forced token,
    an error while tracing or compiling) raises as it is and leaves
    cache, mirror and the step in flight as they were; an error after
    the buffers are gone (a program, the fetch of the step before, the
    prefill that follows a step that failed) is found up to one call
    late and leaves an EMPTY cache, a zeroed mirror, nothing in flight
    and :class:`~ray_tpu.exceptions.SlotStateLostError` — every slot has
    to be prefilled again, and the scheduler fails what was in flight."""

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
        self._parts = None      # the compiled step's table: parts()
        self._start_over()
        # positions a full-attention layer fetches at a time for a slot
        # (models/decode.py; None: the model has no such layer)
        self._kv_chunk = decode_mod.kv_rows_fetched(cfg, self._cache)
        # layers that read such rows a step, for each layer that holds
        # them: 1, and more where cross layers share a layer's cache
        self._kv_readers = decode_mod.kv_readers(cfg)
        # positions of a prompt that a prefill's second stage runs over
        # (None: the model's prefill has one stage)
        self._cross_rows = functools.partial(decode_mod.prefill_cross_rows,
                                             cfg)
        # whether a stepped row's summary state is read and written whole
        self._summary = decode_mod.keeps_summaries(cfg)

    def parts(self) -> Optional[Dict[str, list]]:
        """``{instruction: [run, part]}`` of the compiled decode step
        that this engine runs (``models.decode.program_parts``): which
        run of layers and which part of ``transformer.block`` each of
        its device operations computes. ``step`` makes it, on its own
        thread; here it is only handed out, so any thread may ask, as
        often as it likes. None before the first step, and None where
        the executable's scopes are not this tree's (loaded from a
        compile cache entry another tree wrote)."""
        return self._parts

    def _read_parts(self, fed) -> Optional[Dict[str, list]]:
        """The table of the step just dispatched with the row ``fed``,
        read while the device runs it: lowering the step again for the
        arguments it just took is answered from jax's in-memory caches,
        so this compiles nothing and loads nothing; what it costs is
        the executable's text (0.02 to 0.5 s by the program's size,
        once). None where there is none to be had; the first step after
        the next ``_start_over`` then asks again."""
        decode = self._decode
        try:
            text = decode.slot_decode_step.lower(
                self._params, self._cache, fed, None,
                self._cfg).compile().as_text()
            return decode.program_parts(text, decode.decode_parts(self._cfg))
        except Exception as e:  # noqa: BLE001 — a step must not fail for
            # its table: a step that is no jitted program has no text
            logger.warning("no table of the decode step's parts: %r", e)
            return None

    def _start_over(self) -> None:
        self._cache = self._flight = None   # a result half made goes first
        self._cache = self._decode.init_slot_cache(
            self._cfg, self.slots, self.max_len)
        self._pos = [0] * self.slots    # dispatched: _cache["pos"] to come
        # the token a slot's caller holds (the last answered, or fed to
        # a slot just prefilled) and hands back with the next call; None
        # once its row went on without it (prefill it again)
        self._last: List[Optional[int]] = [0] * self.slots

    @contextlib.contextmanager
    def _giving_the_cache(self):
        """Around what consumes the cache. Where a program or a fetch
        raises: the error passes as it is if the cache still lives
        (nothing was taken: a refusal while tracing or compiling;
        donation takes the buffers at dispatch). Else every slot's state
        went with it, and the step in flight too: start over from an
        empty cache and say so, typed."""
        given = self._cache
        try:
            yield
        except Exception as e:  # noqa: BLE001 — typed if the cache went
            if not given["pos"].is_deleted():
                raise
            self._start_over()
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
        if self._flight is not None:    # its old row answers no one
            self._flight.owed.discard(slot)
        began = time.perf_counter()
        with self._giving_the_cache():
            _, self._cache = self._decode.slot_prefill(
                self._params, tokens, self._cache, jnp.int32(slot),
                self._cfg)
            self._pos[slot] = tokens.shape[1]
            # queued behind the step in flight; the whole row of picks
            self._last[slot] = int(self._jax.device_get(
                self._cache["tok"])[slot])
        # sums, no span: a trace holds the step's five phases alone on
        # this thread, and ``serve.prefill`` around this call
        phase_add("serve.engine.prefill", time.perf_counter() - began)
        phase_add("serve.engine.prefill_tokens", tokens.shape[1])
        behind = self._cross_rows(tokens.shape[1])
        if behind is not None:  # a prefill in two stages: its second's rows
            phase_add("serve.engine.prefill_cross_rows", behind)
        return self._last[slot]

    def _steer(self, tokens: Dict[int, int], owed) -> List[int]:
        """What the next step feeds each row (models/decode.py): CARRY
        for a slot the step in flight owes an answer, the host's token
        for one it does not, IDLE for the rest."""
        CARRY, IDLE = self._decode.CARRY, self._decode.IDLE
        steer = [IDLE] * self.slots
        for slot, t in tokens.items():
            full = self._pos[slot] >= self.max_len
            if slot in owed:
                if t != self._last[slot]:
                    raise ValueError(
                        f"slot {slot} continues from its own last token "
                        f"{self._last[slot]}, which is on the device: "
                        f"{t} cannot be fed in its place")
                # ahead of its answer: a full slot just sits it out
                steer[slot] = IDLE if full else CARRY
            elif self._last[slot] is None:
                raise ValueError(
                    f"slot {slot} left a step out and its row went on "
                    f"without it: prefill it again")
            elif full:
                # a slot at capacity would silently clamp its cache
                # write; refuse loudly (the scheduler's max_tokens bound
                # plus the engine's prompt-length check make this
                # unreachable)
                raise ValueError(f"slot {slot} KV cache full")
            elif t < 0:
                raise ValueError(f"slot {slot}: token {t} is negative")
            else:
                steer[slot] = self._last[slot] = int(t)
        return steer

    def _dispatch(self, steer: List[int], fed) -> _Flight:
        row, self._cache = self._decode.slot_decode_step(
            self._params, self._cache, fed, None, self._cfg)
        rode = {slot for slot, t in enumerate(steer)
                if t != self._decode.IDLE}
        if self._kv_chunk:  # whole chunks, up to the one written to
            n, readers = self._kv_chunk, self._kv_readers
            phase_add("serve.engine.kv_rows_read", readers * sum(
                (self._pos[slot] // n + 1) * n for slot in rode))
            phase_add("serve.engine.kv_rows_held",
                      readers * len(rode) * self.max_len)
        if self._summary:
            phase_add("serve.engine.state_rows", len(rode))
        for slot in rode:
            self._pos[slot] += 1
        return _Flight(row, rode, len(rode))

    def step(self, tokens: Dict[int, int]) -> Dict[int, int]:
        if not tokens:
            return {}
        IDLE = self._decode.IDLE
        with phase("serve.engine.check"):
            first = self._flight is None    # of this cache's life
            before = self._flight or _Flight(None, set(), 0)
            ahead = any(slot in before.owed for slot in tokens)
            steers = [self._steer(tokens, before.owed)]
            if not ahead:
                # nothing in flight answers this call: the step for
                # ``tokens`` will, and one more goes out behind it (made
                # here, before the first is dispatched, so that each
                # phase is entered once a call: the mirror is one short)
                steers.append([
                    IDLE if t == IDLE or self._pos[slot] + 1 >= self.max_len
                    else self._decode.CARRY
                    for slot, t in enumerate(steers[0])])
        with phase("serve.engine.put"):
            fed = [self._jnp.asarray(steer, self._jnp.int32)
                   for steer in steers]
        wasted = 0
        with self._giving_the_cache():
            with phase("serve.engine.dispatch"):
                flights = [self._dispatch(steer, row)
                           for steer, row in zip(steers, fed)]
                if not ahead:
                    # the old step in flight is dropped unfetched (its
                    # expert counts with it): its slots have all left
                    wasted = before.rode
                    for slot in before.owed:
                        self._last[slot] = None
                    before = flights[0]
                self._flight = flights[-1]
            if first and self._parts is None:
                self._parts = self._read_parts(fed[-1])
            with phase("serve.engine.wait"):
                # the call's one transfer: waits for the step before,
                # then brings its whole int32 row
                row = self._jax.device_get(before.row)
        with phase("serve.engine.read"):
            row = row.tolist()
            for name, n in zip(EXPERT_COUNTS, row[self.slots:]):
                phase_add(name, n)
            out = {slot: row[slot] for slot in tokens if slot in before.owed}
            for slot in before.owed:
                self._last[slot] = out.get(slot)    # None: it has left
            phase_add("serve.engine.ahead", int(ahead))
            phase_add("serve.engine.rows_wasted",
                      wasted + before.rode - len(out))
            return out
