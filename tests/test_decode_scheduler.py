"""Continuous batching: slot admission at step boundaries.

Unit half: a fake engine drives :class:`serve.DecodeScheduler` without
jax — pinning the admission policy itself (join mid-batch at the next
step, finished sequence frees its slot immediately, occupancy never
exceeds the slot count, typed shed past the queue cap, step failure
fails in-flight work but the loop survives).

Oracle half: the per-slot KV cache (models/decode.py slot_prefill /
slot_decode_step) must produce, token for token, the argmax of the full
``forward()`` on the growing prefix, including through a slot freed and
re-prefilled mid-flight.

Engine half: :class:`JaxSlotEngine` keeps one decode step in flight
(dispatch, then fetch the step before): what a call answers and when,
what a finished or re-prefilled slot's row in flight comes to, the
position mirror, one transfer a call, and a failure found a call late.
"""

import asyncio
import subprocess
import sys
import time

import pytest

from ray_tpu.exceptions import ServeOverloadedError, SlotStateLostError
from ray_tpu.serve.decode_scheduler import DecodeScheduler


class FreeRunEngine:
    """Deterministic sync engine: prefill emits prompt[0]+100, each step
    increments. Records per-step occupancy."""

    def __init__(self, slots):
        self.slots = slots
        self.step_slots = []       # sorted slot ids per step
        self.prefills = []         # (slot, prompt) in admission order

    def prefill(self, slot, prompt):
        self.prefills.append((slot, tuple(prompt)))
        return prompt[0] + 100

    def step(self, tokens):
        self.step_slots.append(sorted(tokens))
        return {s: t + 1 for s, t in tokens.items()}


class GatedEngine(FreeRunEngine):
    """Async engine whose step() blocks on a semaphore — the test
    releases one permit per decode step, so admission timing relative
    to step boundaries is fully deterministic."""

    def __init__(self, slots):
        super().__init__(slots)
        self.gate = asyncio.Semaphore(0)

    async def step(self, tokens):
        await self.gate.acquire()
        self.step_slots.append(sorted(tokens))
        return {s: t + 1 for s, t in tokens.items()}


class LaggingEngine(FreeRunEngine):
    """FreeRunEngine's tokens, answered one call behind as by an engine
    that keeps a step in flight: a call computes its slots' next tokens
    (from the token in flight where there is one, else from the one it
    is handed) and answers with what the call before computed; with
    nothing in flight for its slots it computes twice."""

    def __init__(self, slots):
        super().__init__(slots)
        self.flying = {}

    def prefill(self, slot, prompt):
        self.flying.pop(slot, None)
        return super().prefill(slot, prompt)

    def step(self, tokens):
        self.step_slots.append(sorted(tokens))
        out = {s: self.flying[s] for s in tokens if s in self.flying}
        self.flying = {s: out.get(s, t) + 1 for s, t in tokens.items()}
        if not out:
            out, self.flying = self.flying, {
                s: t + 1 for s, t in self.flying.items()}
        return out


def test_an_engine_that_answers_a_call_late_is_served_in_full():
    """``step`` may answer fewer slots than it was given (a slot that
    joined with the call): the scheduler hands that slot's token back
    until it is answered, every request gets its own tokens in full,
    and ``slot_steps`` counts each decode token once."""
    async def run():
        eng = LaggingEngine(slots=3)
        sched = DecodeScheduler(eng)
        outs = await asyncio.gather(
            *[sched.submit([i], max_tokens=2 + i % 4) for i in range(10)])
        for i, toks in enumerate(outs):
            assert toks == [i + 100 + k for k in range(2 + i % 4)]
        st = sched.stats()
        assert st["completed"] == 10
        assert st["slot_steps"] == st["tokens_generated"] - 10
        # the joining call's row and a finished slot's one step more are
        # rows the engine stepped and the scheduler did not count
        assert st["slot_steps"] < sum(len(s) for s in eng.step_slots)
        await sched.aclose()
    asyncio.run(run())


def test_single_request_generates_max_tokens():
    async def run():
        eng = FreeRunEngine(slots=2)
        sched = DecodeScheduler(eng)
        toks = await sched.submit([7], max_tokens=4)
        assert toks == [107, 108, 109, 110]
        st = sched.stats()
        assert st["completed"] == 1 and st["active_slots"] == 0
        assert st["free_slots"] == 2
        await sched.aclose()
    asyncio.run(run())


def test_an_idle_scheduler_holds_no_task_and_is_collected_with_its_engine():
    """The loop ends when queue and batch are empty and the next submit
    starts it again; between bursts nothing but its owner refers to the
    scheduler, so dropping it frees the engine (on the chip: the slot
    cache, gigabytes that whatever runs next may need)."""
    import gc
    import weakref

    async def run():
        engine = FreeRunEngine(slots=2)
        sched = DecodeScheduler(engine)
        assert await sched.submit([1], max_tokens=3) == [101, 102, 103]
        await asyncio.sleep(0)          # the loop sees nothing to do
        assert sched._loop_task.done()
        # a second burst starts it again
        assert await asyncio.gather(
            sched.submit([5], max_tokens=2), sched.submit([7], max_tokens=4)
        ) == [[105, 106], [107, 108, 109, 110]]
        await asyncio.sleep(0)
        gone = weakref.ref(engine)
        del engine, sched
        gc.collect()
        assert gone() is None

    asyncio.run(run())


def test_the_phase_sums_go_on_across_the_loops_restarts():
    """Each burst's loop task records into the scheduler's one table:
    ``stats()`` after a second burst holds both bursts' spans and
    counters, not the last task's alone."""
    async def run():
        sched = DecodeScheduler(FreeRunEngine(slots=2))
        await sched.submit([1], max_tokens=3)
        await asyncio.sleep(0)
        first = sched.stats()
        assert sched._loop_task.done()
        await sched.submit([2], max_tokens=4)
        await asyncio.sleep(0)
        second = sched.stats()
        for name in ("serve.admit", "serve.step", "serve.emit"):
            (n1, s1), (n2, s2) = first["phases"][name], second["phases"][name]
            assert 0 < n1 < n2 and 0.0 < s1 < s2, name
        assert (first["steps"], second["steps"]) == (2, 5)
        assert (first["completed"], second["completed"]) == (1, 2)

    asyncio.run(run())


def test_closing_a_scheduler_whose_loop_has_ended_changes_nothing():
    async def run():
        engine = FreeRunEngine(slots=2)
        sched = DecodeScheduler(engine)
        assert await sched.submit([1], max_tokens=2) == [101, 102]
        await asyncio.sleep(0)
        assert sched._loop_task.done()
        before = sched.stats()
        await sched.aclose()            # no task to cancel, nobody to fail
        assert sched.stats() == before
        assert sched._loop_task is None and len(engine.step_slots) == 1
        with pytest.raises(ServeOverloadedError):
            await sched.submit([3], max_tokens=1)
        await sched.aclose()            # and once more

    asyncio.run(run())


def test_occupancy_never_exceeds_slots():
    async def run():
        eng = FreeRunEngine(slots=3)
        sched = DecodeScheduler(eng)
        outs = await asyncio.gather(
            *[sched.submit([i], max_tokens=3) for i in range(10)])
        for i, toks in enumerate(outs):
            assert toks == [i + 100, i + 101, i + 102]
        assert max(len(s) for s in eng.step_slots) <= 3
        assert sched.stats()["completed"] == 10
        await sched.aclose()
    asyncio.run(run())


def test_late_request_joins_next_step_not_batch_drain():
    """The continuous-batching contract: a request arriving while a
    batch decodes is admitted at the NEXT step boundary and decodes
    alongside it — never parked until the batch drains."""
    async def run():
        eng = GatedEngine(slots=2)
        sched = DecodeScheduler(eng)
        a = asyncio.ensure_future(sched.submit([1], max_tokens=8))
        # let A prefill and park at the gated step
        while not eng.prefills:
            await asyncio.sleep(0.001)
        eng.gate.release()          # A decodes step 1 alone
        while len(eng.step_slots) < 1:
            await asyncio.sleep(0.001)
        b = asyncio.ensure_future(sched.submit([2], max_tokens=2))
        for _ in range(10):
            eng.gate.release()
        toks_b = await b
        assert toks_b == [102, 103]
        toks_a = await a
        assert toks_a == [101, 102, 103, 104, 105, 106, 107, 108]
        # B shared a step with A (mid-batch admission, not serial)
        assert any(len(s) == 2 for s in eng.step_slots)
        assert sched.stats()["admitted_mid_batch"] == 1
        # ...and B finished while A was still decoding
        assert b.done() and toks_b[-1] == 103
        await sched.aclose()
    asyncio.run(run())


def test_finished_sequence_frees_slot_immediately():
    async def run():
        eng = GatedEngine(slots=1)
        sched = DecodeScheduler(eng)
        a = asyncio.ensure_future(sched.submit([1], max_tokens=2))
        while not eng.prefills:
            await asyncio.sleep(0.001)
        b = asyncio.ensure_future(sched.submit([2], max_tokens=2))
        for _ in range(4):
            eng.gate.release()
        assert await a == [101, 102]
        assert await b == [102, 103]
        # one slot served both: B's prefill reused slot 0 after A freed
        assert [s for s, _ in eng.prefills] == [0, 0]
        await sched.aclose()
    asyncio.run(run())


def test_eos_token_finishes_early():
    async def run():
        eng = FreeRunEngine(slots=1)
        sched = DecodeScheduler(eng)
        toks = await sched.submit([1], max_tokens=50, eos_token=103)
        assert toks == [101, 102, 103]
        await sched.aclose()
    asyncio.run(run())


def test_queue_cap_sheds_typed():
    async def run():
        eng = GatedEngine(slots=1)
        sched = DecodeScheduler(eng, max_queue_depth=2)
        a = asyncio.ensure_future(sched.submit([1], max_tokens=4))
        while not eng.prefills:
            await asyncio.sleep(0.001)
        # slot busy: these two queue...
        q = [asyncio.ensure_future(sched.submit([i], max_tokens=1))
             for i in (2, 3)]
        await asyncio.sleep(0)   # let them enqueue
        # ...and the third sheds with the typed overload error
        with pytest.raises(ServeOverloadedError) as ei:
            await sched.submit([4], max_tokens=1)
        assert ei.value.retry_after_s > 0
        assert sched.stats()["shed"] == 1
        for _ in range(8):
            eng.gate.release()
        await asyncio.gather(a, *q)
        await sched.aclose()
    asyncio.run(run())


def test_step_failure_fails_inflight_but_loop_survives():
    class FlakyEngine(FreeRunEngine):
        def __init__(self):
            super().__init__(slots=1)
            self.boom = True

        def step(self, tokens):
            if self.boom:
                self.boom = False
                raise RuntimeError("device fell over")
            return super().step(tokens)

    async def run():
        eng = FlakyEngine()
        sched = DecodeScheduler(eng)
        with pytest.raises(RuntimeError, match="device fell over"):
            await sched.submit([1], max_tokens=3)
        # the loop and the slot survive the failed step
        assert await sched.submit([5], max_tokens=2) == [105, 106]
        await sched.aclose()
    asyncio.run(run())


def test_bad_prompt_fails_only_its_request():
    class PickyEngine(FreeRunEngine):
        def prefill(self, slot, prompt):
            if prompt[0] < 0:
                raise ValueError("negative prompt")
            return super().prefill(slot, prompt)

    async def run():
        eng = PickyEngine(slots=2)
        sched = DecodeScheduler(eng)
        good = asyncio.ensure_future(sched.submit([3], max_tokens=2))
        with pytest.raises(ValueError, match="negative prompt"):
            await sched.submit([-1], max_tokens=2)
        assert await good == [103, 104]
        assert sched.stats()["free_slots"] == 2
        await sched.aclose()
    asyncio.run(run())


def test_aclose_fails_pending_typed():
    async def run():
        eng = GatedEngine(slots=1)
        sched = DecodeScheduler(eng)
        a = asyncio.ensure_future(sched.submit([1], max_tokens=4))
        while not eng.prefills:
            await asyncio.sleep(0.001)
        await sched.aclose()
        with pytest.raises(ServeOverloadedError):
            await a
        with pytest.raises(ServeOverloadedError):
            await sched.submit([2], max_tokens=1)
    asyncio.run(run())


def test_zero_slot_engine_rejected():
    eng = FreeRunEngine(slots=0)
    with pytest.raises(ValueError, match="at least one slot"):
        DecodeScheduler(eng)


# ------------------------------------------- phases, from the inside

SCHEDULER_PHASES = ("serve.admit", "serve.admit_stall", "serve.prefill",
                    "serve.step", "serve.hop", "serve.emit")
ENGINE_STEP_PHASES = tuple("serve.engine." + n for n in (
    "check", "put", "dispatch", "wait", "read"))


class SleepyEngine(FreeRunEngine):
    """Sync engine (runs on the executor) whose calls take a known
    time, and which keeps its own clock of them: ``calls`` holds
    (kind, start, end) on ``time.perf_counter``, so a test compares the
    scheduler's sums with what the engine saw and not with what a busy
    box made of a sleep."""

    def __init__(self, slots, prefill_s=0.02, step_s=0.01):
        super().__init__(slots)
        self.prefill_s, self.step_s = prefill_s, step_s
        self.calls = []

    def _sleep(self, kind, seconds):
        t0 = time.perf_counter()
        time.sleep(seconds)
        self.calls.append((kind, t0, time.perf_counter()))

    def prefill(self, slot, prompt):
        self._sleep("prefill", self.prefill_s)
        return super().prefill(slot, prompt)

    def step(self, tokens):
        self._sleep("step", self.step_s)
        return super().step(tokens)

    def ran(self, kind):
        return sum(end - t0 for k, t0, end in self.calls if k == kind)


class StampedScheduler(DecodeScheduler):
    """Keeps the loop's own stamps of its first admission and its last
    completion: the window its top spans are held against."""

    first_admit = last_finish = None

    async def _admit(self):
        if self.first_admit is None and self._queue:
            self.first_admit = time.perf_counter()
        await super()._admit()

    def _finish(self, slot, req):
        super()._finish(slot, req)
        self.last_finish = time.perf_counter()


def test_the_scheduler_imports_no_jax():
    """A replica's control code and the fake-engine tests stay off jax:
    the phase helper annotates the profiler's trace only where jax is
    already there."""
    code = ("import sys; import ray_tpu.serve.decode_scheduler; "
            "from ray_tpu.util.tracing import phase\n"
            "with phase('serve.probe'): pass\n"
            "assert 'jax' not in sys.modules, 'jax was imported'")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_the_phase_module_imports_the_standard_library_only():
    """The hot path's helper arms nothing: ``util/tracing.py`` (whose
    import sets the submit path's hook) offers its names, not the other
    way round."""
    import ast

    import ray_tpu.util.phases as phases
    from ray_tpu.util import tracing

    with open(phases.__file__) as f:
        tree = ast.parse(f.read())
    imported = {a.name.split(".")[0] for n in ast.walk(tree)
                if isinstance(n, ast.Import) for a in n.names}
    imported |= {n.module.split(".")[0] for n in ast.walk(tree)
                 if isinstance(n, ast.ImportFrom)}
    assert imported <= set(sys.stdlib_module_names), imported
    assert tracing.phase is phases.phase
    assert tracing.phase_totals is phases.phase_totals


def test_phases_count_steps_and_admissions():
    async def run():
        eng = SleepyEngine(slots=2, prefill_s=0.04, step_s=0.02)
        sched = StampedScheduler(eng)
        assert sched.stats()["phases"] == {}
        await asyncio.gather(*[sched.submit([i], max_tokens=4)
                               for i in range(4)])
        st = sched.stats()
        await sched.aclose()
        return eng, sched, st

    eng, sched, st = asyncio.run(run())
    got = st["phases"]
    assert set(got) == set(SCHEDULER_PHASES)
    assert got["serve.step"][0] == st["steps"] == len(eng.step_slots)
    assert got["serve.emit"][0] == st["steps"]
    assert got["serve.prefill"][0] == st["admitted"] == 4
    assert got["serve.hop"][0] == st["steps"] + st["admitted"]
    assert got["serve.admit_stall"][0] == got["serve.admit"][0] >= 2
    # the spans hold the engine's calls, and the hop is what they hold
    # besides, by the engine's own clock
    step_s, prefill_s = got["serve.step"][1], got["serve.prefill"][1]
    assert eng.ran("step") <= step_s
    assert eng.ran("prefill") <= prefill_s <= got["serve.admit"][1]
    beyond = step_s + prefill_s - eng.ran("step") - eng.ran("prefill")
    assert 0.0 <= got["serve.hop"][1] <= beyond
    assert got["serve.hop"][1] == pytest.approx(beyond, abs=0.01)
    # what the loop does outside its three top spans is small: they
    # cover the loop's time from its first admission to its last
    # completion (the last emit ends a moment after that stamp)
    window = sched.last_finish - sched.first_admit
    covered = sum(got[n][1] for n in ("serve.admit", "serve.step",
                                      "serve.emit"))
    assert 0.95 * window <= covered <= window + 0.005


def test_phase_sums_only_grow():
    async def run():
        sched = DecodeScheduler(SleepyEngine(slots=1, prefill_s=0.002,
                                             step_s=0.002))
        seen = [sched.stats()["phases"]]
        for i in range(3):
            await sched.submit([i], max_tokens=3)
            seen.append(sched.stats()["phases"])
        await sched.aclose()
        return seen

    seen = asyncio.run(run())
    for earlier, later in zip(seen, seen[1:]):
        for name, (n, s) in earlier.items():
            assert later[name][0] >= n and later[name][1] >= s
    assert [t["serve.step"][0] for t in seen[1:]] == [2, 4, 6]


def test_two_schedulers_keep_their_phases_apart():
    """The table is the scheduler's: what an engine times inside a call
    lands with the scheduler that made the call, through whatever wraps
    the engine (the benchmark hands the scheduler a wrapper that shows
    ``slots``, ``prefill`` and ``step`` only), and two schedulers at
    work at once in one process do not mix."""
    from ray_tpu.util.phases import phase, phase_totals

    class PhasedEngine(SleepyEngine):
        def step(self, tokens):
            with phase("serve.engine.check"):
                return super().step(tokens)

    class Wrapped:
        def __init__(self, engine):
            self.slots = engine.slots
            self.prefill, self.step = engine.prefill, engine.step

    async def run():
        scheds = [DecodeScheduler(Wrapped(PhasedEngine(
            slots=1, prefill_s=0.002, step_s=0.002))) for _ in range(2)]
        await asyncio.gather(scheds[0].submit([1], max_tokens=3),
                             scheds[1].submit([2], max_tokens=6))
        stats = [s.stats() for s in scheds]
        for s in scheds:
            await s.aclose()
        return stats

    process = phase_totals("serve.")
    one, two = asyncio.run(run())
    assert (one["steps"], two["steps"]) == (2, 5)
    for st in (one, two):
        assert st["phases"]["serve.engine.check"][0] == st["steps"]
        assert st["phases"]["serve.step"][0] == st["steps"]
        assert st["phases"]["serve.prefill"][0] == 1
    # and nothing of either went to the process's own table
    assert phase_totals("serve.") == process


def test_request_sums_match_the_engines_clock():
    """One slot, two requests at once: the second waits for the whole
    of the first. Sums are over ``admitted`` and ``completed``, and are
    told from the engine's own stamps: a request is submitted just
    before the first prefill starts, taken when the slot comes free,
    has its first token when its prefill ends."""
    async def run():
        eng = SleepyEngine(slots=1, prefill_s=0.1, step_s=0.05)
        sched = DecodeScheduler(eng)
        await asyncio.gather(sched.submit([1], max_tokens=3),
                             sched.submit([2], max_tokens=3))
        st = sched.stats()
        await sched.aclose()
        return eng, st

    eng, st = asyncio.run(run())
    assert st["admitted"] == st["completed"] == 2
    assert [k for k, _, _ in eng.calls] == ["prefill", "step", "step"] * 2
    t0 = eng.calls[0][1]
    ends = [end - t0 for _, _, end in eng.calls]
    # under the least a sum counted twice would add (a prefill: 0.1)
    near = dict(abs=0.08)
    assert st["queue_wait_s"] == pytest.approx(0.0 + ends[2], **near)
    assert st["first_token_s"] == pytest.approx(ends[0] + ends[3], **near)
    assert st["request_s"] == pytest.approx(ends[2] + ends[5], **near)
    assert st["queue_wait_s"] >= 0.1 + 2 * 0.05
    assert st["request_s"] >= 3 * (0.1 + 2 * 0.05)


def test_admit_stall_is_the_prefill_time_of_mid_batch_admissions():
    async def run(late):
        eng = SleepyEngine(slots=2, prefill_s=0.03, step_s=0.005)
        sched = DecodeScheduler(eng)
        a = asyncio.ensure_future(sched.submit([1], max_tokens=12))
        if late:
            while not eng.step_slots:       # A is decoding
                await asyncio.sleep(0.001)
            await sched.submit([2], max_tokens=2)
        await a
        st = sched.stats()
        await sched.aclose()
        return st["phases"], st["admitted_mid_batch"]

    alone, mid = asyncio.run(run(late=False))
    assert mid == 0 and alone["serve.admit_stall"] == [1, 0.0]
    joined, mid = asyncio.run(run(late=True))
    assert mid == 1
    stall = joined["serve.admit_stall"][1]
    assert 0.03 <= stall <= joined["serve.admit"][1] - 0.03


def test_a_raising_step_still_closes_and_counts_its_span():
    class FlakyEngine(SleepyEngine):
        def step(self, tokens):
            time.sleep(self.step_s)
            raise RuntimeError("device fell over")

    async def run():
        sched = DecodeScheduler(FlakyEngine(slots=1))
        with pytest.raises(RuntimeError, match="device fell over"):
            await sched.submit([1], max_tokens=3)
        st = sched.stats()
        await sched.aclose()
        return st

    st = asyncio.run(run())
    got = st["phases"]
    assert st["steps"] == 0
    assert got["serve.step"][0] == 1 and got["serve.step"][1] >= 0.01
    assert got["serve.hop"][0] == 2         # the prefill's and the step's
    assert "serve.emit" not in got


# ------------------------------------------------------------- jax oracle


def _tiny_engine(slots, max_len, max_seq=64):
    """The real engine over a model of two layers whose head is its
    own, so that a greedy sequence wanders (a tied one repeats its last
    token, and a stale token would pass for the right one)."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig, init_params
    from ray_tpu.serve.decode_scheduler import JaxSlotEngine

    cfg = TransformerConfig(vocab=97, d_model=64, n_heads=4, n_layers=2,
                            d_ff=128, max_seq=max_seq, dtype=jnp.float32,
                            tie_embeddings=False)
    return JaxSlotEngine(init_params(jax.random.key(0), cfg), cfg,
                         slots=slots, max_len=max_len)


def _oracle(eng, prompt, n, eos=None):
    """``n`` greedy tokens (fewer after ``eos``) by the full forward()
    on the growing prefix, which shares no cache code."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import forward

    prefix = list(prompt)
    while len(prefix) < len(prompt) + n:
        logits = forward(eng._params, jnp.asarray([prefix], jnp.int32),
                         eng._cfg)
        prefix.append(int(jnp.argmax(logits[0, -1])))
        if prefix[-1] == eos:
            break
    return prefix[len(prompt):]


def test_the_tiny_models_greedy_sequences_wander():
    """The oracle is worth its name: neighbouring tokens of a greedy
    sequence differ, so a token one step stale is a wrong token."""
    eng = _tiny_engine(slots=1, max_len=8)
    for prompt in ([5, 11, 23], [40, 2], [7]):
        seq = _oracle(eng, prompt, 8)
        assert sum(a != b for a, b in zip(seq, seq[1:])) >= 5, seq


def test_slot_cache_matches_full_forward_on_the_growing_prefix():
    """Greedy tokens through the per-slot cache — including a slot
    freed by one sequence and re-prefilled by another mid-flight —
    are, request by request, the per-step argmax of the full forward()
    on the growing prefix, which shares no cache code."""
    eng = _tiny_engine(slots=2, max_len=32)
    prompts = [[5, 11, 23], [40, 2, 9], [88, 17, 3]]
    steps = [6, 3, 4]   # seq1 finishes early; seq2 takes its slot

    async def run():
        sched = DecodeScheduler(eng)
        outs = await asyncio.gather(
            *[sched.submit(p, max_tokens=n)
              for p, n in zip(prompts, steps)])
        await sched.aclose()
        return outs

    outs = asyncio.run(run())
    for prompt, n, got in zip(prompts, steps, outs):
        assert got == _oracle(eng, prompt, n), (prompt, n)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a_seeded_load_through_the_scheduler_equals_forward(seed):
    """Twelve requests of seeded prompts and lengths on three slots,
    one of them ended by an EOS it really meets, arriving in two waves:
    slots finish at different calls, ride their wasted step and are
    prefilled again while other rows are in flight. Every request gets
    exactly forward()'s greedy tokens on its own growing prefix."""
    import random

    eng = _tiny_engine(slots=3, max_len=40)
    rng = random.Random(seed)
    asks = [([rng.randrange(97) for _ in range(rng.randint(1, 9))],
             rng.randint(1, 14)) for _ in range(12)]
    # an EOS that the fourth request meets half way
    long = _oracle(eng, asks[3][0], 14)
    eos = long[6]
    asks[3] = (asks[3][0], 14)

    async def run():
        sched = DecodeScheduler(eng)
        first = [asyncio.ensure_future(sched.submit(
            p, max_tokens=n, eos_token=eos if i == 3 else None))
            for i, (p, n) in enumerate(asks[:7])]
        await asyncio.wait(first, return_when=asyncio.FIRST_COMPLETED)
        rest = [sched.submit(p, max_tokens=n) for p, n in asks[7:]]
        outs = await asyncio.gather(*first, *rest)
        stats = sched.stats()
        await sched.aclose()
        return outs, stats

    outs, stats = asyncio.run(run())
    for i, ((prompt, n), got) in enumerate(zip(asks, outs)):
        assert got == _oracle(eng, prompt, n, eos if i == 3 else None), i
    assert len(outs[3]) == long.index(eos) + 1 < 14
    # every decode token was counted in one step's occupancy, once
    assert stats["slot_steps"] == stats["tokens_generated"] - 12
    wasted = stats["phases"]["serve.engine.rows_wasted"][1]
    assert 0 < wasted <= sum(n > 1 for _, n in asks) + 1


ENGINE_COUNTS = ("serve.engine.ahead", "serve.engine.rows_wasted",
                 "serve.engine.kv_rows_read", "serve.engine.kv_rows_held")


def test_engine_phases_cover_the_step():
    """Every step of the real engine records its five phases once (and
    its four counts beside them: off the TPU a full-attention layer
    reads every row a stepped slot holds), a prefill its seconds and its prompt's
    length as two sums, and the step's phases are the step: their sum is 90 to 100 % of its wall
    time (the median step's, so that one stall of a shared box between
    two spans does not decide it)."""
    import statistics

    from ray_tpu.util.phases import recording

    eng = _tiny_engine(slots=2, max_len=32)
    with recording({}) as table:
        last = {0: eng.prefill(0, [5, 11, 23]), 1: eng.prefill(1, [40, 2])}
    assert sorted(table) == ["serve.engine.prefill",
                             "serve.engine.prefill_tokens"]
    assert table["serve.engine.prefill"][0] == 2
    assert table["serve.engine.prefill"][1] > 0.0
    assert table["serve.engine.prefill_tokens"] == [2, 3 + 2]
    with recording({}) as table:
        last = eng.step(last)               # compiles; two dispatches
    assert [table[p][0] for p in ENGINE_STEP_PHASES] == [1] * 5
    shares = []
    for _ in range(15):
        with recording({}) as table:
            t0 = time.perf_counter()
            last = eng.step(last)
            wall = time.perf_counter() - t0
        assert sorted(table) == sorted(ENGINE_STEP_PHASES + ENGINE_COUNTS)
        assert [table[p][0] for p in ENGINE_STEP_PHASES] == [1] * 5
        assert table["serve.engine.ahead"] == [1, 1]
        assert table["serve.engine.rows_wasted"] == [1, 0]
        assert table["serve.engine.kv_rows_read"] == [1, 2 * 32]
        assert table["serve.engine.kv_rows_held"] == [1, 2 * 32]
        shares.append(sum(table[p][1] for p in ENGINE_STEP_PHASES) / wall)
    assert max(shares) <= 1.0
    assert statistics.median(shares) >= 0.9


def test_the_engine_counts_the_rows_a_bounded_read_fetches(
        decode_kernel_interpreted):
    """Through the decode kernel (interpreted; a cache of 256 rows gets
    chunks of 128) the engine's tokens are still forward()'s, and it
    counts, from its host mirror alone, the rows a full-attention layer
    copies for the slots it steps (whole chunks up to the one written
    to) beside the rows they hold."""
    from ray_tpu.util.phases import recording

    eng = _tiny_engine(slots=2, max_len=256, max_seq=256)
    assert eng._kv_chunk == 128
    prompts = {0: [5, 11, 23], 1: list(range(1, 127))}
    want = {slot: _oracle(eng, p, 5) for slot, p in prompts.items()}
    last = {slot: eng.prefill(slot, p) for slot, p in prompts.items()}
    got = {slot: [t] for slot, t in last.items()}
    with recording({}) as table:
        for _ in range(4):
            last = eng.step(last)
            for slot, t in last.items():
                got[slot].append(t)
    assert got == want
    # five dispatches (the first call makes two): slot 0 writes at 3..7,
    # one block each; slot 1 at 126..130, of which 128.. reach a second
    assert table["serve.engine.kv_rows_read"] == [
        5, 5 * 128 + 2 * 128 + 3 * 256]
    assert table["serve.engine.kv_rows_held"] == [5, 5 * 2 * 256]


# --------------------------- one step in flight, one transfer a call


def _device_pos(eng):
    """``cache["pos"]`` once the device has run what is queued (the
    read waits for it)."""
    import numpy as np

    return np.asarray(eng._cache["pos"]).tolist()


class _Client:
    """A caller that keeps the engine's protocol: hands each slot's last
    token back, takes an answer when it comes (a slot that joined is
    answered a call late), and holds every token to the oracle."""

    def __init__(self, eng):
        self.eng, self.prefix, self.done = eng, {}, []

    def prefill(self, slot, prompt):
        if slot in self.prefix:
            self.done.append(self.prefix[slot])
        self.prefix[slot] = (len(prompt), list(prompt) + [
            self.eng.prefill(slot, prompt)])

    def step(self, *slots):
        out = self.eng.step({s: self.last(s) for s in slots})
        for slot, tok in out.items():
            self.prefix[slot][1].append(tok)
        return sorted(out)

    def last(self, slot):
        return self.prefix[slot][1][-1]

    def check(self):
        """Every request, the finished ones too; gives how many tokens
        each got."""
        served = self.done + list(self.prefix.values())
        for n, prefix in served:
            assert prefix[n:] == _oracle(self.eng, prefix[:n],
                                         len(prefix) - n), prefix[:n]
        return [len(prefix) - n for n, prefix in served]


# a slot finishes (0 leaves after the fifth call) and is taken again
# while the others' rows are in flight; 1 joins a running batch.
# (call, its arguments, the slots it answers, the mirror after it)
SCRIPT = [
    ("prefill", (0, [5, 11, 23]), None, [3, 0, 0]),
    ("prefill", (2, [40, 2]), None, [3, 0, 2]),
    ("step", (0, 2), [0, 2], [5, 0, 4]),        # the first: two dispatches
    ("step", (0, 2), [0, 2], [6, 0, 5]),
    ("prefill", (1, [88, 17, 3, 9, 1]), None, [6, 5, 5]),
    ("step", (0, 1, 2), [0, 2], [7, 6, 6]),     # 1 is answered a call late
    ("step", (1, 2), [1, 2], [7, 7, 7]),        # 0 is done: its row is waste
    ("prefill", (0, [7]), None, [1, 7, 7]),     # ... and the slot taken again
    ("step", (0, 1, 2), [1, 2], [2, 8, 8]),
    ("step", (0, 1, 2), [0, 1, 2], [3, 9, 9]),
    ("step", (2,), [2], [3, 9, 10]),            # 0 and 1 leave together
    ("prefill", (1, [30, 31]), None, [3, 2, 10]),
    ("step", (1, 2), [2], [3, 3, 11]),
    ("step", (1, 2), [1, 2], [3, 4, 12]),
]


def test_position_mirror_is_what_was_dispatched_and_the_device_follows():
    """Prefills, steps, a slot that finishes and is prefilled again, a
    slot that joins: after each call the host's positions are what has
    been dispatched, every row of every step counted (the second
    dispatch of a call that found nothing in flight, the wasted step of
    a slot that left), and the device's ``cache["pos"]`` reads the same
    in every row once it has run what is queued."""
    eng = _tiny_engine(slots=3, max_len=32)
    assert eng._pos == _device_pos(eng) == [0, 0, 0]
    client = _Client(eng)
    for kind, args, answered, want in SCRIPT:
        got = getattr(client, kind)(*args)
        assert got == answered, (kind, args)
        assert eng._pos == want == _device_pos(eng), (kind, args)


def test_a_script_of_prefills_and_steps_equals_forward_on_the_prefix():
    """The same script: every token the engine gives, one call behind
    the step that made it, is the argmax of ``forward()`` on that
    slot's growing prefix (no cache code); the slot taken again is
    answered from its new prompt alone."""
    client = _Client(_tiny_engine(slots=3, max_len=32))
    for kind, args, _, _ in SCRIPT:
        getattr(client, kind)(*args)
    # the two requests that ended (slot 0's, slot 1's), then slot 0's
    # second, slot 2's only one and slot 1's second
    assert client.check() == [4, 4, 2, 10, 2]


def test_the_counters_count_what_the_script_did():
    """``serve.engine.ahead``: of the script's nine steps all but the
    first were answered from a step already in flight.
    ``rows_wasted``: slot 0's row after its request was done, and slots
    0 and 1 when they left together; a slot prefilled while its row was
    in flight (none here) would count too."""
    from ray_tpu.util.phases import recording

    client = _Client(_tiny_engine(slots=3, max_len=32))
    with recording({}) as table:
        for kind, args, _, _ in SCRIPT:
            getattr(client, kind)(*args)
    assert table["serve.engine.ahead"] == [9, 8]
    assert table["serve.engine.rows_wasted"] == [9, 3]


def test_a_slot_taken_again_is_never_answered_from_its_old_row():
    """Slot 0's request ends while its next row is in flight; a new
    prompt is prefilled into the slot before the next call. The old
    row's token, which the fetch of that step brings to the host, is a
    real and different token: the new request gets its own, a call
    later, and its neighbour is answered without a break."""
    import numpy as np

    eng = _tiny_engine(slots=2, max_len=32)
    client = _Client(eng)
    client.prefill(0, [5, 11, 23])
    client.prefill(1, [40, 2])
    assert client.step(0, 1) == [0, 1] == client.step(0, 1)
    stale = np.asarray(eng._flight.row).tolist()[0]
    client.prefill(0, [7])                      # slot 0 ended, taken again
    assert eng._flight.owed == {1}
    assert client.step(0, 1) == [1]             # nothing for 0 from that row
    assert client.step(0, 1) == [0, 1]
    client.check()
    assert client.prefix[0][1][2] != stale
    # ... and alone: nothing in flight is owed to anyone, so the call
    # dispatches twice and answers from the first
    client.prefill(0, [9, 9])
    client.prefill(1, [1])
    assert eng._flight.owed == set()
    assert client.step(0, 1) == [0, 1]
    client.check()


def test_a_full_slot_rides_no_step_ahead_and_is_refused_in_earnest():
    """A slot whose position has reached ``max_len`` is left out of the
    step dispatched ahead of its last answer (no write past the row's
    end, no error: the answer it is owed still comes). Asked to step
    again it raises from the host-side check: no put, no dispatch (the
    cache is the object it was), and no slot of the call advances."""
    from ray_tpu.util.phases import recording

    eng = _tiny_engine(slots=2, max_len=8)
    client = _Client(eng)
    client.prefill(0, [5, 11, 23, 4, 9])
    client.prefill(1, [40])
    assert client.step(0, 1) == [0, 1]
    assert eng._pos == [7, 3]
    assert client.step(0, 1) == [0, 1]          # dispatched: row 7, the last
    assert eng._pos == _device_pos(eng) == [8, 4]
    assert client.step(0, 1) == [0, 1]          # 0 sits the step ahead out
    assert eng._pos == _device_pos(eng) == [8, 5]
    assert eng._flight.owed == {1}
    assert client.check()[0] == 4       # the prefill's and rows 5, 6, 7's
    cache, flight = eng._cache, eng._flight
    with recording({}) as table:
        with pytest.raises(ValueError, match="^slot 0 KV cache full$"):
            client.step(1, 0)
    assert eng._cache is cache and eng._flight is flight
    assert eng._pos == _device_pos(eng) == [8, 5]
    assert sorted(table) == ["serve.engine.check"]
    assert client.step(1) == [1]                # the other decodes on
    assert eng._pos == _device_pos(eng) == [8, 6]
    client.check()


@pytest.mark.parametrize("what,match", [
    ("forced", "continues from its own last token"),
    ("sat_out", "left a step out"),
    ("negative", "is negative")])
def test_a_token_the_engine_cannot_honour_is_refused_before_dispatch(
        what, match):
    """The token of a slot that continues is on the device: a caller
    that hands back another is refused, not ignored. So is a slot that
    comes back after a call it was left out of (its row went on without
    it), and a token that would read as a steering mark. Each before
    anything is dispatched: the step in flight still answers."""
    eng = _tiny_engine(slots=2, max_len=32)
    client = _Client(eng)
    client.prefill(0, [5, 11, 23])
    client.prefill(1, [40, 2])
    client.step(0, 1)
    held = {s: client.last(s) for s in (0, 1)}
    if what == "forced":
        bad = {0: held[0], 1: (held[1] + 1) % 97}
    elif what == "sat_out":
        client.step(0)
        held[0] = client.last(0)
        bad = dict(held)
    else:
        client.prefill(1, [3])
        bad = {0: held[0], 1: -1}
    cache, flight, pos = eng._cache, eng._flight, list(eng._pos)
    with pytest.raises(ValueError, match=match):
        eng.step(bad)
    assert eng._cache is cache and eng._flight is flight
    assert eng._pos == pos
    assert client.step(0) == [0]
    client.check()


def test_a_fresh_slot_is_fed_the_token_its_caller_hands_it():
    """A slot just prefilled is in no step in flight: what it is fed is
    the host's token, whatever the prefill picked, and the answers
    follow from that token."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import forward

    eng = _tiny_engine(slots=1, max_len=16)
    picked = eng.prefill(0, [5, 11, 23])
    forced = (picked + 1) % 97
    prefix = [5, 11, 23, forced]
    for _ in range(3):
        prefix.append(eng.step({0: prefix[-1]})[0])
        want = forward(eng._params, jnp.asarray([prefix[:-1]], jnp.int32),
                       eng._cfg)
        assert prefix[-1] == int(jnp.argmax(want[0, -1]))


@pytest.mark.parametrize("call", ["prefill", "step"])
def test_a_raising_program_leaves_the_mirror_where_the_cache_is(call):
    import types

    eng = _tiny_engine(slots=2, max_len=16)
    last = {0: eng.prefill(0, [5, 11, 23])}
    last = eng.step(last)

    def boom(*a, **k):
        raise RuntimeError("device lost")

    cache, flight = eng._cache, eng._flight
    real = eng._decode
    eng._decode = types.SimpleNamespace(
        slot_prefill=boom, slot_decode_step=boom, CARRY=real.CARRY,
        IDLE=real.IDLE)
    with pytest.raises(RuntimeError):
        if call == "prefill":
            eng.prefill(1, [40, 2])
        else:
            eng.step(last)
    assert eng._cache is cache and eng._flight is flight
    assert eng._pos == _device_pos(eng) == [5, 0]
    eng._decode = real
    assert sorted(eng.step(last)) == [0]    # the step in flight answers


# ----------------------------- one cache, donated and written in place


def _consuming(eng, fail):
    """The engine's programs and its fetch, each of which runs for real
    (so the cache a program is given is consumed) and then raises where
    ``fail`` names it and ``fail`` is armed: the error of a device that
    gave out after the dispatch, found at once (``slot_prefill``,
    ``slot_decode_step``) or by the next transfer (``fetch``: in a step
    that of the step dispatched a call earlier, in a prefill the first
    read behind a step that failed in flight)."""
    import types

    real, jax = eng._decode, eng._jax

    def program(name, of):
        def call(*args, **kwargs):
            out = getattr(of, name)(*args, **kwargs)
            if fail.get(name):
                fail[name] -= 1
                raise RuntimeError(f"device lost in {name}")
            return out
        return call

    eng._jax = types.SimpleNamespace(
        device_get=program("device_get", jax))
    return types.SimpleNamespace(
        init_slot_cache=real.init_slot_cache, CARRY=real.CARRY,
        IDLE=real.IDLE, slot_prefill=program("slot_prefill", real),
        slot_decode_step=program("slot_decode_step", real))


# where the error surfaces: (the call that raises, the name armed)
LOST_AT = {"prefill": ("prefill", "slot_prefill"),
           "step": ("step", "slot_decode_step"),
           "late_fetch": ("step", "device_get"),
           "prefill_after_failed_step": ("prefill", "device_get")}


@pytest.mark.parametrize("call", ["prefill", "step"])
def test_a_call_consumes_the_engines_cache_and_leaves_one_to_go_on(call):
    """Either program takes the engine's cache donated: the arrays it
    was given are deleted after the call, ``_cache`` is the result and
    is whole (its positions read back, the next call runs on it)."""
    eng = _tiny_engine(slots=2, max_len=16)
    last = {0: eng.prefill(0, [5, 11, 23])}
    given = eng._cache
    if call == "prefill":
        eng.prefill(1, [40, 2])
        want = [3, 2]
    else:
        last = eng.step(last)
        want = [5, 0]
    assert eng._cache is not given
    import jax

    assert all(a.is_deleted() for a in jax.tree.leaves(given))
    assert not any(a.is_deleted() for a in jax.tree.leaves(eng._cache))
    assert eng._pos == _device_pos(eng) == want
    assert sorted(eng.step(last)) == [0]


@pytest.mark.parametrize("where", sorted(LOST_AT))
def test_a_program_that_raises_with_the_cache_gone_starts_over(where):
    """A program that raises after it has consumed the cache, or a
    fetch that finds the step in flight failed (in the step after it,
    or in the prefill queued behind it): the engine raises the typed
    error with the device's own as its cause, holds a fresh empty
    cache, a zeroed mirror and nothing in flight, and serves the next
    prompt from there."""
    eng = _tiny_engine(slots=2, max_len=16)
    first = eng.prefill(0, [5, 11, 23])
    last = eng.step({0: first})             # a step is in flight
    assert eng._flight is not None
    call, armed = LOST_AT[where]
    eng._decode = _consuming(eng, {armed: 1})
    with pytest.raises(SlotStateLostError,
                       match="all 2 slots") as raised:
        if call == "prefill":
            eng.prefill(1, [40, 2])
        else:
            eng.step(last)
    assert isinstance(raised.value.__cause__, RuntimeError)
    assert eng._flight is None
    assert eng._pos == _device_pos(eng) == [0, 0]
    import jax

    assert not any(a.is_deleted() for a in jax.tree.leaves(eng._cache))
    (k,), (v,) = eng._cache["k"], eng._cache["v"]   # one run of layers
    assert not k.any() and not v.any()
    assert eng.prefill(0, [5, 11, 23]) == first
    assert eng.step({0: first}) == last


def _served_alone(prompt, n):
    """What a fresh engine serves for one prompt, token by token."""
    eng = _tiny_engine(slots=2, max_len=32)
    out = [eng.prefill(0, prompt)]
    while len(out) < n:
        out.append(eng.step({0: out[-1]})[0])
    return out


@pytest.mark.parametrize("where", sorted(LOST_AT))
def test_lost_slot_state_fails_all_in_flight_and_the_next_is_right(where):
    """The scheduler over an engine whose device gives out after the
    cache was consumed: in a prefill that joins a running batch, in a
    step, at the fetch of the step before (the failure found one call
    late), or in the prefill queued behind a step that failed. Every
    request in flight fails with the typed error (none is answered from
    a zeroed row), every slot is free again, the loop lives, and the
    next request is served token for token what a fresh engine
    serves."""
    eng = _tiny_engine(slots=2, max_len=32)
    fail = {}
    eng._decode = _consuming(eng, fail)
    call, armed = LOST_AT[where]
    real_step, real_prefill = eng.step, eng.prefill

    async def run():
        decoding = asyncio.Event()
        loop = asyncio.get_running_loop()

        def step(tokens):       # on the executor's thread
            out = real_step(tokens)
            loop.call_soon_threadsafe(decoding.set)
            return out

        def prefill(slot, prompt):
            if decoding.is_set() and not fail.get("done"):
                fail[armed] = fail["done"] = 1
            return real_prefill(slot, prompt)

        eng.step = step
        if call == "prefill":       # armed as the late prefill begins
            eng.prefill = prefill
        sched = DecodeScheduler(eng)
        running = asyncio.ensure_future(
            sched.submit([5, 11, 23], max_tokens=20))
        await decoding.wait()       # the first request is decoding
        if call == "prefill":
            late = [sched.submit([40, 2], max_tokens=4)]
        else:
            fail[armed] = 1
            late = []
        results = await asyncio.gather(running, *late,
                                       return_exceptions=True)
        stats = sched.stats()
        after = await sched.submit([88, 17, 3], max_tokens=5)
        await sched.aclose()
        return results, stats, after

    results, stats, after = asyncio.run(run())
    assert all(isinstance(r, SlotStateLostError) for r in results), results
    assert (stats["active_slots"], stats["free_slots"]) == (0, 2)
    assert stats["completed"] == 0
    assert eng._flight is not None      # ... of the request served after
    assert after == _served_alone([88, 17, 3], 5) == _oracle(
        eng, [88, 17, 3], 5)


def test_a_prompt_refused_before_dispatch_still_fails_alone():
    """A prompt as long as the slot is refused by the engine before any
    program runs: the cache is not consumed, so its request alone fails
    (with the engine's own error) and the one decoding beside it is
    served in full and right."""
    eng = _tiny_engine(slots=2, max_len=16)

    async def run():
        sched = DecodeScheduler(eng)
        good = asyncio.ensure_future(
            sched.submit([5, 11, 23], max_tokens=8))
        await asyncio.sleep(0)
        bad = sched.submit(list(range(16)), max_tokens=2)
        results = await asyncio.gather(good, bad, return_exceptions=True)
        stats = sched.stats()
        await sched.aclose()
        return results, stats

    (good, bad), stats = asyncio.run(run())
    assert isinstance(bad, ValueError)
    assert not isinstance(bad, SlotStateLostError)
    assert "slot max_len" in str(bad)
    assert good == _served_alone([5, 11, 23], 8)
    assert (stats["completed"], stats["free_slots"]) == (1, 2)


class _HostReads:
    """Counts what reaches for a device array from the host while it is
    open: ``indexed`` (``x[i]``, a slice program each) and ``fetched``
    (a conversion to the host: ``int(x)``, ``x.tolist()``,
    ``jax.device_get(x)`` through the array's ``_value``; ``np.asarray``
    and ``np.array`` by name, since on the CPU backend numpy reads the
    buffer in place and calls nothing of jax). The transfer guard cannot
    serve: on the CPU backend of jax 0.9.0 every one of these passes
    under ``transfer_guard_device_to_host("disallow")``."""

    def __init__(self, monkeypatch):
        import numpy as np
        from jax._src.array import ArrayImpl

        self.indexed = self.fetched = 0
        getitem, value = ArrayImpl.__getitem__, ArrayImpl._value

        def counted_getitem(arr, idx):
            self.indexed += 1
            return getitem(arr, idx)

        def counted_value(arr):
            self.fetched += 1
            return value.fget(arr)

        def counted(fn):
            def call(a, *args, **kwargs):
                self.fetched += isinstance(a, ArrayImpl)
                return fn(a, *args, **kwargs)
            return call

        monkeypatch.setattr(ArrayImpl, "__getitem__", counted_getitem)
        monkeypatch.setattr(ArrayImpl, "_value", property(counted_value))
        monkeypatch.setattr(np, "asarray", counted(np.asarray))
        monkeypatch.setattr(np, "array", counted(np.array))


def test_the_seam_counts_every_way_to_read_a_device_array(monkeypatch):
    """The counter itself: each of the reads the engine used to make,
    and each it could make, is seen once."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    import numpy as np

    y = jnp.arange(8, dtype=jnp.int32) + 1
    reads = {"int(y[0])": lambda: int(y[0]),
             "np.asarray": lambda: np.asarray(y),
             "np.array": lambda: np.array(y),
             "device_get": lambda: jax.device_get(y),
             "tolist": lambda: y.tolist()}
    with monkeypatch.context() as m:
        seen = _HostReads(m)
        for name, read in reads.items():
            y = jnp.arange(8, dtype=jnp.int32) + 1  # nothing cached on it
            seen.indexed = seen.fetched = 0
            read()
            assert (seen.indexed, seen.fetched) == (
                (1, 1) if name == "int(y[0])" else (0, 1)), name


@pytest.mark.parametrize("active", [(1,), (0, 2), (0, 1, 2)])
def test_a_call_dispatches_then_fetches_one_row_and_indexes_nothing(
        monkeypatch, active):
    """However many slots go on, a call asks the device for one thing,
    the row of picks of the step dispatched a call earlier, whole, and
    only after it has dispatched its own step: nothing is read before
    the dispatch, and nothing of the new step at all. The tokens it
    returns are those the old row held."""
    eng = _tiny_engine(slots=3, max_len=32)
    import numpy as np

    last = {0: eng.prefill(0, [5, 11, 23]), 1: eng.prefill(1, [40, 2]),
            2: eng.prefill(2, [88])}
    last = eng.step(last)                   # compiles
    row = np.asarray(eng._flight.row).tolist()
    order = []
    real = eng._decode.slot_decode_step

    def dispatched(*args, **kwargs):
        order.append(("dispatch", seen.fetched))
        return real(*args, **kwargs)

    with monkeypatch.context() as m:
        seen = _HostReads(m)
        m.setattr(eng._decode, "slot_decode_step", dispatched)
        out = eng.step({s: last[s] for s in active})
        order.append(("returned", seen.fetched))
    assert (seen.indexed, seen.fetched) == (0, 1)
    assert order == [("dispatch", 0), ("returned", 1)]
    assert out == {s: row[s] for s in active}
    assert all(type(t) is int for t in out.values())
    assert eng._flight.owed == set(active)


def test_a_call_with_nothing_in_flight_dispatches_twice_and_fetches_once(
        monkeypatch):
    """The first call ever, and one after every slot has been prefilled
    anew: two dispatches, then the one fetch, of the first of them."""
    eng = _tiny_engine(slots=2, max_len=32)
    last = {0: eng.prefill(0, [5, 11, 23]), 1: eng.prefill(1, [40, 2])}
    eng.step(last)                          # compiles
    last = {0: eng.prefill(0, [7]), 1: eng.prefill(1, [30, 31])}
    order = []
    real = eng._decode.slot_decode_step

    def dispatched(*args, **kwargs):
        order.append(("dispatch", seen.fetched))
        return real(*args, **kwargs)

    with monkeypatch.context() as m:
        seen = _HostReads(m)
        m.setattr(eng._decode, "slot_decode_step", dispatched)
        out = eng.step(last)
    assert (seen.indexed, seen.fetched) == (0, 1)
    assert order == [("dispatch", 0), ("dispatch", 0)]
    assert out == {0: _oracle(eng, [7], 2)[1],
                   1: _oracle(eng, [30, 31], 2)[1]}
