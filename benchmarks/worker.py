"""What the benchmark runs inside the worker that holds the chip: the
serving deployment, the training function, and the tracing, counting
and checking around the program's own calls. The driver (``run.py``)
never imports jax; every device fact comes from here.

The program is driven through its own entry points and classes
(``serve.DecodeScheduler`` here; the slot engine and the train step
through the family's ``program.py``); the benchmark wraps their calls
with host clocks and ``jax.profiler.TraceAnnotation``s and changes
nothing inside. Which model runs is data: ``run["family"]`` names the
directory whose ``reference.py`` and ``program.py`` this module drives,
and nothing here knows a block.
"""

from __future__ import annotations

import asyncio
import functools
import gc
import os
import shutil
import tempfile
import time

from benchmarks import loader

MOSAIC_CALL = "tpu_custom_call"
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_compile_stamps = []        # perf_counter at each compile or cache load
_cache_misses = []          # ... at each program the cache did not hold
STAMPS = []                 # [name, time.time()] at the end of each part of
                            # set-up inside the process that holds the chip


def stamp(name: str) -> None:
    STAMPS.append([name, time.time()])


def process_started() -> float:
    """When this process began, on ``time.time()``'s clock (from /proc:
    the start in clock ticks since boot)."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        up = float(f.read().split()[0])
    return time.time() - up + ticks / os.sysconf("SC_CLK_TCK")


def setup_jax() -> None:
    """Once per worker: start the TPU client, stamping the parts of
    set-up so far, and count compiles. Each time: let the persistent
    cache keep programs that compile in under jax's one-second
    threshold."""
    first = not STAMPS
    if first:
        STAMPS.append(["worker.process", process_started()])
        stamp("worker.entered")
    import jax

    if first:
        stamp("worker.jax_imported")
        jax.local_devices()
        stamp("worker.tpu_client")

        def on_event(name, _secs, **_kw):
            if name in COMPILE_EVENTS:
                _compile_stamps.append(time.perf_counter())

        def on_miss(name, **_kw):
            if name == CACHE_MISS_EVENT:
                _cache_misses.append(time.perf_counter())

        jax.monitoring.register_event_duration_secs_listener(on_event)
        jax.monitoring.register_event_listener(on_miss)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def device_report() -> dict:
    import jax

    devs = jax.local_devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip (0 where the backend keeps
    no such count, as the CPU's does not)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


class Tracer:
    """One short profiler trace inside the process that holds the chip.
    ``start``/``stop`` run on one thread (the annotation that marks the
    slice belongs to it); ``reduce`` parses the file afterwards."""

    def __init__(self):
        self.dir = None
        self.slice = None

    def start(self) -> None:
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self._window = jax.profiler.TraceAnnotation("bench.window")
        self._window.__enter__()
        self._t0 = time.perf_counter()

    def stop(self) -> None:
        import jax

        self.slice = [self._t0, time.perf_counter()]
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self, describe: bool = False):
        from benchmarks import trace

        if self.dir is None:
            return None
        try:
            planes = trace.read_xplane(self.dir)
            out = trace.reduce_trace(planes)
            out["slice"] = self.slice
            if describe:
                out["describe"] = trace.describe(planes)
            return out
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir = None


# ------------------------------------------------------------------ serve

class TimedEngine:
    """The program's slot engine with a host clock and a trace
    annotation around each call. Spans: ``steps`` [t0, t1, active rows,
    positions attended], ``prefills`` [t0, t1, prompt length]."""

    def __init__(self, inner):
        self.inner = inner
        self.slots, self.max_len = inner.slots, inner.max_len
        self.steps, self.prefills = [], []
        self.prefill_at = {}            # id(prompt) -> entry time
        self._pos = {}

    def prefill(self, slot: int, prompt) -> int:
        import jax

        t0 = time.perf_counter()
        self.prefill_at[id(prompt)] = t0
        with jax.profiler.TraceAnnotation("engine.prefill"):
            first = self.inner.prefill(slot, prompt)
        self.prefills.append([t0, time.perf_counter(), len(prompt)])
        self._pos[slot] = len(prompt)
        return first

    def step(self, tokens):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("engine.step"):
            out = self.inner.step(tokens)
        attended = 0
        for slot in tokens:
            self._pos[slot] += 1
            attended += self._pos[slot]
        self.steps.append([t0, time.perf_counter(), len(tokens), attended])
        return out


class BenchLM:
    """The benchmark's deployment. ``POST <route>`` with
    {"prompt": [...], "max_tokens": n} answers {"tokens": [...]} and the
    replica's own stamps; side routes: ``GET /stats``, ``GET /programs``,
    ``POST /trace``, ``POST /check``."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 family: dict):
        setup_jax()
        from ray_tpu import serve

        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.ref = loader.family_module(family, "reference")
        self.program = loader.family_module(family, "program")
        self.sz = self.ref.sizes_of(config)
        self.cfg = self.program.program_config(config, traffic["slot_len"])
        stamp("worker.modules")
        self.params = self.ref.seeded_params(self.seed, self.sz)
        stamp("worker.weights")
        self.engine = self.make_engine()
        self.decode_scheduler = serve.DecodeScheduler(self.engine)
        stamp("worker.engine")
        self.tracer = Tracer()
        self.called_at = {}

    def make_engine(self):
        return TimedEngine(self.program.make_engine(
            self.params, self.cfg, slots=int(self.traffic["slots"]),
            max_len=int(self.traffic["slot_len"])))

    async def __call__(self, request):
        route = request.path.rsplit("/", 1)[-1]
        loop = asyncio.get_running_loop()
        if request.method == "GET":
            if route == "programs":
                return await loop.run_in_executor(None, self.programs)
            return await loop.run_in_executor(
                None, functools.partial(self.stats, **request.query))
        body = request.json()
        if route == "trace":
            return await loop.run_in_executor(
                None, self.trace_for, float(body["seconds"]))
        if route == "check":
            return await loop.run_in_executor(
                None, self.check, body["requests"], body.get("control"))
        t_call = time.perf_counter()
        prompt = body["prompt"]
        tokens = await self.decode_scheduler.submit(
            prompt, max_tokens=int(body["max_tokens"]))
        return {"tokens": tokens, "t_call": t_call,
                "t_prefill": self.engine.prefill_at.pop(id(prompt), None),
                "t_done": time.perf_counter()}

    # ---- side routes (each runs on an executor thread)

    def trace_for(self, seconds: float) -> dict:
        self.tracer.start()
        time.sleep(seconds)
        self.tracer.stop()
        return {"slice": self.tracer.slice}

    def stats(self, t0="-inf", t1="inf", describe="0") -> dict:
        lo, hi = float(t0), float(t1)
        inside = lambda spans: [s for s in spans           # noqa: E731
                                if lo <= s[0] and s[1] <= hi]
        return {"device": device_report(),
                "memory_peak_bytes": memory_peak_bytes(),
                "decode": self.decode_scheduler.stats(),
                "steps": inside(self.engine.steps),
                "prefills": inside(self.engine.prefills),
                "compiles": [t for t in _compile_stamps if lo <= t <= hi],
                "trace": self.tracer.reduce(describe == "1"),
                "setup_stamps": STAMPS,
                "cache_misses": sum(t <= hi for t in _cache_misses)}

    def programs(self) -> dict:
        """Mosaic custom calls in each prefill program the mix uses."""
        texts = self.program.prefill_programs(
            self.params, self.cfg, self.engine.slots, self.engine.max_len,
            self.traffic["prompt_lengths"])
        return {"prefill_mosaic_calls": {
            str(length): text.count(MOSAIC_CALL)
            for length, text in texts.items()}}

    def check(self, requests: list, control=None) -> dict:
        """Free the program's state, then run the plain reference once
        over each sampled request's prompt and served tokens."""
        from benchmarks import reference

        t0 = time.perf_counter()
        params = self.params
        self.engine = self.decode_scheduler = self.params = None
        gc.collect()
        served, controlled = [], {}
        for r in requests:
            gaps = reference.served_logit_gaps(
                self.ref, params, r["prompt"], r["tokens"], self.sz)
            served.append(max(gaps["served"]))
        # calibration only: the precisions below, at the same positions
        for quant in (control.split(",") if control else ()):
            controlled[quant] = [max(reference.served_logit_gaps(
                self.ref, params, r["prompt"], r["tokens"], self.sz,
                quant=quant)["control"]) for r in requests]
        return {"served_gaps": served, "control_gaps": controlled,
                "tokens": sum(len(r["tokens"]) for r in requests),
                "seconds": time.perf_counter() - t0}


# ------------------------------------------------------------------ train

class TrainCell:
    """The compiled train step with its state: one object, built once,
    driven from the seed through the checked steps and then through the
    window."""

    def __init__(self, run: dict):
        setup_jax()
        import jax

        from benchmarks import traffic as traffic_mod

        self.run = run
        mix, config = run["traffic"], run["config"]
        self.seed = int(run["seed"])
        self.ref = loader.family_module(run["family"], "reference")
        program = loader.family_module(run["family"], "program")
        self.sz = self.ref.sizes_of(config)
        step, optimizer = program.make_train_step(
            program.program_config(config, mix["seq"]), mix)
        stamp("worker.modules")
        self.params = self.ref.seeded_params(self.seed, self.sz)
        self.opt_state = jax.jit(optimizer.init)(self.params)
        stamp("worker.state")
        self.batch_of = traffic_mod.batch_maker(
            self.seed, int(mix["batch"]), int(mix["seq"]),
            int(config["vocab_size"]))
        lowered = step.lower(self.params, self.opt_state, self.batch_of(0))
        stamp("worker.lowered")
        self.compiled = lowered.compile()
        stamp("worker.compiled")
        self.mosaic_calls = self.compiled.as_text().count(MOSAIC_CALL)
        self.spans = []                 # [t0, t1] of each step
        self.steps_done = 0

    def call_step(self, batch):
        """The window's own call: state in, state out."""
        self.params, self.opt_state, loss = self.compiled(
            self.params, self.opt_state, batch)
        return loss

    def one_step(self, phase: str) -> float:
        import jax

        from ray_tpu import train

        i = self.steps_done
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("train.step"):
            loss = float(self.call_step(self.batch_of(i)))
        self.spans.append([t0, time.perf_counter()])
        self.steps_done += 1
        train.report(step=i, loss=loss, phase=phase)
        return loss

    def first_moment(self):
        """Adam's first moment: after step 1 it is (1 - b1) times the
        gradient that the optimizer got."""
        import jax

        holders = [s for s in jax.tree.leaves(
            self.opt_state, is_leaf=lambda s: hasattr(s, "mu"))
            if hasattr(s, "mu")]
        return holders[0].mu

    def checked_steps(self, n: int) -> dict:
        """The first ``n`` steps through the window's own call. Keeps the
        per-leaf norms of the first gradient and, on the host, the
        gradient itself, for the reference to be set against once the
        state is freed."""
        import jax
        import numpy as np

        from benchmarks import reference

        losses, grad_norms = [], None
        for i in range(n):
            losses.append(self.one_step("check"))
            if i == 0:
                mu = self.first_moment()
                grad_norms = {
                    k: float(v) / (1.0 - reference.ADAMW["b1"])
                    for k, v in reference.leaf_norms(
                        mu, self.ref.by_leaf).items()}
                # leaf by leaf: transfers in flight that together pass
                # libtpu's premapped buffer (run.PREMAPPED_BUFFER_BYTES)
                # are pinned on demand, at three times the time
                self.first_mu = jax.tree.map(np.asarray, mu)
                stamp("worker.gradient_fetched")
                del mu      # or it stays on the device through the window
        change = reference.leaf_diff_norms(
            self.params, self.ref.seeded_params(self.seed, self.sz),
            1.0, self.ref.by_leaf)
        return {"losses": losses, "grad_norms": grad_norms,
                "change_norms": {k: float(v) for k, v in change.items()}}

    def window(self, seconds: float, trace: bool, trace_steps: int) -> Tracer:
        from ray_tpu import train

        tracer = Tracer()
        train.report(step=self.steps_done, loss=0.0, phase="window")
        t_end = time.perf_counter() + seconds + 0.5
        first = self.steps_done
        while time.perf_counter() < t_end:
            at = self.steps_done - first
            if trace and at == 2:
                tracer.start()
            self.one_step("run")
            if trace and at == 1 + trace_steps:
                tracer.stop()
        if trace and tracer.slice is None:
            tracer.stop()
        return tracer

    def release(self) -> None:
        self.params = self.opt_state = self.compiled = None
        gc.collect()


def train_func(run: dict, cell_class=TrainCell) -> dict:
    """Inside the Trainer's worker: build, check, run the window, then
    free the state and follow the checked steps with the reference."""
    from benchmarks import reference

    mix = run["traffic"]
    cell = cell_class(run)
    n_check = int(mix["checked_steps"])
    got = cell.checked_steps(n_check)
    t_window = time.perf_counter()
    tracer = cell.window(float(run["seconds"]), bool(run["trace"]),
                         int(mix["trace_steps"]))
    t_close = time.perf_counter()
    out = {"device": device_report(),
           "memory_peak_bytes": memory_peak_bytes(),
           "mosaic_calls": cell.mosaic_calls,
           "steps": [s for s in cell.spans if s[0] >= t_window],
           "compiles": [t for t in _compile_stamps
                        if t_window <= t <= t_close],
           "trace": tracer.reduce(bool(run.get("describe"))),
           "setup_stamps": STAMPS,
           "cache_misses": sum(t <= t_window for t in _cache_misses)}
    batch_of = cell.batch_of
    cell.release()
    t0 = time.perf_counter()
    control = run.get("control")
    follow = functools.partial(reference.train_reference, cell.ref,
                               cell.seed, cell.sz, batch_of, n_check)
    want = follow(other_first_gradient=cell.first_mu,
                  other_scale=1.0 / (1.0 - reference.ADAMW["b1"]),
                  keep_first_gradient=bool(control))
    out["check"] = reference.compare_training(got, want,
                                              want["grad_diff_norms"])
    out["calibration"] = {"losses": got["losses"],
                          "reference_losses": want["losses"],
                          "seconds": time.perf_counter() - t0}
    if control:
        # calibration only: the reference in the program's place, in the
        # precision below, and with half of the batch left out
        first = want.pop("first_gradient")
        half = list(range(int(mix["batch"]) // 2))
        faults = [("half_batch", {"rows": half})] + [
            ("control." + q, {"quant": q}) for q in control.split(",")]
        for name, fault in faults:
            read = follow(other_first_gradient=first, **fault)
            out["calibration"][name] = reference.compare_training(
                read, want, read["grad_diff_norms"])
    return out
