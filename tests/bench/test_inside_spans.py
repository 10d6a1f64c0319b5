"""The program's own spans and counters (``ray_tpu.util.phases.phase``
in ``DecodeScheduler`` and ``JaxSlotEngine``, the flash kernels' names)
as the benchmark reads them: in a real profiler trace beside
``bench.window``, through each new reader on hand-built observations,
and in the traced line of the tiny closed-loop cell.
"""

import asyncio
import os

import pytest
from test_bench_run import (TracedOnCpuLM, check_line,  # noqa: F401
                            compile_cache, cpu_tpu_workers, tiny_bench)

from benchmarks import inside, loader, peaks, run, trace

SERVING = ("decode_device_wait_ms.closed", "decode_host_ms.closed",
           "decode_slot_reads_ms.closed", "scheduler_overhead_ms.closed",
           "prefill_stall_pct.closed")
ROOFLINES = ("flash_fwd_roofline_pct.train", "flash_bwd_roofline_pct.train")
DECODE_ROOFLINE = "decode_roofline_pct.closed"
LOOP_SPANS = ("serve.admit", "serve.prefill", "serve.step", "serve.emit")
STEP_SPANS = tuple("serve.engine." + n for n in (
    "check", "put", "dispatch", "wait", "read"))


@pytest.fixture(scope="module")
def bench():
    return loader.load_benchmark()


def reader(bench, name):
    return loader.load_reader(bench, name)


# ------------------------------------------------- in a profiler's trace

@pytest.fixture(scope="module")
def traced_planes(tmp_path_factory):
    """A few admissions and steps of the real scheduler and engine on
    CPU jax, inside ``bench.window``, under a profiler session opened as
    the benchmark's worker opens it."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig, init_params
    from ray_tpu.serve.decode_scheduler import DecodeScheduler, JaxSlotEngine

    cfg = TransformerConfig(vocab=97, d_model=64, n_heads=4, n_layers=2,
                            d_ff=128, max_seq=64, dtype=jnp.float32)
    engine = JaxSlotEngine(init_params(jax.random.key(0), cfg), cfg,
                           slots=2, max_len=32)

    async def drive(n):
        sched = DecodeScheduler(engine)
        await asyncio.gather(*[sched.submit(p, max_tokens=n) for p in (
            [5, 11, 23], [40, 2, 9], [88, 17, 3])])
        await sched.aclose()

    asyncio.run(drive(2))                   # compiles
    log_dir = str(tmp_path_factory.mktemp("inside_trace"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            asyncio.run(drive(4))
    finally:
        jax.profiler.stop_trace()
    return trace.read_xplane(log_dir)


def program_spans(planes):
    """(the loop thread's ``serve.*`` spans, the other threads', the
    window), each sorted by start."""
    loop, others, window = [], [], None
    for lines in planes.values():
        for events in lines.values():
            mine = [e for e in events if e[0].startswith("serve.")]
            marks = [e for e in events if e[0] == trace.WINDOW]
            if marks:           # asyncio.run: the loop is on this thread
                window, loop = marks[0], loop + mine
            else:
                others += mine
    by_start = lambda evs: sorted(evs, key=lambda e: e[1])  # noqa: E731
    return by_start(loop), by_start(others), window


def covers(outer, inner):
    return outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2]


def test_the_programs_spans_lie_nested_in_order_inside_the_window(
        traced_planes):
    loop, engine, window = program_spans(traced_planes)
    assert window is not None
    assert {e[0] for e in loop} == set(LOOP_SPANS)
    assert {e[0] for e in engine} == set(STEP_SPANS)
    assert all(covers(window, e) for e in loop + engine)
    # the loop thread: admissions hold their prefills, every step is
    # followed by its emit, and nothing else overlaps
    top = [e for e in loop if e[0] != "serve.prefill"]
    for a, b in zip(top, top[1:]):
        assert a[1] + a[2] <= b[1], (a, b)
    admits = [e for e in top if e[0] == "serve.admit"]
    prefills = [e for e in loop if e[0] == "serve.prefill"]
    assert len(prefills) == 3 and len(admits) == 2
    assert all(any(covers(a, p) for a in admits) for p in prefills)
    kinds = [e[0] for e in top if e[0] != "serve.admit"]
    assert kinds == ["serve.step", "serve.emit"] * (len(kinds) // 2)
    # the executor's side: each step's phases in order, back to back,
    # inside the loop-side span that waited for them
    steps = [e for e in loop if e[0] == "serve.step"]
    assert [e[0] for e in engine] == list(STEP_SPANS) * len(steps)
    for a, b in zip(engine, engine[1:]):
        assert a[1] + a[2] <= b[1], (a, b)
    for i, step in enumerate(steps):
        call = engine[i * len(STEP_SPANS):(i + 1) * len(STEP_SPANS)]
        assert all(covers(step, e) for e in call), (step, call)


def test_no_program_span_takes_a_name_of_the_benchmarks(traced_planes):
    names = {e[0] for lines in traced_planes.values()
             for events in lines.values() for e in events}
    assert not names & set(trace.HOST_SPANS)
    assert trace.WINDOW in names


# ------------------------------------------------ the readers, by hand

def phases(scale):
    """A program's table after ``scale`` times: 10 steps of 2 + 3 + 5 +
    20 + 10 ms, 2 admissions of 40 ms of which 30 stalled."""
    ms = {"serve.engine.check": 2, "serve.engine.put": 3,
          "serve.engine.dispatch": 5, "serve.engine.wait": 20,
          "serve.engine.read": 10, "serve.step": 41, "serve.hop": 0.6,
          "serve.emit": 0.4}
    table = {name: [10 * scale, 10 * scale * v / 1e3]
             for name, v in ms.items()}
    table["serve.admit"] = [2 * scale, 2 * scale * 0.040]
    table["serve.admit_stall"] = [2 * scale, 2 * scale * 0.030]
    return table


def serving_obs(before=1, after=3, steps=(10, 30)):
    return {"decode_before": {"steps": steps[0], "phases": phases(before)},
            "decode_after": {"steps": steps[1], "phases": phases(after)}}


@pytest.mark.parametrize("name,want", [
    ("decode_device_wait_ms.closed", 20.0),
    ("decode_host_ms.closed", 2.0 + 3.0 + 5.0 + 10.0),
    ("decode_slot_reads_ms.closed", 2.0 + 10.0),
    ("scheduler_overhead_ms.closed", 0.6 + 0.4),
    # 2 x 2 x 30 ms stalled of 2 x (2 x 40 + 10 x 41 + 10 x 0.4) ms
    ("prefill_stall_pct.closed", 100.0 * 120.0 / 988.0)])
def test_a_serving_reader_takes_the_windows_delta(bench, name, want):
    read = reader(bench, name)
    assert read(serving_obs()) == pytest.approx(want)
    # a program that records no phases (the parent commit), or not this
    # one: nothing to read
    absent = serving_obs()
    del absent["decode_before"]["phases"], absent["decode_after"]["phases"]
    assert read(absent) is None
    renamed = serving_obs()
    for name_ in list(renamed["decode_after"]["phases"]):
        if name_ in ("serve.engine.wait", "serve.engine.read", "serve.hop",
                     "serve.admit_stall"):
            del renamed["decode_after"]["phases"][name_]
    assert read(renamed) is None
    # no step in the window
    assert read(serving_obs(steps=(10, 10))) is None
    # a table that began inside the window counts from nothing
    fresh = serving_obs()
    fresh["decode_before"]["phases"] = {}
    assert read(fresh) is not None


def training_obs(op_totals):
    config = {"model_type": "ouro", "num_attention_heads": 2, "head_dim": 4,
              "torch_dtype": "bfloat16"}
    return {"run": {"config": config,
                    "family": loader.find_family(loader.load_benchmark(),
                                                 config),
                    "traffic": {"batch": 3, "seq": 8}},
            "device": {"kind": "TPU v5 lite"},
            "trace": {"window_s": 1.0, "op_totals": op_totals}}


def test_the_roofline_readers_against_a_hand_worked_shape(bench,
                                                         monkeypatch):
    chip = peaks.PEAKS["TPU v5 lite"]
    # 3 x 2 heads x 8 x 9 / 2 = 216 unmasked pairs; forward 4 x 4 FLOPs a
    # pair = 3456, q k v o at 3 x 8 x 2 x 4 x 2 bytes each + the logsumexp
    # row: 1536 + 192 = 1728 bytes. At this size the bytes bound both.
    fwd = max(3456 / chip["bf16_flops_per_s"], 1728 / chip["hbm_bytes_per_s"])
    bwd = max(2 * 4 * 216 * 5 / chip["bf16_flops_per_s"],
              (8 * 384 + 8 * 48) / chip["hbm_bytes_per_s"])
    assert fwd == 1728 / 819e9 and bwd == 3456 / 819e9
    totals = {"local_step/flash_fwd.15": [2e-6, 4],
              "local_step/flash_fwd.16": [1e-6, 2],
              "local_step/flash_bwd_dkv.11": [3e-6, 3],
              "local_step/flash_bwd_dq.11": [2e-6, 3],
              "local_step/flash_fwd_helper.1": [9.0, 9],
              "local_step/jvp_flash_fwd_.1": [9.0, 9],
              "local_step/fusion.3": [9.0, 9],
              "slot_prefill/flash_fwd.1": [9.0, 9]}
    obs = training_obs(totals)
    assert reader(bench, ROOFLINES[0])(obs) == pytest.approx(
        100.0 * 6 * fwd / 3e-6)
    assert reader(bench, ROOFLINES[1])(obs) == pytest.approx(
        100.0 * 3 * bwd / 5e-6)
    # the kernels under the compiler's own numbering (before PR 25), a
    # CPU trace with no Mosaic call, an untraced run: nothing to read
    unnamed = training_obs({"local_step/closed_call.7": [0.3, 48],
                            "local_step/checkpoint.22": [0.3, 48]})
    untraced = dict(training_obs({}), trace=None)
    for name in ROOFLINES:
        assert reader(bench, name)(unnamed) is None
        assert reader(bench, name)(untraced) is None
    with pytest.raises(ValueError, match="no peaks on record"):
        reader(bench, ROOFLINES[0])(dict(obs, device={"kind": "cpu"}))
    # a family whose K and V have a head of their own gives it sixth:
    # k, v (and dk, dv) cost half the bytes, q, o (do, dq) what they did
    costs = loader.family_module(obs["run"]["family"], "costs")
    monkeypatch.setattr(costs, "flash_shape",
                        lambda config, mix: (3, 8, 2, 4, 2, 1))
    assert reader(bench, ROOFLINES[0])(obs) == pytest.approx(
        100.0 * 6 * ((1536 * 3 / 4 + 192) / 819e9) / 3e-6)
    assert reader(bench, ROOFLINES[1])(obs) == pytest.approx(
        100.0 * 3 * ((3072 * 3 / 4 + 384) / 819e9) / 5e-6)


def decode_obs(program_seconds, config=None):
    """Three decode steps, two of them begun in the slice [10, 11): 2
    rows that attend 7 positions between them, then 1 row at 5."""
    config = config or {
        "model_type": "ouro", "hidden_size": 8, "intermediate_size": 16,
        "head_dim": 4, "num_attention_heads": 2, "num_hidden_layers": 3,
        "vocab_size": 10, "torch_dtype": "bfloat16"}
    obs = serving_obs()
    obs.update(
        run={"config": config, "traffic": {"slots": 2},
             "family": loader.find_family(loader.load_benchmark(), config)},
        device={"kind": "TPU v5 lite"},
        steps=[[9.5, 9.9, 2, 5], [10.1, 10.4, 2, 7], [10.6, 10.9, 1, 5]],
        prefills=[[10.45, 10.55, 4]],
        trace={"window_s": 1.0, "busy_s": 0.9, "slice": [10.0, 11.0],
               "program_seconds": program_seconds})
    return obs


def test_the_decode_roofline_against_a_hand_worked_step(bench, monkeypatch):
    chip = peaks.PEAKS["TPU v5 lite"]
    # 2056 parameters at 2 bytes; a position's K and V in 3 layers x 2
    # heads x 4 are 96 bytes: (7 + 2) and (5 + 1) positions read or
    # written. The bytes bound both steps at this size.
    step_bytes = (2056 * 2 + 9 * 96, 2056 * 2 + 6 * 96)
    assert step_bytes == (4976, 4688)
    read = reader(bench, DECODE_ROOFLINE)
    obs = decode_obs({"slot_decode_step": 4e-6, "slot_prefill": 9.0,
                      "argmax": 9.0})
    assert read(obs) == pytest.approx(
        100.0 * sum(step_bytes) / chip["hbm_bytes_per_s"] / 4e-6)
    # where a step's FLOPs take the chip longer than its bytes, they
    # price it: a chip with 1e-3 of the FLOP/s
    costs = loader.family_module(obs["run"]["family"], "costs")
    flops = (costs.forward_flops(obs["run"]["config"], 2, 7, logit_rows=2)
             + costs.forward_flops(obs["run"]["config"], 1, 5, logit_rows=1))
    slow = dict(chip, bf16_flops_per_s=chip["bf16_flops_per_s"] * 1e-3)
    assert flops / slow["bf16_flops_per_s"] > sum(step_bytes) / 819e9
    monkeypatch.setitem(peaks.PEAKS, "slow chip", slow)
    assert read(dict(obs, device={"kind": "slow chip"})) == pytest.approx(
        100.0 * flops / slow["bf16_flops_per_s"] / 4e-6)
    # nothing to read: an untraced run, a trace in which the program
    # ran no operation (the CPU's stand-in plane), no step in the slice
    assert read(dict(obs, trace=None)) is None
    assert read(decode_obs({"slot_prefill": 9.0})) is None
    assert read(decode_obs({})) is None
    assert read(dict(obs, steps=[[9.5, 9.9, 2, 5]])) is None
    # a family that prices no decode step in bytes reads nothing
    for name in ("DECODE_PROGRAM", "decode_step_bytes"):
        with monkeypatch.context() as patch:
            patch.delattr(costs, name)
            assert read(obs) is None
    assert read(obs) is not None


def test_an_engines_own_counters_reach_its_familys_costs():
    """What an engine records under ``serve.engine.`` beside the timed
    phases is a counter: its mean a step of the window."""
    obs = serving_obs()
    assert inside.engine_counts(obs) == {}
    obs["decode_before"]["phases"]["serve.engine.experts_touched"] = [10, 90]
    obs["decode_after"]["phases"]["serve.engine.experts_touched"] = [30, 330]
    assert inside.engine_counts(obs) == {
        "serve.engine.experts_touched": pytest.approx(12.0)}
    # a counter that began inside the window counts from nothing
    del obs["decode_before"]["phases"]["serve.engine.experts_touched"]
    assert inside.engine_counts(obs) == {
        "serve.engine.experts_touched": pytest.approx(16.5)}
    assert inside.engine_counts(serving_obs(steps=(10, 10))) == {}


def test_a_tpu_line_must_carry_the_rooflines_and_a_cpu_line_need_not(bench):
    """``check_line`` lets a kernel's metric be absent where the device
    is no TPU (its trace holds no Mosaic call) and nowhere else."""
    cell = "ouro-2.6b-d12.train-2k"
    kernels = {"local_step/flash_fwd.15": [2e-6, 4],
               "local_step/flash_bwd_dkv.11": [3e-6, 3],
               "local_step/flash_bwd_dq.11": [2e-6, 3]}

    def line_of(platform, op_totals):
        obs = training_obs(op_totals)
        obs["run"]["config"].update(hidden_size=8, intermediate_size=16,
                                    num_hidden_layers=3, vocab_size=10)
        obs["trace"].update(busy_s=0.9, slice=[10.0, 11.0])
        obs["steps"] = [[10.1, 10.4], [10.4, 10.7]]
        return {"correct": True, "attempted": 2, "failed": 0,
                "metrics": loader.read_metrics(bench, cell, True, obs),
                "device": {"platform": platform, "kind": "any", "count": 1,
                           "memory_peak_bytes": 1, "busy_s": 0.9,
                           "window_s": 1.0},
                "breakdown": {"device_ops": [], "idle_gaps": []},
                "compared": {}}

    on_tpu = line_of("tpu", kernels)
    assert set(ROOFLINES) <= set(on_tpu["metrics"])
    check_line(bench, cell, on_tpu, True)
    without = line_of("tpu", {"local_step/fusion.3": [9.0, 9]})
    assert not set(ROOFLINES) & set(without["metrics"])
    with pytest.raises(AssertionError):
        check_line(bench, cell, without, True)
    check_line(bench, cell, dict(without, device=dict(
        without["device"], platform="cpu")), True)
    # only a roofline may be absent, also off the TPU
    del without["metrics"]["train_step_ms"]
    with pytest.raises(AssertionError):
        check_line(bench, cell, dict(without, device=dict(
            without["device"], platform="cpu")), True)


def test_a_tpu_line_must_carry_the_decode_roofline(bench):
    """The same for the serving cell's whole-step share: a TPU's trace
    names the decode program's operations, the CPU's stand-in does not."""
    cell = "ouro-2.6b.decode-closed"

    def line_of(platform, program_seconds):
        obs = decode_obs(program_seconds)
        for when, slot_steps in (("decode_before", 15), ("decode_after", 50)):
            obs[when]["slot_steps"] = slot_steps
        return {"correct": True, "attempted": 2, "failed": 0,
                "metrics": loader.read_metrics(bench, cell, True, obs),
                "device": {"platform": platform, "kind": "any", "count": 1,
                           "memory_peak_bytes": 1, "busy_s": 0.9,
                           "window_s": 1.0},
                "breakdown": {"device_ops": [], "idle_gaps": []},
                "compared": {}}

    on_tpu = line_of("tpu", {"slot_decode_step": 0.5})
    assert {DECODE_ROOFLINE, "serve_mfu_pct.closed"} <= set(on_tpu["metrics"])
    check_line(bench, cell, on_tpu, True)
    without = line_of("tpu", {})
    assert DECODE_ROOFLINE not in without["metrics"]
    with pytest.raises(AssertionError):
        check_line(bench, cell, without, True)
    on_cpu = dict(without, device=dict(without["device"], platform="cpu"))
    check_line(bench, cell, on_cpu, True)
    del on_cpu["metrics"]["device_idle_pct.closed"]   # device_trace too
    with pytest.raises(AssertionError):
        check_line(bench, cell, on_cpu, True)


@pytest.mark.parametrize("instruction,kernel", [
    ("flash_fwd.15", "flash_fwd"), ("flash_fwd", "flash_fwd"),
    ("flash_bwd_dq.11", "flash_bwd_dq"),
    ("flash_bwd_dkv.11", "flash_bwd_dkv"),
    ("jvp_flash_fwd_.1", None), ("flash_fwd.1.2", None),
    ("flash_fwd_helper.1", None), ("my_flash_fwd2.1", None),
    ("closed_call.7", None), ("flash_bwd.1", None)])
def test_a_kernel_is_found_by_its_own_name(instruction, kernel):
    assert inside.kernel_of(instruction, (
        "flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")) == kernel


def test_the_rooflines_are_entered_beside_their_readers(bench):
    """PR 25 put them off (a CPU trace holds no Mosaic call, and the
    traced CPU run asked for every metric); PR 27 entered them."""
    assert not os.path.exists(os.path.join(
        bench["root"], "benchmarks", "put_off", "kernel-rooflines.json"))
    entered = {m["name"]: m for m in bench["per_layer"]}
    assert set(SERVING) | set(ROOFLINES) | {DECODE_ROOFLINE} <= set(entered)
    # one clock on the step (PR 28): the wrapper's median went, the
    # engine's own wait and host phases say the same from inside
    assert "decode_step_ms.closed" not in entered
    whole = entered[DECODE_ROOFLINE]
    assert "ouro-2.6b.decode-closed" in whole["workloads"]
    assert (whole["moves"], whole["unit"], whole["source"]) == (
        "serve_tokens_per_s", "%", "device_trace")
    assert whole["layer"] == entered["serve_mfu_pct.closed"]["layer"]
    for name in ROOFLINES:
        m = entered[name]
        assert "ouro-2.6b-d12.train-2k" in m["workloads"]
        assert m["layer"] == "kernels: ops/attention.py"
        assert (m["moves"], m["unit"], m["source"]) == (
            "train_tokens_per_s", "%", "device_trace")
        assert callable(reader(bench, name))
    train = {m["name"] for m in loader.cell_metrics(
        bench, "ouro-2.6b-d12.train-2k", True)}
    serve = {m["name"] for m in loader.cell_metrics(
        bench, "ouro-2.6b.decode-closed", True)}
    assert set(ROOFLINES) <= train and DECODE_ROOFLINE not in train
    assert DECODE_ROOFLINE in serve and not set(ROOFLINES) & serve


# -------------------------------------------- in the tiny cell's line

def test_the_traced_closed_cell_prints_the_inside_metrics(
        tiny_bench, cpu_tpu_workers):
    line = run.run_cell(tiny_bench, "tiny.closed", seed=2**31 + 17,
                        seconds=3.0, trace=True, platform="cpu",
                        lm_class=TracedOnCpuLM)
    assert line["correct"], line["faults"]
    check_line(tiny_bench, "tiny.closed", line, True)
    got = {n: line["metrics"][n]["value"] for n in SERVING}
    assert 0.0 < got["prefill_stall_pct.closed"] < 100.0
    assert got["decode_slot_reads_ms.closed"] < got["decode_host_ms.closed"]
    # the inside pair is the step's one clock: the steps begun in the
    # traced slice fit into it at that length (a mean over the window
    # against a count in the slice, on a shared CPU: loosely)
    inside_ms = (got["decode_host_ms.closed"]
                 + got["decode_device_wait_ms.closed"])
    assert line["slice"]["steps"] > 0 and line["slice"]["prefills"] >= 0
    assert 0.0 < inside_ms * line["slice"]["steps"] < \
        2.0 * 1e3 * line["device"]["window_s"]
    # the whole-step roofline's time is the decode program's operations
    # by name, which the CPU's stand-in plane does not hold
    assert DECODE_ROOFLINE not in line["metrics"]
