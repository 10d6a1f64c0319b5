"""A run of the benchmark on the CPU at a tiny size, through the same
front door (serve proxy -> replica -> DecodeScheduler -> JaxSlotEngine;
train.Trainer), in both trace modes: the result line's shape, and
``correct`` coming out false when the timed path is broken underneath.

The harness's look for a chip is what these tests skip: they call
``run.run_cell`` with the platform they expect, and the TPU lease's
worker is pinned to CPU jax as in ``tests/test_chip_smoke_cpu.py``. The
last test leaves the worker as it is, on a machine without a TPU.
"""

import json
import os
import re
import shutil
import sys

import cloudpickle
import pytest

from benchmarks import loader, peaks, run, worker

# workers unpickle this file's classes by value: they cannot import it
cloudpickle.register_pickle_by_value(sys.modules[__name__])

TINY = {"model_type": "ouro", "head_dim": 16, "hidden_act": "silu",
        "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_hidden_layers": 2,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-6,
        "rope_scaling": None, "rope_theta": 10000,
        "tie_word_embeddings": True, "total_ut_steps": 1,
        "use_sliding_window": False, "vocab_size": 512,
        "torch_dtype": "float32"}
# float32 on the CPU: the program and the reference differ by rounding
# order alone (the program reads 3e-7 or less on each training number;
# the int8 control 2e-3 on the gradient and on the change)
SERVE_LIMITS = {"served_logit_gap": 1e-3, "answers_wrong": 0}
TRAIN_LIMITS = {"loss_gap": 1e-5, "grad_norm_gap": 1e-4,
                "change_norm_gap": 1e-4, "grad_diff_gap": 1e-3,
                "steps_failed": 0}
MIXES = {
    "tiny-closed": {"kind": "serve", "loop": "closed", "clients": 4,
                    "slots": 2, "slot_len": 64, "prompt_lengths": [8, 16],
                    "output_tokens": {"min": 4, "max": 8}, "cycle": 8,
                    "check_requests": 3, "trace_seconds": 0.5,
                    "limits": SERVE_LIMITS},
    "tiny-open": {"kind": "serve", "loop": "open", "rate_per_s": 8.0,
                  "slots": 2, "slot_len": 64, "prompt_lengths": [8, 16],
                  "output_tokens": {"min": 2, "max": 4}, "cycle": 8,
                  "check_requests": 3, "trace_seconds": 0.5,
                  "limits": SERVE_LIMITS},
    "tiny-train": {"kind": "train", "batch": 4, "seq": 32, "remat": True,
                   "checked_steps": 3, "trace_steps": 2,
                   "limits": TRAIN_LIMITS},
}


def build_tiny_bench(root: str, real: dict) -> dict:
    """A benchmark root of its own: the repo's metric readers and model
    families, a tiny configuration and tiny mixes, added as files and
    entries. ``real`` is the repo's ``BENCHMARK.json`` however many
    cells it has grown: of each metric's ``workloads`` the three cells
    renamed here are kept and the rest dropped, and a metric left with
    none is left out."""
    for sub in ("metrics", "families"):
        shutil.copytree(os.path.join(loader.ROOT, "benchmarks", sub),
                        os.path.join(root, "benchmarks", sub),
                        ignore=shutil.ignore_patterns("__pycache__"))
    os.makedirs(os.path.join(root, "benchmarks", "workloads"))
    os.makedirs(os.path.join(root, "benchmarks", "configs"))
    with open(os.path.join(root, "benchmarks", "configs", "tiny.json"),
              "w") as f:
        json.dump(TINY, f)
    for name, mix in MIXES.items():
        with open(os.path.join(root, "benchmarks", "workloads",
                               name + ".json"), "w") as f:
            json.dump(mix, f)
    cells = {"tiny.closed": "tiny-closed", "tiny.open": "tiny-open",
             "tiny.train": "tiny-train"}
    rename = {"ouro-2.6b.decode-closed": "tiny.closed",
              "ouro-2.6b.longprompt-open": "tiny.open",
              "ouro-2.6b-d12.train-2k": "tiny.train"}
    # the open-loop cell was put off with its metrics (PERF.md section
    # 7): its entries come back here as a later PR would add them
    with open(os.path.join(loader.ROOT, "benchmarks", "put_off",
                           "longprompt-open.json")) as f:
        put_off = json.load(f)
    bench = dict(real, root=root)
    bench["configs"] = [{"name": "tiny", "file": "benchmarks/configs/tiny.json"}]
    bench["workloads"] = [{"name": c, "config": "tiny", "traffic": t,
                           "chips": 1} for c, t in cells.items()]
    for kind in ("end_to_end", "per_layer"):
        entries = {m["name"]: dict(m) for m in real[kind]}
        for m in put_off[kind]:
            if m["name"] in entries:
                entries[m["name"]]["workloads"] = \
                    entries[m["name"]]["workloads"] + m["workloads"]
            else:
                entries[m["name"]] = dict(m)
        bench[kind] = []
        for m in entries.values():
            if "workloads" in m:
                m["workloads"] = [rename[w] for w in m["workloads"]
                                  if w in rename]
            if m.get("workloads", True):
                bench[kind].append(m)
    return bench


@pytest.fixture(scope="module")
def tiny_bench(tmp_path_factory):
    return build_tiny_bench(str(tmp_path_factory.mktemp("tiny_bench")),
                            loader.load_benchmark())


@pytest.fixture(scope="module")
def compile_cache(tmp_path_factory):
    return str(tmp_path_factory.mktemp("jax_cache"))


@pytest.fixture
def cpu_tpu_workers(monkeypatch, compile_cache):
    """TPU-lease workers run CPU jax and share a compile cache of this
    file's own: each test is a new worker process, and most of its time
    is compiling what the test before it compiled."""
    from ray_tpu._private import raylet as raylet_mod

    real_env = raylet_mod.Raylet._tpu_worker_env
    monkeypatch.setattr(
        raylet_mod.Raylet, "_tpu_worker_env",
        lambda self, chips: {**real_env(self, chips),
                             "JAX_PLATFORMS": "cpu",
                             "JAX_COMPILATION_CACHE_DIR": compile_cache})
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])


def may_be_absent(metric, line):
    """A roofline's time is that of the device's operations under their
    own names (a Mosaic call, a program's operations): off the TPU the
    host's plane stands in, which names none, and the reader returns
    nothing."""
    return (line["device"]["platform"] != "tpu"
            and metric["source"] == "device_trace"
            and "roofline" in re.split(r"[_.]", metric["name"]))


def check_line(bench, cell, line, trace):
    """The contract's shape of a result line."""
    line = json.loads(json.dumps(line))     # it must survive the print
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert list(line)[-1] == "compared"
    assert line["attempted"] > 0 and line["failed"] == 0
    wanted = {m["name"]: m["unit"]
              for m in loader.cell_metrics(bench, cell, trace)
              if m["name"] in line["metrics"] or not may_be_absent(m, line)}
    if trace:
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert {n: m["unit"] for n, m in line["metrics"].items()} == wanted
    for m in line["metrics"].values():
        assert isinstance(m["value"], float) and m["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])
    for number in line["compared"].values():
        assert set(number) == {"value", "limit"}


def host_plane_stands_in():
    """Inside the worker: the CPU's trace has no device plane, so the
    host's stands in. The reduction itself is tested on a hand-built
    trace; here it is the plumbing that is driven."""
    from benchmarks import trace as trace_mod

    trace_mod.DEVICE_PLANE = "/host:CPU"


class TracedOnCpuLM(worker.BenchLM):
    def __init__(self, *args):
        host_plane_stands_in()
        super().__init__(*args)


def traced_on_cpu_train_func(run_):
    host_plane_stands_in()
    return worker.train_func(run_)


@pytest.mark.parametrize("cell,trace", [
    ("tiny.closed", 0), ("tiny.open", 1), ("tiny.train", 1)])
def test_run_prints_a_contract_line(tiny_bench, cpu_tpu_workers, cell,
                                    trace):
    line = run.run_cell(tiny_bench, cell, seed=2**31 + 11, seconds=3.0,
                        trace=bool(trace), platform="cpu",
                        lm_class=TracedOnCpuLM if trace else None,
                        train_func=traced_on_cpu_train_func if trace
                        else None)
    assert line["correct"], line["faults"]
    check_line(tiny_bench, cell, line, bool(trace))


class AlteredTokenLM(worker.BenchLM):
    """A token altered where it is produced: every 5th decode step hands
    one slot the next token id instead of its own."""

    def make_engine(self):
        engine = super().make_engine()
        inner_step, calls = engine.inner.step, [0]
        vocab = int(self.config["vocab_size"])

        def step(tokens):
            out = inner_step(tokens)
            calls[0] += 1
            if calls[0] % 5 == 0:
                slot = next(iter(out))
                out[slot] = (out[slot] + 1) % vocab
            return out

        engine.inner.step = step
        return engine


def test_an_altered_token_is_not_correct(tiny_bench, cpu_tpu_workers):
    line = run.run_cell(tiny_bench, "tiny.closed", seed=5, seconds=3.0,
                        trace=False, platform="cpu", lm_class=AlteredTokenLM)
    assert not line["correct"]
    gap = line["compared"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]


class FrozenStep(worker.TrainCell):
    """A step that returns its state unchanged."""

    def call_step(self, batch):
        _, _, loss = self.compiled(self.params, self.opt_state, batch)
        return loss


class HalfBatch(worker.TrainCell):
    """Half of the batch left out, the mean taken over the rest (the
    compiled step's shape is kept: the first half stands in twice)."""

    def call_step(self, batch):
        import jax.numpy as jnp

        half = {k: jnp.concatenate([v[:2], v[:2]]) for k, v in batch.items()}
        return super().call_step(half)


def frozen_train_func(run_):
    return worker.train_func(run_, cell_class=FrozenStep)


def half_batch_train_func(run_):
    return worker.train_func(run_, cell_class=HalfBatch)


@pytest.mark.parametrize("fault,number", [
    (frozen_train_func, "change_norm_gap"),
    (half_batch_train_func, "grad_norm_gap")])
def test_a_broken_train_step_is_not_correct(tiny_bench, cpu_tpu_workers,
                                            fault, number):
    line = run.run_cell(tiny_bench, "tiny.train", seed=9, seconds=2.0,
                        trace=False, platform="cpu", train_func=fault)
    assert not line["correct"]
    assert line["compared"][number]["value"] > \
        line["compared"][number]["limit"]


def test_without_a_tpu_a_run_prints_no_result(capsys):
    """The leased worker is started for the TPU and nothing else, jax
    finds none here and raises in it: the run exits non-zero and its
    standard output stays empty."""
    code = run.main(["--workload", "ouro-2.6b-d12.train-2k", "--seed",
                     str(2**31 + 5), "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert code != 0 and out.out == "" and "NO RESULT" in out.err
