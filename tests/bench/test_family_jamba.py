"""The ``jamba`` family beside the harness: the configuration file
against the catalog row, the costs against a hand-worked shape and
against what the issue reckoned, the program against the family's plain
reference with the int8 control failing where the program passes, what
the family cannot express refused, the cell's entries and its mix
letter for letter, the two readers that read what this family's program
adds on hand-built observations, and one run of such a cell through the
front door on the CPU.

The block itself (forward, the two serving programs, the planted
faults) is held to the same reference in tests/test_mamba_block.py.
"""

import copy
import json
import os
import shutil
import sys

import cloudpickle
import pytest
from test_bench_run import (MIXES, TracedOnCpuLM, check_line,  # noqa: F401
                            compile_cache, cpu_tpu_workers)
from test_bench_units import (every_cell_reports_what_the_contract_asks,
                              keeps_the_contract)

from benchmarks import inside_scan, loader, peaks, reference, run, traffic

cloudpickle.register_pickle_by_value(sys.modules[__name__])

CELL = "jamba2-3b.rollout-closed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY = {"model_type": "jamba", "attn_layer_offset": 1,
        "attn_layer_period": 4, "expert_layer_offset": 1,
        "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 32,
        "intermediate_size": 64, "mamba_conv_bias": True,
        "mamba_d_conv": 4, "mamba_d_state": 8, "mamba_dt_rank": 6,
        "mamba_expand": 2, "mamba_proj_bias": False,
        "num_attention_heads": 4, "num_experts": 1,
        "num_experts_per_tok": 1, "num_hidden_layers": 4,
        "num_key_value_heads": 1, "rms_norm_eps": 1e-6,
        "sliding_window": None, "tie_word_embeddings": True,
        "vocab_size": 128, "torch_dtype": "float32"}
# as tests/test_mamba_block.py: float32 on the CPU, rounding alone (the
# sound program reads 4e-7), and far under the int8 control (3e-2)
TOLERANCE = 2e-5


@pytest.fixture(scope="module")
def family():
    return loader.find_family(loader.load_benchmark(), TINY)


@pytest.fixture(scope="module")
def model(family):
    import jax

    ref = loader.family_module(family, "reference")
    sz = ref.sizes_of(TINY)
    cfg = loader.family_module(family, "program").program_config(TINY, 64)
    params = jax.tree.map(
        lambda a: a * 6 if a.ndim >= 3 and a.shape[-2:] != (8, 64) else a,
        ref.seeded_params(2**31 + 5, sz))
    return ref, sz, cfg, params


# ------------------------------------------------ the configuration file

def test_the_configuration_is_the_catalog_row(family):
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "AI21-Jamba2-3B")
    bench = loader.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == "jamba2-3b")
    config = loader.load_config(bench, "jamba2-3b")
    assert entry["source"] == config["source"] == row["source_url"]
    assert entry["reduced"] == config["reduced"] == []
    assert {k: config[k] for k in row["config"]} == row["config"]
    assert len(entry["why"]) <= 200
    # what the row does not say is said under ``assumed``
    assert {"torch_dtype", "state_dtype", "layer_rule", "inner_norms",
            "initializer"} <= set(config["assumed"])
    assert "A_log" in config["assumed"]["initializer"]
    assert "one chip" in config["stands_for"]
    sz = loader.family_module(family, "reference").sizes_of(config)
    assert [i for i, m in enumerate(sz.mixers) if m == "full"] == [7, 21]
    assert (sz.n_layers, sz.head_dim, sz.kv_heads, sz.ssm_inner,
            sz.ssm_state, sz.ssm_dt_rank, sz.ssm_conv, sz.dtype) == (
        28, 128, 1, 5120, 16, 160, 4, "bfloat16")
    # five runs: 7 Mamba, 1 attention, 13 Mamba, 1 attention, 6 Mamba
    assert [n for _, n in loader.family_module(
        family, "reference").runs_of(sz.mixers)] == [7, 1, 13, 1, 6]


def test_the_published_model_holds_what_the_issue_reckoned(family):
    config = loader.load_config(loader.load_benchmark(), "jamba2-3b")
    costs = loader.family_module(family, "costs")
    assert round(costs.n_params(config) / 1e6, 1) == 3029.3
    assert round(costs.mamba_params(config) / 1e6, 2) == 41.24
    assert round(costs.attention_params(config) / 1e6, 2) == 13.76
    assert costs.layer_counts(config) == (26, 2)
    # 256 slots' recurrent state: 2.18 GB of state and 0.20 GB of tails
    assert round(256 * 26 * costs.slot_state_bytes(config) / 1e9, 2) == 2.39
    # a decode step of 256 rows at a mean context of 600: 11 GB
    step = costs.decode_step_bytes(config, 256, 256 * 600, {})
    assert round(step / 1e9, 1) == 11.0
    # a prefill of the mean prompt, 294 tokens: 1.7 TFLOP
    assert round(costs.forward_flops(config, 294, 294 * 295 // 2, 1)
                 / 1e12, 1) == 1.7


SMALL = dict(TINY, torch_dtype="bfloat16")


def test_costs_against_a_hand_worked_count(family):
    costs = loader.family_module(family, "costs")
    # C = 64 channels, N = 8, R = 6, K = 4. A Mamba mixer's matrices:
    # in 32 x 128, x 64 x (6 + 16), dt 6 x 64, out 64 x 32
    matrices = 4096 + 1408 + 384 + 2048
    # ... and the convolution with its bias, the three norms, dt's bias,
    # A_log and D
    mamba = matrices + 256 + 64 + (6 + 8 + 8) + 64 + 512 + 64
    # attention: q and o 32 x 32, k and v 32 x 8
    attention = 2 * 1024 + 2 * 256
    ffn = 3 * 32 * 64
    assert costs.layer_counts(SMALL) == (3, 1)
    assert costs.mamba_matrices(SMALL) == matrices
    assert costs.mamba_params(SMALL) == mamba
    assert costs.attention_params(SMALL) == attention
    assert costs.n_params(SMALL) == (128 * 32 + 32 + 3 * mamba + attention
                                     + 4 * (ffn + 64))
    # the recurrence at one position: 7 FLOPs a value of the state, 3 a
    # channel, and the convolution's 2 a tap
    scan = 64 * (2 * 4 + 8 * 7 + 3)
    assert costs.scan_flops(SMALL, 1) == scan
    # a prompt of 20: 210 causal pairs in the attention layer
    assert costs.forward_flops(SMALL, 20, 210, logit_rows=1) == (
        2 * (3 * matrices + attention + 4 * ffn) * 20 + 2 * 128 * 32
        + 3 * scan * 20 + 4 * 4 * 8 * 210)
    # a slot's state in a Mamba layer: 8 x 64 float32 and 3 x 64 bf16
    assert costs.slot_state_bytes(SMALL) == 2048 + 384
    # a decode step of 3 rows that attend 50 positions: every parameter
    # once, the three Mamba layers' state read and written for each row,
    # K and V of 50 + 3 positions in the one attention layer (1 head of 8)
    assert costs.decode_step_bytes(SMALL, 3, 50, {}) == (
        2 * costs.n_params(SMALL) + 3 * 3 * 2432 * 2 + 2 * 53 * 2 * 8)
    assert costs.DECODE_PROGRAM == "slot_decode_step"
    calls = costs.prefill_scan_costs(SMALL, 20)
    assert len(calls) == 3
    # u, B, C in and y out at 2 bytes; dt in and the state out at 4
    assert calls[0] == {
        "flops": 20 * 64 * (8 * 7 + 3),
        "bytes": 20 * (2 * 64 + 2 * 8) * 2 + (20 * 64 + 8 * 64) * 4}
    (flash,) = costs.prefill_flash_costs(SMALL, 20)
    assert flash == {"flops": 4 * 4 * 8 * 210,
                     "bytes": 2 * 20 * (4 + 1) * 8 * 2 + 4 * 4 * 20}
    with pytest.raises(NotImplementedError):
        costs.train_flops(SMALL, 1, 1)
    with pytest.raises(NotImplementedError):
        costs.flash_shape(SMALL, {})


def test_no_cut_of_this_model_trains_on_one_chip(family):
    program = loader.family_module(family, "program")
    with pytest.raises(NotImplementedError, match="25.6 GB"):
        program.make_train_step(None, {})


@pytest.mark.parametrize("key,value", [
    ("num_experts", 16), ("hidden_act", "gelu"), ("mamba_proj_bias", True),
    ("mamba_conv_bias", False), ("sliding_window", 4096),
    ("tie_word_embeddings", False), ("num_key_value_heads", 3),
    ("attn_layer_offset", 4)])
def test_what_the_family_cannot_express_is_refused(family, key, value):
    ref = loader.family_module(family, "reference")
    with pytest.raises(ValueError, match="cannot express"):
        ref.sizes_of(dict(TINY, **{key: value}))
    with pytest.raises(ValueError, match="cannot express"):
        loader.family_module(family, "program").program_config(
            dict(TINY, **{key: value}), 64)


# ------------------------------- the program against the plain reference

def test_the_int8_control_fails_where_the_program_passes(model):
    """The harness's own comparison: the program's greedy tokens lie
    within the tolerance of the reference's best logit; the int8
    control's do not, and neither does an altered token."""
    import jax.numpy as jnp

    from ray_tpu.models import decode

    ref, sz, cfg, params = model
    prompt = traffic.prompt_tokens(3, 0, 12, sz.vocab)
    served = decode.generate(params, jnp.asarray([prompt], jnp.int32), cfg,
                             steps=50, max_len=64)[0].tolist()
    gaps = reference.served_logit_gaps(ref, params, prompt, served, sz,
                                       quant="int8", pad_to=16)
    assert len(gaps["served"]) == len(gaps["control"]) == 50
    assert max(gaps["served"]) < TOLERANCE
    assert max(gaps["control"]) > 30 * TOLERANCE
    wrong = list(served)
    wrong[7] = (wrong[7] + 1) % sz.vocab
    assert max(reference.served_logit_gaps(
        ref, params, prompt, wrong, sz, pad_to=16)["served"]) > 30 * TOLERANCE


def test_the_leaves_are_named_by_layer(model):
    ref, sz, _, params = model
    leaves = ref.by_leaf(params)
    assert {"embed", "final_norm", "w_in.0", "wq.1", "w_in.2", "a_log.3",
            "w_down.3"} <= set(leaves)
    assert "wq.0" not in leaves and "w_in.1" not in leaves
    assert "head" not in leaves                     # tied
    assert leaves["a_log.3"].shape == (8, 64)


# -------------------------------------------------- the cell's readers

def obs_with(phases, family, steps=10, before=None, **more):
    return dict({
        "run": {"config": SMALL, "family": family},
        "device": {"kind": "TPU v5 lite"},
        "decode_before": {"steps": 5, "phases": before or {}},
        "decode_after": {"steps": 5 + steps, "phases": dict(
            {"serve.engine.wait": [steps, 0.1]}, **phases)}}, **more)


def test_the_prefill_rate_is_the_engines_own_tokens_over_its_own_seconds(
        family):
    obs = obs_with({"serve.engine.prefill": [12, 0.9],
                    "serve.engine.prefill_tokens": [12, 3600.0]}, family,
                   before={"serve.engine.prefill": [2, 0.1],
                           "serve.engine.prefill_tokens": [2, 400.0]})
    assert inside_scan.prefill_tokens_per_s(obs) == pytest.approx(4000.0)
    # a program that keeps no such span (the parent) reads as nothing,
    # and so does a window without a prefill
    assert inside_scan.prefill_tokens_per_s(obs_with({}, family)) is None
    still = {"serve.engine.prefill": [2, 0.1],
             "serve.engine.prefill_tokens": [2, 400.0]}
    assert inside_scan.prefill_tokens_per_s(
        obs_with(still, family, before=still)) is None


def test_the_scan_kernels_roofline_prices_the_prefills_in_the_slice(family):
    costs = loader.family_module(family, "costs")
    chip = peaks.peaks_of("TPU v5 lite")
    least = {n: sum(peaks.roofline_seconds(c, chip)["seconds"]
                    for c in costs.prefill_scan_costs(SMALL, n))
             for n in (20, 40)}
    obs = obs_with({}, family, prefills=[
        [9.0, 9.5, 40],         # before the slice
        [10.1, 10.2, 20],       # inside
        [10.9, 11.1, 40]],      # half inside
        trace={"window_s": 1.0, "slice": [10.0, 11.0], "op_totals": {
            "slot_prefill/ssm_scan.12": [2e-6, 7],
            "slot_prefill/ssm_scan.14": [1e-6, 7],
            "slot_prefill/flash_fwd.4": [9.0, 2],
            "slot_decode_step/fusion.1": [5.0, 3],
            "forward/ssm_scan.1": [7.0, 1]}})
    assert inside_scan.prefill_scan_roofline_pct(obs) == pytest.approx(
        100.0 * (least[20] + 0.5 * least[40]) / 3e-6)
    # the same function reads the attention kernel by its own price
    flash = {n: sum(peaks.roofline_seconds(c, chip)["seconds"]
                    for c in costs.prefill_flash_costs(SMALL, n))
             for n in (20, 40)}
    assert inside_scan.prefill_kernel_roofline_pct(
        obs, "flash_fwd", "prefill_flash_costs") == pytest.approx(
        100.0 * (flash[20] + 0.5 * flash[40]) / 9.0)
    # no such kernel in the trace (the parent's, or a slice without a
    # prefill), no price in the family, no trace: nothing to read
    obs["trace"]["op_totals"] = {"slot_decode_step/fusion.1": [5.0, 3]}
    assert inside_scan.prefill_scan_roofline_pct(obs) is None
    assert inside_scan.prefill_kernel_roofline_pct(
        obs, "ssm_scan", "no_such_price") is None
    obs["trace"] = None
    assert inside_scan.prefill_scan_roofline_pct(obs) is None


# ------------------------------------------------- the cell's entries

TWINS = ("serve_mfu_pct", "device_idle_pct", "decode_roofline_pct",
         "decode_occupancy_pct", "decode_device_wait_ms", "decode_host_ms",
         "decode_slot_reads_ms", "scheduler_overhead_ms",
         "prefill_stall_pct")


def test_the_benchmark_with_the_cell_keeps_the_contract():
    bench = loader.load_benchmark()
    keeps_the_contract(bench)
    every_cell_reports_what_the_contract_asks(bench)
    cell = loader.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "jamba2-3b", "rollout-closed", 1)
    mix = loader.load_traffic(bench, cell["traffic"])
    assert (mix["loop"], mix["clients"], mix["slots"], mix["slot_len"],
            mix["cycle"], mix["pattern_seed"], mix["prompt_lengths"],
            mix["prompt_weights"], mix["output_tokens"],
            mix["check_requests"], mix["trace_seconds"]) == (
        "closed", 320, 256, 2048, 128, 0, [128, 256, 512], [0.3, 0.4, 0.3],
        {"min": 128, "max": 1024}, 4, 3.0)
    assert mix["limits"]["answers_wrong"] == 0
    assert set(mix["limits"]) == {"served_logit_gap", "answers_wrong"}
    # the longest request and the step in flight fit a slot; every
    # prompt takes both kernels
    assert max(mix["prompt_lengths"]) + mix["output_tokens"]["max"] + 1 \
        < mix["slot_len"]
    assert all(n % 128 == 0 for n in mix["prompt_lengths"])
    lengths = [r["prompt_len"] for r in traffic.cycle_of(mix)]
    assert sum(lengths) / len(lengths) == pytest.approx(294, abs=2)
    assert [m["name"] for m in loader.cell_metrics(bench, CELL, False)] == [
        "serve_tokens_per_s", "setup_s"]
    own = [name + ".rollout" for name in TWINS + (
        "ssm_scan_roofline_pct", "prefill_tokens_per_s")]
    assert [m["name"] for m in loader.cell_metrics(bench, CELL, True)] == own
    # each lists this cell alone, under its twin's layer and unit
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in TWINS:
        twin, mine = by_name[name + ".reason"], by_name[name + ".rollout"]
        assert mine == dict(twin, name=mine["name"], workloads=[CELL])
        assert loader.load_reader(bench, mine["name"]) is loader.load_reader(
            bench, twin["name"])
    assert by_name["ssm_scan_roofline_pct.rollout"]["layer"] == \
        "kernels: ops/ssm.py"
    assert loader.load_reader(bench, "ssm_scan_roofline_pct.rollout") \
        is inside_scan.prefill_scan_roofline_pct
    assert loader.load_reader(bench, "prefill_tokens_per_s.rollout") \
        is inside_scan.prefill_tokens_per_s
    # what was there is as it was, but for the one list that grew
    serve = next(m for m in bench["end_to_end"]
                 if m["name"] == "serve_tokens_per_s")
    assert serve["workloads"][-1] == CELL and len(serve["workloads"]) == 3


# ----------------------------------- such a cell through the front door

TINY_MIX = dict(MIXES["tiny-closed"], clients=5, slots=3, slot_len=64,
                prompt_lengths=[6, 16], prompt_weights=[0.5, 0.5],
                output_tokens={"min": 10, "max": 20},
                limits={"served_logit_gap": 1e-3, "answers_wrong": 0})


def tiny_cell(root: str) -> dict:
    """The real ``BENCHMARK.json`` with the cell's configuration and mix
    replaced by tiny ones under a path of its own: every entry, reader
    and family file is the repo's."""
    shutil.copytree(os.path.join(loader.ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for sub, name, data in (("configs", "jamba2-3b", TINY),
                            ("workloads", "rollout-closed", TINY_MIX)):
        with open(os.path.join(root, "benchmarks", sub, name + ".json"),
                  "w") as f:
            json.dump(data, f)
    return copy.deepcopy(dict(loader.load_benchmark(), root=root))


def test_such_a_cell_is_served_through_the_front_door(tmp_path,
                                                      cpu_tpu_workers):
    bench = tiny_cell(str(tmp_path / "tiny_jamba"))
    line = run.run_cell(bench, CELL, seed=2**31 + 31, seconds=3.0,
                        trace=True, platform="cpu", lm_class=TracedOnCpuLM)
    assert line["correct"], line["faults"]
    check_line(bench, CELL, line, True)
    got = line["metrics"]
    # the engine's span and counter of its prefills came through the
    # scheduler's table and were read as a rate
    assert got["prefill_tokens_per_s.rollout"]["value"] > 0.0
    assert got["decode_occupancy_pct.rollout"]["value"] > 0.0
    assert got["serve_mfu_pct.rollout"]["value"] > 0.0
    assert 0.0 < got["decode_slot_reads_ms.rollout"]["value"] < 1.0
    # the rooflines' time is the device's operations by name: the CPU's
    # stand-in plane names none
    assert "decode_roofline_pct.rollout" not in got
    assert "ssm_scan_roofline_pct.rollout" not in got
