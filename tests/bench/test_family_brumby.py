"""The ``brumby`` family beside the harness: the configuration file
against the catalog row, the costs against a hand-worked shape and
against what the issue reckoned, the program (prefill through the
chunked form, then decode through the state, through the slot engine)
against the family's plain reference, which is the attention form, with
the int8 control and a state rounded to bfloat16 failing where the
program passes, what the family cannot express refused, the cell's
entries and its mix letter for letter, the readers that read what this
family's program adds on hand-built observations (the two kernels'
rooflines, the rows a step moved, the parts that wait), and one run of
such a cell through the front door on the CPU.

The block itself (forward, the two serving programs, the kernels, the
planted faults) is held to the same reference in tests/test_retention.py.
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

from benchmarks import (inside_parts, inside_scan, inside_step, loader, peaks,
                        reference, run, traffic)

cloudpickle.register_pickle_by_value(sys.modules[__name__])

CELL = "brumby-14b-d8.longdoc-closed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY = {"model_type": "brumby", "attention_bias": False, "head_dim": 8,
        "hidden_act": "silu", "hidden_size": 32, "intermediate_size": 64,
        "max_position_embeddings": 64, "max_window_layers": 3,
        "num_attention_heads": 4, "num_hidden_layers": 3,
        "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
        "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 128,
        "torch_dtype": "float32"}
# as tests/test_retention.py: float32 on the CPU, rounding alone (the
# sound program reads 4e-7 against the attention form), and far under
# the int8 control (8e-4, 150 times the tolerance) and a state rounded
# to bfloat16 between steps (6e-3)
TOLERANCE = 5e-6


@pytest.fixture(scope="module")
def family():
    return loader.find_family(loader.load_benchmark(), TINY)


@pytest.fixture(scope="module")
def model(family):
    import jax

    ref = loader.family_module(family, "reference")
    sz = ref.sizes_of(TINY)
    cfg = loader.family_module(family, "program").program_config(TINY, 64)
    params = jax.tree.map(lambda a: a * 6 if a.ndim >= 3 else a,
                          ref.seeded_params(2**31 + 5, sz))
    return ref, sz, cfg, params


# ------------------------------------------------ the configuration file

def test_the_configuration_is_the_catalog_row_but_for_its_depth(family):
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Brumby-14B-Base")
    bench = loader.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == "brumby-14b-d8")
    config = loader.load_config(bench, "brumby-14b-d8")
    assert entry["source"] == config["source"] == row["source_url"]
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    published = dict(row["config"], num_hidden_layers=8)
    assert {k: config[k] for k in row["config"]} == published
    assert row["config"]["num_hidden_layers"] == 40
    assert len(entry["why"]) <= 200
    # what the row does not say is said under ``assumed``
    assert {"torch_dtype", "retention_degree", "gate", "qk_norm", "rope",
            "normaliser", "state_dtype", "state_form", "layout",
            "initializer"} <= set(config["assumed"])
    assert "half-life" in config["assumed"]["initializer"]
    assert "one stage of five" in config["stands_for"]
    assert "4,198.7 M (8.40 GB" in config["parameters"]
    sz = loader.family_module(family, "reference").sizes_of(config)
    assert (sz.n_layers, sz.n_heads, sz.kv_heads, sz.head_dim, sz.d_model,
            sz.d_ff, sz.vocab, sz.rope_theta, sz.dtype) == (
        8, 40, 8, 128, 5120, 17408, 151936, 1e6, "bfloat16")


def test_the_published_model_holds_what_the_issue_reckoned(family):
    config = loader.load_config(loader.load_benchmark(), "brumby-14b-d8")
    costs = loader.family_module(family, "costs")
    assert round(costs.n_params(config) / 1e6, 1) == 4198.7
    assert round(costs.layer_params(config) / 1e6, 2) == 330.35
    # a slot's state in a layer: 8 heads x 8,256 x (128 + 1) float32
    assert costs.slot_state_bytes(config) == 8 * 8256 * 129 * 4
    assert round(16 * 8 * costs.slot_state_bytes(config) / 1e9, 2) == 4.36
    # a decode step of 16 rows: 15.6 GB, state 56 %, whatever the context
    step = costs.decode_step_bytes(config, 16, 0, {})
    assert step == costs.decode_step_bytes(config, 16, 16 * 9000, {})
    assert round(step / 1e9, 1) == 15.6
    assert round(100 * 16 * 8 * 2 * costs.slot_state_bytes(config) / step) \
        == 56
    assert round(costs.forward_flops(config, 16, 0, 16) / 1e12, 2) == 0.12
    # a prefill of the mean prompt, 4,096 tokens: 25 TFLOP, 764 MFLOP a
    # token and layer, 104 of them the retention's
    flops = costs.forward_flops(config, 4096, 4096 * 4097 // 2, 1)
    assert round(flops / 1e12) == 25
    assert round(flops / 4096 / 8 / 1e6) == 764
    assert round(costs.retention_flops(config, 4096) / 4096 / 1e6) in (
        103, 104)


SMALL = dict(TINY, torch_dtype="bfloat16")


def test_costs_against_a_hand_worked_count(family):
    costs = loader.family_module(family, "costs")
    # q and o 32 x 32, k and v 32 x 16 (2 K/V heads of 8), the gate 32 x 2
    matrices = 2 * 1024 + 2 * 512 + 64
    # ... its bias, the q and k norms, SwiGLU 3 x 32 x 64, two norms
    layer = matrices + 2 + 16 + 6144 + 64
    assert costs.mixer_matrices(SMALL) == matrices
    assert costs.layer_params(SMALL) == layer
    assert costs.n_params(SMALL) == 2 * 128 * 32 + 32 + 3 * layer
    # the symmetric square of a head of 8: 36 products. A position:
    # (4 + 2) heads x 2 x 36 x 8 for the products with the state, 4 x 2 x
    # 36 + 2 x 36 for z; and inside a chunk, a position's mean of 10
    # pairs at 20 positions, 4 heads x 4 x 8 FLOPs a pair
    state = 6 * 2 * 36 * 8 + 4 * 2 * 36 + 2 * 36
    assert costs.retention_flops(SMALL, 1) == state + 4 * 4 * 8 * 0.5
    assert costs.retention_flops(SMALL, 20) == 20 * (state + 4 * 4 * 8 * 10)
    assert costs.forward_flops(SMALL, 20, 210, logit_rows=1) == (
        2 * 3 * (matrices + 6144) * 20 + 2 * 128 * 32
        + 3 * costs.retention_flops(SMALL, 20))
    # a decode step of 5 rows: a position each, no pairs in a chunk
    assert costs.forward_flops(SMALL, 5, 0, logit_rows=5) == (
        2 * 3 * (matrices + 6144) * 5 + 2 * 128 * 32 * 5
        + 3 * 5 * costs.retention_flops(SMALL, 1))
    # a slot's state in a layer: 2 heads x 36 x (8 + 1) float32
    assert costs.slot_state_bytes(SMALL) == 2 * 36 * 9 * 4
    # a decode step of 3 rows: every weight but the embedding once, the
    # rows looked up in it, each row's state read and written a layer
    assert costs.decode_step_bytes(SMALL, 3, 50, {}) == (
        2 * (costs.n_params(SMALL) - 128 * 32 + 3 * 32)
        + 3 * 3 * 2592 * 2)
    assert costs.DECODE_PROGRAM == "slot_decode_step"
    calls = costs.prefill_retention_costs(SMALL, 20)
    assert len(calls) == 3
    # q, k, v in and o out at 2 bytes, the gate in and the state out at 4
    assert calls[0] == {
        "flops": costs.retention_flops(SMALL, 20),
        "bytes": 20 * 2 * (4 + 2) * 8 * 2 + 20 * 2 * 4 + 2592}
    assert costs.retention_step_costs(SMALL, 3) == {
        "flops": 3 * costs.retention_flops(SMALL, 1),
        "bytes": 3 * (2 * 2592 + 2 * (4 + 2) * 8 * 2 + 2 * 4)}
    with pytest.raises(NotImplementedError):
        costs.train_flops(SMALL, 1, 1)
    with pytest.raises(NotImplementedError):
        costs.flash_shape(SMALL, {})


def test_no_cut_of_this_model_trains_on_one_chip(family):
    program = loader.family_module(family, "program")
    with pytest.raises(NotImplementedError, match="46.0 GB"):
        program.make_train_step(None, {})


@pytest.mark.parametrize("key,value", [
    ("use_sliding_window", True), ("sliding_window", 4096),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4}),
    ("attention_bias", True), ("tie_word_embeddings", True),
    ("hidden_act", "gelu"), ("num_key_value_heads", 3), ("head_dim", 7)])
def test_what_the_family_cannot_express_is_refused(family, key, value):
    ref = loader.family_module(family, "reference")
    with pytest.raises(ValueError, match="cannot express"):
        ref.sizes_of(dict(TINY, **{key: value}))
    with pytest.raises(ValueError, match="cannot express"):
        loader.family_module(family, "program").program_config(
            dict(TINY, **{key: value}), 64)


# ------------------------------- the program against the plain reference

def served_logits(params, cfg, tokens, prompt_len, spoil=None):
    """The logits the slot engine's own programs give along ``tokens``
    [1, T]: a prefill of the first ``prompt_len`` (the chunked form),
    then a decode step a token (the state). ``spoil(cache)`` runs
    between the steps."""
    import jax.numpy as jnp

    from ray_tpu.models import decode

    cache = decode.init_slot_cache(cfg, 1, 64)
    logits, cache = decode.slot_prefill(params, tokens[:, :prompt_len],
                                        cache, jnp.int32(0), cfg)
    got = [logits]
    for t in range(prompt_len, tokens.shape[1]):
        if spoil:
            cache = spoil(cache)
        logits, cache = decode.slot_decode_step(
            params, cache, tokens[:, t], jnp.ones(1, bool), cfg)
        got.append(logits)
    return jnp.stack(got, axis=1)


def test_a_state_rounded_to_bfloat16_fails_where_the_program_passes(model):
    """Logits, not tokens: the program's prefill and 40 decode steps
    against the attention form over the whole sequence. The state in
    the precision below the one the configuration states for it reads
    46 times the sound program's gap and more."""
    import jax
    import jax.numpy as jnp

    ref, sz, cfg, params = model
    tokens = jax.random.randint(jax.random.key(3), (1, 52), 0, sz.vocab)
    want = ref.forward(params, tokens, sz)[:, 11:]

    def rounded(cache):
        return dict(cache, **{name: tuple(
            a.astype(jnp.bfloat16).astype(jnp.float32) for a in cache[name])
            for name in ("ret", "ret_z")})

    sound = float(jnp.max(jnp.abs(
        served_logits(params, cfg, tokens, 12) - want)))
    spoilt = float(jnp.max(jnp.abs(
        served_logits(params, cfg, tokens, 12, rounded) - want)))
    assert sound < TOLERANCE
    assert spoilt > 46 * sound and spoilt > 30 * TOLERANCE


def test_the_int8_control_fails_where_the_program_passes(model):
    """The harness's own comparison, through ``JaxSlotEngine``: the
    engine's greedy tokens lie within the tolerance of the reference's
    best logit; the int8 control's do not, and neither does an altered
    token."""
    import jax.numpy as jnp

    from ray_tpu import serve
    from ray_tpu.models import decode

    ref, sz, cfg, params = model
    prompt = traffic.prompt_tokens(3, 0, 12, sz.vocab)
    engine = serve.JaxSlotEngine(params, cfg, slots=2, max_len=64)
    served = [engine.prefill(1, prompt)]
    while len(served) < 50:
        out = engine.step({1: served[-1]})
        served.extend(out.values())
    assert served == decode.generate(
        params, jnp.asarray([prompt], jnp.int32), cfg, steps=50,
        max_len=64)[0].tolist()
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
    assert {"embed", "final_norm", "head", "wq.0", "w_g.1", "b_g.2",
            "q_norm.0", "k_norm.2", "w_down.2"} <= set(leaves)
    assert "wq.3" not in leaves
    assert leaves["w_g.1"].shape == (32, 2) and leaves["b_g.2"].shape == (2,)


# -------------------------------------------------- the cell's readers

def obs_with(phases, family, steps=10, before=None, **more):
    return dict({
        "run": {"config": SMALL, "family": family},
        "device": {"kind": "TPU v5 lite"},
        "decode_before": {"steps": 5, "slot_steps": 40,
                          "phases": before or {}},
        "decode_after": {"steps": 5 + steps, "slot_steps": 40 + 3 * steps,
                         "phases": dict(
            {"serve.engine.wait": [steps, 0.1]}, **phases)}}, **more)


def test_the_rows_a_step_moved_are_set_against_the_rows_it_answered(family):
    obs = obs_with({"serve.engine.state_rows": [14, 60.0]}, family,
                   before={"serve.engine.state_rows": [2, 10.0]})
    # 30 rows answered of the 50 whose state the dispatched steps moved
    assert inside_step.state_rows_pct(obs) == pytest.approx(60.0)
    # a program that keeps no such count (the parent) reads as nothing,
    # and so does a window without a step
    assert inside_step.state_rows_pct(obs_with({}, family)) is None
    still = {"serve.engine.state_rows": [2, 10.0]}
    assert inside_step.state_rows_pct(
        obs_with(still, family, before=still)) is None


def traced_obs(family, op_totals):
    obs = obs_with({}, family, prefills=[
        [9.0, 9.5, 40],         # before the slice
        [10.1, 10.2, 20],       # inside
        [10.9, 11.1, 40]],      # half inside
        trace={"window_s": 1.0, "slice": [10.0, 11.0],
               "op_totals": op_totals, "program_seconds": {
                   "slot_decode_step": 0.5}})
    # host spans of the engine's steps: [t0, t1, rows, positions]
    obs["steps"] = [[10.0, 10.1, 3, 0], [10.5, 10.6, 5, 0],
                    [11.5, 11.6, 7, 0]]
    return obs


def test_the_chunk_kernels_roofline_prices_the_prefills_in_the_slice(
        family):
    costs = loader.family_module(family, "costs")
    bench = loader.load_benchmark()
    read = loader.load_reader(bench, "retention_chunk_roofline_pct.longdoc")
    chip = peaks.peaks_of("TPU v5 lite")
    least = {n: sum(peaks.roofline_seconds(c, chip)["seconds"]
                    for c in costs.prefill_retention_costs(SMALL, n))
             for n in (20, 40)}
    obs = traced_obs(family, {
        "slot_prefill/retention_chunk.12": [2e-6, 7],
        "slot_prefill/retention_chunk.14": [1e-6, 7],
        "slot_prefill/fusion.4": [9.0, 2],
        "slot_decode_step/retention_step.3": [5.0, 3],
        "forward/retention_chunk.1": [7.0, 1]})
    assert read(obs) == pytest.approx(
        100.0 * (least[20] + 0.5 * least[40]) / 3e-6)
    assert read(obs) == pytest.approx(inside_scan.prefill_kernel_roofline_pct(
        obs, "retention_chunk", "prefill_retention_costs"))
    # no such kernel in the trace (the parent's, or a slice without a
    # prefill), no trace: nothing to read
    obs["trace"]["op_totals"] = {"slot_decode_step/fusion.1": [5.0, 3]}
    assert read(obs) is None
    obs["trace"] = None
    assert read(obs) is None


def test_the_step_kernels_roofline_prices_its_calls_at_the_steps_rows(
        family):
    costs = loader.family_module(family, "costs")
    bench = loader.load_benchmark()
    read = loader.load_reader(bench, "retention_step_roofline_pct.longdoc")
    chip = peaks.peaks_of("TPU v5 lite")
    obs = traced_obs(family, {
        "slot_decode_step/retention_step.3": [4e-6, 6],
        "slot_prefill/retention_step.9": [9.0, 2],
        "slot_decode_step/fusion.1": [5.0, 3]})
    # two steps begun in the slice, of 3 and 5 rows: six calls at 4 rows
    least = peaks.roofline_seconds(costs.retention_step_costs(SMALL, 4.0),
                                   chip)["seconds"]
    assert read(obs) == pytest.approx(100.0 * 6 * least / 4e-6)
    assert inside_step.decode_kernel_roofline_pct(
        obs, "retention_step", "no_such_price") is None
    assert inside_step.decode_kernel_roofline_pct(
        obs, "no_such_kernel", "retention_step_costs") is None
    obs["steps"] = [[12.0, 12.1, 3, 0]]         # none begun in the slice
    assert read(obs) is None
    obs["trace"] = None
    assert read(obs) is None


def test_the_parts_that_wait_read_the_decode_steps_table(family):
    """The three per-part entries of ``put_off/longdoc-closed.json``:
    well-formed, their readers in place, and reading a hand-built
    table of the decode step's parts."""
    bench = loader.load_benchmark()
    with open(os.path.join(loader.ROOT, "benchmarks", "put_off",
                           "longdoc-closed.json")) as f:
        waiting = json.load(f)
    names = [m["name"] for m in waiting["per_layer"]]
    assert names == ["decode_retention_step_ms.longdoc",
                     "decode_mlp_ms.longdoc", "decode_unscoped_pct.longdoc"]
    grown = dict(bench, per_layer=bench["per_layer"] + waiting["per_layer"])
    keeps_the_contract(grown)
    every_cell_reports_what_the_contract_asks(grown)
    assert not set(names) & {m["name"] for m in bench["per_layer"]}
    obs = traced_obs(family, {
        "slot_decode_step/retention_step.3": [0.2, 6],
        "slot_decode_step/fusion.7": [0.1, 6],
        "slot_decode_step/fusion.8": [0.1, 6],
        "slot_decode_step/copy.1": [0.05, 2],
        "slot_prefill/fusion.7": [9.0, 1]})
    obs["decode_after"]["parts"] = {"slot_decode_step": {
        "retention_step.3": ["run0", "retention_step"],
        "fusion.7": ["run0", "mlp"], "fusion.8": ["run0", "qkv"],
        "copy.1": [None, None]}}
    read = {n: loader.load_reader(grown, n) for n in names}
    # two steps begun in the slice
    assert read[names[0]](obs) == pytest.approx(1e3 * 0.2 / 2)
    assert read[names[1]](obs) == pytest.approx(1e3 * 0.1 / 2)
    assert read[names[2]](obs) == pytest.approx(100.0 * (0.5 - 0.4) / 0.5)
    assert inside_parts.part_seconds(obs) == {
        ("run0", "retention_step"): 0.2, ("run0", "mlp"): 0.1,
        ("run0", "qkv"): 0.1}
    del obs["decode_after"]["parts"]    # the parent's program gives none
    assert all(r(obs) is None for r in read.values())


# ------------------------------------------------- the cell's entries

TWINS = ("serve_mfu_pct", "decode_roofline_pct", "device_idle_pct",
         "decode_occupancy_pct", "decode_device_wait_ms", "decode_host_ms",
         "decode_slot_reads_ms", "scheduler_overhead_ms",
         "prefill_stall_pct", "prefill_tokens_per_s")


def test_the_benchmark_with_the_cell_keeps_the_contract():
    bench = loader.load_benchmark()
    keeps_the_contract(bench)
    every_cell_reports_what_the_contract_asks(bench)
    cell = loader.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "brumby-14b-d8", "longdoc-closed", 1)
    mix = loader.load_traffic(bench, cell["traffic"])
    assert (mix["kind"], mix["loop"], mix["clients"], mix["slots"],
            mix["slot_len"], mix["cycle"], mix["pattern_seed"],
            mix["prompt_lengths"], mix["prompt_weights"],
            mix["output_tokens"], mix["check_requests"],
            mix["trace_seconds"]) == (
        "serve", "closed", 20, 16, 9216, 40, 0, [2048, 4096, 8192],
        [0.4, 0.4, 0.2], {"min": 128, "max": 768}, 4, 3.0)
    assert mix["limits"]["answers_wrong"] == 0
    assert set(mix["limits"]) == {"served_logit_gap", "answers_wrong"}
    assert "my chip runs, PR 40" in mix["limits_from"]
    # the longest request and the step in flight fit the rope's table;
    # every prompt takes the chunk kernel
    assert max(mix["prompt_lengths"]) + mix["output_tokens"]["max"] + 1 \
        < mix["slot_len"]
    assert all(n % 128 == 0 for n in mix["prompt_lengths"])
    cycle = traffic.cycle_of(mix)
    assert sum(r["prompt_len"] for r in cycle) / len(cycle) == 4096
    assert sum(r["max_tokens"] for r in cycle) / len(cycle) == \
        pytest.approx(448, abs=1)
    assert [m["name"] for m in loader.cell_metrics(bench, CELL, False)] == [
        "serve_tokens_per_s", "setup_s"]
    own = [name + ".longdoc" for name in TWINS + (
        "retention_chunk_roofline_pct", "retention_step_roofline_pct",
        "decode_state_rows_pct")]
    assert [m["name"] for m in loader.cell_metrics(bench, CELL, True)] == own
    # each lists this cell alone, under its twin's layer and unit
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in TWINS:
        twin, mine = by_name[name + ".rollout"], by_name[name + ".longdoc"]
        assert mine == dict(twin, name=mine["name"], workloads=[CELL])
        assert loader.load_reader(bench, mine["name"]) is loader.load_reader(
            bench, twin["name"])
    for name in ("retention_chunk_roofline_pct.longdoc",
                 "retention_step_roofline_pct.longdoc"):
        assert by_name[name] == {
            "name": name, "unit": "%", "better": "higher",
            "source": "device_trace", "layer": "kernels: ops/retention.py",
            "moves": "serve_tokens_per_s", "workloads": [CELL]}
    assert by_name["decode_state_rows_pct.longdoc"] == dict(
        by_name["decode_occupancy_pct.longdoc"],
        name="decode_state_rows_pct.longdoc",
        layer="engine: JaxSlotEngine, models/decode.py")
    assert loader.load_reader(bench, "decode_state_rows_pct.longdoc") \
        is inside_step.state_rows_pct
    # what was there is as it was, but for the one list that grew (a
    # later cell grows it again: nothing here pins its length)
    serve = next(m for m in bench["end_to_end"]
                 if m["name"] == "serve_tokens_per_s")
    assert serve["workloads"][:4] == [
        "ouro-2.6b.decode-closed", "mimo-v2-flash-ep16-d7.reason-closed",
        "jamba2-3b.rollout-closed", CELL]


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
    for sub, name, data in (("configs", "brumby-14b-d8", TINY),
                            ("workloads", "longdoc-closed", TINY_MIX)):
        with open(os.path.join(root, "benchmarks", sub, name + ".json"),
                  "w") as f:
            json.dump(data, f)
    return copy.deepcopy(dict(loader.load_benchmark(), root=root))


def test_such_a_cell_is_served_through_the_front_door(tmp_path,
                                                      cpu_tpu_workers):
    bench = tiny_cell(str(tmp_path / "tiny_brumby"))
    line = run.run_cell(bench, CELL, seed=2**31 + 31, seconds=3.0,
                        trace=True, platform="cpu", lm_class=TracedOnCpuLM)
    assert line["correct"], line["faults"]
    check_line(bench, CELL, line, True)
    got = line["metrics"]
    # the engine's count of the rows whose state it moved came through
    # the scheduler's table: no more rows answered than moved
    assert 0.0 < got["decode_state_rows_pct.longdoc"]["value"] <= 100.0
    assert got["prefill_tokens_per_s.longdoc"]["value"] > 0.0
    assert got["decode_occupancy_pct.longdoc"]["value"] > 0.0
    assert got["serve_mfu_pct.longdoc"]["value"] > 0.0
    assert 0.0 < got["decode_slot_reads_ms.longdoc"]["value"] < 1.0
    # the rooflines' time is the device's operations by name: the CPU's
    # stand-in plane names none
    assert "decode_roofline_pct.longdoc" not in got
    assert "retention_chunk_roofline_pct.longdoc" not in got
    assert "retention_step_roofline_pct.longdoc" not in got
