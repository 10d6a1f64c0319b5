"""The ``mimo_v2_flash`` family at a tiny size on the CPU: the program
(prefill, then decoding through the two kinds of cache) against the
family's plain reference on seeded weights; the shares of the experts
adding up to the whole layer; one planted fault for each mechanism,
which must fail the comparison; the costs against a hand-worked count;
the int8 control failing where the program passes; the cell's readers
on hand-built observations; and one run of such a cell through the
front door.

Same structure as the published model: 7 layers of the three kinds
(full + dense, then window + experts x4, full + experts, window +
experts), grouped heads with their own count by layer kind, q.k width
12 against v width 8, rope on 4 of 12, window 8, a sink in window
layers, 16 experts of which this chip holds 4, top-2.
"""

import copy
import dataclasses
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

from benchmarks import inside_serve, loader, peaks, reference, run, traffic

cloudpickle.register_pickle_by_value(sys.modules[__name__])

CELL = "mimo-v2-flash-ep16-d7.reason-closed"
TINY = {
    "model_type": "mimo_v2_flash", "attention_value_scale": 0.707,
    "hidden_act": "silu", "hidden_size": 32, "intermediate_size": 64,
    "num_attention_heads": 4, "head_dim": 12, "v_head_dim": 8,
    "num_hidden_layers": 7, "num_key_value_heads": 1,
    "layernorm_epsilon": 1e-5, "rope_theta": 5000000,
    "tie_word_embeddings": False, "vocab_size": 128,
    "partial_rotary_factor": 0.334, "sliding_window": 8,
    "sliding_window_size": 8, "swa_rope_theta": 10000,
    "attention_bias": False, "hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1],
    "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False, "attention_chunk_size": 8,
    "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1], "moe_intermediate_size": 16,
    "n_routed_experts": 4, "router_experts": 16, "experts_held": [4, 4],
    "n_shared_experts": None, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "scoring_func": "sigmoid", "n_group": 1,
    "topk_group": 1, "topk_method": "noaux_tc",
    "routed_scaling_factor": None, "swa_num_attention_heads": 4,
    "swa_num_key_value_heads": 2, "swa_head_dim": 12, "swa_v_head_dim": 8,
    "torch_dtype": "float32"}
# float32 on the CPU: the program and the reference run the same
# mathematics in another order (the program's router, softmax and
# accumulations are float32 too), so they differ by rounding: 1.5e-7 is
# what the sound program reads on the planted faults' tokens. 1e-4
# leaves that hundreds of times of room (a near tie of router scores
# that rounding flips would need it) and lies as far under the least
# planted fault (4.5e-2, the full layers' rope base in a window layer).
TOLERANCE = 1e-4


@pytest.fixture(scope="module")
def family():
    return loader.find_family(loader.load_benchmark(), TINY)


def scaled(params):
    """The 0.02 initializer leaves a 32-wide model's logits to its
    embedding alone; the matrices are scaled up until the layers decide
    them, as they do at the published width, and the sinks brought down
    to where a window of 8 scores leaves them a share."""
    import jax

    out = jax.tree.map(lambda a: a * 6 if a.ndim >= 3 else a, params)
    out["layers"] = tuple(
        dict(run, sink=run["sink"] - 4.0) if "sink" in run else run
        for run in out["layers"])
    # a correction bias wide enough to change the choice of many rows
    out["layers"] = tuple(
        dict(run, router_bias=run["router_bias"] * 10)
        if "router_bias" in run else run for run in out["layers"])
    return out


@pytest.fixture(scope="module")
def model(family):
    ref = loader.family_module(family, "reference")
    sz = ref.sizes_of(TINY)
    cfg = loader.family_module(family, "program").program_config(TINY, 64)
    return ref, sz, cfg, scaled(ref.seeded_params(2**31 + 5, sz))


def test_the_sizes_are_the_structure_of_the_published_model(model):
    ref, sz, cfg, params = model
    assert sz.kinds == (("full", "dense"),) + (("window", "experts"),) * 4 \
        + (("full", "experts"), ("window", "experts"))
    assert (sz.rotary_dim, sz.kv_heads, sz.window_kv_heads) == (4, 1, 2)
    assert ref.runs_of(sz.kinds) == (
        (("full", "dense"), 1), (("window", "experts"), 4),
        (("full", "experts"), 1), (("window", "experts"), 1))
    # the program's own parameters have the reference's layout
    import jax

    from ray_tpu.models import init_params

    mine = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    assert jax.tree.structure(mine) == jax.tree.structure(params)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in zip(
        jax.tree.leaves(mine), jax.tree.leaves(params)))
    assert sorted(ref.by_leaf(params))[:3] == [
        "attn_norm.0", "attn_norm.1", "attn_norm.2"]
    assert len([k for k in ref.by_leaf(params) if k.startswith("wq.")]) == 7
    assert len([k for k in ref.by_leaf(params) if k.startswith("sink.")]) == 5


@pytest.mark.parametrize("prompt_len", [5, 8, 21])
def test_prefill_then_cached_decoding_is_the_references_forward(
        model, prompt_len):
    """Logits, not tokens: the prompt's last position from
    ``slot_prefill`` and every later one from ``slot_decode_step``,
    through several wraps of the ring of 8, against the reference's
    full forward over the whole sequence."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import decode

    ref, sz, cfg, params = model
    tokens = jax.random.randint(jax.random.key(prompt_len), (2, 48), 0,
                                sz.vocab)
    want = ref.forward(params, tokens, sz)
    cache = decode.init_slot_cache(cfg, 2, 64)
    worst = 0.0
    for row in range(2):
        logits, cache = decode.slot_prefill(
            params, tokens[row:row + 1, :prompt_len], cache,
            jnp.int32(row), cfg)
        worst = max(worst, float(jnp.max(jnp.abs(
            logits[0] - want[row, prompt_len - 1]))))
    for t in range(prompt_len, 48):
        logits, cache = decode.slot_decode_step(
            params, cache, tokens[:, t], jnp.ones(2, bool), cfg)
        worst = max(worst, float(jnp.max(jnp.abs(logits - want[:, t]))))
    assert worst < TOLERANCE, worst
    assert float(jnp.max(jnp.abs(want))) > 0.2     # logits worth the name


def program_logits(params, tokens, cfg):
    """The program's full forward: the same block the serving programs
    scan (tests/test_mimo_block.py holds them to it)."""
    from ray_tpu.models import forward

    return forward(params, tokens, cfg)


def drop_one_assignment(monkeypatch):
    """The expert layer with the first assignment routed here left out,
    as a capacity limit would leave it."""
    from ray_tpu.models import transformer

    real = transformer.expert_ffn

    def dropping(h, chosen, weights, *mats, first, held, **kw):
        import jax.numpy as jnp

        here = ((chosen >= first) & (chosen < first + held)).reshape(-1)
        drop = jnp.argmax(here)
        weights = weights.reshape(-1).at[drop].set(0.0).reshape(
            weights.shape)
        return real(h, chosen, weights, *mats, first=first, held=held, **kw)

    monkeypatch.setattr(transformer, "expert_ffn", dropping)


def weigh_over_held_alone(monkeypatch):
    """The router with the weights normalised over the chosen experts
    that this chip holds, not over all the chosen."""
    from ray_tpu.models import transformer

    real = transformer.route

    def held_only(h, router, bias, k):
        import jax.numpy as jnp

        chosen, weights = real(h, router, bias, k)
        here = (chosen >= 4) & (chosen < 8)
        total = jnp.sum(jnp.where(here, weights, 0.0), -1, keepdims=True)
        return chosen, jnp.where(total > 0, weights / jnp.maximum(
            total, 1e-9), weights)

    monkeypatch.setattr(transformer, "route", held_only)


def without(params, leaf):
    return dict(params, layers=tuple(
        {k: v * 0 if k == leaf else v for k, v in run.items()}
        for run in params["layers"]))


FAULTS = {
    # name: (changes to the program's config, to its weights, a patch)
    "no sink": ({"sink_kinds": ()}, lambda p: dict(p, layers=tuple(
        {k: v for k, v in run.items() if k != "sink"}
        for run in p["layers"])), None),
    "window off by one": ({"window": 9}, None, None),
    "the full layers' theta in a window layer": (
        {"window_rope_theta": 5e6}, None, None),
    "no correction bias in the choice": (
        {}, lambda p: without(p, "router_bias"), None),
    "weights normalised over held experts only": (
        {}, None, weigh_over_held_alone),
    "one assignment dropped": ({}, None, drop_one_assignment),
    "value scale left out": ({"value_scale": 1.0}, None, None),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_fails_the_comparison(model, monkeypatch, fault):
    """Each mechanism left out or bent in the program moves the logits
    by at least thirty times the tolerance, so none can go missing
    inside it. The sound program passes on the same tokens."""
    import jax
    import jax.numpy as jnp

    ref, sz, cfg, params = model
    tokens = jax.random.randint(jax.random.key(7), (2, 40), 0, sz.vocab)
    want = ref.forward(params, tokens, sz)
    sound = float(jnp.max(jnp.abs(program_logits(params, tokens, cfg)
                                  - want)))
    assert sound < TOLERANCE, sound
    changes, reweigh, patch = FAULTS[fault]
    if patch is not None:
        patch(monkeypatch)
    got = program_logits(reweigh(params) if reweigh else params, tokens,
                         dataclasses.replace(cfg, **changes))
    gap = float(jnp.max(jnp.abs(got - want)))
    print(f"{fault}: sound {sound:.2e}, planted {gap:.2e}")
    assert gap > 30 * TOLERANCE, gap


def test_the_shares_of_the_experts_add_up_to_the_whole_layer(model):
    """Four chips that hold experts 0-3, 4-7, 8-11 and 12-15: what the
    program's expert layer gives on each, summed, is what the
    reference's gives with all 16 held; the residual and the router are
    every chip's alike and counted once. One window expert layer, its
    16 experts drawn here."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.parallel.experts import expert_ffn, route

    ref, sz, _, params = model
    run = {k: v[0] for k, v in params["layers"][1].items()}
    key = jax.random.key(3)
    all_experts = {
        name: jax.random.normal(jax.random.fold_in(key, i),
                                (16,) + run[name].shape[1:]) / 4
        for i, name in enumerate(("w_gate", "w_up", "w_down"))}
    h = jax.random.normal(jax.random.fold_in(key, 9), (1, 24, sz.d_model))
    whole = ref._held_experts(
        h, dict(run, **all_experts),
        dataclasses.replace(sz, experts_first=0, experts_held=16), None)
    chosen, weights = route(h[0], run["router"], run["router_bias"], 2)
    parts, rows = [], 0
    for first in (0, 4, 8, 12):
        part, counts = expert_ffn(
            h[0], chosen, weights,
            *(all_experts[n][first:first + 4]
              for n in ("w_gate", "w_up", "w_down")),
            first=first, held=4, tile=4)
        # ... and each is the reference's own share
        share = ref._held_experts(
            h, dict(run, **{n: m[first:first + 4]
                            for n, m in all_experts.items()}),
            dataclasses.replace(sz, experts_first=first), None)
        assert jnp.max(jnp.abs(part - share[0])) < TOLERANCE
        parts.append(part)
        rows += int(counts.sum())
    assert rows == 24 * 2                   # every assignment, once
    assert jnp.max(jnp.abs(sum(parts) - whole[0])) < TOLERANCE
    assert float(jnp.max(jnp.abs(whole))) > 0.1


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


@pytest.mark.parametrize("key,value", [
    ("n_group", 8), ("topk_group", 4), ("n_shared_experts", 1),
    ("tie_word_embeddings", True),
    ("scoring_func", "softmax"), ("hidden_act", "gelu"),
    ("swa_head_dim", 16), ("routed_scaling_factor", 2.5),
    ("experts_held", [14, 4]), ("hybrid_layer_pattern", [0, 1])])
def test_what_the_family_cannot_express_is_refused(family, key, value):
    """Where the published config is mapped, by its published key's
    matter; the program's config is made through the same refusal and
    has no field for what the block lacks."""
    ref = loader.family_module(family, "reference")
    with pytest.raises(ValueError, match="cannot express"):
        ref.sizes_of(dict(TINY, **{key: value}))
    with pytest.raises(ValueError, match="cannot express"):
        loader.family_module(family, "program").program_config(
            dict(TINY, **{key: value}), 64)


def test_no_cut_of_this_model_trains_on_one_chip(family):
    program = loader.family_module(family, "program")
    with pytest.raises(NotImplementedError, match="trains on one chip"):
        program.make_train_step(None, {})


# ---------------------------------------------------------------- costs

SMALL = dict(TINY, torch_dtype="bfloat16")


def test_costs_against_a_hand_worked_count(family):
    costs = loader.family_module(family, "costs")
    # attention of a full layer: q 32*4*12, k and v 32*1*(12+8), o 4*8*32
    full = 1536 + 640 + 1024
    # of a window layer: two K/V heads, and four sinks
    window = 1536 + 1280 + 1024 + 4
    router = 33 * 16                        # the matrix and the bias
    outside = (full + 64 + 3 * 32 * 64      # layer 0: dense, two norms
               + 5 * (window + 64 + router) + (full + 64 + router) + 32)
    assert costs.expert_params(SMALL) == 3 * 32 * 16 == 1536
    assert costs.experts_held(SMALL) == (4, 6)
    assert costs.n_params(SMALL) == 2 * 128 * 32 + outside + 6 * 4 * 1536
    # a prompt of 20: 210 causal pairs in a full layer; in a window
    # layer the first 8 positions see 36 pairs and the other 12 see 8
    assert costs.window_pairs(SMALL, 20, 210) == 36 + 12 * 8 == 132
    # a decode step of 3 rows over 50 positions: a window of 8 a row
    assert costs.window_pairs(SMALL, 3, 50) == 24
    per_pair = 2 * 4 * (12 + 8)
    assert costs.attention_flops(SMALL, 20, 210) == per_pair * (
        2 * 210 + 5 * 132)
    # active parameters of a token: all outside the experts, and of the
    # six layers' experts the expected 2 * 4 / 16 of one
    active = outside + 6 * 1536 * 0.5
    assert costs.forward_flops(SMALL, 20, 210, logit_rows=1) == \
        2 * active * 20 + 2 * 128 * 32 + per_pair * (2 * 210 + 5 * 132)
    # a decode step of 3 rows that attend 50 positions, 10 experts hit:
    # the weights outside the experts, the head, 3 embedding rows and 10
    # experts; K and V of 50 + 3 positions in the two full layers (1
    # head of 20) and of 3 * 8 + 3 in the five window layers (2 heads)
    moved = (outside + 128 * 32 + 3 * 32 + 10 * 1536
             + 2 * 53 * 20 + 5 * 27 * 40)
    assert costs.decode_step_bytes(
        SMALL, 3, 50, {costs.EXPERTS_HIT: 10}) == 2 * moved
    # without the engine's count every held expert is read
    assert costs.decode_step_bytes(SMALL, 3, 50, {}) == 2 * (
        moved + 14 * 1536)
    assert costs.DECODE_PROGRAM == "slot_decode_step"
    calls = costs.prefill_flash_costs(SMALL, 20)
    assert len(calls) == 7
    assert calls[0] == {"flops": per_pair * 210,
                        "bytes": 20 * (4 + 1) * 20 * 2 + 4 * 4 * 20}
    assert calls[1] == {"flops": per_pair * 132,
                        "bytes": 20 * (4 + 2) * 20 * 2 + 4 * 4 * 20}
    with pytest.raises(NotImplementedError):
        costs.train_flops(SMALL, 1, 1)
    with pytest.raises(NotImplementedError):
        costs.flash_shape(SMALL, {})


def test_the_published_cut_holds_what_the_issue_reckoned(family):
    bench = loader.load_benchmark()
    config = loader.load_config(bench, "mimo-v2-flash-ep16-d7")
    costs = loader.family_module(family, "costs")
    assert round(costs.n_params(config) / 1e6, 1) == 4523.6
    assert costs.experts_held(config) == (16, 6)
    # a decode step of 128 rows at a mean context of 1,400: 9.1 GB
    step = costs.decode_step_bytes(config, 128, 128 * 1400, {})
    assert round(step / 1e9, 1) == 9.1
    assert round(costs.forward_flops(config, 128, 128 * 1400, 128)
                 / 1e12, 1) == 0.4
    # what the file keeps of the published config, and what it says of
    # the cut
    assert (config["hidden_size"], config["num_attention_heads"],
            config["head_dim"], config["v_head_dim"]) == (4096, 64, 192, 128)
    assert (config["num_key_value_heads"],
            config["swa_num_key_value_heads"], config["sliding_window"],
            config["intermediate_size"], config["moe_intermediate_size"],
            config["num_experts_per_tok"], config["router_experts"],
            config["vocab_size"]) == (4, 8, 128, 16384, 2048, 8, 256, 152576)
    assert sorted(config["reduced"]) == [
        "hybrid_layer_pattern", "moe_layer_freq", "n_routed_experts",
        "num_hidden_layers"]
    assert config["published"]["n_routed_experts"] == 256
    assert "EP16" in config["stands_for"]


# -------------------------------------------------- the cell's readers

def obs_with(phases, family, steps=10, **more):
    return dict({
        "run": {"config": SMALL, "family": family},
        "device": {"kind": "TPU v5 lite"},
        "decode_before": {"steps": 5, "phases": {}},
        "decode_after": {"steps": 5 + steps, "phases": dict(
            {"serve.engine.wait": [steps, 0.1]}, **phases)}}, **more)


def test_the_expert_counters_read_as_a_steps_means(family):
    obs = obs_with({"serve.engine.experts_hit": [10, 120.0],
                    "serve.engine.expert_rows": [10, 300.0],
                    "serve.engine.expert_rows_max": [10, 90.0]}, family)
    # 12 of the 4 x 6 held experts a step; the fullest expert's 9 rows
    # over the mean's 30 / 4 = 7.5 over the layers
    assert inside_serve.experts_hit_pct(obs) == pytest.approx(50.0)
    assert inside_serve.expert_rows_max_over_mean(obs) == pytest.approx(1.2)
    # a program that keeps no such counter (the parent) reads as nothing
    assert inside_serve.experts_hit_pct(obs_with({}, family)) is None
    assert inside_serve.expert_rows_max_over_mean(
        obs_with({}, family)) is None


def test_the_prefill_kernels_roofline_prices_the_prefills_in_the_slice(
        family):
    costs = loader.family_module(family, "costs")
    chip = peaks.peaks_of("TPU v5 lite")
    least = {n: sum(peaks.roofline_seconds(c, chip)["seconds"]
                    for c in costs.prefill_flash_costs(SMALL, n))
             for n in (20, 40)}
    obs = obs_with({}, family, prefills=[
        [9.0, 9.5, 40],         # before the slice
        [10.1, 10.2, 20],       # inside
        [10.9, 11.1, 40]],      # half inside
        trace={"window_s": 1.0, "slice": [10.0, 11.0], "op_totals": {
            "slot_prefill/flash_fwd.3": [2e-6, 7],
            "slot_prefill/flash_fwd.9": [1e-6, 7],
            "slot_decode_step/fusion.1": [5.0, 3],
            "local_step/flash_fwd.1": [7.0, 1]}})
    assert inside_serve.prefill_flash_roofline_pct(obs) == pytest.approx(
        100.0 * (least[20] + 0.5 * least[40]) / 3e-6)
    obs["trace"]["op_totals"] = {"slot_decode_step/fusion.1": [5.0, 3]}
    assert inside_serve.prefill_flash_roofline_pct(obs) is None
    obs["trace"] = None
    assert inside_serve.prefill_flash_roofline_pct(obs) is None


# ----------------------------------- such a cell through the front door

TINY_MIX = dict(MIXES["tiny-closed"], clients=5, slots=3, slot_len=64,
                prompt_lengths=[6, 16], prompt_weights=[0.5, 0.5],
                output_tokens={"min": 10, "max": 20},
                limits={"served_logit_gap": TOLERANCE, "answers_wrong": 0})


def tiny_cell(root: str) -> dict:
    """The real ``BENCHMARK.json`` with the cell's configuration and mix
    replaced by tiny ones under a path of its own: every entry, reader
    and family file is the repo's."""
    shutil.copytree(os.path.join(loader.ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for sub, name, data in (("configs", "mimo-v2-flash-ep16-d7", TINY),
                            ("workloads", "reason-closed", TINY_MIX)):
        with open(os.path.join(root, "benchmarks", sub, name + ".json"),
                  "w") as f:
            json.dump(data, f)
    return copy.deepcopy(dict(loader.load_benchmark(), root=root))


def test_the_benchmark_with_the_cell_keeps_the_contract():
    bench = loader.load_benchmark()
    keeps_the_contract(bench)
    every_cell_reports_what_the_contract_asks(bench)
    cell = loader.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mimo-v2-flash-ep16-d7", "reason-closed", 1)
    mix = loader.load_traffic(bench, cell["traffic"])
    assert (mix["clients"], mix["slots"], mix["slot_len"], mix["cycle"],
            mix["prompt_lengths"], mix["prompt_weights"],
            mix["output_tokens"]) == (
        160, 128, 3200, 96, [512, 1024, 2048], [0.5, 0.3, 0.2],
        {"min": 512, "max": 1024})
    # the longest request fits a slot
    assert max(mix["prompt_lengths"]) + mix["output_tokens"]["max"] \
        <= mix["slot_len"]
    assert [m["name"] for m in loader.cell_metrics(bench, CELL, False)] == [
        "serve_tokens_per_s", "setup_s"]
    assert {m["name"] for m in loader.cell_metrics(bench, CELL, True)} == {
        name + ".reason" for name in (
            "serve_mfu_pct", "decode_roofline_pct", "device_idle_pct",
            "decode_occupancy_pct", "decode_device_wait_ms",
            "decode_host_ms", "decode_slot_reads_ms",
            "scheduler_overhead_ms", "prefill_stall_pct",
            "experts_hit_pct", "expert_rows_max_over_mean")}
    # the slice is the issue's 3 s, which start a quarter into the
    # window, before any request of this mix can finish: it holds no
    # prefill, so the prefill kernel's roofline has nothing to read and
    # its entry waits beside its reader (PERF.md section 7)
    assert mix["trace_seconds"] == 3.0
    with open(os.path.join(loader.ROOT, "benchmarks", "put_off",
                           "reason-closed.json")) as f:
        (waiting,) = json.load(f)["per_layer"]
    assert waiting["name"] == "flash_fwd_roofline_pct.reason"
    assert waiting["workloads"] == [CELL]
    assert waiting["name"] not in {m["name"] for m in bench["per_layer"]}
    grown = copy.deepcopy(bench)
    grown["per_layer"].append(waiting)
    keeps_the_contract(grown)
    assert loader.load_reader(grown, waiting["name"]) \
        is inside_serve.prefill_flash_roofline_pct


def test_such_a_cell_is_served_through_the_front_door(tmp_path,
                                                      cpu_tpu_workers):
    bench = tiny_cell(str(tmp_path / "tiny_mimo"))
    line = run.run_cell(bench, CELL, seed=2**31 + 29, seconds=3.0,
                        trace=True, platform="cpu", lm_class=TracedOnCpuLM)
    assert line["correct"], line["faults"]
    check_line(bench, CELL, line, True)
    got = line["metrics"]
    # the engine's counts came with the tokens and were read as means
    assert 0.0 < got["experts_hit_pct.reason"]["value"] <= 100.0
    assert got["expert_rows_max_over_mean.reason"]["value"] >= 1.0
    assert got["decode_occupancy_pct.reason"]["value"] > 0.0
    assert got["serve_mfu_pct.reason"]["value"] > 0.0
    # the counts are taken out of the fetched row on the host: the read
    # phase stays far under a millisecond a step
    assert 0.0 < got["decode_slot_reads_ms.reason"]["value"] < 1.0
    # the rooflines' time is the device's operations by name: the CPU's
    # stand-in plane names none
    assert "decode_roofline_pct.reason" not in got
    assert "flash_fwd_roofline_pct.reason" not in got
