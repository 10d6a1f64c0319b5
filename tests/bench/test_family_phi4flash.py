"""The ``phi4flash`` family beside the harness: the configuration file
against the catalog row, the costs against the parameters the seeded
weights really hold and against what the issue reckoned, the program
(a prefill in two stages, then decode through the slot cache, rows
admitted and left out mid-batch, the prompt longer than the window)
against the family's plain reference, which runs every layer at every
position, with planted faults that each fail where the program passes,
what the family cannot express refused, the cell's entries and its mix
letter for letter, the two readers this cell adds on hand-built
observations, and one run of such a cell through the front door on the
CPU.

The block itself (the period rule of ``layer_runs``, the refusals,
``forward`` against the two serving programs, the table of a decode
step's parts) is held in tests/test_shared_cache.py.
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

from benchmarks import inside_attend, loader, peaks, reference, run, traffic

cloudpickle.register_pickle_by_value(sys.modules[__name__])

CELL = "phi-4-mini-flash.session-closed"
CONFIG = "phi-4-mini-flash"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# eight layers: (Mamba, window) x 2, (Mamba with the memory, full) x 1,
# (gated memory unit, cross) x 1; a window of 8
TINY = {"model_type": "phi4flash", "embd_pdrop": 0, "hidden_act": "silu",
        "hidden_size": 32, "intermediate_size": 64, "layer_norm_eps": 1e-5,
        "max_position_embeddings": 64, "mb_per_layer": 2,
        "num_attention_heads": 4, "num_hidden_layers": 8,
        "num_key_value_heads": 2, "resid_pdrop": 0, "sliding_window": 8,
        "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False, "vocab_size": 128, "torch_dtype": "float32",
        "assumed_sizes": {"mamba_d_state": 4, "mamba_d_conv": 4,
                          "mamba_expand": 2, "mamba_dt_rank": 2}}
# twelve: three periods of the self-decoder and two of the cross-decoder,
# so that both scans loop
TWELVE = dict(TINY, num_hidden_layers=12)
# float32 on the CPU, rounding alone: the sound program reads under 3e-6
# against the reference at every position; every planted fault reads
# more than a hundred times the tolerance
TOLERANCE = 2e-5


@pytest.fixture(scope="module")
def family():
    return loader.find_family(loader.load_benchmark(), TINY)


def model_of(family, config):
    import jax

    ref = loader.family_module(family, "reference")
    sz = ref.sizes_of(config)
    cfg = loader.family_module(family, "program").program_config(config, 64)
    # matrices six times as large: a softmax that is not flat; and the
    # Mamba layers' x projection thirty times as large again: with no
    # norms on dt, B and C (Jamba has them) these are small under
    # normal(0.02) weights, and a state that hardly reaches y would hide
    # what is done to it
    params = jax.tree.map(lambda a: a * 6 if a.ndim >= 3 else a,
                          ref.seeded_params(2**31 + 5, sz))
    params["layers"] = tuple(
        tuple(dict(stack, w_x=stack["w_x"] * 30) if "w_x" in stack else stack
              for stack in pair) for pair in params["layers"])
    return ref, sz, cfg, params


@pytest.fixture(scope="module")
def model(family):
    return model_of(family, TWELVE)


# ------------------------------------------------ the configuration file

def test_the_configuration_is_the_catalog_row_whole(family):
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Phi-4-mini-flash-reasoning")
    bench = loader.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    config = loader.load_config(bench, CONFIG)
    assert entry["source"] == config["source"] == row["source_url"]
    assert entry["reduced"] == config["reduced"] == []
    assert len(row["config"]) == 17
    assert {k: config[k] for k in row["config"]} == row["config"]
    assert config["sliding_window"] == 512
    assert len(entry["why"]) <= 200
    # what the row does not say is said under ``assumed``
    assert {"torch_dtype", "state_dtype", "assumed_sizes", "layer_rule",
            "sliding_window", "mamba", "gated_memory_unit", "attention",
            "differential", "norms", "feed_forward", "layout", "prefill",
            "initializer"} <= set(config["assumed"])
    assert config["assumed_sizes"] == {
        "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
        "mamba_dt_rank": 160}
    assert "nothing is cut and nothing is sharded" in config["stands_for"]
    assert "3,852.6 M (7.71 GB" in config["parameters"]
    ref = loader.family_module(family, "reference")
    sz = ref.sizes_of(config)
    assert (sz.n_layers, sz.n_heads, sz.kv_heads, sz.head_dim, sz.d_model,
            sz.d_ff, sz.vocab, sz.window, sz.ssm_inner, sz.ssm_state,
            sz.ssm_dt_rank, sz.ssm_conv, sz.eps, sz.dtype) == (
        32, 40, 20, 64, 2560, 10240, 200064, 512, 5120, 16, 160, 4, 1e-5,
        "bfloat16")
    M, W, F, G, X = ref.MAMBA, ref.WINDOW, ref.FULL, ref.GMU, ref.CROSS
    assert sz.mixers == (M, W) * 8 + (M, F) + (G, X) * 7
    assert ref.runs_of(sz.mixers) == (((M, W), 8), ((M, F), 1), ((G, X), 7))


def test_the_published_model_holds_what_the_issue_reckoned(family):
    config = loader.load_config(loader.load_benchmark(), CONFIG)
    costs = loader.family_module(family, "costs")
    assert costs.layer_counts(config) == {
        "mamba": 9, "gmu": 7, "window": 8, "full": 1, "cross": 7}
    assert round(costs.n_params(config) / 1e6, 1) == 3852.6
    assert round(32 * costs._ffn_params(config) / 1e6) == 2517
    assert round(9 * costs.mamba_params(config) / 1e6) == 371
    assert round(7 * costs.gmu_params(config) / 1e6) in (183, 184)
    assert round(9 * costs.attention_params(config) / 1e6) == 177
    assert round(7 * costs.cross_params(config) / 1e6) == 92
    # a position of the one growing cache: 20 + 20 heads of 64
    assert costs.kv_row_bytes(config) == 5120
    # a slot of 6,144 rows: the full layer 31.5 MB, eight rings 21.0 MB,
    # nine states and tails 3.2 MB
    assert round(6144 * 5120 / 1e6, 1) == 31.5
    assert round(9 * costs.slot_state_bytes(config) / 1e6, 1) == 3.2
    # a decode step of 64 rows at a mean position of 2,850: 17 GB, the
    # shared cache's eight reads 44 % of it
    step = costs.decode_step_bytes(config, 64, 64 * 2850, {})
    assert round(step / 1e9, 1) == 16.9
    assert round(100 * 8 * 64 * 2850 * 5120 / step) == 44
    # a prompt position costs half of what a decoded one does: the
    # prefill stops at the cross-decoder
    prefill = costs.forward_flops(config, 4096, 4096 * 4097 // 2, 1) / 4096
    decoded = costs.forward_flops(config, 64, 64 * 2850, 64) / 64
    assert round(prefill / 1e9, 1) == 3.8 and round(decoded / 1e9, 1) == 8.1
    # ... priced apart: one more logit row adds the cross-decoder and the
    # head at one position and the prompt's length of pairs a reader
    one_more = (costs.forward_flops(config, 4096, 4096 * 4097 // 2, 2)
                - costs.forward_flops(config, 4096, 4096 * 4097 // 2, 1))
    assert 3.8e9 < one_more - 8 * costs.pair_flops(config) * 4096 < 4.1e9
    # the kernels' calls: a scan a Mamba layer of the self-decoder and the
    # layer behind it, a flash call a window layer, an attend call a reader
    assert len(costs.prefill_scan_costs(config, 1024)) == 9
    assert len(costs.prefill_flash_costs(config, 1024)) == 8
    calls = costs.decode_attend_costs(config, 64, 64 * 2850)
    assert len(calls) == 8
    assert calls[0]["bytes"] == pytest.approx(64 * 2850 * 5120, rel=0.002)


@pytest.mark.parametrize("config", [TINY, TWELVE],
                         ids=["eight-layers", "twelve-layers"])
def test_the_parameters_counted_are_the_parameters_held(family, config):
    """``costs.n_params`` against the leaves ``seeded_params`` makes,
    which are the ones the program runs on (its own ``init_params``
    makes the same tree)."""
    import jax

    from ray_tpu.models import init_params

    ref, sz, cfg, params = model_of(family, config)
    costs = loader.family_module(family, "costs")
    held = sum(leaf.size for leaf in jax.tree.leaves(params))
    assert costs.n_params(config) == held
    own = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), own) == jax.tree.map(
        lambda a: (a.shape, a.dtype), params)
    # the file's own count is the published model's
    published = loader.load_config(loader.load_benchmark(), CONFIG)
    assert f"{costs.n_params(published) / 1e6:,.1f} M" in \
        published["parameters"]


def test_no_cut_of_this_model_trains_on_one_chip(family):
    program = loader.family_module(family, "program")
    with pytest.raises(NotImplementedError, match="14.3 GB"):
        program.make_train_step(None, {})
    costs = loader.family_module(family, "costs")
    with pytest.raises(NotImplementedError):
        costs.train_flops(TINY, 1, 8)


@pytest.mark.parametrize("key,value", [
    ("hidden_act", "gelu"), ("mlp_bias", True), ("lm_head_bias", True),
    ("tie_word_embeddings", False), ("mb_per_layer", 4),
    ("num_key_value_heads", 1), ("num_attention_heads", 6),
    ("num_hidden_layers", 10), ("num_hidden_layers", 4),
    ("sliding_window", None), ("resid_pdrop", 0.1)])
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
    [1, T]: a prefill of the first ``prompt_len``, then a decode step a
    token, in slot 1 of 3. ``spoil(cache)`` runs between the steps."""
    import jax.numpy as jnp

    from ray_tpu.models import decode

    cache = decode.init_slot_cache(cfg, 3, 64)
    logits, cache = decode.slot_prefill(params, tokens[:, :prompt_len],
                                        cache, jnp.int32(1), cfg)
    got, only = [logits], jnp.arange(3) == 1
    for t in range(prompt_len, tokens.shape[1]):
        if spoil:
            cache = spoil(cache)
        logits, cache = decode.slot_decode_step(
            params, cache, jnp.where(only, tokens[0, t], 0), only, cfg)
        got.append(logits[1:2])
    return jnp.stack(got, axis=1)


def gap(a, b) -> float:
    import jax.numpy as jnp

    return float(jnp.max(jnp.abs(a - b)))


def sequence(sz, length=44, key=3):
    import jax

    return jax.random.randint(jax.random.key(key), (1, length), 0, sz.vocab)


def test_prefill_then_decode_reads_the_references_logits(model):
    """Logits, not tokens, at every served position: a prompt of 20, more
    than twice the window, then 24 decode steps, which pass the window's
    ring once more; slot 1 of 3, the others idle."""
    ref, sz, cfg, params = model
    tokens = sequence(sz)
    want = ref.forward(params, tokens, sz)[:, 19:]
    assert gap(served_logits(params, cfg, tokens, 20), want) < TOLERANCE


def test_rows_admitted_and_left_out_mid_batch_keep_their_logits(model):
    """Two sequences in a cache of three slots: the second is admitted
    (prefilled) while the first decodes, the first is left out of four
    steps while the second goes on, then both step together. Every
    logit either row gives is the reference's at that position of its
    own sequence, and a row left out keeps every state bit for bit."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import decode

    ref, sz, cfg, params = model
    a, b = sequence(sz, 40, key=5), sequence(sz, 36, key=6)
    want_a, want_b = (ref.forward(params, t, sz)[0] for t in (a, b))
    cache = decode.init_slot_cache(cfg, 3, 64)
    at = {0: 18, 2: None}      # the next position each slot is fed
    logits, cache = decode.slot_prefill(params, a[:, :18], cache,
                                        jnp.int32(0), cfg)
    worst = gap(logits[0], want_a[17])

    def step(cache, slots):
        feed = jnp.asarray([a[0, at[0]] if 0 in slots else 0, 0,
                            b[0, at[2]] if 2 in slots else 0], jnp.int32)
        active = jnp.asarray([0 in slots, False, 2 in slots])
        logits, cache = decode.slot_decode_step(params, cache, feed, active,
                                                cfg)
        gaps = [gap(logits[s], (want_a if s == 0 else want_b)[at[s]])
                for s in slots]
        for s in slots:
            at[s] += 1
        return cache, max(gaps)

    for _ in range(5):
        cache, g = step(cache, (0,))
        worst = max(worst, g)
    logits, cache = decode.slot_prefill(params, b[:, :12], cache,
                                        jnp.int32(2), cfg)
    worst, at[2] = max(worst, gap(logits[0], want_b[11])), 12

    def of_slot_0(cache):
        return jax.tree.map(lambda t: t[:, 0],
                            {name: cache[name] for name in ("ssm", "k")})

    held = of_slot_0(cache)
    for _ in range(4):
        cache, g = step(cache, (2,))
        worst = max(worst, g)
    after = of_slot_0(cache)
    # slot 0 sat the four steps out: its scan states bit for bit, and of
    # its K/V every position it had written
    for was, now in zip(jax.tree.leaves(held["ssm"]),
                        jax.tree.leaves(after["ssm"])):
        assert bool(jnp.all(was == now))
    full_k_was, full_k_now = held["k"][1][1], after["k"][1][1]
    assert bool(jnp.all(full_k_was[:, :23] == full_k_now[:, :23]))
    for _ in range(12):
        cache, g = step(cache, (0, 2))
        worst = max(worst, g)
    assert at == {0: 35, 2: 28}
    assert worst < TOLERANCE


def faulty(cfg, k: int):
    """``cfg`` as another key of the jitted programs' caches, so that a
    program traced under a planted fault is never another test's."""
    return dataclasses.replace(cfg, max_seq=cfg.max_seq + k)


def test_the_second_softmax_dropped_fails(model, monkeypatch):
    """lambda = 0: plain attention over the doubled value head under
    differential attention's name."""
    from ray_tpu.models import transformer

    ref, sz, cfg, params = model
    tokens = sequence(sz)
    want = ref.forward(params, tokens, sz)[:, 19:]

    def first_alone(o, lp, eps):
        import jax.numpy as jnp

        (H, W), dtype = o.shape[-2:], o.dtype
        o = o.astype(jnp.float32).reshape(o.shape[:-2] + (H // 2, 2, W))
        return (transformer.rmsnorm(o[..., 0, :], lp["sub_norm"], eps=eps)
                * (1.0 - transformer.lambda_init(lp["depth"]))).astype(dtype)

    monkeypatch.setattr(transformer, "_differ", first_alone)
    assert gap(served_logits(params, faulty(cfg, 1), tokens, 20),
               want) > 100 * TOLERANCE


def test_a_cross_layer_with_a_cache_of_its_own_fails(family, model):
    """The cross layers as full-attention layers that project K and V of
    their own (and so keep a cache of their own): everything else as it
    was, their new matrices drawn as the others are."""
    import jax

    ref, sz, cfg, params = model
    program = loader.family_module(family, "program")
    own = dataclasses.replace(cfg, layer_kinds=tuple(
        ("full" if mixer == "cross" else mixer, ffn)
        for mixer, ffn in cfg.layer_kinds))
    assert program.program_config(TWELVE, 64) == cfg
    gmu, cross = params["layers"][2]
    like = params["layers"][1][1]       # the full layer's stack
    keys = jax.random.split(jax.random.key(9), 4)
    grown = dict(cross, **{
        name: 6 * 0.02 * jax.random.normal(kk, (2,) + like[name].shape[1:])
        * (1 if name.startswith("w") else 1 / 6)
        for name, kk in zip(("wk", "wv", "bk", "bv"), keys)})
    spoilt = dict(params, layers=params["layers"][:2] + ((gmu, grown),))
    tokens = sequence(sz)
    want = ref.forward(params, tokens, sz)[:, 19:]
    assert gap(served_logits(spoilt, own, tokens, 20),
               want) > 100 * TOLERANCE


def test_the_memory_taken_after_the_gate_fails(model, monkeypatch):
    from ray_tpu.models import transformer

    ref, sz, cfg, params = model
    tokens = sequence(sz)
    want = ref.forward(params, tokens, sz)[:, 19:]
    mixer = transformer.mamba_mixer

    def gated_memory(lp, h, recur, cfg):
        import jax
        import jax.numpy as jnp

        out, (tail, state, y) = mixer(lp, h, recur, cfg)
        z = (h @ lp["w_in"])[..., cfg.ssm_inner:]
        return out, (tail, state, (y.astype(jnp.float32) * jax.nn.silu(
            z.astype(jnp.float32))).astype(y.dtype))

    monkeypatch.setattr(transformer, "mamba_mixer", gated_memory)
    assert gap(served_logits(params, faulty(cfg, 2), tokens, 20),
               want) > 100 * TOLERANCE


@pytest.mark.parametrize("window", [7, 9])
def test_the_window_off_by_one_fails(model, window):
    ref, sz, cfg, params = model
    tokens = sequence(sz)
    want = ref.forward(params, tokens, sz)[:, 19:]
    off = dataclasses.replace(cfg, window=window)
    assert gap(served_logits(params, off, tokens, 20),
               want) > 100 * TOLERANCE


def test_the_scans_state_in_bfloat16_fails(model):
    """The state in the precision below the one the configuration
    states for it, rounded between the steps."""
    import jax
    import jax.numpy as jnp

    ref, sz, cfg, params = model
    tokens = sequence(sz)
    want = ref.forward(params, tokens, sz)[:, 19:]

    def rounded(cache):
        return dict(cache, ssm=jax.tree.map(
            lambda s: s.astype(jnp.bfloat16).astype(jnp.float32),
            cache["ssm"]))

    assert gap(served_logits(params, cfg, tokens, 20, rounded),
               want) > 100 * TOLERANCE


def test_the_second_stage_on_another_position_fails(model, monkeypatch):
    """The prefill's second stage run from the position before the
    last: the first token's logits are another position's (the decode
    steps behind it are sound: the cache is)."""
    from ray_tpu.models import decode, transformer

    ref, sz, cfg, params = model
    tokens = sequence(sz)
    want = ref.forward(params, tokens, sz)[:, 19:]

    def before_the_last(t):
        return t[:, -2:-1] if t.shape[1] > 1 else t

    monkeypatch.setattr(transformer, "last_position", before_the_last)
    monkeypatch.setattr(decode, "last_position", before_the_last)
    got = served_logits(params, faulty(cfg, 3), tokens, 20)
    assert gap(got[:, :1], want[:, :1]) > 100 * TOLERANCE
    assert gap(got[:, 1:], want[:, 1:]) < TOLERANCE


def test_the_int8_control_fails_where_the_program_passes(model):
    """The harness's own comparison, through ``JaxSlotEngine``: the
    engine's greedy tokens lie within the tolerance of the reference's
    best logit; the int8 control's do not, and neither does an altered
    token."""
    import jax.numpy as jnp

    from ray_tpu import serve
    from ray_tpu.models import decode

    ref, sz, cfg, params = model
    prompt = traffic.prompt_tokens(3, 0, 20, sz.vocab)
    engine = serve.JaxSlotEngine(params, cfg, slots=2, max_len=64)
    served = [engine.prefill(1, prompt)]
    while len(served) < 40:
        out = engine.step({1: served[-1]})
        served.extend(out.values())
    assert served == decode.generate(
        params, jnp.asarray([prompt], jnp.int32), cfg, steps=40,
        max_len=64)[0].tolist()
    gaps = reference.served_logit_gaps(ref, params, prompt, served, sz,
                                       quant="int8", pad_to=16)
    assert len(gaps["served"]) == len(gaps["control"]) == 40
    assert max(gaps["served"]) < TOLERANCE
    assert max(gaps["control"]) > 30 * TOLERANCE
    wrong = list(served)
    wrong[7] = (wrong[7] + 1) % sz.vocab
    assert max(reference.served_logit_gaps(
        ref, params, prompt, wrong, sz, pad_to=16)["served"]) > 30 * TOLERANCE


def test_the_leaves_are_named_by_layer(model):
    ref, sz, _, params = model
    leaves = ref.by_leaf(params)
    assert {"embed", "final_norm", "final_norm_b", "w_in.0", "wk.1",
            "a_log.6", "wk.7", "lambda_q1.7", "w_mem.8", "wq.9", "bo.11",
            "sub_norm.11", "w_down.11"} <= set(leaves)
    assert not {"wk.9", "w_in.8", "w_mem.6", "wq.12"} & set(leaves)
    assert leaves["w_mem.10"].shape == (32, 64)
    assert leaves["lambda_k2.5"].shape == (8,)


# -------------------------------------------------- the cell's readers

SMALL = dict(TINY, torch_dtype="bfloat16")


def obs_with(phases, family, steps=10, before=None, **more):
    return dict({
        "run": {"config": SMALL, "family": family},
        "device": {"kind": "TPU v5 lite"},
        "decode_before": {"steps": 5, "slot_steps": 40,
                          "phases": before or {}},
        "decode_after": {"steps": 5 + steps, "slot_steps": 40 + 3 * steps,
                         "phases": dict(
            {"serve.engine.wait": [steps, 0.1]}, **phases)}}, **more)


def test_the_attend_kernels_roofline_prices_rows_and_positions(family):
    costs = loader.family_module(family, "costs")
    bench = loader.load_benchmark()
    read = loader.load_reader(bench, "decode_attend_roofline_pct.session")
    assert read is inside_attend.decode_attend_roofline_pct
    chip = peaks.peaks_of("TPU v5 lite")
    obs = obs_with({}, family, trace={
        "window_s": 1.0, "slice": [10.0, 11.0], "program_seconds": {
            "slot_decode_step": 0.5},
        "op_totals": {"slot_decode_step/decode_attend.3": [3e-6, 2],
                      "slot_decode_step/decode_attend.7": [1e-6, 2],
                      "slot_prefill/decode_attend.9": [9.0, 2],
                      "slot_decode_step/fusion.1": [5.0, 3]}})
    # host spans of the engine's steps: [t0, t1, rows, positions]
    obs["steps"] = [[10.0, 10.1, 3, 60], [10.5, 10.6, 5, 200],
                    [11.5, 11.6, 7, 900]]
    # two steps begun in the slice, two readers each (the full layer and
    # the one cross layer of eight layers): four calls in the trace at
    # the mean of the two steps' prices
    assert len(costs.decode_attend_costs(SMALL, 3, 60)) == 2
    least = sum(peaks.roofline_seconds(call, chip)["seconds"]
                for rows, positions in ((3, 60), (5, 200))
                for call in costs.decode_attend_costs(SMALL, rows,
                                                      positions)) / 4
    assert read(obs) == pytest.approx(100.0 * 4 * least / 4e-6)
    # more positions, more bytes: the price follows what the rows attend
    far = copy.deepcopy(obs)
    far["steps"][0][3] = 6000
    assert read(far) > read(obs)
    # no such kernel in the trace (the parent's), no step begun in the
    # slice, a family that prices no such call, no trace: nothing to read
    none = copy.deepcopy(obs)
    none["trace"]["op_totals"] = {"slot_decode_step/fusion.1": [5.0, 3]}
    assert read(none) is None
    none = dict(obs, steps=[[12.0, 12.1, 3, 60]])
    assert read(none) is None
    other = loader.find_family(bench, {"model_type": "jamba"})
    jamba = loader.load_config(bench, "jamba2-3b")
    assert read(dict(obs, run={"config": jamba, "family": other})) is None
    assert read(dict(obs, trace=None)) is None


def test_the_cross_decoders_share_of_the_prefilled_positions(family):
    bench = loader.load_benchmark()
    read = loader.load_reader(bench, "prefill_cross_rows_pct.session")
    assert read is inside_attend.prefill_cross_rows_pct
    obs = obs_with({"serve.engine.prefill_cross_rows": [14, 14.0],
                    "serve.engine.prefill_tokens": [14, 30000.0]}, family,
                   before={"serve.engine.prefill_cross_rows": [2, 2.0],
                           "serve.engine.prefill_tokens": [2, 6000.0]})
    # twelve prefills of 24,000 positions, the cross-decoder on one each
    assert read(obs) == pytest.approx(100.0 * 12 / 24000)
    # a program that lost the second stage counts every position
    lost = obs_with({"serve.engine.prefill_cross_rows": [12, 24000.0],
                     "serve.engine.prefill_tokens": [12, 24000.0]}, family)
    assert read(lost) == pytest.approx(100.0)
    # a program that keeps no such count (the parent) reads as nothing
    assert read(obs_with({"serve.engine.prefill_tokens": [12, 24000.0]},
                         family)) is None
    assert read(obs_with({"serve.engine.prefill_cross_rows": [0, 0.0]},
                         family)) is None


# ----------------------------------------------------- the cell's entries

TWINS = ("serve_mfu_pct", "decode_roofline_pct", "device_idle_pct",
         "decode_occupancy_pct", "decode_device_wait_ms", "decode_host_ms",
         "decode_slot_reads_ms", "scheduler_overhead_ms",
         "prefill_stall_pct", "prefill_tokens_per_s")
EARLIER = ["ouro-2.6b.decode-closed", "ouro-2.6b-d12.train-2k",
           "mimo-v2-flash-ep16-d7.reason-closed", "jamba2-3b.rollout-closed",
           "brumby-14b-d8.longdoc-closed"]


def test_the_benchmark_with_the_cell_keeps_the_contract():
    bench = loader.load_benchmark()
    keeps_the_contract(bench)
    every_cell_reports_what_the_contract_asks(bench)
    cell = loader.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "session-closed", 1)
    mix = loader.load_traffic(bench, cell["traffic"])
    assert (mix["kind"], mix["loop"], mix["cycle"], mix["pattern_seed"],
            mix["prompt_lengths"], mix["prompt_weights"],
            mix["output_tokens"], mix["check_requests"],
            mix["trace_seconds"], mix["slot_len"]) == (
        "serve", "closed", 80, 0, [1024, 2048, 4096], [0.3, 0.4, 0.3],
        {"min": 256, "max": 1536}, 4, 3.0, 6144)
    # the issue's size, or its stated fall-back: nothing else changes
    assert (mix["clients"], mix["slots"]) in ((80, 64), (60, 48))
    assert mix["limits"]["answers_wrong"] == 0
    assert set(mix["limits"]) == {"served_logit_gap", "answers_wrong"}
    assert "my chip runs, PR 43" in mix["limits_from"]
    # the longest request and the step in flight fit the slot; every
    # prompt takes both prefill kernels and fills the window's ring
    assert max(mix["prompt_lengths"]) + mix["output_tokens"]["max"] + 1 \
        < mix["slot_len"]
    assert all(n % 512 == 0 for n in mix["prompt_lengths"])
    cycle = traffic.cycle_of(mix)
    assert sum(r["prompt_len"] for r in cycle) / len(cycle) == \
        pytest.approx(2355, abs=1)
    assert sum(r["max_tokens"] for r in cycle) / len(cycle) == \
        pytest.approx(896, abs=1)
    assert [m["name"] for m in loader.cell_metrics(bench, CELL, False)] == [
        "serve_tokens_per_s", "setup_s"]
    own = [name + ".session" for name in TWINS + (
        "ssm_scan_roofline_pct", "flash_fwd_roofline_pct",
        "decode_attend_roofline_pct", "prefill_cross_rows_pct")]
    assert [m["name"] for m in loader.cell_metrics(bench, CELL, True)] == own
    # each lists this cell alone, under its twin's layer and unit
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in TWINS + ("ssm_scan_roofline_pct",):
        twin, mine = by_name[name + ".rollout"], by_name[name + ".session"]
        assert mine == dict(twin, name=mine["name"], workloads=[CELL])
        assert loader.load_reader(bench, mine["name"]) is loader.load_reader(
            bench, twin["name"])
    kernel = {"unit": "%", "better": "higher", "source": "device_trace",
              "layer": "kernels: ops/attention.py",
              "moves": "serve_tokens_per_s", "workloads": [CELL]}
    for name in ("flash_fwd_roofline_pct.session",
                 "decode_attend_roofline_pct.session"):
        assert by_name[name] == dict(kernel, name=name)
    assert loader.load_reader(bench, "flash_fwd_roofline_pct.session") \
        is loader.load_reader(bench, "flash_fwd_roofline_pct.reason")
    assert by_name["prefill_cross_rows_pct.session"] == {
        "name": "prefill_cross_rows_pct.session", "unit": "%",
        "better": "lower", "source": "program_counter",
        "layer": "engine: JaxSlotEngine, models/decode.py",
        "moves": "serve_tokens_per_s", "workloads": [CELL]}
    # what was there is as it was: the earlier cells are a prefix of the
    # cells and, of the one list that grew, of that list (a later cell
    # grows both again: nothing here pins a length)
    cells = [w["name"] for w in bench["workloads"]]
    assert cells[:6] == EARLIER + [CELL]
    serve = next(m for m in bench["end_to_end"]
                 if m["name"] == "serve_tokens_per_s")
    assert serve["workloads"][:5] == [
        c for c in EARLIER if "train" not in c] + [CELL]
    assert [c["name"] for c in bench["configs"]][:6] == [
        "ouro-2.6b", "ouro-2.6b-d12", "mimo-v2-flash-ep16-d7", "jamba2-3b",
        "brumby-14b-d8", CONFIG]
    # every file the cell is made of lies where the loader looks
    family = loader.find_family(bench, loader.load_config(bench, CONFIG))
    assert family["model_type"] == "phi4flash"
    with open(os.path.join(family["dir"], "reference.py")) as f:
        assert not [line for line in f if "ray_tpu" in line
                    and line.lstrip().startswith(("import ", "from "))]


def test_the_steps_parts_wait_beside_the_others_that_wait(family):
    """The by-part entries of this cell are not in ``BENCHMARK.json``:
    they wait in ``put_off/`` as PR 38's fourteen do, each with its
    reader in place, which reads a hand-built table and trace."""
    bench = loader.load_benchmark()
    with open(os.path.join(loader.ROOT, "benchmarks", "put_off",
                           "session-parts.json")) as f:
        waiting = json.load(f)["per_layer"]
    names = [m["name"] for m in waiting]
    assert {"decode_cross_attention_ms.session",
            "decode_gmu_ms.session"} <= set(names)
    assert not set(names) & {m["name"] for m in bench["per_layer"]}
    assert all(m["workloads"] == [CELL] and m["moves"] ==
               "serve_tokens_per_s" for m in waiting)
    obs = obs_with({}, family, trace={
        "window_s": 1.0, "slice": [10.0, 11.0], "program_seconds": {
            "slot_decode_step": 0.5},
        "op_totals": {"slot_decode_step/decode_attend.7": [0.04, 14],
                      "slot_decode_step/decode_attend.3": [0.006, 2],
                      "slot_decode_step/fusion.9": [0.02, 14],
                      "slot_decode_step/fusion.1": [0.1, 3]}})
    obs["steps"] = [[10.0, 10.1, 3, 60], [10.5, 10.6, 5, 200]]
    obs["decode_after"]["parts"] = {"slot_decode_step": {
        "decode_attend.7": ["run2", "cross_attention"],
        "decode_attend.3": ["run1", "full_attention"],
        "fusion.9": ["run2", "gmu"], "fusion.1": [None, None]}}
    read = {name: loader.load_reader(bench, name) for name in names}
    assert read["decode_cross_attention_ms.session"](obs) == \
        pytest.approx(20.0)
    assert read["decode_attention_ms.session"](obs) == pytest.approx(3.0)
    assert read["decode_gmu_ms.session"](obs) == pytest.approx(10.0)
    assert read["decode_mlp_ms.session"](obs) == 0.0
    assert read["decode_unscoped_pct.session"](obs) == pytest.approx(
        100.0 * (0.5 - 0.066) / 0.5)
    # a program that hands out no table (the parent): nothing to read
    del obs["decode_after"]["parts"]
    assert all(reader(obs) is None for reader in read.values())


# ----------------------------------- such a cell through the front door

TINY_MIX = dict(MIXES["tiny-closed"], clients=5, slots=3, slot_len=64,
                prompt_lengths=[12, 24], prompt_weights=[0.5, 0.5],
                output_tokens={"min": 10, "max": 20},
                limits={"served_logit_gap": 1e-3, "answers_wrong": 0})


def tiny_cell(root: str) -> dict:
    """The real ``BENCHMARK.json`` with the cell's configuration and mix
    replaced by tiny ones under a path of its own: every entry, reader
    and family file is the repo's."""
    shutil.copytree(os.path.join(loader.ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for sub, name, data in (("configs", CONFIG, TINY),
                            ("workloads", "session-closed", TINY_MIX)):
        with open(os.path.join(root, "benchmarks", sub, name + ".json"),
                  "w") as f:
            json.dump(data, f)
    return copy.deepcopy(dict(loader.load_benchmark(), root=root))


def test_such_a_cell_is_served_through_the_front_door(tmp_path,
                                                      cpu_tpu_workers):
    bench = tiny_cell(str(tmp_path / "tiny_phi4flash"))
    line = run.run_cell(bench, CELL, seed=2**31 + 43, seconds=3.0,
                        trace=True, platform="cpu", lm_class=TracedOnCpuLM)
    assert line["correct"], line["faults"]
    check_line(bench, CELL, line, True)
    got = line["metrics"]
    # the engine's count of the positions its prefills' second stage ran
    # over came through the scheduler's table: one a prompt of 12 or 24
    assert 100.0 / 24 <= got["prefill_cross_rows_pct.session"]["value"] \
        <= 100.0 / 12
    assert got["prefill_tokens_per_s.session"]["value"] > 0.0
    assert got["decode_occupancy_pct.session"]["value"] > 0.0
    assert got["serve_mfu_pct.session"]["value"] > 0.0
    assert 0.0 < got["decode_slot_reads_ms.session"]["value"] < 1.0
    # the rooflines' time is the device's operations by name: the CPU's
    # stand-in plane names none
    for name in ("decode_roofline_pct", "ssm_scan_roofline_pct",
                 "flash_fwd_roofline_pct", "decode_attend_roofline_pct"):
        assert name + ".session" not in got
