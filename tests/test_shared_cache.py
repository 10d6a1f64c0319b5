"""Layers that read what an earlier layer made inside the same pass: a
gated memory unit on the memory of the Mamba layer before it, a cross
layer on the K/V of the full-attention layer before it, differential
attention in every attention and cross layer. On the CPU at tiny
widths: the period rule of ``layer_runs`` (every family the benchmark
serves keeps its runs; a pattern that alternates gets runs of periods),
what ``TransformerConfig`` refuses, ``forward`` (every layer at every
position) against ``slot_prefill`` (two stages) and ``slot_decode_step``
(the memory carried from run to run, the cross layers handed the full
run's cache), differential attention against its definition written
out, the engine's counters from its host mirror, and the compiled decode
step's table of parts. The family's plain reference holds the same
programs in tests/bench/test_family_phi4flash.py; the programs compiled
for the chip are held in tests/test_chip_compile.py.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import decode, forward, init_params
from ray_tpu.models import transformer
from ray_tpu.models.transformer import (LAYER_WEIGHTS, PARTS, ParallelConfig,
                                        TransformerConfig, layer_runs,
                                        param_specs, period_of)
from ray_tpu.serve.decode_scheduler import JaxSlotEngine
from ray_tpu.util.phases import recording

F, W, M, R, G, X = "full", "window", "mamba", "retention", "gmu", "cross"
D, E = "dense", "experts"


def kinds(*mixers, ffn=D):
    return tuple((mixer, ffn) for mixer in mixers)


def hybrid(pattern, **more):
    """A tiny decoder-hybrid-decoder of ``pattern``'s mixers."""
    return TransformerConfig(**dict(dict(
        vocab=64, d_model=32, n_heads=4, n_kv_heads=2, n_layers=len(pattern),
        d_ff=64, max_seq=64, rope=False, layer_kinds=kinds(*pattern),
        window=8, ssm_inner=64, ssm_state=4, ssm_dt_rank=2, ssm_conv=4,
        ssm_inner_norms=False, differential=True, attn_bias=True,
        layer_norm=True, dtype=jnp.float32, norm_eps=1e-5), **more))


SAMBAY = (M, W) * 3 + (M, F) + (G, X) * 2


def spread(params, key=1):
    """``params`` with every bias and norm leaf off its start (zeros,
    ones), so that one left out or misplaced shows."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(key), len(leaves))
    return jax.tree.unflatten(tree, [
        leaf + 0.05 * jax.random.normal(k, leaf.shape, leaf.dtype)
        if leaf.ndim <= 2 and leaf.shape[-1] in (32, 16) else leaf
        for leaf, k in zip(leaves, keys)])


# -------------------------------------------------- runs of alike periods

def benchmark_cfg(name):
    from benchmarks import loader

    bench = loader.load_benchmark()
    config = loader.load_config(bench, name)
    program = loader.family_module(loader.find_family(bench, config),
                                   "program")
    return program.program_config(config, 256)


@pytest.mark.parametrize("name,runs", [
    ("ouro-2.6b", [((F, D), 48)]),
    ("ouro-2.6b-d12", [((F, D), 12)]),
    ("mimo-v2-flash-ep16-d7", [((F, D), 1), ((W, E), 4), ((F, E), 1),
                               ((W, E), 1)]),
    ("jamba2-3b", [((M, D), 7), ((F, D), 1), ((M, D), 13), ((F, D), 1),
                   ((M, D), 6)]),
    ("brumby-14b-d8", [((R, D), 8)]),
    ("phi-4-mini-flash", [(kinds(M, W), 8), (kinds(M, F), 1),
                          (kinds(G, X), 7)]),
])
def test_each_served_family_is_cut_into_its_runs(name, runs):
    """The five configurations that were there keep their runs of alike
    layers, each a period of one; the one that alternates gets three
    runs of periods of two, six layer bodies where it had thirty-two."""
    cfg = benchmark_cfg(name)
    assert list(layer_runs(cfg)) == runs
    assert sum(n * len(period_of(kind)) for kind, n in runs) == cfg.n_layers


@pytest.mark.parametrize("pattern,runs", [
    ((F,) * 5, [((F, D), 5)]),
    ((M, M, F, M), [((M, D), 2), ((F, D), 1), ((M, D), 1)]),
    # a tie goes to the shorter period: four bodies either way
    ((W, F, W, F), [(kinds(W, F), 2)]),
    ((W, W, F, W, W, F), [(kinds(W, W, F), 2)]),
    # no period that divides seven layers beats runs of alike layers
    ((F, W, W, W, W, W, F), [((F, D), 1), ((W, D), 5), ((F, D), 1)]),
    (SAMBAY, [(kinds(M, W), 3), (kinds(M, F), 1), (kinds(G, X), 2)]),
])
def test_the_period_is_the_one_with_the_fewest_layer_bodies(pattern, runs):
    cfg = hybrid(pattern, differential=False)
    assert list(layer_runs(cfg)) == runs
    params = init_params(jax.random.key(0), cfg)
    assert len(params["layers"]) == len(runs)
    for (kind, n), stack in zip(runs, params["layers"]):
        period = period_of(kind)
        stacks = stack if len(period) > 1 else (stack,)
        assert isinstance(stack, tuple) == (len(period) > 1)
        assert [s["attn_norm"].shape[0] for s in stacks] == [n] * len(period)
    # the specs follow the same tree
    specs = param_specs(ParallelConfig(), cfg)
    assert jax.tree.structure(
        jax.tree.map(lambda a: 0, params)) == jax.tree.structure(
        jax.tree.map(lambda s: 0, specs,
                     is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec)))


def test_a_models_weights_say_what_each_layer_is():
    params = init_params(jax.random.key(0), hybrid(SAMBAY))
    (mamba, window), (_, full), (gmu, cross) = params["layers"]
    assert "w_in" in mamba and "dt_norm" not in mamba
    assert {"wq", "wk", "wv", "wo", "bq", "bk", "bv", "bo", "lambda_q1",
            "lambda_k2", "sub_norm"} <= set(window)
    assert set(window) == set(full)
    assert set(gmu) == {"attn_norm", "attn_norm_b", "mlp_norm",
                        "mlp_norm_b", "w_mem", "w_out", "w_gate", "w_up",
                        "w_down"}
    assert set(cross) == set(full) - {"wk", "wv", "bk", "bv"}
    assert "final_norm_b" in params
    # Jamba's Mamba layers keep their inner norms
    jamba = init_params(jax.random.key(0), hybrid(
        (M, M, F, M), ssm_inner_norms=True, differential=False,
        layer_norm=False, attn_bias=False))
    assert {"dt_norm", "b_norm", "c_norm"} <= set(jamba["layers"][0])
    assert "attn_norm_b" not in jamba["layers"][0]


# ------------------------------------------------------------- refusals

@pytest.mark.parametrize("pattern,more,match", [
    ((M, W, G, X), {}, "no full-attention layer before it"),
    ((X, F, M, G), {}, "no full-attention layer before it"),
    ((W, F, G, X), {}, "no Mamba layer before it"),
    ((G, M, F, X), {}, "no Mamba layer before it"),
    (SAMBAY, {"n_heads": 3, "n_kv_heads": 1}, "3 is odd"),
    (SAMBAY, {"n_kv_heads": 1}, "1 is odd"),
    (SAMBAY, {"sink_kinds": (W,)}, "no differential form"),
    ((M, F, "other", X), {}, "unknown kind"),
    # a cross layer that stands before the full layer of its own period
    # would attend the period before's: nothing carries that
    ((M, F, X, F, X, F), {}, "before the full-attention layer of its own"),
])
def test_what_cannot_run_is_refused_when_the_config_is_made(pattern, more,
                                                            match):
    with pytest.raises(ValueError, match=match):
        hybrid(pattern, **more)


@pytest.mark.parametrize("axis", ["tp", "sp", "pp"])
@pytest.mark.parametrize("pattern,more,what", [
    (SAMBAY, {}, "Mamba"),
    ((M, F, G, X), {"differential": False}, "Mamba"),
    ((F, X, F, X), {"differential": False}, "gated-memory or cross"),
    ((F, F, W, W), {}, "differential-attention"),
])
def test_no_sharding_of_a_lent_value_is_expressed(axis, pattern, more, what):
    cfg = hybrid(pattern, **more)
    pcfg = ParallelConfig(**{axis: axis})
    with pytest.raises(ValueError, match=f"a model with {what} layers"):
        param_specs(pcfg, cfg)
    with pytest.raises(ValueError, match=f"a model with {what} layers"):
        forward(None, jnp.zeros((1, 8), jnp.int32), cfg, pcfg)


# --------------------------------------- differential attention, spelled

def test_paired_heads_through_plain_attention_are_the_definition():
    """``_paired`` and ``_differ`` around grouped attention against the
    two softmaxes written out head by head."""
    from ray_tpu.ops.attention import attention

    B, T, H, Gk, Dh = 2, 12, 8, 4, 4
    keys = jax.random.split(jax.random.key(0), 8)
    q = jax.random.normal(keys[0], (B, T, H, Dh))
    k = jax.random.normal(keys[1], (B, T, Gk, Dh))
    v = jax.random.normal(keys[2], (B, T, Gk, Dh))
    lp = {"lambda_q1": 0.3 * jax.random.normal(keys[3], (Dh,)),
          "lambda_k1": 0.3 * jax.random.normal(keys[4], (Dh,)),
          "lambda_q2": 0.3 * jax.random.normal(keys[5], (Dh,)),
          "lambda_k2": 0.3 * jax.random.normal(keys[6], (Dh,)),
          "sub_norm": 1 + 0.1 * jax.random.normal(keys[7], (2 * Dh,)),
          "depth": 5}
    wide = transformer._paired(q, k, v)
    assert [t.shape for t in wide] == [(B, T, H, 2 * Dh), (B, T, 2, 2 * Dh),
                                       (B, T, 2, 2 * Dh)]
    got = transformer._differ(
        attention(*wide, causal=True, sm_scale=Dh ** -0.5, window=5), lp,
        1e-5)

    start = 0.8 - 0.6 * jnp.exp(-0.3 * 5)
    lam = (jnp.exp(lp["lambda_q1"] @ lp["lambda_k1"])
           - jnp.exp(lp["lambda_q2"] @ lp["lambda_k2"]) + start)
    t = jnp.arange(T)
    keep = (t[None, :] <= t[:, None]) & (t[None, :] > t[:, None] - 5)

    def softmax(qh, kh):
        s = jnp.einsum("btd,bsd->bts", qh, kh) / jnp.sqrt(Dh)
        return jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)

    pairs = []
    for p in range(H // 2):
        g = p // 2                      # two query pairs a K/V pair
        value = jnp.concatenate([v[:, :, 2 * g], v[:, :, 2 * g + 1]], -1)
        a1 = softmax(q[:, :, 2 * p], k[:, :, 2 * g])
        a2 = softmax(q[:, :, 2 * p + 1], k[:, :, 2 * g + 1])
        o = jnp.einsum("bts,bsc->btc", a1 - lam * a2, value)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-5)
        pairs.append(o * lp["sub_norm"] * (1 - start))
    want = jnp.stack(pairs, axis=2)
    assert got.shape == want.shape == (B, T, H // 2, 2 * Dh)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


# ------------------------------ forward against the two serving programs

def served_against_forward(cfg, prompt_len=20, total=34, slots=3, slot=1):
    params = spread(init_params(jax.random.key(0), cfg))
    tokens = jax.random.randint(jax.random.key(2), (1, total), 0, cfg.vocab)
    want = forward(params, tokens, cfg)[0]
    cache = decode.init_slot_cache(cfg, slots, 48)
    logits, cache = decode.slot_prefill(params, tokens[:, :prompt_len],
                                        cache, jnp.int32(slot), cfg)
    worst = float(jnp.max(jnp.abs(logits[0] - want[prompt_len - 1])))
    only = jnp.arange(slots) == slot
    for t in range(prompt_len, total):
        logits, cache = decode.slot_decode_step(
            params, cache, jnp.where(only, tokens[0, t], 0), only, cfg)
        worst = max(worst, float(jnp.max(jnp.abs(logits[slot] - want[t]))))
    assert int(cache["pos"][slot]) == total
    return worst


@pytest.mark.parametrize("pattern,more", [
    (SAMBAY, {}),
    # the stage changes behind a window layer, and between runs
    ((M, F, M, W, G, X), {}),
    # ... inside a run of several periods, which is run whole and cut
    # behind it (prefill_stages' fallback)
    ((M, F, X, M, F, X), {}),
    # cross layers alone borrow; no differential attention, RMSNorm
    ((F, X, F, X, X, X), {"differential": False, "layer_norm": False,
                          "attn_bias": False}),
    # every query head its own K/V head: rows of [H, Dh] in the cache
    (SAMBAY, {"n_kv_heads": 4, "differential": False}),
    # with rope, every position its own
    ((M, W, M, F, G, X), {"rope": True}),
], ids=["sambay", "behind-a-window", "inside-a-run", "cross-alone",
        "own-kv-heads", "roped"])
def test_prefill_and_decode_are_the_forward(pattern, more):
    """The training forward runs every layer at every position; the
    prefill stops at the cross-decoder and the decode step hands the
    cross layers another run's cache. Same logits, at a prompt more
    than twice the window and on past the ring's wrap."""
    assert served_against_forward(hybrid(pattern, **more)) < 2e-5


def test_the_prefills_stages_are_read_off_the_layers():
    assert decode.prefill_stages(hybrid(SAMBAY)) == (8, 7)
    assert decode.prefill_stages(hybrid((M, F, M, W, G, X))) == (4, 3)
    # a Mamba layer last before the tail: nothing of it is cut
    assert decode.prefill_stages(hybrid((M, F, M, G))) == (3, None)
    # no tail: every layer at every position
    assert decode.prefill_stages(hybrid((M, F, M, W))) == (4, None)
    assert decode.prefill_stages(TransformerConfig()) == (4, None)
    assert decode.prefill_cross_rows(hybrid(SAMBAY), 512) == 1
    assert decode.prefill_cross_rows(hybrid((M, F, X, M, F, X)), 512) == 512
    assert decode.prefill_cross_rows(hybrid((M, F, M, W)), 512) is None
    assert decode.kv_readers(hybrid(SAMBAY)) == 3
    assert decode.kv_readers(TransformerConfig()) == 1


def test_the_cache_holds_what_each_layer_of_a_period_keeps():
    cfg = hybrid(SAMBAY)
    cache = decode.init_slot_cache(cfg, 3, 48)
    shape = lambda t: None if t is None else t.shape        # noqa: E731
    # a tuple a run, in it one entry a layer of the period: state and
    # tail of the Mamba layers, a ring of 8 or all 48 rows of the
    # attention layers (K/V heads side by side in a row), nothing of
    # the gated memory units and the cross layers
    assert [[shape(t) for t in run] for run in cache["ssm"]] == [
        [(3, 3, 4, 64), None], [(1, 3, 4, 64), None], [None, None]]
    assert [[shape(t) for t in run] for run in cache["conv"]] == [
        [(3, 3, 3, 64), None], [(1, 3, 3, 64), None], [None, None]]
    assert [[shape(t) for t in run] for run in cache["k"]] == [
        [None, (3, 3, 8, 16)], [None, (1, 3, 48, 16)], [None, None]]
    assert jax.tree.map(shape, cache["k"]) == jax.tree.map(shape, cache["v"])
    held = sum(t.size * t.dtype.itemsize for t in jax.tree.leaves(cache))
    assert held == 4 * (4 * 3 * (4 + 3) * 64 + 2 * 3 * 3 * 8 * 16
                        + 2 * 3 * 48 * 16) + 2 * 4 * 3


def test_a_row_left_out_of_a_step_keeps_every_state_bit_for_bit():
    cfg = hybrid(SAMBAY)
    params = spread(init_params(jax.random.key(0), cfg))
    cache = decode.init_slot_cache(cfg, 2, 48)
    for slot in (0, 1):
        _, cache = decode.slot_prefill(
            params, jax.random.randint(jax.random.key(slot), (1, 12), 0, 64),
            cache, jnp.int32(slot), cfg)
    before = jax.tree.map(jnp.copy, cache)
    _, after = decode.slot_decode_step(
        params, cache, jnp.asarray([7, 0], jnp.int32),
        jnp.asarray([True, False]), cfg)
    for name in ("ssm", "conv"):
        for was, now in zip(jax.tree.leaves(before[name]),
                            jax.tree.leaves(after[name])):
            rows = (slice(None), 1) if name == "ssm" else (
                slice(None), slice(None), 1)
            assert bool(jnp.all(was[rows] == now[rows]))
            assert not bool(jnp.all(was == now))
    assert after["pos"].tolist() == [13, 12]
    assert int(after["tok"][1]) == int(before["tok"][1])


# the smallest model with a layer of each kind of mixer
SMALLEST = {F: (F,), W: (W,), M: (M,), R: (R,), G: (M, G), X: (F, X)}


@pytest.mark.parametrize("mixer", transformer.MIXERS)
def test_a_kinds_entry_is_what_the_cache_and_the_parts_read(mixer):
    """Every kind of mixer has its entry in ``decode.KINDS``; in the
    smallest model with such a layer the cache holds the tuples of the
    model's kinds' entries and no other, that kind's run exactly the
    entry's arrays at the entry's shapes, and the decode step names the
    entry's parts."""
    assert set(decode.KINDS) == set(transformer.MIXERS)
    kind = decode.KINDS[mixer]
    cfg = hybrid(SMALLEST[mixer], differential=mixer != R)
    slots, max_len = 3, 48
    cache = jax.eval_shape(lambda: decode.init_slot_cache(cfg, slots, max_len))
    present = {m for m, _ in cfg.layer_kinds}
    assert set(cache) - {"pos", "tok"} == {
        name for m in present for name in decode.KINDS[m].state}
    runs = [(r, n) for r, (run, n) in enumerate(layer_runs(cfg))
            if run[0] == mixer]
    assert runs
    for r, n in runs:
        held = {name: cache[name][r] for name in cache
                if isinstance(cache[name], tuple)}
        assert {name for name, t in held.items() if t is not None} == set(
            kind.state)
        want = kind.shapes(cfg, mixer, n, slots, max_len) if kind.state \
            else ()
        assert [(held[name].shape, held[name].dtype) for name in kind.state] \
            == [(tuple(shape), jnp.dtype(dtype)) for shape, dtype in want]
    assert set(kind.parts) <= set(PARTS)
    assert set(kind.parts) <= set(decode.decode_parts(cfg))


# -------------------------------------------------- the engine's counters

def test_the_engine_counts_the_shared_caches_readers_and_the_second_stage():
    cfg = hybrid(SAMBAY)
    engine = JaxSlotEngine(init_params(jax.random.key(0), cfg), cfg,
                           slots=2, max_len=48)
    with recording({}) as got:
        first = engine.prefill(0, list(range(1, 21)))
        engine.step({0: first})
    # one prefill of 20 positions, the cross-decoder on one of them
    assert got["serve.engine.prefill_tokens"] == [1, 20]
    assert got["serve.engine.prefill_cross_rows"] == [1, 1]
    # the first call dispatches two steps of one row: off the TPU every
    # reader reads all 48 rows, the full layer and two cross layers
    assert got["serve.engine.kv_rows_read"] == [2, 2 * 3 * 48]
    assert got["serve.engine.kv_rows_held"] == [2, 2 * 3 * 48]
    assert got["serve.engine.state_rows"] == [2, 2]
    # a model with no second stage keeps no such count
    plain = hybrid((M, F, M, W))
    engine = JaxSlotEngine(init_params(jax.random.key(0), plain), plain,
                           slots=2, max_len=48)
    with recording({}) as got:
        engine.prefill(0, [1, 2, 3])
    assert sorted(got) == ["serve.engine.prefill",
                           "serve.engine.prefill_tokens"]


# ------------------------------------------- a decode step, part by part

def test_every_instruction_of_the_step_is_in_a_part():
    """``gmu`` and ``cross_attention`` are parts like the others, listed
    by ``decode_parts`` and named in the compiled step; nothing of a
    run lies outside every part but the scan's slices of the weights."""
    assert {"gmu", "cross_attention"} <= set(PARTS)
    cfg = hybrid(SAMBAY)
    want = decode.decode_parts(cfg)
    assert {"gmu", "cross_attention", "full_attention", "window_attention",
            "mamba_mixer", "ssm_step", "qkv", "attn_out", "mlp", "embed",
            "head", "run0", "run1", "run2"} == set(want)
    params = init_params(jax.random.key(0), cfg)
    cache = decode.init_slot_cache(cfg, 2, 48)
    text = decode.slot_decode_step.lower(
        params, cache, jax.ShapeDtypeStruct((2,), jnp.int32), None,
        cfg).compile().as_text()
    table = decode.program_parts(text, want)
    assert table is not None and len(table) > 40
    runs = {"run0", "run1", "run2"}
    for name, (run, part) in table.items():
        assert run is None or run in runs, name
        assert part in PARTS + (LAYER_WEIGHTS,) or (
            part is None and run is None), (name, run, part)
    by_part = {}
    for run, part in table.values():
        by_part.setdefault(part, set()).add(run)
    # the gated memory units and the cross layers are the third run's,
    # the one growing cache's own layer the second's
    assert by_part["gmu"] - {None} == {"run2"}
    assert by_part["cross_attention"] == {"run2"}
    assert by_part["full_attention"] - {None} == {"run1"}
    assert by_part["window_attention"] - {None} == {"run0"}
    assert by_part["ssm_step"] == {"run0", "run1"}
    # the table of a model with neither has neither
    plain = hybrid((M, F, M, W))
    assert not {"gmu", "cross_attention"} & set(decode.decode_parts(plain))
