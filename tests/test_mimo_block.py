"""The block's newer parts at a tiny size on the CPU: layers of several
kinds in one model (window and full attention with their own K/V heads,
caches and rope bases, a sink, q.k and v widths that differ, rope on
part of a head) and a chip's share of a sparse-expert layer.

The flash forward kernel runs in interpret mode against ``attention``
(the XLA form); the expert layer against the dense form written out
here; cached decoding against the full forward's argmax, through the
ring's wraps, a reused slot and ``DecodeScheduler``. The comparison
with the family's plain reference, the planted faults and the costs are
in tests/bench/test_family_mimo.py.
"""

import asyncio
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import TransformerConfig, decode, forward, init_params
from ray_tpu.ops.attention import attention, flash_attention, flash_blocks
from ray_tpu.ops.rotary import rope_frequencies, rotate
from ray_tpu.parallel.experts import expert_ffn, expert_tile, route

F, W, D, E = "full", "window", "dense", "experts"
# MiMo-V2-Flash's structure at toy widths: a dense full layer, then a
# period of expert layers, window and full; 4 experts held of 16, top-2
TINY = dict(
    vocab=97, d_model=32, n_heads=4, n_layers=7, d_ff=64, max_seq=64,
    rope_theta=5e6, dtype=jnp.float32, norm_eps=1e-5,
    tie_embeddings=False, n_kv_heads=1, qk_head_dim=12, v_head_dim=8,
    rotary_dim=4, value_scale=0.707,
    layer_kinds=((F, D), (W, E), (W, E), (W, E), (W, E), (F, E), (W, E)),
    window=8, window_kv_heads=2, window_rope_theta=1e4, sink_kinds=(W,),
    n_experts=16, experts_per_token=2, experts_first=4, experts_held=4,
    d_expert=16)


def tiny_model(seed=1, **changes):
    """(cfg, params): the 0.02 initializer leaves a 32-wide model's
    logits to its embedding alone, so the matrices are scaled up until
    the layers decide them, as they do at the published width."""
    cfg = TransformerConfig(**dict(TINY, **changes))
    params = init_params(jax.random.key(seed), cfg)
    return cfg, jax.tree.map(lambda a: a * 6 if a.ndim >= 3 else a, params)


# ----------------------------------------------------- the flash forward

def naive_attention(q, k, v, window, sink):
    """Softmax attention written out: heads repeated, one big mask."""
    B, T, H, D_ = q.shape
    G = k.shape[2]
    k, v = (jnp.repeat(t, H // G, axis=2) for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * D_ ** -0.5
    i = jnp.arange(T)
    keep = i[:, None] >= i[None, :]
    if window:
        keep &= i[None, :] > i[:, None] - window
    s = jnp.where(keep, s, -jnp.inf)
    if sink is not None:
        s = jnp.concatenate([s, jnp.broadcast_to(
            sink[None, :, None, None], (B, H, T, 1))], -1)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1)[..., :T], v)


FLASH_CASES = {
    # T, H, G, Dqk, Dv, window, sink, block_q, block_k
    "window-sink-grouped": (64, 4, 2, 24, 16, 8, True, 16, 16),
    "plain": (64, 4, 4, 16, 16, None, False, 16, 16),
    "window-longer-than-a-block": (96, 8, 2, 24, 16, 20, True, 32, 16),
    "one-kv-head-wide-k-blocks": (64, 4, 1, 24, 16, 8, False, 16, 32),
    "window-of-the-whole": (128, 4, 2, 24, 16, 128, True, 32, 32),
    "sink-alone": (64, 4, 2, 24, 16, None, True, 16, 16),
    "window-alone": (64, 4, 4, 16, 16, 5, False, 16, 16),
}


@pytest.mark.parametrize("case", sorted(FLASH_CASES))
def test_flash_forward_with_window_sink_and_grouped_heads(case):
    """The kernel (interpret mode) and the XLA form against the
    written-out softmax, to float32 rounding; and the gradient of such
    a call, which is the XLA form's."""
    T, H, G, Dh, Dv, window, has_sink, bq, bk = FLASH_CASES[case]
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (2, T, H, Dh))
    k = jax.random.normal(ks[1], (2, T, G, Dh))
    v = jax.random.normal(ks[2], (2, T, G, Dv))
    sink = 1 + 2 * jax.random.normal(ks[3], (H,)) if has_sink else None
    want = naive_attention(q, k, v, window, sink)

    def kernel(q, k, v):
        return flash_attention(q, k, v, window=window, sink=sink,
                               block_q=bq, block_k=bk, interpret=True)

    assert jnp.max(jnp.abs(attention(q, k, v, window=window, sink=sink)
                           - want)) < 2e-6
    assert jnp.max(jnp.abs(kernel(q, k, v) - want)) < 2e-6
    got = jax.grad(lambda *a: jnp.sum(jnp.sin(kernel(*a))),
                   argnums=(0, 1, 2))(q, k, v)
    ref = jax.grad(lambda *a: jnp.sum(jnp.sin(naive_attention(
        *a, window, sink))), argnums=(0, 1, 2))(q, k, v)
    assert all(jnp.max(jnp.abs(a - b)) < 2e-5 for a, b in zip(got, ref))


def test_a_window_without_causal_is_refused():
    t = jnp.zeros((1, 8, 2, 4))
    with pytest.raises(ValueError, match="causal"):
        attention(t, t, t, causal=False, window=4)


def test_window_blocks_are_at_most_two_windows_long():
    assert flash_blocks(2048, 192, 2, "flash_fwd") == (1024, 1024)
    assert flash_blocks(2048, 192, 2, "flash_fwd", 128) == (256, 256)
    assert flash_blocks(512, 192, 2, "flash_fwd", 128) == (256, 256)
    assert flash_blocks(2048, 128, 2, "flash_fwd", 300) == (512, 512)
    assert flash_blocks(128, 192, 2, "flash_fwd", 128) == (128, 128)


def test_rope_on_part_of_a_head_passes_the_rest_through():
    cos, sin = rope_frequencies(4, 16, theta=1e4)
    x = jax.random.normal(jax.random.key(0), (1, 16, 2, 12))
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    got = rotate(x, c, s)
    np.testing.assert_array_equal(got[..., 4:], x[..., 4:])
    np.testing.assert_allclose(got[..., :4], rotate(x[..., :4], c, s))
    assert not np.allclose(got[:, 1:, :, :4], x[:, 1:, :, :4])


# -------------------------------------------------------- the expert layer

def dense_experts(h, chosen, weights, w_gate, w_up, w_down, first):
    """Every held expert on every row, weighted by the row's weight on
    it (zero where the row did not choose it)."""
    out = jnp.zeros(h.shape, jnp.float32)
    for e in range(w_gate.shape[0]):
        weight = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), -1)
        y = (jax.nn.silu(h @ w_gate[e]) * (h @ w_up[e])) @ w_down[e]
        out += weight[:, None] * y
    return out


def expert_weights(key, E, D_, F_):
    ks = jax.random.split(key, 3)
    return (jax.random.normal(ks[0], (E, D_, F_)) / D_ ** 0.5,
            jax.random.normal(ks[1], (E, D_, F_)) / D_ ** 0.5,
            jax.random.normal(ks[2], (E, F_, D_)) / F_ ** 0.5)


def test_route_chooses_by_score_plus_bias_and_weighs_by_score_alone():
    h = jax.random.normal(jax.random.key(0), (50, 8))
    router = jax.random.normal(jax.random.key(1), (8, 16))
    bias = jnp.zeros(16).at[3].set(10.0)        # expert 3 always chosen
    chosen, weights = route(h, router, bias, 4)
    scores = jax.nn.sigmoid(h @ router)
    assert chosen.shape == weights.shape == (50, 4)
    assert (chosen == 3).any(axis=1).all()
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    np.testing.assert_allclose(weights, picked / picked.sum(-1, keepdims=True),
                               rtol=1e-6)
    # the bias steers the choice and carries no weight: without it the
    # other three are the three best scores
    plain, _ = route(h, router, jnp.zeros(16), 4)
    assert (jnp.sort(plain, -1) != jnp.sort(chosen, -1)).any()


@pytest.mark.parametrize("tile", [1, 4, 16])
@pytest.mark.parametrize("first", [0, 4, 12])
def test_the_grouped_product_is_the_dense_form(first, tile):
    T, D_, F_, E, N, k = 37, 8, 12, 4, 16, 3
    h = jax.random.normal(jax.random.key(0), (T, D_))
    chosen, weights = route(h, jax.random.normal(jax.random.key(1), (D_, N)),
                            jnp.zeros(N), k)
    mats = expert_weights(jax.random.key(2), E, D_, F_)
    got, counts = expert_ffn(h, chosen, weights, *mats, first=first,
                             held=E, tile=tile)
    want = dense_experts(h, chosen, weights, *mats, first)
    np.testing.assert_allclose(got, want, atol=2e-6)
    np.testing.assert_array_equal(counts, [
        int(jnp.sum(chosen == first + e)) for e in range(E)])


def test_no_row_is_dropped_when_every_row_goes_to_one_expert():
    """A skew no capacity factor survives: all 64 rows choose experts 5
    and 6, of which this chip (experts 2 to 5) holds 5. Expert 5 gets
    64 rows, the others none, and every row's part is computed."""
    T, D_, F_, E, N = 64, 8, 12, 4, 16
    h = jax.random.normal(jax.random.key(0), (T, D_))
    bias = jnp.zeros(N).at[jnp.asarray([5, 6])].set(10.0)
    chosen, weights = route(h, jnp.zeros((D_, N)), bias, 2)
    mats = expert_weights(jax.random.key(2), E, D_, F_)
    got, counts = expert_ffn(h, chosen, weights, *mats, first=2, held=E,
                             tile=16)
    np.testing.assert_array_equal(counts, [0, 0, 0, T])
    want = 0.5 * (jax.nn.silu(h @ mats[0][3]) * (h @ mats[1][3])) @ mats[2][3]
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert (jnp.abs(got).sum(-1) > 0).all()


def test_a_stack_of_layers_is_indexed_where_it_lies():
    """``base`` picks the layer's experts out of the layers' experts
    flattened to one leading dimension."""
    T, D_, F_, E = 20, 8, 12, 2
    h = jax.random.normal(jax.random.key(0), (T, D_))
    chosen, weights = route(h, jax.random.normal(jax.random.key(1), (D_, 4)),
                            jnp.zeros(4), 2)
    stack = expert_weights(jax.random.key(2), 3 * E, D_, F_)
    for layer in range(3):
        got, _ = expert_ffn(h, chosen, weights, *stack, first=0, held=E,
                            tile=4, base=jnp.int32(layer * E))
        want = dense_experts(h, chosen, weights, *(
            m[layer * E:(layer + 1) * E] for m in stack), 0)
        np.testing.assert_allclose(got, want, atol=2e-6)


def test_a_tile_is_about_twice_an_experts_expected_rows():
    assert expert_tile(128, 8, 256) == 16       # a decode step: 4 expected
    assert expert_tile(512, 8, 256) == 32
    assert expert_tile(2048, 8, 256) == 128     # a prefill: 64 expected
    assert expert_tile(8192, 8, 256) == 128
    assert expert_tile(1, 1, 256) == 16


# ------------------------------------------------------------ the config

@pytest.mark.parametrize("key,changes", [
    ("window", {"window": None}),
    ("window_kv_heads", {"window_kv_heads": 3}),
    ("n_kv_heads", {"n_kv_heads": 3}),
    ("rotary_dim", {"rotary_dim": 5}),
    ("layer_kinds", {"n_layers": 6}),
    ("layer_kinds", {"layer_kinds": (("linear", D),) * 7}),
    ("n_experts", {"experts_first": 14}),
    ("n_experts", {"experts_per_token": 0}),
])
def test_a_config_that_contradicts_itself_is_refused_by_its_key(key, changes):
    with pytest.raises(ValueError, match=rf"TransformerConfig\.{key}:"):
        TransformerConfig(**dict(TINY, **changes))


def test_the_cache_is_allocated_by_layer_kind():
    cfg = TransformerConfig(**TINY)
    cache = decode.init_slot_cache(cfg, 3, 40)
    # runs: full x1, window x4, full x1, window x1; a window run is a
    # ring of 8 rows; a row is the K/V heads side by side
    assert [k.shape for k in cache["k"]] == [
        (1, 3, 40, 12), (4, 3, 8, 24), (1, 3, 40, 12), (1, 3, 8, 24)]
    assert [v.shape for v in cache["v"]] == [
        (1, 3, 40, 8), (4, 3, 8, 16), (1, 3, 40, 8), (1, 3, 8, 16)]
    assert cache["load"].shape == (3,) and cache["pos"].shape == (3,)
    plain = decode.init_slot_cache(TransformerConfig(), 3, 40)
    # layers all alike are one run: a tuple of one
    assert [a.shape for a in plain["k"] + plain["v"]] == [
        (4, 3, 40, 4, 32)] * 2
    assert "load" not in plain


# ------------------------------------------ cached decoding = the forward

def test_cached_decoding_is_the_full_forward_through_the_rings_wraps():
    """Prefill (shorter and longer than the window) then 29 or 20 steps,
    more than three wraps of the ring of 8, against ``forward``'s logits
    on the whole sequence: the same block, so float32 rounding order is
    all that differs (2.4e-7 read; 2e-5 is a hundred times that)."""
    cfg, params = tiny_model()
    tokens = jax.random.randint(jax.random.key(2), (2, 40), 0, cfg.vocab)
    full = forward(params, tokens, cfg)
    for T0 in (5, 11, 20):
        cache = decode.init_slot_cache(cfg, 2, 40)
        worst = 0.0
        for row in range(2):
            logits, cache = decode.slot_prefill(
                params, tokens[row:row + 1, :T0], cache, jnp.int32(row), cfg)
            worst = max(worst, float(jnp.max(jnp.abs(
                logits[0] - full[row, T0 - 1]))))
        for t in range(T0, 40):
            logits, cache = decode.slot_decode_step(
                params, cache, tokens[:, t], jnp.ones(2, bool), cfg)
            worst = max(worst, float(jnp.max(jnp.abs(logits - full[:, t]))))
        assert worst < 2e-5, (T0, worst)
        # the last step's counts, summed over the six expert layers:
        # held experts hit, rows routed here, the fullest expert's rows
        hit, rows, fullest = (int(n) for n in cache["load"])
        assert 0 < hit <= 24 and hit <= rows <= 2 * 2 * 6
        assert 0 < fullest <= rows


def test_the_rings_through_the_decode_kernel_are_the_full_forward(
        decode_kernel_interpreted):
    """The same through the decode kernel (interpreted): the window
    layers' rings, with their sinks, as ``decode_ring`` and the full
    layers' rows as ``decode_attend``; every step's logits are
    ``forward``'s to float32 rounding, more than three wraps of the ring
    of 8 on."""
    cfg, params = tiny_model()
    tokens = jax.random.randint(jax.random.key(2), (2, 40), 0, cfg.vocab)
    full = forward(params, tokens, cfg)
    for T0 in (5, 11):
        cache = decode.init_slot_cache(cfg, 2, 40)
        for row in range(2):
            _, cache = decode.slot_prefill(
                params, tokens[row:row + 1, :T0], cache, jnp.int32(row), cfg)
        worst = 0.0
        for t in range(T0, 40):
            logits, cache = decode.slot_decode_step(
                params, cache, tokens[:, t], jnp.ones(2, bool), cfg)
            worst = max(worst, float(jnp.max(jnp.abs(logits - full[:, t]))))
        assert worst < 2e-5, (T0, worst)
    step = str(jax.make_jaxpr(functools.partial(
        decode.slot_decode_step, cfg=cfg))(
            params, cache, tokens[:, 0], jnp.ones(2, bool)))
    # one call a run of layers: two full runs, two window runs
    assert step.count("name=decode_ring") == 2
    assert step.count("name=decode_attend") == 2


def test_a_reused_slot_never_sees_its_predecessors_ring():
    """Slot 0 serves a long request, then a prompt shorter than the
    window: the ring's rows beyond the prompt still hold the first
    request's K/V and must not be attended."""
    cfg, params = tiny_model()
    first = jax.random.randint(jax.random.key(3), (1, 30), 0, cfg.vocab)
    second = jax.random.randint(jax.random.key(4), (1, 12), 0, cfg.vocab)
    cache = decode.init_slot_cache(cfg, 1, 40)
    _, cache = decode.slot_prefill(params, first[:, :20], cache,
                                   jnp.int32(0), cfg)
    for t in range(20, 30):
        _, cache = decode.slot_decode_step(params, cache, first[:, t],
                                           jnp.ones(1, bool), cfg)
    full = forward(params, second, cfg)
    logits, cache = decode.slot_prefill(params, second[:, :3], cache,
                                        jnp.int32(0), cfg)
    assert jnp.max(jnp.abs(logits[0] - full[0, 2])) < 2e-5
    for t in range(3, 12):
        logits, cache = decode.slot_decode_step(
            params, cache, second[:, t], jnp.ones(1, bool), cfg)
        assert jnp.max(jnp.abs(logits[0] - full[0, t])) < 2e-5


def test_greedy_decoding_through_the_scheduler_is_the_forwards_argmax():
    """Three prompts on two slots through ``DecodeScheduler`` and
    ``JaxSlotEngine``: each answer is the argmax of ``forward`` on the
    growing prefix, the third in a slot that the first or second left;
    and the engine's three expert counts arrive with the tokens."""
    from ray_tpu.serve.decode_scheduler import (EXPERT_COUNTS,
                                                DecodeScheduler,
                                                JaxSlotEngine)

    cfg, params = tiny_model()
    engine = JaxSlotEngine(params, cfg, slots=2, max_len=40)
    prompts = [[int(t) for t in jax.random.randint(
        jax.random.key(10 + i), (n,), 0, cfg.vocab)]
        for i, n in enumerate((3, 12, 9))]
    steps = (20, 11, 17)

    async def serve():
        scheduler = DecodeScheduler(engine)
        answers = await asyncio.gather(*(
            scheduler.submit(p, max_tokens=n)
            for p, n in zip(prompts, steps)))
        stats = scheduler.stats()
        await scheduler.aclose()
        return answers, stats

    answers, stats = asyncio.run(serve())
    for prompt, n, answer in zip(prompts, steps, answers):
        # causal: the logits at a position are those of its prefix
        logits = forward(params, jnp.asarray([prompt + answer], jnp.int32),
                         cfg)[0, len(prompt) - 1:-1]
        assert answer == [int(t) for t in jnp.argmax(logits, -1)]
        assert len(answer) == n
    counted = {name: stats["phases"][name] for name in EXPERT_COUNTS}
    hit, rows, fullest = (counted[name][1] for name in EXPERT_COUNTS)
    assert {c[0] for c in counted.values()} == {stats["steps"]}
    assert 0 < hit <= rows and 0 < fullest <= rows
    # two experts a row, a quarter of them held, six layers, at most two
    # rows a step
    assert rows <= stats["steps"] * 2 * 2 * 6


def test_the_default_queue_holds_a_batch_of_waiters_and_as_many_again():
    from ray_tpu.serve.decode_scheduler import DecodeScheduler

    class Slots:
        def __init__(self, slots):
            self.slots = slots

    assert DecodeScheduler(Slots(8))._max_queue_depth == 64
    assert DecodeScheduler(Slots(128))._max_queue_depth == 256
    assert DecodeScheduler(Slots(128),
                           max_queue_depth=3)._max_queue_depth == 3


def test_a_model_of_one_kind_keeps_its_one_stack_of_layers():
    """``layer_kinds`` None: the params and the cache are what they
    were before layers had kinds."""
    cfg = TransformerConfig()
    params = init_params(jax.random.key(0), cfg)
    assert sorted(params) == ["embed", "final_norm", "layers"]
    assert params["layers"]["wq"].shape == (4, 128, 128)
    kinds = dataclasses.replace(cfg, layer_kinds=((F, D),) * 4)
    stacked = init_params(jax.random.key(0), kinds)
    assert isinstance(stacked["layers"], tuple) and len(
        stacked["layers"]) == 1
    tokens = jax.random.randint(jax.random.key(1), (1, 8), 0, cfg.vocab)
    np.testing.assert_allclose(
        forward(dict(stacked, layers=(params["layers"],)), tokens, kinds),
        forward(params, tokens, cfg), atol=1e-6)


# ------------------------- a decode step's q, k and v, and the forward's

# one small model for each family's heads: every head with K and V of
# its own; 64 query heads on 8, the value narrower than q and k, rope on
# part of a head, v scaled; 20 on 1 with no rotation at all
QKV_HEADS = {
    "a-head-its-own-kv": dict(n_heads=4),
    "64-on-8-value-narrower": dict(
        n_heads=64, n_kv_heads=8, qk_head_dim=24, v_head_dim=16,
        rotary_dim=8, value_scale=0.707),
    "20-on-1-no-rotation": dict(n_heads=20, n_kv_heads=1, qk_head_dim=16,
                                rope=False),
}


@pytest.mark.parametrize("heads", sorted(QKV_HEADS))
def test_a_decode_steps_q_k_v_are_the_forwards_bit_for_bit(heads):
    """``block`` keeps the three flat products from their reshape to
    heads where the rows are fewer than the weight's own (a decode
    step's one token a row: models/transformer.py says why). What it
    then hands the attention, at the decode step's shape and rope, is
    bit for bit what the form the training forward keeps computes, the
    reshape written on the product: the same three bfloat16 products,
    the same rope, the same roundings."""
    from ray_tpu.models.transformer import block, kind_rope, no_rotation
    from ray_tpu.ops.norms import rmsnorm

    cfg = TransformerConfig(vocab=97, d_model=64, n_layers=1, d_ff=64,
                            max_seq=32, dtype=jnp.bfloat16,
                            **QKV_HEADS[heads])
    params = init_params(jax.random.key(3), cfg)
    lp = jax.tree.map(lambda a: a[0] * 6 if a.ndim >= 3 else a[0],
                      params["layers"])
    slots = 4

    def rope_at(pos):       # as ``slot_decode_step`` rotates: a row at
        if not cfg.rope:    # its own position
            return no_rotation
        cos, sin = kind_rope(cfg, F, cfg.max_seq)
        return lambda t: rotate(t, cos[pos][:, None, None, :],
                                sin[pos][:, None, None, :])

    def handed(q, k, v):    # what the block hands the attention
        return jnp.zeros(q.shape[:3] + v.shape[3:], q.dtype), (q, k, v)

    @jax.jit
    def of_the_block(x, pos):
        return block(lp, x, rope_at(pos), handed, cfg)[1]

    @jax.jit
    def written_on_the_product(x, pos):
        B, T, _ = x.shape
        h = rmsnorm(x, lp["attn_norm"], eps=cfg.norm_eps)
        q = (h @ lp["wq"]).reshape(B, T, -1, cfg.head_dim)
        k = (h @ lp["wk"]).reshape(B, T, -1, cfg.head_dim)
        v = (h @ lp["wv"]).reshape(B, T, -1, cfg.v_dim)
        if cfg.value_scale != 1.0:
            v = v * cfg.value_scale
        rope = rope_at(pos)
        return rope(q), rope(k), v

    key = jax.random.key(7)
    for step in range(3):
        key, a, b = jax.random.split(key, 3)
        tokens = jax.random.randint(a, (slots,), 0, cfg.vocab)
        pos = jax.random.randint(b, (slots,), 0, cfg.max_seq)
        x = params["embed"][tokens][:, None, :] * 20     # [slots, 1, D]
        for got, want, name in zip(of_the_block(x, pos),
                                   written_on_the_product(x, pos), "qkv"):
            assert got.dtype == want.dtype == jnp.bfloat16
            assert got.shape == want.shape
            assert float(jnp.std(want.astype(jnp.float32))) > 0.1
            np.testing.assert_array_equal(
                np.asarray(got.astype(jnp.float32)),
                np.asarray(want.astype(jnp.float32)),
                err_msg=f"{name} at step {step}")
    # the decode step's shape takes the flat form, the training shape
    # (rows no fewer than the weight's) keeps the reshape on the product
    kept_flat = "optimization_barrier"
    assert kept_flat in str(jax.make_jaxpr(of_the_block)(x, pos))
    many = jnp.zeros((2, cfg.max_seq, cfg.d_model), cfg.dtype)
    assert kept_flat not in str(jax.make_jaxpr(
        lambda x: block(lp, x, no_rotation, handed, cfg)[1])(many))
