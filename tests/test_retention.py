"""Power retention (ops/retention.py) and a model made of such layers,
at a tiny size on the CPU: the feature map against the square of the
inner product; the three forms of the one function against each other
(chunked against the attention form across chunk edges and from a state
that is not zero, a step behind a prefill against the attention form
one position longer); both kernels under ``interpret`` against the XLA
forms, the rows a step leaves out bit for bit; the block's ``forward``
against the ``brumby`` family's plain reference, which is the attention
form and shares no line with the program; ``slot_prefill`` and
``slot_decode_step`` against ``forward`` on the growing prefix; what a
slot's state may and may not suffer; a cache without any K/V; planted
faults, which must fail the comparison; the shardings that are not
expressed, refused; and the slot engine's count of the rows whose state
a step moves.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import loader
from ray_tpu.models import (ParallelConfig, TransformerConfig, decode,
                            forward, init_params, param_specs)
from ray_tpu.models import transformer
from ray_tpu.models.transformer import DENSE, FULL, RETENTION
from ray_tpu.ops import retention as ret

TINY = {"model_type": "brumby", "attention_bias": False, "head_dim": 8,
        "hidden_act": "silu", "hidden_size": 32, "intermediate_size": 64,
        "max_position_embeddings": 64, "max_window_layers": 3,
        "num_attention_heads": 4, "num_hidden_layers": 3,
        "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
        "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 128,
        "torch_dtype": "float32"}
# float32 on the CPU: the program runs the chunked and the recurrent
# form through a state, the reference the attention form over the whole
# sequence, so they differ by rounding in another order: 4e-7 is what
# the sound program reads. 5e-6 leaves that twelve times of room and
# lies far under the least planted fault (the state rounded to bfloat16
# between decode steps: 6e-3) and under the int8 control of
# tests/bench/test_family_brumby.py (8e-4).
TOLERANCE = 5e-6


def scaled(params):
    """The 0.02 initializer leaves a 32-wide model's logits to its
    embedding alone; the matrices are scaled up until the layers decide
    them, as they do at the published width."""
    return jax.tree.map(lambda a: a * 6 if a.ndim >= 3 else a, params)


@pytest.fixture(scope="module")
def model():
    family = loader.find_family(loader.load_benchmark(), TINY)
    ref = loader.family_module(family, "reference")
    sz = ref.sizes_of(TINY)
    cfg = loader.family_module(family, "program").program_config(TINY, 64)
    return ref, sz, cfg, scaled(ref.seeded_params(2**31 + 5, sz))


def gap(a, b):
    return float(jnp.max(jnp.abs(a - b)))


def inputs(key, B=2, T=40, H=4, J=2, d=8, dv=8, dtype=jnp.float32):
    """q, k, v and a log decay whose half-lives run from a few
    positions to hundreds."""
    ks = jax.random.split(jax.random.key(key), 4)
    q = jax.random.normal(ks[0], (B, T, H, d)).astype(dtype)
    k = jax.random.normal(ks[1], (B, T, J, d)).astype(dtype)
    v = jax.random.normal(ks[2], (B, T, J, dv)).astype(dtype)
    g = jax.nn.log_sigmoid(2.0 * jax.random.normal(ks[3], (B, T, J)) + 3.0)
    return q, k, v, g


# ------------------------------------------------------ the feature map

@pytest.mark.parametrize("d", [2, 8, 128])
def test_the_feature_map_squares_the_inner_product(d):
    x, y = jax.random.normal(jax.random.key(d), (2, 7, d))
    fx, fy = ret.power_features(x), ret.power_features(y)
    assert fx.shape == (7, ret.feature_dim(d)) and fx.dtype == jnp.float32
    # half of the full product's d * d, and d / 2 rows over the triangle
    assert ret.feature_dim(d) == d * (d + 1) // 2 + d // 2
    want = jnp.sum(x * y, axis=-1) ** 2
    assert gap(jnp.sum(fx * fy, axis=-1), want) <= 2e-6 * float(
        jnp.max(want) + d * d)
    with pytest.raises(ValueError, match="odd"):
        ret.power_features(jnp.ones((3,)))


# ------------------------------------------- three forms of one function

def test_the_chunked_form_is_the_attention_form_across_chunk_edges():
    q, k, v, g = inputs(0, T=150)       # two chunks of 64 and a rest
    want = ret.retention_quadratic(q, k, v, g)
    got, (S, z) = ret.retention(q, k, v, g)
    assert gap(got, want) < 2e-5
    assert S.shape == (2, 2, 8, 40) and z.shape == (2, 2, 40)
    assert S.dtype == z.dtype == jnp.float32


@pytest.mark.parametrize("cut", [1, 24, 64, 100])
def test_a_sequence_cut_in_two_carries_its_state_over_the_cut(cut):
    q, k, v, g = inputs(1, T=130)
    want = ret.retention_quadratic(q, k, v, g)
    whole = ret.retention(q, k, v, g)[1]
    head, state = ret.retention(q[:, :cut], k[:, :cut], v[:, :cut],
                                g[:, :cut])
    assert float(jnp.max(jnp.abs(state[0]))) > 0    # from a state not zero
    tail, end = ret.retention(q[:, cut:], k[:, cut:], v[:, cut:],
                              g[:, cut:], state)
    assert gap(jnp.concatenate([head, tail], axis=1), want) < 2e-5
    for a, b in zip(end, whole):
        assert gap(a, b) <= 1e-5 * float(jnp.max(jnp.abs(b)))


def test_a_step_behind_a_prefill_is_the_attention_form_one_longer():
    q, k, v, g = inputs(2, T=71)
    want = ret.retention_quadratic(q, k, v, g)
    _, (S, z) = ret.retention(q[:, :-1], k[:, :-1], v[:, :-1], g[:, :-1])
    o, S1, z1 = ret.retention_step_xla(q[:, -1], k[:, -1], v[:, -1],
                                       g[:, -1], S, z)
    assert gap(o, want[:, -1]) < 2e-5
    # ... and leaves the state the longer prefill leaves
    for a, b in zip((S1, z1), ret.retention(q, k, v, g)[1]):
        assert gap(a, b) <= 1e-5 * float(jnp.max(jnp.abs(b)))


def test_where_every_score_is_zero_the_output_is_zero():
    q, k, v, g = inputs(3, T=6)
    k = k.at[:, :3].set(0.0)            # nothing to retain so far
    for form in (ret.retention_quadratic(q, k, v, g),
                 ret.retention(q, k, v, g)[0],
                 ret.retention(q, k, v, g, block_t=6, interpret=True)[0]):
        np.testing.assert_array_equal(np.asarray(form[:, :3]), 0.0)
        assert np.isfinite(np.asarray(form)).all()
        assert float(jnp.max(jnp.abs(form[:, 3:]))) > 0


def test_bfloat16_inputs_keep_a_float32_state():
    q, k, v, g = inputs(4, T=96, dtype=jnp.bfloat16)
    for how in ({}, {"block_t": 32, "interpret": True}):
        o, (S, z) = ret.retention(q, k, v, g, **how)
        assert o.dtype == jnp.bfloat16
        assert S.dtype == z.dtype == jnp.float32
        want = ret.retention_quadratic(*(t.astype(jnp.float32)
                                         for t in (q, k, v)), g)
        assert gap(o.astype(jnp.float32), want) < 0.05


# ----------------------------------------------------------- the kernels

@pytest.mark.parametrize("block_t,start", [(16, False), (32, True),
                                           (64, False), (8, True)])
def test_the_chunk_kernel_is_the_xla_form(block_t, start):
    q, k, v, g = inputs(5, T=64, H=6, J=2)      # three query heads a K/V head
    state = None
    if start:
        state = ret.retention(*inputs(6, T=20, H=6, J=2))[1]
    want, (S, z) = ret.retention(q, k, v, g, state)
    got, (Sk, zk) = ret.retention(q, k, v, g, state, block_t=block_t,
                                  interpret=True)
    assert gap(got, want) < 2e-5
    assert gap(Sk, S) <= 1e-5 * float(jnp.max(jnp.abs(S)))
    assert gap(zk, z) <= 1e-5 * float(jnp.max(jnp.abs(z)))


def test_the_kernels_gradient_is_the_chunked_forms():
    q, k, v, g = inputs(7, T=32)

    def loss(how):
        def f(q, k, v, g):
            o, (S, z) = ret.retention(q, k, v, g, **how)
            return jnp.sum(o * o) + jnp.sum(S) * 1e-3 + jnp.sum(z) * 1e-3
        return jax.grad(f, argnums=(0, 1, 2, 3))(q, k, v, g)

    for a, b in zip(loss({"block_t": 16, "interpret": True}), loss({})):
        assert float(jnp.max(jnp.abs(b))) > 0
        assert gap(a, b) <= 1e-4 * float(jnp.max(jnp.abs(b)))


def test_which_form_runs_is_read_from_the_platform_and_the_shape(
        monkeypatch):
    calls = []
    monkeypatch.setattr(ret, "_chunk_forward_only",
                        lambda *a: calls.append(a[5]) or (None, None))
    q, k, v, g = (jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in (
        (1, 256, 40, 128), (1, 256, 8, 128), (1, 256, 8, 128), (1, 256, 8)))
    monkeypatch.setattr(ret, "_on_tpu", lambda: True)
    jax.eval_shape(lambda *a: ret.retention(*a) and 0, q, k, v, g)
    assert calls == [128]
    # off the TPU, a length that is no multiple of the chunk, heads that
    # are no whole lane tiles: the XLA form
    for platform, length, d in ((False, 256, 128), (True, 200, 128),
                                (True, 256, 64)):
        monkeypatch.setattr(ret, "_on_tpu", lambda p=platform: p)
        shapes = [jax.ShapeDtypeStruct((1, length, h, d), jnp.bfloat16)
                  for h in (40, 8, 8)] + [jax.ShapeDtypeStruct(
                      (1, length, 8), jnp.float32)]
        o, (S, z) = jax.eval_shape(ret.retention, *shapes)
        assert o.shape == (1, length, 40, d) and len(calls) == 1
    monkeypatch.setattr(ret, "_on_tpu", lambda: True)
    assert ret.step_block(128, 128, 5) == 128
    assert ret.step_block(96, 128, 5) == 32
    assert ret.step_block(128, 64, 5) is None       # half a lane tile
    assert ret.step_block(128, 128, 8) is None      # no row left for the key
    monkeypatch.setattr(ret, "_on_tpu", lambda: False)
    assert ret.step_block(128, 128, 5) is None


def carried(key, L=3, B=4, J=2, d=8, dv=8):
    ks = jax.random.split(jax.random.key(key), 2)
    return (jax.random.normal(ks[0], (L, B, J, dv, ret.feature_dim(d))),
            jnp.abs(jax.random.normal(ks[1], (L, B, J, ret.feature_dim(d)))))


@pytest.mark.parametrize("active", [
    (True, True, True, True), (True, False, True, False),
    (False, False, True, True), (False, True, False, False),
    (False, False, False, False)], ids=str)
@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "kernel"])
def test_a_step_advances_its_live_rows_and_keeps_the_rest_bit_for_bit(
        active, interpret):
    """Both forms of ``retention_step`` over a run's whole carried
    state: a live row's state is the one-token recurrence's, a row left
    out, and every other layer, is bit for bit what it was."""
    S, z = carried(8)
    q, k, v, g = (t[:, 0] for t in inputs(9, B=4, T=1))
    live = np.asarray(active)
    o, S1, z1 = ret.retention_step(q, k, v, g, S, z, jnp.int32(1),
                                   jnp.asarray(active), interpret=interpret)
    want_o, want_S, want_z = ret.retention_step_xla(q, k, v, g, S[1], z[1])
    for layer in (0, 2):
        np.testing.assert_array_equal(np.asarray(S1[layer]),
                                      np.asarray(S[layer]))
        np.testing.assert_array_equal(np.asarray(z1[layer]),
                                      np.asarray(z[layer]))
    np.testing.assert_array_equal(np.asarray(S1[1])[~live],
                                  np.asarray(S[1])[~live])
    np.testing.assert_array_equal(np.asarray(z1[1])[~live],
                                  np.asarray(z[1])[~live])
    if live.any():
        assert gap(S1[1][live], want_S[live]) < 1e-5
        assert gap(z1[1][live], want_z[live]) < 1e-5
        assert gap(o[live], want_o[live]) < 1e-5
        assert not np.array_equal(np.asarray(S1[1])[live],
                                  np.asarray(S[1])[live])


def test_the_step_kernel_takes_as_many_query_heads_as_a_tile_holds():
    S, z = carried(10, J=1, dv=16)
    q, k, v, g = (t[:, 0] for t in inputs(11, B=4, T=1, H=7, J=1, dv=16))
    active = jnp.asarray([True, True, False, True])
    want = ret.retention_step(q, k, v, g, S, z, jnp.int32(2), active)
    got = ret.retention_step(q, k, v, g, S, z, jnp.int32(2), active,
                             interpret=True)
    live = np.asarray(active)
    assert gap(got[0][live], want[0][live]) < 1e-5
    assert gap(got[1], want[1]) < 1e-5 and gap(got[2], want[2]) < 1e-5


# ------------------------------------------------- the block, end to end

def test_the_programs_parameters_have_the_references_layout(model):
    ref, sz, cfg, params = model
    assert cfg.layer_kinds == ((RETENTION, DENSE),) * 3
    assert transformer.layer_runs(cfg) == (((RETENTION, DENSE), 3),)
    assert cfg.qk_norm
    assert not cfg.tie_embeddings and cfg.kv_heads(RETENTION) == 2
    mine = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    theirs = jax.eval_shape(lambda: params)
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert jax.tree.leaves(mine) == jax.tree.leaves(theirs)
    assert set(params["layers"][0]) == set(ref.LAYER_LEAVES)


def test_a_fresh_gates_decay_has_the_half_lives_the_file_states(model):
    ref, sz, cfg, _ = model
    for bias in (init_params(jax.random.key(3), dataclasses.replace(
            cfg, n_layers=64, layer_kinds=((RETENTION, DENSE),) * 64))[
                "layers"][0]["b_g"],
            ref.seeded_params(7, dataclasses.replace(sz, n_layers=64))[
                "layers"][0]["b_g"]):
        assert bias.dtype == jnp.float32 and bias.shape == (64, 2)
        half = -1.0 / jnp.log2(jax.nn.sigmoid(bias))
        assert 64.0 <= float(jnp.min(half)) < 128.0
        assert 4096.0 < float(jnp.max(half)) <= 8192.0 * (1 + 1e-3)


def test_forward_is_the_references(model):
    ref, sz, cfg, params = model
    tokens = jax.random.randint(jax.random.key(1), (2, 40), 0, sz.vocab)
    want = ref.forward(params, tokens, sz)
    assert gap(forward(params, tokens, cfg), want) < TOLERANCE
    # the layers decide the logits, the embedding alone does not
    bare = ref.forward(dict(params, layers=jax.tree.map(
        jnp.zeros_like, params["layers"])), tokens, sz)
    assert gap(bare, want) > 0.1


def cached_logits(params, tokens, cfg, prompt_len=6):
    cache = decode.init_slot_cache(cfg, 1, 64)
    logits, cache = decode.slot_prefill(
        params, tokens[:, :prompt_len], cache, jnp.int32(0), cfg)
    got = [logits]
    for t in range(prompt_len, tokens.shape[1]):
        logits, cache = decode.slot_decode_step(
            params, cache, tokens[:, t], jnp.ones(1, bool), cfg)
        got.append(logits)
    return jnp.stack(got, axis=1)           # [1, T - prompt_len + 1, V]


@pytest.mark.parametrize("prompt_len", [1, 7, 16])
def test_prefill_then_cached_decoding_is_the_reference_on_the_growing_prefix(
        model, prompt_len):
    ref, sz, cfg, params = model
    tokens = jax.random.randint(jax.random.key(2), (1, 30), 0, sz.vocab)
    want = ref.forward(params, tokens, sz)[:, prompt_len - 1:]
    assert gap(cached_logits(params, tokens, cfg, prompt_len),
               want) < TOLERANCE


def test_greedy_generate_is_forwards_argmax_on_the_growing_prefix(model):
    _, sz, cfg, params = model
    prompt = jax.random.randint(jax.random.key(3), (2, 9), 0, sz.vocab)
    got = decode.generate(params, prompt, cfg, steps=8, max_len=64)
    seq = prompt
    for _ in range(8):
        nxt = jnp.argmax(forward(params, seq, cfg)[:, -1], axis=-1)
        seq = jnp.concatenate([seq, nxt[:, None].astype(seq.dtype)], axis=1)
    assert got.tolist() == seq[:, 9:].tolist()


def test_a_model_of_such_layers_alone_has_no_key_and_no_value(model):
    _, _, cfg, params = model
    cache = decode.init_slot_cache(cfg, 5, 64)
    assert set(cache) == {"pos", "tok", "ret", "ret_z"}
    (S,), (z,) = cache["ret"], cache["ret_z"]
    assert S.shape == (3, 5, 2, 8, 40) and z.shape == (3, 5, 2, 40)
    assert S.dtype == z.dtype == jnp.float32
    # nothing in it is sized by max_len, and no layer fetches rows
    other = decode.init_slot_cache(cfg, 5, 9216)
    assert jax.tree.map(jnp.shape, other) == jax.tree.map(jnp.shape, cache)
    assert decode.kv_rows_fetched(cfg, cache) is None
    assert decode.keeps_summaries(cfg)
    assert decode.decode_parts(cfg) == sorted([
        "embed", "head", "run0", "qkv", "retention_step", "attn_out", "mlp"])


def test_retention_layers_beside_attention_layers_share_one_cache():
    cfg = TransformerConfig(
        vocab=128, d_model=32, n_heads=4, n_layers=4, d_ff=64, max_seq=64,
        dtype=jnp.float32, n_kv_heads=2, qk_norm=True,
        layer_kinds=((RETENTION, DENSE), (FULL, DENSE), (RETENTION, DENSE),
                     (RETENTION, DENSE)))
    params = scaled(init_params(jax.random.key(4), cfg))
    assert "q_norm" in params["layers"][1] and "w_g" not in params["layers"][1]
    cache = decode.init_slot_cache(cfg, 2, 64)
    assert [a is None for a in cache["k"]] == [True, False, True]
    assert [a is None for a in cache["ret"]] == [False, True, False]
    assert cache["ret"][2].shape == (2, 2, 2, 8, 40)
    tokens = jax.random.randint(jax.random.key(5), (1, 24), 0, 128)
    want = forward(params, tokens, cfg)[:, 5:]
    assert gap(cached_logits(params, tokens, cfg), want) < TOLERANCE


def slot_state(cache, row):
    return [np.asarray(a[:, row]) for name in ("ret", "ret_z")
            for a in cache[name]] + [int(cache["pos"][row])]


def test_a_reused_slot_never_sees_its_predecessor(model):
    _, sz, cfg, params = model
    first = jax.random.randint(jax.random.key(6), (1, 12), 0, sz.vocab)
    second = jax.random.randint(jax.random.key(7), (1, 5), 0, sz.vocab)
    used = decode.init_slot_cache(cfg, 2, 64)
    _, used = decode.slot_prefill(params, first, used, jnp.int32(1), cfg)
    for t in (3, 4, 5):
        _, used = decode.slot_decode_step(
            params, used, jnp.asarray([0, t], jnp.int32),
            jnp.asarray([False, True]), cfg)
    _, used = decode.slot_prefill(params, second, used, jnp.int32(1), cfg)
    fresh = decode.init_slot_cache(cfg, 2, 64)
    _, fresh = decode.slot_prefill(params, second, fresh, jnp.int32(1), cfg)
    for was, now in zip(slot_state(fresh, 1), slot_state(used, 1)):
        np.testing.assert_array_equal(was, now)


@pytest.mark.parametrize("served", [False, True],
                         ids=["active mask", "IDLE in the served row"])
def test_a_row_left_out_of_a_step_keeps_its_state_bit_for_bit(model, served):
    _, sz, cfg, params = model
    prompts = jax.random.randint(jax.random.key(8), (3, 8), 0, sz.vocab)
    cache = decode.init_slot_cache(cfg, 3, 64)
    for row in range(3):
        _, cache = decode.slot_prefill(params, prompts[row:row + 1], cache,
                                       jnp.int32(row), cfg)
    before = jax.tree.map(np.asarray, cache)
    if served:
        steer = jnp.asarray([decode.CARRY, decode.IDLE, 5], jnp.int32)
        picks, cache = decode.slot_decode_step(params, cache, steer, None,
                                               cfg)
        assert picks.shape == (3,) and picks.dtype == jnp.int32
    else:
        _, cache = decode.slot_decode_step(
            params, cache, jnp.asarray([3, 4, 5], jnp.int32),
            jnp.asarray([True, False, True]), cfg)
    after = jax.tree.map(np.asarray, cache)
    for was, now in zip(slot_state(before, 1), slot_state(after, 1)):
        np.testing.assert_array_equal(was, now)
    for row in (0, 2):
        for was, now in zip(slot_state(before, row)[:2],
                            slot_state(after, row)):
            assert not np.array_equal(was, now)
    assert np.asarray(cache["pos"]).tolist() == [9, 8, 9]
    assert int(cache["tok"][1]) == int(before["tok"][1])


# --------------------------------------------------------- planted faults

def without(params, leaf, value=0.0):
    return dict(params, layers=tuple(
        {k: jnp.full_like(v, value) if k == leaf else v
         for k, v in run.items()} for run in params["layers"]))


def state_in_bfloat16(monkeypatch):
    real = ret.retention_step_xla

    def rounded(q, k, v, g, S, z):
        return real(q, k, v, g,
                    S.astype(jnp.bfloat16).astype(jnp.float32),
                    z.astype(jnp.bfloat16).astype(jnp.float32))

    monkeypatch.setattr(ret, "retention_step_xla", rounded)


def forgotten_state(monkeypatch):
    """Every step starts from nothing, as a decode step that dropped
    what the prefill left would."""
    real = ret.retention_step_xla
    monkeypatch.setattr(
        ret, "retention_step_xla", lambda q, k, v, g, S, z: real(
            q, k, v, g, jnp.zeros_like(S), jnp.zeros_like(z)))


def softmax_scores(monkeypatch):
    """exp(q.k) where the layer has (q.k)^2: attention, not retention."""
    def attention_form(q, k, v, g):
        from ray_tpu.ops.attention import attention
        return attention(q, k, v, causal=True), ret.zero_state(
            q.shape[0], k.shape[2], q.shape[3], v.shape[3])

    monkeypatch.setattr(transformer, "retention", attention_form)


FAULTS = {
    # name: (changes to the config, to the weights, a patch, cached?)
    "no norm on q and k": ({"qk_norm": False}, None, None, False),
    "no rope": ({"rope": False}, None, None, False),
    "no gate: the past never decays": (
        {}, lambda p: without(without(p, "w_g"), "b_g", 30.0), None, False),
    "the gate's bias left out": ({}, lambda p: without(p, "b_g"), None,
                                 False),
    "softmax in the place of the square": ({}, None, softmax_scores, False),
    "the state forgotten between steps": ({}, None, forgotten_state, True),
    "the state rounded to bfloat16 between steps": (
        {}, None, state_in_bfloat16, True),
}


@pytest.fixture
def fresh_programs():
    """A patch changes what a jitted program traces: drop what was
    traced before it and after it."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_fails_the_comparison(model, monkeypatch,
                                              fresh_programs, fault):
    ref, sz, cfg, params = model
    changes, weights, patch, cached = FAULTS[fault]
    tokens = jax.random.randint(jax.random.key(9), (1, 30), 0, sz.vocab)
    want = ref.forward(params, tokens, sz)
    if patch:
        patch(monkeypatch)
    cfg = dataclasses.replace(cfg, **changes)
    params = weights(params) if weights else params
    if cached:
        got, want = cached_logits(params, tokens, cfg), want[:, 5:]
    else:
        got = forward(params, tokens, cfg)
    assert gap(got, want) > 30 * TOLERANCE, fault


def test_bfloat16_where_float32_is_stated_fails(model, monkeypatch,
                                                fresh_programs):
    """The precision below the one the configuration states for the
    state: a hundred times over the tolerance within 24 steps."""
    ref, sz, cfg, params = model
    tokens = jax.random.randint(jax.random.key(9), (1, 30), 0, sz.vocab)
    want = ref.forward(params, tokens, sz)[:, 5:]
    sound = gap(cached_logits(params, tokens, cfg), want)
    state_in_bfloat16(monkeypatch)
    jax.clear_caches()
    assert gap(cached_logits(params, tokens, cfg), want) > 46 * max(
        sound, TOLERANCE / 10)


# ------------------------------------------------------ what is refused

@pytest.mark.parametrize("axis", ["tp", "sp", "pp"])
def test_a_sharded_retention_mixer_is_refused(model, axis):
    _, sz, cfg, params = model
    pcfg = ParallelConfig(**{axis: axis})
    with pytest.raises(ValueError, match="retention layers runs on one"):
        param_specs(pcfg, cfg)
    with pytest.raises(ValueError, match="retention layers runs on one"):
        forward(params, jnp.zeros((1, 4), jnp.int32), cfg, pcfg)
    specs = param_specs(ParallelConfig(dp="dp"), cfg)
    assert jax.tree.structure(specs, is_leaf=lambda s: isinstance(
        s, jax.sharding.PartitionSpec)) == jax.tree.structure(params)


def test_a_config_the_feature_map_cannot_hold_is_refused():
    with pytest.raises(ValueError, match="even width"):
        TransformerConfig(vocab=128, d_model=32, n_heads=4, n_layers=2,
                          d_ff=64, qk_head_dim=7, rotary_dim=6,
                          layer_kinds=((RETENTION, DENSE),) * 2)


def test_a_gradient_exists_through_the_retention_layers(model):
    _, sz, cfg, params = model
    tokens = jax.random.randint(jax.random.key(10), (2, 17), 0, sz.vocab)
    batch = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}
    loss, grads = jax.value_and_grad(transformer.loss_fn)(params, batch, cfg)
    assert np.isfinite(float(loss))
    for name in ("wq", "wk", "wv", "wo", "w_g", "b_g", "q_norm", "k_norm"):
        assert float(jnp.max(jnp.abs(grads["layers"][0][name]))) > 0, name


# --------------------------------------------------------- the slot engine

def test_the_engine_serves_it_and_counts_the_rows_whose_state_it_moves(
        model):
    """Through ``JaxSlotEngine`` as the scheduler drives it: a request's
    tokens are ``generate``'s, a slot is reused, and the rows each
    dispatched step moved land in the table of whoever is recording:
    more than the answers owed, by the steps dispatched ahead."""
    from ray_tpu import serve
    from ray_tpu.util.phases import recording

    _, sz, cfg, params = model
    engine = serve.JaxSlotEngine(params, cfg, slots=2, max_len=64)
    table, answered = {}, 0
    prompts = [[5, 9, 2, 77, 31], [8] * 11, [3, 1, 4]]
    wants = [decode.generate(params, jnp.asarray([p], jnp.int32), cfg,
                             steps=6, max_len=64)[0].tolist()
             for p in prompts]
    with recording(table):
        for slot, (prompt, want) in zip((0, 1, 0), zip(prompts, wants)):
            last = engine.prefill(slot, prompt)
            got = [last]
            while len(got) < 6:
                out = engine.step({slot: last})
                answered += len(out)
                if slot in out:
                    last = out[slot]
                    got.append(last)
            assert got == want
    steps, rows = table["serve.engine.state_rows"]
    assert answered == 15
    # a row a step: every dispatched step of these calls held one; each
    # request's first call dispatched two, and its last answer left one
    # in flight that the next prefill dropped
    assert steps == rows == 15 + 3
    assert "serve.engine.kv_rows_read" not in table
    # the capacity check stays: the rope's table ends at max_len
    with pytest.raises(ValueError, match="max_len"):
        engine.prefill(1, [1] * 64)
