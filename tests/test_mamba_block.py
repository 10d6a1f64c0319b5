"""A model with Mamba layers beside an attention layer, at a tiny size
on the CPU: ``forward`` against the ``jamba`` family's plain reference
on seeded weights; greedy ``generate`` and ``slot_prefill`` +
``slot_decode_step`` against ``forward`` on the growing prefix; what a
slot's recurrent state may and may not suffer (a reused slot, an idle
row); each mechanism of the mixer planted as a fault, which must fail
the comparison; the shardings that are not expressed, refused; and the
slot engine's span and counter of its prefills.

Same structure as the published model: four layers, layer 1 attention
(20 heads on 1 K/V head there, 4 on 1 here, no rope), the others Mamba
(expansion 2, dt through a low rank with the three inner norms, a
convolution of 4), a tied head.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import loader
from ray_tpu.models import (ParallelConfig, TransformerConfig, decode,
                            forward, init_params, param_specs)
from ray_tpu.models import transformer
from ray_tpu.ops import ssm

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
# float32 on the CPU: the program and the reference run the same
# mathematics in another order (the program's chunked scan multiplies a
# chunk's decays together where the reference steps through them), so
# they differ by rounding: 4e-7 is what the sound program reads. 2e-5
# leaves that fifty times of room and lies as far under the least
# planted fault (9e-4: the recurrent state rounded to bfloat16 between
# decode steps, which is the precision below the one the configuration
# states for it; every mechanism left out reads 0.2 or more).
TOLERANCE = 2e-5


def scaled(params):
    """The 0.02 initializer leaves a 32-wide model's logits to its
    embedding alone; the matrices are scaled up until the layers decide
    them, as they do at the published width."""
    return jax.tree.map(lambda a: a * 6 if a.ndim >= 3 and a.shape[-2:] != (
        8, 64) else a, params)       # every stacked matrix but a_log


@pytest.fixture(scope="module")
def model():
    family = loader.find_family(loader.load_benchmark(), TINY)
    ref = loader.family_module(family, "reference")
    sz = ref.sizes_of(TINY)
    cfg = loader.family_module(family, "program").program_config(TINY, 64)
    return ref, sz, cfg, scaled(ref.seeded_params(2**31 + 5, sz))


def gap(a, b):
    return float(jnp.max(jnp.abs(a - b)))


def test_the_programs_parameters_have_the_references_layout(model):
    ref, sz, cfg, params = model
    assert cfg.layer_kinds == (("mamba", "dense"), ("full", "dense"),
                               ("mamba", "dense"), ("mamba", "dense"))
    assert transformer.layer_runs(cfg) == (
        (("mamba", "dense"), 1), (("full", "dense"), 1),
        (("mamba", "dense"), 2))
    assert not cfg.rope and cfg.kv_heads("full") == 1
    mine = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    assert jax.tree.structure(mine) == jax.tree.structure(params)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in zip(
        jax.tree.leaves(mine), jax.tree.leaves(params)))
    # the program's own initialisation starts the recurrence as the
    # family does: dt between 1e-3 and 1e-1, A = -1..-N, D = 1
    run = init_params(jax.random.key(1), cfg)["layers"][2]
    step = jax.nn.softplus(run["dt_bias"])
    assert 1e-3 <= float(step.min()) and float(step.max()) <= 1e-1
    np.testing.assert_allclose(np.exp(run["a_log"][0, :, 0]),
                               np.arange(1, 9), rtol=1e-6)
    assert run["a_log"].dtype == run["d_skip"].dtype == jnp.float32


def test_forward_is_the_references(model):
    ref, sz, cfg, params = model
    tokens = jax.random.randint(jax.random.key(1), (2, 40), 0, sz.vocab)
    want = ref.forward(params, tokens, sz)
    assert gap(forward(params, tokens, cfg), want) < TOLERANCE
    assert float(jnp.max(jnp.abs(want))) > 0.2     # logits worth the name


@pytest.mark.parametrize("prompt_len", [2, 7, 16])
def test_prefill_then_cached_decoding_is_forward_on_the_growing_prefix(
        model, prompt_len):
    """Logits, not tokens: the prompt's last position from
    ``slot_prefill`` (a prompt of 2 is shorter than the convolution's
    tail) and every later one from ``slot_decode_step``, against the
    full forward over the whole sequence and against the reference's;
    and the same argmax at every position."""
    ref, sz, cfg, params = model
    tokens = jax.random.randint(jax.random.key(prompt_len), (2, 40), 0,
                                sz.vocab)
    want = forward(params, tokens, cfg)
    cache = decode.init_slot_cache(cfg, 2, 64)
    got = []
    for row in range(2):
        logits, cache = decode.slot_prefill(
            params, tokens[row:row + 1, :prompt_len], cache,
            jnp.int32(row), cfg)
        got.append(logits[0])
    got = [jnp.stack(got)]
    for t in range(prompt_len, 40):
        logits, cache = decode.slot_decode_step(
            params, cache, tokens[:, t], jnp.ones(2, bool), cfg)
        got.append(logits)
    got = jnp.stack(got, axis=1)                    # [2, steps, V]
    assert gap(got, want[:, prompt_len - 1:]) < TOLERANCE
    assert gap(got, ref.forward(params, tokens, sz)[:, prompt_len - 1:]) \
        < TOLERANCE
    np.testing.assert_array_equal(
        np.asarray(jnp.argmax(got, -1)),
        np.asarray(jnp.argmax(want[:, prompt_len - 1:], -1)))
    assert int(cache["pos"][0]) == 40


def test_greedy_generate_is_forwards_argmax_on_the_growing_prefix(model):
    _, sz, cfg, params = model
    prompt = jax.random.randint(jax.random.key(3), (2, 9), 0, sz.vocab)
    out = decode.generate(params, prompt, cfg, steps=12, max_len=64)
    seq = prompt
    for t in range(12):
        nxt = jnp.argmax(forward(params, seq, cfg)[:, -1], axis=-1)
        np.testing.assert_array_equal(np.asarray(out[:, t]),
                                      np.asarray(nxt))
        seq = jnp.concatenate([seq, nxt[:, None].astype(seq.dtype)], axis=1)


def test_the_cache_holds_each_run_the_state_of_its_own_kind(model):
    _, _, cfg, _ = model
    cache = decode.init_slot_cache(cfg, 5, 64)
    assert [None if a is None else a.shape for a in cache["k"]] == [
        None, (1, 5, 64, 8), None]              # one K/V head: flat rows
    assert [None if a is None else (a.shape, a.dtype)
            for a in cache["ssm"]] == [
        ((1, 5, 8, 64), jnp.float32), None, ((2, 5, 8, 64), jnp.float32)]
    # the tail's K - 1 rows lie before the slots
    assert [None if a is None else a.shape for a in cache["conv"]] == [
        (1, 3, 5, 64), None, (2, 3, 5, 64)]
    # a model without Mamba layers has neither tuple
    plain = decode.init_slot_cache(TransformerConfig(), 3, 64)
    assert "ssm" not in plain and "conv" not in plain


def slot_state(cache, slot):
    """One slot's share of every state array, with the layers first."""
    return [np.asarray(a[:, :, slot] if name == "conv" else a[:, slot])
            for name in ("ssm", "conv", "k", "v")
            for a in cache[name] if a is not None]


def test_a_reused_slot_never_sees_its_predecessor(model):
    """A slot that held a long request, and rode a step more after it,
    is prefilled again: its recurrent state, its tail, and the rows the
    new prompt covers are bit for bit those of a fresh cache given the
    same prompt, and so are the logits of the steps that follow."""
    _, sz, cfg, params = model
    old = jax.random.randint(jax.random.key(4), (1, 20), 0, sz.vocab)
    new = jax.random.randint(jax.random.key(5), (1, 6), 0, sz.vocab)
    feed = jnp.asarray([7, 9], jnp.int32)
    both = jnp.ones(2, bool)

    def start(cache):
        logits, cache = decode.slot_prefill(params, new, cache,
                                            jnp.int32(1), cfg)
        return logits, cache

    used = decode.init_slot_cache(cfg, 2, 64)
    _, used = decode.slot_prefill(params, old, used, jnp.int32(1), cfg)
    for _ in range(5):
        _, used = decode.slot_decode_step(params, used, feed, both, cfg)
    got_first, used = start(used)
    want_first, fresh = start(decode.init_slot_cache(cfg, 2, 64))
    np.testing.assert_array_equal(np.asarray(got_first),
                                  np.asarray(want_first))
    for got, want in zip(slot_state(used, 1), slot_state(fresh, 1)):
        # of the rows, those the prompt covers: the tail beyond them is
        # stale in the one and zero in the other, and never attended
        rows = got.shape[1] if got.shape[1] != 64 else 6
        np.testing.assert_array_equal(got[:, :rows], want[:, :rows])
    only = jnp.asarray([False, True])
    for _ in range(4):
        got, used = decode.slot_decode_step(params, used, feed, only, cfg)
        want, fresh = decode.slot_decode_step(params, fresh, feed, only, cfg)
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(want[1]))


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["the layer's slice", "the step's kernel"])
@pytest.mark.parametrize("served", [False, True],
                         ids=["active mask", "IDLE in the served row"])
def test_a_row_left_out_of_a_step_keeps_its_recurrent_state_bit_for_bit(
        model, served, kernel, monkeypatch, fresh_programs):
    _, sz, cfg, params = model
    if kernel:  # the decode step's ``ssm_step``, interpreted on the CPU
        monkeypatch.setattr(ssm, "carried_step", functools.partial(
            ssm.carried_step, interpret=True))
        fresh_programs()
    prompts = jax.random.randint(jax.random.key(6), (3, 8), 0, sz.vocab)
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
    for was, now in zip(slot_state(before, 1)[:4], slot_state(after, 1)):
        np.testing.assert_array_equal(was, now)
    for row in (0, 2):
        for was, now in zip(slot_state(before, row)[:4],
                            slot_state(after, row)):
            assert not np.array_equal(was, now)
    assert np.asarray(cache["pos"]).tolist() == [9, 8, 9]
    assert int(cache["tok"][1]) == int(before["tok"][1])


# --------------------------------------------------------- planted faults

def without(params, leaf, value=0.0):
    return dict(params, layers=tuple(
        {k: jnp.full_like(v, value) if k == leaf else v
         for k, v in run.items()} for run in params["layers"]))


def no_inner_norms(monkeypatch):
    real = transformer.rmsnorm

    def outer_only(x, weight, *, eps):
        return real(x, weight, eps=eps) if x.shape[-1] == 32 else x

    monkeypatch.setattr(transformer, "rmsnorm", outer_only)


def forgotten_tail(monkeypatch):
    """Every step's convolution starts from nothing, as a decode step
    that dropped its tail would."""
    real = ssm.causal_conv
    monkeypatch.setattr(ssm, "causal_conv",
                        lambda u, w, b, tail=None: real(u, w, b, None))


def state_in_bfloat16(monkeypatch):
    real = ssm.selective_step

    def rounded(u, dt, A, B, C, D, state):
        y, state = real(u, dt, A, B, C, D,
                        state.astype(jnp.bfloat16).astype(jnp.float32))
        return y, state

    monkeypatch.setattr(ssm, "selective_step", rounded)


FAULTS = {
    # name: (changes to the config, to the weights, a patch, cached?)
    "rope on the attention layer": ({"rope": True}, None, None, False),
    "no skip D": ({}, lambda p: without(p, "d_skip"), None, False),
    "no bias on dt": ({}, lambda p: without(p, "dt_bias"), None, False),
    "no bias on the convolution": (
        {}, lambda p: without(p, "conv_b"), None, False),
    "A = -1 for every state": (
        {}, lambda p: without(p, "a_log"), None, False),
    "no inner norms": ({}, None, no_inner_norms, False),
    "the convolution's tail forgotten between steps": (
        {}, None, forgotten_tail, True),
    "the state rounded to bfloat16 between steps": (
        {}, None, state_in_bfloat16, True),
}


def cached_logits(params, tokens, cfg, prompt_len=6):
    cache = decode.init_slot_cache(cfg, 1, 64)
    logits, cache = decode.slot_prefill(
        params, tokens[:, :prompt_len], cache, jnp.int32(0), cfg)
    got = [logits]
    for t in range(prompt_len, tokens.shape[1]):
        logits, cache = decode.slot_decode_step(
            params, cache, tokens[:, t], jnp.ones(1, bool), cfg)
        got.append(logits)
    return jnp.stack(got, axis=1)


@pytest.fixture
def fresh_programs():
    """A patch under the two serving programs is traced only by a
    program that has not compiled yet: forget them before, and after,
    so that no later test runs the bent one."""
    def forget():
        decode.slot_prefill.clear_cache()
        decode.slot_decode_step.clear_cache()

    yield forget
    forget()


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_fails_the_comparison(model, monkeypatch,
                                              fresh_programs, fault):
    """Each mechanism left out or bent in the program moves the logits
    by at least thirty times the tolerance, so none can go missing
    inside it. The sound program passes on the same tokens."""
    ref, sz, cfg, params = model
    changes, reweigh, patch, cached = FAULTS[fault]
    tokens = jax.random.randint(jax.random.key(7), (1, 40), 0, sz.vocab)
    want = ref.forward(params, tokens, sz)
    run = cached_logits if cached else forward
    if cached:
        want = want[:, 5:]
    sound = gap(run(params, tokens, cfg), want)
    assert sound < TOLERANCE, sound
    if patch is not None:
        patch(monkeypatch)
        fresh_programs()
    planted = gap(run(reweigh(params) if reweigh else params, tokens,
                      dataclasses.replace(cfg, **changes)), want)
    print(f"{fault}: sound {sound:.2e}, planted {planted:.2e}")
    assert planted > 30 * TOLERANCE, planted


def test_bfloat16_where_float32_is_stated_fails(model):
    ref, sz, cfg, params = model
    tokens = jax.random.randint(jax.random.key(8), (1, 40), 0, sz.vocab)
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16)
                       if a.ndim >= 3 and a.shape[-2:] != (8, 64) or
                       a.ndim == 2 and a.shape[0] == 128 else a, params)
    got = forward(low, tokens, dataclasses.replace(cfg, dtype=jnp.bfloat16))
    assert gap(got, ref.forward(params, tokens, sz)) > 30 * TOLERANCE


# ------------------------------------------------ what is not expressed

@pytest.mark.parametrize("axis", ["tp", "sp", "pp"])
def test_a_sharded_mamba_mixer_is_refused(model, axis):
    _, sz, cfg, params = model
    pcfg = ParallelConfig(**{axis: axis})
    with pytest.raises(ValueError, match=f"Mamba layers.*{axis}"):
        param_specs(pcfg, cfg)
    with pytest.raises(ValueError, match=f"Mamba layers.*{axis}"):
        forward(params, jnp.zeros((1, 8), jnp.int32), cfg, pcfg)
    # dp alone is data: every leaf is replicated
    specs = param_specs(ParallelConfig(dp="dp"), cfg)
    assert jax.tree.structure(specs, is_leaf=lambda s: isinstance(
        s, jax.sharding.PartitionSpec)) == jax.tree.structure(params)


@pytest.mark.parametrize("changes,match", [
    ({"rotary_dim": 0}, "rope=False"),
    ({"layer_kinds": (("mamba", "dense"),) * 4}, "ssm_inner"),
    ({"layer_kinds": (("mamba", "dense"),) * 4, "ssm_inner": 64,
      "ssm_state": 8, "ssm_dt_rank": 4, "ssm_conv": 1}, "ssm_inner"),
    ({"layer_kinds": (("conv", "dense"),) * 4}, "unknown kind")])
def test_a_config_that_says_nothing_is_refused(changes, match):
    with pytest.raises(ValueError, match=match):
        TransformerConfig(**changes)
    # rotary_dim None is still the whole head, and a width still a width
    assert TransformerConfig().rope_dim == 32
    assert TransformerConfig(rotary_dim=8).rope_dim == 8


def test_a_gradient_exists_through_the_mamba_layers(model):
    _, sz, cfg, params = model
    tokens = jax.random.randint(jax.random.key(9), (2, 17), 0, sz.vocab)
    batch = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}
    loss, grads = jax.value_and_grad(transformer.loss_fn)(params, batch, cfg)
    assert np.isfinite(float(loss))
    for name in ("w_in", "conv_w", "w_x", "w_dt", "dt_bias", "a_log",
                 "d_skip", "w_out", "dt_norm", "b_norm", "c_norm"):
        assert float(jnp.max(jnp.abs(grads["layers"][0][name]))) > 0, name


# --------------------------------------------------------- the slot engine

def test_the_engine_serves_it_and_times_its_prefills(model):
    """Through ``JaxSlotEngine`` as the scheduler drives it: a request's
    tokens are ``generate``'s, a slot is reused, and the engine's own
    span and counter of its prefills land in the table of whoever is
    recording."""
    from ray_tpu import serve
    from ray_tpu.util.phases import recording

    _, sz, cfg, params = model
    engine = serve.JaxSlotEngine(params, cfg, slots=2, max_len=64)
    table = {}
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
                if slot in out:
                    last = out[slot]
                    got.append(last)
            assert got == want
    assert table["serve.engine.prefill"][0] == 3
    assert table["serve.engine.prefill"][1] > 0.0
    assert table["serve.engine.prefill_tokens"] == [3, 5 + 11 + 3]
