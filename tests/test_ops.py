"""Differential tests for the Pallas ops against their XLA oracles.

Runs the flash kernel in ``interpret=True`` mode so the exact kernel
code (grid, block specs, scratch accumulators) is exercised on CPU;
the real-TPU compile is covered by the bench/driver runs.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import attention, flash_attention


def _qkv(key, B=2, T=256, H=2, D=64, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, T, H, D), dtype)
    k = jax.random.normal(kk, (B, T, H, D), dtype)
    v = jax.random.normal(kv, (B, T, H, D), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("block_q,block_k", [(128, 128), (64, 128),
                                             (128, 64)])
def test_flash_matches_oracle(causal, block_q, block_k):
    q, k, v = _qkv(jax.random.key(0))
    want = attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=block_q,
                          block_k=block_k, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_multi_kv_block_accumulation():
    # T = 4 * block ensures the online-softmax rescale path (alpha)
    # actually fires across k/v blocks.
    q, k, v = _qkv(jax.random.key(1), T=256)
    want = attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_bf16():
    q, k, v = _qkv(jax.random.key(2), dtype=jnp.bfloat16)
    want = attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("block_q,block_k", [(64, 64), (32, 64),
                                             (64, 32)])
def test_flash_grad_matches_oracle(causal, block_q, block_k):
    # The Pallas backward (blocked dK/dV + dQ kernels over the saved
    # logsumexp) against XLA's autodiff through the reference math.
    q, k, v = _qkv(jax.random.key(3), T=128)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_q=block_q, block_k=block_k,
                                       interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention(q, k, v, causal=causal) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=2e-4, rtol=2e-4)


def test_flash_grad_bf16():
    q, k, v = _qkv(jax.random.key(5), T=128, dtype=jnp.bfloat16)

    def loss(attn):
        def f(q, k, v):
            return jnp.sum(
                attn(q, k, v).astype(jnp.float32) ** 2)
        return f

    g_flash = jax.grad(loss(functools.partial(
        flash_attention, causal=True, block_q=64, block_k=64,
        interpret=True)), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(functools.partial(attention, causal=True)),
                     argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(gf, np.float32),
                                   np.asarray(gr, np.float32),
                                   atol=1e-1, rtol=1e-1)


def _loss(attn):
    return lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("T,block_q,block_k", [
    (512, 256, 256), (1024, 256, 512), (1024, 512, 256)])
def test_flash_bf16_large_blocks_match_oracle(causal, T, block_q, block_k):
    # bfloat16 operands go into the products as they are; blocks above
    # 128 put a wholly masked block, a diagonal block and a wholly live
    # one (which skips the mask) into one call, with several k blocks a
    # row so that the rescale fires, and with block_q != block_k the
    # clamped index maps name other blocks than the grid's own.
    q, k, v = _qkv(jax.random.key(6), B=1, T=T, H=2, D=128,
                   dtype=jnp.bfloat16)
    flash = functools.partial(flash_attention, causal=causal,
                              block_q=block_q, block_k=block_k,
                              interpret=True)
    ref = functools.partial(attention, causal=causal)
    np.testing.assert_allclose(
        np.asarray(flash(q, k, v), np.float32),
        np.asarray(ref(q, k, v), np.float32), atol=3e-2, rtol=3e-2)
    g_flash = jax.grad(_loss(flash), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(_loss(ref), argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(gf, np.float32),
                                   np.asarray(gr, np.float32),
                                   atol=1e-1, rtol=1e-1)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_float32_keeps_float32_products_at_its_own_blocks(causal):
    # no blocks given: the table's (384 whole); a float32 input is
    # multiplied in float32, so the tolerances of the explicit-block
    # cases above hold
    q, k, v = _qkv(jax.random.key(7), B=1, T=384)
    flash = functools.partial(flash_attention, causal=causal,
                              interpret=True)
    ref = functools.partial(attention, causal=causal)
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    g_flash = jax.grad(_loss(flash), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(_loss(ref), argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=2e-4, rtol=2e-4)


# (block_q, block_k) of flash_fwd, flash_bwd_dkv, flash_bwd_dq at two
# bytes an element, as swept on the chip (PERF.md section 6, PR 29)
BLOCK_TABLE = {
    128: ((128, 128), (128, 128), (128, 128)),
    256: ((256, 256), (256, 256), (256, 256)),
    384: ((384, 384), (384, 384), (384, 384)),
    640: ((640, 640), (128, 128), (640, 640)),
    896: ((896, 896), (128, 128), (896, 896)),
    1024: ((1024, 1024), (512, 512), (1024, 1024)),
    2048: ((1024, 1024), (512, 512), (1024, 1024)),
}


@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("T", sorted(BLOCK_TABLE))
def test_flash_blocks_table(T, head_dim):
    from ray_tpu.ops.attention import KERNELS, flash_blocks

    got = tuple(flash_blocks(T, head_dim, 2, kernel) for kernel in KERNELS)
    assert got == BLOCK_TABLE[T]
    # up to 512 bytes a row (float32 at 128) the caps hold; at twice
    # that, half the rows a block
    assert got == tuple(flash_blocks(T, 128, 4, kernel)
                        for kernel in KERNELS)
    wide = tuple(flash_blocks(T, 256, 4, kernel) for kernel in KERNELS)
    for pair, narrow in zip(wide, got):
        for block, was in zip(pair, narrow):
            assert block % 128 == 0 and T % block == 0
            assert block <= max(128, was)
    assert flash_blocks(2048, 256, 4, "flash_fwd") == (512, 512)
    assert flash_blocks(2048, 256, 4, "flash_bwd_dkv") == (256, 256)


def _program(q, k, v, **kwargs):
    return str(jax.make_jaxpr(functools.partial(
        flash_attention, **kwargs))(q, k, v))


@pytest.mark.parametrize("T,kernel_runs", [(128, True), (640, True),
                                           (192, False)])
def test_flash_takes_every_multiple_of_128(T, kernel_runs, monkeypatch):
    # the rule of a TPU process (traced here, not run): a prompt of 128
    # is a prefill program of the serving cell and must hold the kernel;
    # 192 is no multiple and runs the reference
    import importlib

    monkeypatch.setattr(importlib.import_module("ray_tpu.ops.attention"),
                        "_on_tpu", lambda: True)
    q, k, v = _qkv(jax.random.key(8), B=1, T=T, H=1)
    assert ("pallas_call" in _program(q, k, v)) == kernel_runs


@pytest.mark.parametrize("T,D", [(32, 16), (128, 64), (192, 64), (640, 64),
                                 (32, 48), (256, 192)])
def test_flash_interpret_runs_the_kernel_at_its_own_blocks(T, D):
    # interpret mode exists to exercise the kernel: with no blocks given
    # it runs at every T, one the table has no blocks for taken whole
    # (chip_smoke's CPU composition checks the kernels at T=32), and at
    # head dimensions the scratch rows' width neither divides nor is
    # divided by
    q, k, v = _qkv(jax.random.key(8), B=1, T=T, H=1, D=D)
    flash = functools.partial(flash_attention, interpret=True)
    assert "pallas_call" in _program(q, k, v, interpret=True)
    np.testing.assert_allclose(
        np.asarray(flash(q, k, v)), np.asarray(attention(q, k, v)),
        atol=2e-5, rtol=2e-5)
    g_flash = jax.grad(_loss(flash), argnums=(0, 1, 2))(q, k, v)
    assert "pallas_call" in str(jax.make_jaxpr(jax.grad(
        _loss(flash), argnums=(0, 1, 2)))(q, k, v))
    g_ref = jax.grad(_loss(attention), argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=2e-4, rtol=2e-4)


def test_flash_explicit_blocks_come_as_a_pair():
    q, k, v = _qkv(jax.random.key(9), B=1, T=384, H=1)
    with pytest.raises(ValueError, match="both block_q and block_k"):
        flash_attention(q, k, v, block_q=128, interpret=True)
    # a k-block that is no multiple of a vreg's lanes, nor a divisor
    got = flash_attention(q, k, v, block_q=128, block_k=192,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(attention(q, k, v)),
                               atol=2e-5, rtol=2e-5)


def test_flash_fallback_paths():
    # Non-block-aligned T and decode (Tq != Tk) fall back to the
    # reference — results must still be exact.
    q, k, v = _qkv(jax.random.key(4), T=96)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, block_q=64, block_k=64)),
        np.asarray(attention(q, k, v)), atol=1e-6)
    qd = q[:, -1:], k, v
    np.testing.assert_allclose(
        np.asarray(flash_attention(*qd)),
        np.asarray(attention(*qd)), atol=1e-6)


_TINY = {
    "f32": dict(vocab=97, d_model=64, n_heads=4, n_layers=3, d_ff=128,
                max_seq=64, dtype=jnp.float32),
    "bf16": dict(vocab=61, d_model=32, n_heads=2, n_layers=2, d_ff=64,
                 max_seq=32, dtype=jnp.bfloat16),
}


def _one_body(name, max_len):
    """``slot_prefill`` of each of B prompts into its slot, then
    ``slot_decode_step`` with every row active, against ``forward()``
    on the growing prefix; returns the step's jaxpr."""
    from ray_tpu.models import TransformerConfig, forward, init_params
    from ray_tpu.models import decode

    cfg = TransformerConfig(**_TINY[name])
    params = init_params(jax.random.key(0), cfg)
    B, T0, steps = 3, 5, 6
    prompt = jax.random.randint(jax.random.key(1), (B, T0), 0, cfg.vocab)
    tol = 2e-5 if cfg.dtype == jnp.float32 else 3e-2

    cache = decode.init_slot_cache(cfg, B, max_len or T0 + steps)
    rows = []
    for b in range(B):
        logits, cache = decode.slot_prefill(
            params, prompt[b:b + 1], cache, jnp.int32(b), cfg)
        rows.append(logits)
    logits = jnp.concatenate(rows)
    prefix = np.asarray(prompt)
    full = forward(params, jnp.asarray(prefix), cfg)[:, -1]
    active = jnp.ones(B, bool)
    for t in range(steps):
        nxt = np.asarray(jnp.argmax(full, axis=-1))
        np.testing.assert_array_equal(
            np.asarray(jnp.argmax(logits, axis=-1)), nxt,
            err_msg=f"step {t}")
        np.testing.assert_allclose(np.asarray(logits), np.asarray(full),
                                   atol=tol, err_msg=f"step {t}")
        prefix = np.concatenate([prefix, nxt[:, None]], axis=1)
        logits, cache = decode.slot_decode_step(
            params, cache, jnp.asarray(nxt, jnp.int32), active, cfg)
        full = forward(params, jnp.asarray(prefix), cfg)[:, -1]
    np.testing.assert_array_equal(np.asarray(cache["pos"]),
                                  [T0 + steps] * B)
    return str(jax.make_jaxpr(
        functools.partial(decode.slot_decode_step, cfg=cfg))(
            params, cache, jnp.asarray(nxt, jnp.int32), active))


@pytest.mark.parametrize("name", sorted(_TINY))
def test_train_prefill_and_decode_are_one_body(name):
    """``transformer.block`` under its three callers: ``slot_prefill``
    of each of B prompts into its slot, then ``slot_decode_step`` with
    every row active, against ``forward()`` on the growing prefix (no
    cache code at all). At every step, the prefill's included, the
    argmax is forward()'s exactly and the logits are forward()'s to
    float tolerance (the same products summed in another order: a
    one-row unembed, a cache read back). Off the TPU the step's
    attention is the XLA form."""
    assert "decode_attend" not in _one_body(name, None)


@pytest.mark.parametrize("name", sorted(_TINY))
def test_the_oracle_holds_through_the_decode_kernel(
        name, decode_kernel_interpreted):
    """The same, with the step's attention through the kernel
    ``decode_attend`` (interpreted): a cache of 256 rows gets blocks of
    128, of which each slot's first alone is live."""
    assert "decode_attend" in _one_body(name, 256)


@pytest.mark.parametrize("name", sorted(_TINY))
def test_slot_prefill_leaves_the_other_rows_untouched(name):
    """``slot_prefill`` of one prompt into slot 1 of 3 writes rows
    [0, T0) of that slot and its pos; rows 0 and 2 keep every byte."""
    from ray_tpu.models import TransformerConfig, init_params
    from ray_tpu.models import decode

    cfg = TransformerConfig(**_TINY[name])
    params = init_params(jax.random.key(0), cfg)
    T0, max_len = 4, 8
    before = decode.init_slot_cache(cfg, 3, max_len)
    (k,) = before["k"]              # layers all alike: one run
    marks = jax.random.normal(jax.random.key(2), k.shape, cfg.dtype)
    before = {"k": (marks,), "v": (-marks,), "pos": jnp.asarray([7, 2, 5]),
              "tok": jnp.asarray([1, 2, 3])}
    prompt = jax.random.randint(jax.random.key(3), (1, T0), 0, cfg.vocab)
    # the program consumes the cache it is given: it gets a copy
    _, after = decode.slot_prefill(params, prompt,
                                   jax.tree.map(jnp.copy, before),
                                   jnp.int32(1), cfg)
    np.testing.assert_array_equal(np.asarray(after["pos"]), [7, T0, 5])
    for kv in ("k", "v"):
        was, now = (np.asarray(c[kv][0], np.float32)
                    for c in (before, after))
        np.testing.assert_array_equal(now[:, [0, 2]], was[:, [0, 2]])
        np.testing.assert_array_equal(now[:, 1, T0:], was[:, 1, T0:])
        # every (layer, position, head) of the prompt's rows is new
        assert (now[:, 1, :T0] != was[:, 1, :T0]).any(axis=-1).all()


@pytest.mark.parametrize("program", ["slot_prefill", "slot_decode_step"])
@pytest.mark.parametrize("name", sorted(_TINY))
def test_a_serving_program_consumes_the_cache_it_is_given(name, program):
    """Both programs take the cache donated: K, V and pos of the
    argument are deleted after the call (on the CPU backend too), the
    result is whole, and it equals what the same call makes of a copy,
    so the donation changes where the result lives and not what it is."""
    from ray_tpu.models import TransformerConfig, init_params
    from ray_tpu.models import decode

    cfg = TransformerConfig(**_TINY[name])
    params = init_params(jax.random.key(0), cfg)
    marks = jax.random.normal(jax.random.key(2), (cfg.n_layers, 3, 8,
                                                  cfg.n_heads,
                                                  cfg.head_dim), cfg.dtype)
    # the layers are all alike: one run, so one array of K and of V
    given = {"k": (marks,), "v": (-marks,), "pos": jnp.asarray([3, 2, 5]),
             "tok": jnp.asarray([1, 2, 3])}
    if program == "slot_prefill":
        args = (jax.random.randint(jax.random.key(3), (1, 4), 0,
                                   cfg.vocab),)

        def call(cache):
            return decode.slot_prefill(params, *args, cache,
                                       jnp.int32(1), cfg)
    else:
        def call(cache):
            return decode.slot_decode_step(
                params, cache, jnp.asarray([5, 9, 2], jnp.int32),
                jnp.asarray([True, False, True]), cfg)

    want_logits, want = call(jax.tree.map(jnp.copy, given))
    logits, after = call(given)
    assert all(a.is_deleted() for a in jax.tree.leaves(given))
    assert not any(a.is_deleted() for a in jax.tree.leaves(after))
    np.testing.assert_array_equal(np.asarray(logits),
                                  np.asarray(want_logits))
    for got, wanted in zip(jax.tree.leaves(after), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(wanted, np.float32))
    # a bare array is no cache: it would be read as a run a layer
    whole = jnp.zeros(marks.shape, cfg.dtype)
    with pytest.raises(ValueError, match="one array for each"):
        call({"k": whole, "v": whole, "pos": jnp.asarray([3, 2, 5]),
              "tok": jnp.asarray([1, 2, 3])})
    # ... and it is a cache to go on from
    decode.slot_decode_step(params, after, jnp.zeros(3, jnp.int32),
                            jnp.ones(3, bool), cfg)


def test_kv_cached_decode_matches_full_forward():
    """Serving path (models/decode.py): greedy KV-cached generation
    must match per-step argmax of the FULL training forward on the
    growing prefix EXACTLY — pins rope offsets, cache update slices,
    position masking, and the bit-matched unembed."""
    import numpy as np

    from ray_tpu.models import (TransformerConfig, forward, generate,
                                init_params)

    cfg = TransformerConfig(vocab=97, d_model=64, n_heads=4,
                            n_layers=3, d_ff=128, max_seq=64,
                            dtype=jnp.float32)
    params = init_params(jax.random.key(0), cfg)
    prompt = jax.random.randint(jax.random.key(1), (2, 5), 0, cfg.vocab)

    steps = 8
    toks = np.asarray(generate(params, prompt, cfg, steps=steps))
    prefix = np.asarray(prompt)
    for t in range(steps):
        logits = forward(params, jnp.asarray(prefix), cfg)
        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
        np.testing.assert_array_equal(toks[:, t], nxt, err_msg=f"step {t}")
        prefix = np.concatenate([prefix, nxt[:, None]], axis=1)

    # temperature sampling shape + determinism under a fixed key;
    # keyless sampling is rejected (silent fixed seed = same output)
    s1 = generate(params, prompt, cfg, steps=4, temperature=0.8,
                  key=jax.random.key(3))
    s2 = generate(params, prompt, cfg, steps=4, temperature=0.8,
                  key=jax.random.key(3))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    with pytest.raises(ValueError, match="explicit key"):
        generate(params, prompt, cfg, steps=2, temperature=0.5)

    # the default model dtype (bf16) must hold the oracle too — the
    # decode accumulation dtypes bit-match ops.attention
    cfg16 = TransformerConfig(vocab=61, d_model=32, n_heads=2,
                              n_layers=2, d_ff=64, max_seq=32,
                              dtype=jnp.bfloat16)
    p16 = init_params(jax.random.key(4), cfg16)
    pr16 = jax.random.randint(jax.random.key(5), (2, 4), 0, cfg16.vocab)
    toks16 = np.asarray(generate(p16, pr16, cfg16, steps=3))
    prefix = np.asarray(pr16)
    for t in range(3):
        logits = forward(p16, jnp.asarray(prefix), cfg16)
        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
        np.testing.assert_array_equal(toks16[:, t], nxt,
                                      err_msg=f"bf16 step {t}")
        prefix = np.concatenate([prefix, nxt[:, None]], axis=1)


# ------------------------------ a decode step's attention: decode_attend

# H query heads of D on G K/V heads, values Dv wide; a cache of ROWS
# positions in chunks of CHUNK
_DECODE_LAYOUTS = {
    "mha-16x128": (16, 128, 128, 16),
    "grouped-64on4-192-128": (64, 192, 128, 4),
    "grouped-20on1-128": (20, 128, 128, 1),
}
_ROWS, _CHUNK = 64, 16
_DECODE_POS = {
    "first": [0, 0, 0, 0],
    "short-of-an-edge": [_CHUNK - 1, 2 * _CHUNK - 1, 3 * _CHUNK - 1, 15],
    "on-an-edge": [_CHUNK, 2 * _CHUNK, 3 * _CHUNK, _CHUNK],
    "past-an-edge": [_CHUNK + 1, 2 * _CHUNK + 1, 3 * _CHUNK + 1, 1],
    "last": [_ROWS - 1] * 4,
    "mixed": [0, _CHUNK - 1, _CHUNK, _ROWS - 1],
}


def _decode_case(layout, dtype, pos, layers=2, rows=_ROWS):
    """q, the run's K and V poisoned past every slot's position (NaN,
    +inf and -inf in turn), the same with zeros there, and ``valid``."""
    H, D, Dv, G = _DECODE_LAYOUTS[layout]
    B = len(pos)
    keys = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(keys[0], (B, H, D), dtype)
    row = (lambda w: (H, w)) if G == H else (lambda w: (G * w,))
    k = jax.random.normal(keys[1], (layers, B, rows) + row(D), dtype)
    v = jax.random.normal(keys[2], (layers, B, rows) + row(Dv), dtype)
    pos = jnp.asarray(pos, jnp.int32)
    past = jnp.arange(rows)[None, :] > pos[:, None]          # [B, rows]
    lift = (1, B, rows) + (1,) * (k.ndim - 3)
    poison = jnp.asarray([jnp.nan, jnp.inf, -jnp.inf], dtype)[
        jnp.arange(rows) % 3].reshape((1, 1, rows) + lift[3:])
    poisoned = [jnp.where(past.reshape(lift), poison, t) for t in (k, v)]
    clean = [jnp.where(past.reshape(lift), 0, t) for t in (k, v)]
    return q, poisoned, clean, pos, ~past[:, None, :]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("positions", sorted(_DECODE_POS))
@pytest.mark.parametrize("layout", sorted(_DECODE_LAYOUTS))
def test_decode_kernel_matches_the_xla_form_and_reads_nothing_past_pos(
        layout, positions, dtype):
    """``decode_attend`` (interpreted) against ``cached_attention`` on
    the layer's slice: every position ``[0, pos]`` of a slot attended,
    and nothing of what lies past it (NaN and +-inf there, in the
    chunks not copied and in the tail of the last one) in the output,
    which has q's dtype; each row's copies started under the row before
    it."""
    from ray_tpu.ops.attention import cached_attention, decode_attention

    q, (pk, pv), (ck, cv), pos, valid = _decode_case(
        layout, dtype, _DECODE_POS[positions])
    layer = 1
    want = cached_attention(q, ck[layer], cv[layer], valid,
                            q.shape[-1] ** -0.5)
    got = decode_attention(q, pk, pv, jnp.int32(layer), pos, chunk=_CHUNK,
                           interpret=True)
    assert got.dtype == q.dtype and got.shape == want.shape
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=1e-5 if dtype == jnp.float32 else 2e-2)


def test_decode_kernel_accumulates_in_float32_across_blocks():
    """A uniform softmax over 512 positions of value 1, in bfloat16, 32
    chunks: the numerator and the denominator are carried in float32,
    so the output is 1 exactly (summed in bfloat16, 512 terms of 1/512
    stall far below it)."""
    from ray_tpu.ops.attention import decode_attention

    B, H, D, rows = 2, 4, 16, 512
    q = jnp.zeros((B, H, D), jnp.bfloat16)
    k = jax.random.normal(jax.random.key(0), (1, B, rows, H, D),
                          jnp.bfloat16)
    v = jnp.ones((1, B, rows, H, D), jnp.bfloat16)
    got = decode_attention(q, k, v, jnp.int32(0),
                           jnp.asarray([rows - 1, 300], jnp.int32),
                           chunk=16, interpret=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got, np.float32), 1.0)


# rows, bytes of K and V a position -> positions in a chunk
_DECODE_CHUNKS = {
    "ouro-2.6b.decode-closed": ((1024, 2 * 2 * 16 * 128), 128),
    "mimo-v2-flash-ep16-d7.reason-closed": (
        (3200, 2 * 4 * (192 + 128)), 128),
    "jamba2-3b.rollout-closed": ((2048, 2 * 2 * 128), 512),
    "phi-4-mini-flash.session-closed": ((6144, 2 * 2 * 1280), 128),
    "mimo-v2-flash-ep16-d7.reason-closed ring": (
        (128, 2 * (8 * 192 + 8 * 128)), 128),
    "phi-4-mini-flash.session-closed ring": ((512, 2 * 10 * 256), 128),
    "no multiple of 128": ((1000, 8192), None),
    "rows of 32 KB": ((2048, 32768), 128),
}


@pytest.mark.parametrize("cell", sorted(_DECODE_CHUNKS))
def test_decode_chunks_table(cell):
    from ray_tpu.ops.attention import decode_chunks

    shape, chunk = _DECODE_CHUNKS[cell]
    assert decode_chunks(*shape) == chunk
    if chunk:
        assert shape[0] % chunk == 0


# q, k and v as each cell's decode step hands them to the kernel (a run
# of one layer), and a few slots' positions
_DECODE_CELLS = {
    "ouro-2.6b.decode-closed": ((8, 16, 128), (8, 1024, 16, 128),
                                (8, 1024, 16, 128)),
    "mimo-v2-flash-ep16-d7.reason-closed": (
        (128, 64, 192), (128, 3200, 768), (128, 3200, 512)),
    "jamba2-3b.rollout-closed": ((256, 20, 128), (256, 2048, 128),
                                 (256, 2048, 128)),
    "phi-4-mini-flash.session-closed": ((64, 40, 128), (64, 6144, 1280),
                                        (64, 6144, 1280)),
}


@pytest.mark.parametrize("cell", sorted(_DECODE_CELLS))
def test_decode_rows_fetched_is_what_the_kernel_copies(cell, monkeypatch):
    """At a cell's decode shape the kernel copies a row's K and V in
    chunks of ``decode_rows_fetched`` positions, the first chunk to the
    one that holds the row's position, each once: ``(p // n + 1) * n``
    positions a row, which is what the engine counts. Counted where the
    copies are started, in the interpreted kernel at the cell's rows
    and widths (six slots, at 0, n - 1, n, n + 1 and the last row, and
    0 again), the plan taken as on the TPU."""
    import importlib

    import jax.experimental.pallas.tpu as pltpu

    attention = importlib.import_module("ray_tpu.ops.attention")
    qs, ks, vs = _DECODE_CELLS[cell]

    def struct(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    full = struct(qs), struct((1,) + ks), struct((1,) + vs)
    n = attention.decode_rows_fetched(*full)
    stride = attention._decode_plan(*full, None, False)[1]
    monkeypatch.undo()

    rows = ks[1]
    pos = [0, n - 1, n, n + 1, rows - 1, 0]
    started = []
    real = pltpu.make_async_copy

    class Counted:
        """A copy whose start is counted: the row and the first of the
        positions it copies (its source is a chunk of one row)."""

        def __init__(self, src, dst, sem):
            self.copy, self.at = real(src, dst, sem), src.transforms[-1]
            self.size = dst.shape[0] // stride

        def start(self):
            _, row, first = self.at.indices[:3]
            jax.debug.callback(
                lambda r, f, size=self.size: started.append(
                    (int(r), int(f) // stride, size)), row, first.start)
            self.copy.start()

        def wait(self):
            self.copy.wait()

    monkeypatch.setattr(pltpu, "make_async_copy", Counted)
    B = len(pos)
    k = jnp.zeros((1, B) + ks[1:], jnp.bfloat16)
    v = jnp.zeros((1, B) + vs[1:], jnp.bfloat16)
    q = jnp.zeros((B,) + qs[1:], jnp.bfloat16)
    attention.decode_attention(q, k, v, jnp.int32(0), jnp.asarray(
        pos, jnp.int32), chunk=n, interpret=True).block_until_ready()
    # K and V: each chunk of a row twice
    want = sorted((r, first, n) for r, p in enumerate(pos)
                  for first in range(0, (p // n + 1) * n, n)) * 2
    assert sorted(started) == sorted(want)
    assert sum(size for _, _, size in started) == 2 * sum(
        (p // n + 1) * n for p in pos)


def test_decode_attention_takes_the_kernel_by_platform_and_shape(
        monkeypatch):
    """Off the TPU the XLA form; on it the kernel where the shape has a
    chunk (``decode_rows_fetched`` says which), with a sink too, named
    ``decode_ring`` over a ring; the XLA form for rows that are no
    multiple of 128 or not whole lanes wide; an explicit chunk wins."""
    import importlib

    attention_mod = importlib.import_module("ray_tpu.ops.attention")
    decode_attention = attention_mod.decode_attention
    fetched = attention_mod.decode_rows_fetched

    def struct(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    def kernel_runs(q, k, v, sink=None, ring=False, name="decode_attend"):
        pos = jax.ShapeDtypeStruct((q.shape[0],), jnp.int32)
        layer = jax.ShapeDtypeStruct((), jnp.int32)
        # (a function of its own each time: a trace is kept by function)
        return name in str(jax.make_jaxpr(
            lambda *args: decode_attention(*args[:5], sink=args[5],
                                           ring=ring))(
                q, k, v, layer, pos, sink))

    cell = struct(8, 16, 128), struct(2, 8, 1024, 16, 128), \
        struct(2, 8, 1024, 16, 128)
    assert not kernel_runs(*cell) and fetched(*cell) == 1024
    monkeypatch.setattr(attention_mod, "_on_tpu", lambda: True)
    assert kernel_runs(*cell) and fetched(*cell) == 128
    sink = jax.ShapeDtypeStruct((16,), jnp.float32)
    assert kernel_runs(*cell, sink)
    flat = struct(8, 64, 192), struct(1, 8, 3200, 768), \
        struct(1, 8, 3200, 512)
    assert kernel_runs(*flat) and fetched(*flat) == 128
    ring = struct(8, 64, 192), struct(4, 8, 128, 1536), \
        struct(4, 8, 128, 1024)
    sink = jax.ShapeDtypeStruct((64,), jnp.float32)
    assert kernel_runs(*ring, sink, ring=True, name="decode_ring")
    assert not kernel_runs(*ring, sink, ring=True) and fetched(*ring) == 128
    for q, k, v in (
            (struct(8, 16, 128), struct(2, 8, 1000, 16, 128),
             struct(2, 8, 1000, 16, 128)),          # rows
            (struct(8, 4, 64), struct(2, 8, 1024, 4, 64),
             struct(2, 8, 1024, 4, 64)),            # half a lane row
            (struct(8, 12, 128), struct(2, 8, 1024, 12, 128),
             struct(2, 8, 1024, 12, 128))):         # 12 rows a position
        assert not kernel_runs(q, k, v) and fetched(q, k, v) == k.shape[2]


def test_decode_attention_with_a_sink_is_the_xla_form():
    """Off the TPU a layer with a sink logit attends through
    ``cached_attention``: the sink is in the denominator, and the output
    is that form's to the bit."""
    from ray_tpu.ops.attention import cached_attention, decode_attention

    q, _, (ck, cv), pos, valid = _decode_case(
        "grouped-20on1-128", jnp.float32, _DECODE_POS["mixed"])
    sink = jnp.linspace(-1.0, 3.0, q.shape[1])
    got = decode_attention(q, ck, cv, jnp.int32(1), pos, sink=sink)
    want = cached_attention(q, ck[1], cv[1], valid, q.shape[-1] ** -0.5,
                            sink)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    bare = decode_attention(q, ck, cv, jnp.int32(1), pos)
    assert float(jnp.max(jnp.abs(bare - got))) > 1e-3


# the two serving cells' window rings: H query heads of D on G K/V heads,
# values Dv wide, a ring of ROWS positions, with a sink or not, in the
# chunks ``decode_rows_fetched`` gives (None) or in more of them
_RING_LAYOUTS = {
    "mimo-128-64on8-192-128-sink": (64, 192, 128, 8, 128, True, None),
    "phi-512-40on10-128": (40, 128, 128, 10, 512, False, None),
    "a-sink-across-chunks-of-32": (64, 192, 128, 8, 128, True, 32),
}
# four slots' positions (a ring's row is the position modulo the rows)
_RING_POS = {
    "below-the-last-row": lambda rows: [0, 5, rows // 2 + 3, rows - 2],
    "past-it": lambda rows: [rows - 1, rows, 2 * rows + 7, 25 * rows - 1],
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("positions", sorted(_RING_POS))
@pytest.mark.parametrize("layout", sorted(_RING_LAYOUTS))
def test_the_decode_kernel_reads_a_ring_where_it_lies(
        layout, positions, dtype, monkeypatch):
    """``decode_ring`` (interpreted, at the chunk ``decode_chunks`` gives
    the cell's ring) against ``cached_attention`` on the layer's ring:
    a slot attends rows ``[0, min(pos, rows - 1)]``, with the layer's
    sink in the denominator where it has one (once, however many chunks
    a row takes); where the position is short of the ring's last row,
    what lies past it (NaN and +-inf) does not reach the output."""
    import importlib

    attention = importlib.import_module("ray_tpu.ops.attention")
    H, D, Dv, G, rows, has_sink, chunk = _RING_LAYOUTS[layout]
    pos = jnp.asarray(_RING_POS[positions](rows), jnp.int32)
    B, layers, layer = len(pos), 2, 1
    keys = jax.random.split(jax.random.key(12), 4)
    q = jax.random.normal(keys[0], (B, H, D), dtype)
    k = jax.random.normal(keys[1], (layers, B, rows, G * D), dtype)
    v = jax.random.normal(keys[2], (layers, B, rows, G * Dv), dtype)
    sink = (jax.random.normal(keys[3], (H,), jnp.float32) + 2.0
            if has_sink else None)
    last = jnp.minimum(pos, rows - 1)
    past = (jnp.arange(rows)[None, :] > last[:, None])[None, :, :, None]
    poison = jnp.asarray([jnp.nan, jnp.inf, -jnp.inf], dtype)[
        jnp.arange(rows) % 3][None, None, :, None]
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    n = attention.decode_rows_fetched(q, k, v)
    monkeypatch.undo()
    assert n == 128
    n = chunk or n
    got = attention.decode_attention(
        q, jnp.where(past, poison, k), jnp.where(past, poison, v),
        jnp.int32(layer), pos, sink=sink, ring=True, chunk=n,
        interpret=True)
    valid = (jnp.arange(rows)[None, :] <= last[:, None])[:, None, :]
    want = attention.cached_attention(
        q, jnp.where(past, 0, k)[layer], jnp.where(past, 0, v)[layer],
        valid, D ** -0.5, sink)
    assert got.dtype == q.dtype and got.shape == want.shape
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=1e-5 if dtype == jnp.float32 else 2e-2)
