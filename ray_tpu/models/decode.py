"""KV-cached autoregressive decoding for the flagship transformer.

One cache form: ``[L, slots, max_len, H, Dh]`` K and V, preallocated,
with a decode offset per row (``pos: [slots]``). Continuous batching
(serve/decode_scheduler.py) needs exactly that: one sequence prefills
into an open row while the other rows keep stepping, and a finished row
frees at once. Whole-batch generation (``generate``) is its
all-rows-active case. Every shape is static, so serving is two compiled
programs, each a ``lax.scan`` of ``transformer.block`` (the one
definition of the layer) over the stacked layers and their index, with
the whole K and V as the scan's carry:

* ``slot_prefill`` runs a prompt through the block with the training
  forward's rope and attention (flash kernel on TPU, XLA off it) and
  writes the roped K/V into the carry at ``(layer, slot, 0, 0, 0)``;
* ``slot_decode_step`` ropes each row's new token at the row's own
  position, scatters its K/V into the carry at ``[layer, rows, pos]``
  and attends the layer's K/V, read out of the carry, under a per-row
  mask (no recompute, no dynamic shapes).

The cache is one buffer, written in place. Both programs take it
donated, and K and V are carried through the layers' scan, not scanned
over: XLA then aliases the result to the argument and the only
operations that produce K or V are the in-place writes of the new rows.
Either half alone leaves a copy (a carry not donated is copied whole at
entry; a donated cache that is a scanned input is sliced out and
stacked again, layer by layer): tests/test_chip_compile.py holds the
compiled programs to it.

Invariants the scheduler relies on:

* A call consumes the cache it is given: after ``slot_prefill`` or
  ``slot_decode_step`` the argument's arrays are deleted (on every
  backend) and the returned cache is the one to go on with. A caller
  that wants the old state afterwards passes a copy. Inside another jit
  (``_decode_loop``) the inner donation does nothing and the cache is
  the outer carry.
* ``slot_prefill`` rewrites rows [0, T0) of its slot and resets that
  slot's pos, so a reused slot never sees its predecessor's K/V — the
  stale tail beyond T0 is always overwritten (step s writes position
  pos BEFORE attending it) and never attended.
* ``slot_decode_step`` writes every row's K/V unconditionally (a
  masked write would cost a gather per layer) but advances ``pos``
  only where ``active``: an inactive row's cache may take garbage at
  its frozen pos, which is sound because inactive rows are only ever
  re-entered through ``slot_prefill``.
* A row at ``pos == max_len`` would have its write clamped onto the
  last position; the callers refuse before that (``generate``'s
  ``T0 + steps > max_len``, ``JaxSlotEngine.step``'s host mirror).

Oracle: greedy decoding must match the per-step argmax of the FULL
forward() on the growing prefix, which shares no cache code —
tests/test_ops.py and tests/test_decode_scheduler.py assert it exactly,
which pins the cache bookkeeping (rope offsets, masking, row writes) to
the training forward's semantics.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.transformer import TransformerConfig, block, unembed
from ray_tpu.ops.attention import flash_attention
from ray_tpu.ops.rotary import apply_rotary, rope_frequencies, rotate


def init_slot_cache(cfg: TransformerConfig, slots: int,
                    max_len: int) -> Dict:
    """KV cache with an independent decode offset per batch row."""
    shape = (cfg.n_layers, slots, max_len, cfg.n_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype),
            "pos": jnp.zeros((slots,), jnp.int32)}


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("cache",))
def slot_prefill(params, tokens, cache: Dict, slot,
                 cfg: TransformerConfig) -> Tuple[jnp.ndarray, Dict]:
    """Run one prompt [1, T0] through the stack, writing each layer's
    K/V into cache row ``slot`` (a traced index: one compiled program
    serves every slot). Returns (last-token logits [1, V], cache); the
    cache given is consumed and the one returned is its memory, updated
    in place. Compiles once per distinct T0 — serving callers should
    bucket or pad prompt lengths if retrace cost matters."""
    _, T0 = tokens.shape
    max_len = cache["k"].shape[2]
    cos, sin = rope_frequencies(cfg.head_dim, max_len,
                                theta=cfg.rope_theta)
    rope = functools.partial(apply_rotary, cos=cos, sin=sin,
                             positions=jnp.arange(T0))
    x = params["embed"][tokens]

    def attend(q, k, v):
        # the training forward's local attention, so the last token's
        # logits are forward()'s; the roped k and v are what a later
        # step attends
        return flash_attention(q, k, v, causal=True), (k, v)

    def body(carry, layer_in):
        x, ck, cv = carry  # ck/cv: the whole [L, slots, max_len, H, Dh]
        lp, i = layer_in
        x, (k, v) = block(lp, x, rope, attend, cfg)
        ck = lax.dynamic_update_slice(ck, k.astype(ck.dtype)[None],
                                      (i, slot, 0, 0, 0))
        cv = lax.dynamic_update_slice(cv, v.astype(cv.dtype)[None],
                                      (i, slot, 0, 0, 0))
        return (x, ck, cv), None

    (x, ck, cv), _ = lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["layers"], jnp.arange(cfg.n_layers)))
    return unembed(params, x, last=True), {
        "k": ck, "v": cv, "pos": cache["pos"].at[slot].set(T0)}


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("cache",))
def slot_decode_step(params, cache: Dict, token, active,
                     cfg: TransformerConfig) -> Tuple[jnp.ndarray, Dict]:
    """One continuous-batching step: token [B] in, next-token logits
    [B, V] out; each ACTIVE row attends its own prefix (per-row
    position mask) and advances its own pos. Inactive rows are free
    riders — their logits are garbage and their pos is frozen. The
    cache given is consumed and the one returned is its memory, with
    one position a row and layer written in place."""
    B = token.shape[0]
    max_len = cache["k"].shape[2]
    pos = cache["pos"]  # [B]
    cos, sin = rope_frequencies(cfg.head_dim, max_len,
                                theta=cfg.rope_theta)
    x = params["embed"][token][:, None, :]  # [B, 1, D]
    sm_scale = cfg.head_dim ** -0.5
    # row r attends positions [0, pos[r]] (pos[r] is written this step)
    valid = (jnp.arange(max_len)[None, None, :]
             <= pos[:, None, None])  # [B, 1, Tmax]
    rows = jnp.arange(B)

    def rope(t):  # every row at its own position
        return rotate(t, cos[pos][:, None, None, :],
                      sin[pos][:, None, None, :])

    def body(carry, layer_in):
        x, ck, cv = carry  # ck/cv: the whole [L, B, max_len, H, Dh]
        lp, i = layer_in

        def attend(q, k, v):
            # write, then attend: the layer's K/V are read out of the
            # carry after the rows' new token is in it
            nk = ck.at[i, rows, pos].set(k[:, 0].astype(ck.dtype))
            nv = cv.at[i, rows, pos].set(v[:, 0].astype(cv.dtype))
            lk = lax.dynamic_index_in_dim(nk, i, keepdims=False)
            lv = lax.dynamic_index_in_dim(nv, i, keepdims=False)
            s = jnp.einsum("bhd,bkhd->bhk", q[:, 0], lk,
                           preferred_element_type=jnp.float32) * sm_scale
            s = jnp.where(valid, s, -jnp.inf)
            # accumulation dtypes as ops.attention's: softmax fp32, p
            # cast to the value dtype, p@v accumulated in fp32
            p = jax.nn.softmax(s, axis=-1).astype(lv.dtype)
            o = jnp.einsum("bhk,bkhd->bhd", p, lv,
                           preferred_element_type=jnp.float32
                           ).astype(q.dtype)
            return o, (nk, nv)

        x, (ck, cv) = block(lp, x, rope, attend, cfg)
        return (x, ck, cv), None

    (x, ck, cv), _ = lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["layers"], jnp.arange(cfg.n_layers)))
    return unembed(params, x[:, 0]), {
        "k": ck, "v": cv, "pos": jnp.where(active, pos + 1, pos)}


@functools.partial(jax.jit,
                   static_argnames=("cfg", "steps", "sample"))
def _decode_loop(params, logits, cache, key, temperature, *, cfg,
                 steps, sample):
    """Module-level jit: the scanned decode loop compiles ONCE per
    (cfg, steps, sample, shapes) across generate() calls — a per-call
    closure would retrace every invocation, and a static temperature
    would recompile per distinct float, so only the greedy/sampling
    BRANCH is static and the magnitude is a traced operand."""
    active = jnp.ones(logits.shape[0], bool)

    def pick(logits, k):
        if not sample:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            k, logits / temperature).astype(jnp.int32)

    def body(carry, i):
        logits, cache, key = carry
        key, sub = jax.random.split(key)
        tok = pick(logits, sub)
        # the token sampled on the LAST iteration needs no successor
        # logits: skip its decode step (at steps=1 this halves the
        # per-generation device work)
        logits, cache = lax.cond(
            i < steps - 1,
            lambda: slot_decode_step(params, cache, tok, active, cfg),
            lambda: (logits, cache))
        return (logits, cache, key), tok

    (_, cache, _), toks = lax.scan(
        body, (logits, cache, key), jnp.arange(steps))
    return toks.swapaxes(0, 1)  # [B, steps]


def generate(params, prompt, cfg: TransformerConfig, *, steps: int,
             key: Optional[jax.Array] = None, temperature: float = 0.0,
             max_len: Optional[int] = None) -> jnp.ndarray:
    """Autoregressive sampling: greedy at temperature 0, categorical
    otherwise (an explicit ``key`` is required then — a silent fixed
    seed would make every call return the same completion). Returns
    generated tokens [B, steps]. The slot cache with every row active:
    two compiled programs total, cached across calls — ``slot_prefill``
    (dispatched once a prompt row) and the scanned decode loop."""
    B, T0 = prompt.shape
    max_len = max_len or min(cfg.max_seq, T0 + steps)
    if T0 + steps > max_len:
        raise ValueError(f"prompt ({T0}) + steps ({steps}) exceeds "
                         f"max_len ({max_len})")
    if temperature > 0.0 and key is None:
        raise ValueError("temperature > 0 requires an explicit key")
    cache = init_slot_cache(cfg, B, max_len)
    first = []
    for row in range(B):
        logits, cache = slot_prefill(params, prompt[row:row + 1], cache,
                                     jnp.int32(row), cfg)
        first.append(logits)
    if key is None:
        key = jax.random.key(0)  # unused by the greedy path
    return _decode_loop(params, jnp.concatenate(first), cache, key,
                        jnp.asarray(max(temperature, 1e-8),
                                    jnp.float32),
                        cfg=cfg, steps=steps,
                        sample=temperature > 0.0)
