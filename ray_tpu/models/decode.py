"""KV-cached autoregressive decoding for the flagship transformer.

One cache form: ``cache["k"]`` and ``cache["v"]`` are tuples of arrays,
one for each run of alike layers (``transformer.layer_runs``; a model
whose layers are all alike has one run): ``[L, slots, rows, H, Dh]`` K
and ``[.., Dv]`` V (``[L, slots, rows, G * Dh]``, the K/V heads side by
side in a row, where G of them serve more query heads each:
``init_slot_cache``), preallocated, with a decode offset per row
(``pos: [slots]``).
Continuous batching (serve/decode_scheduler.py) needs exactly that: one
sequence prefills into an open row while the other rows keep stepping,
and a finished row frees at once. Whole-batch generation (``generate``)
is its all-rows-active case. Every shape is static, so serving is two
compiled programs, each a ``lax.scan`` of ``transformer.block`` (the
one definition of the layer) over the stacked layers and their index,
with the whole K and V as the scan's carry:

* ``slot_prefill`` runs a prompt through the block with the training
  forward's rope and attention (flash kernel on TPU, XLA off it) and
  writes the roped K/V into the carry at ``(layer, slot, 0, 0, 0)``;
* ``slot_decode_step`` ropes each row's new token at the row's own
  position, scatters its K/V into the carry at ``[layer, rows, pos]``
  and attends the layer's K/V, read out of the carry, under a per-row
  mask (no recompute, no dynamic shapes).

**Layers of several kinds** (``TransformerConfig.layer_kinds``) are
scanned run by run, and the cache is allocated by kind: each run's
array at the run's own K/V heads. A run of full-attention
layers has ``max_len`` rows. A run of window layers has ``window`` rows,
a ring: position p lives in row ``p % window``, ``slot_prefill`` writes
a prompt's last ``window`` positions there, and ``slot_decode_step``
writes row ``pos % window`` and attends the rows filled so far (rope is
applied before the write, so the order of the rows does not matter),
with the layer's sink logit in the denominator where it has one. A
model with expert layers carries ``cache["load"]``, int32 [3]: the held
experts that got a row, the rows routed to held experts and the fullest
expert's rows in the last decode step, each summed over the expert
layers, for the engine to fetch with the step's tokens.

**The picked token stays on the device.** ``cache["tok"]``, int32
[slots], is each row's last pick (:func:`pick`, the one place a token
is chosen: ``slot_prefill`` picks a prompt's first token into its
slot's entry, ``slot_decode_step`` each active row's next one,
``generate`` draws through it). A step is told what to feed a row by
one int32 a row: a token id, ``CARRY`` for the row's own last pick, or
``IDLE``. So the serving engine dispatches step n + 1 before the host
has seen step n's tokens: they are read where they were made, and what
the step hands back for the host is the row of picks (with the three
counts behind it), never ``[slots, vocab]`` logits.

The cache is one set of buffers, written in place. Both programs take
it donated, and K and V are carried through the layers' scan, not
scanned over: XLA then aliases the result to the argument and the only
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
* ``slot_prefill`` rewrites rows [0, T0) of its slot (of a ring, the
  rows its last ``window`` positions fall on) and resets that slot's
  pos, so a reused slot never sees its predecessor's K/V — the stale
  tail beyond T0 is always overwritten (step s writes position pos
  BEFORE attending it) and never attended.
* ``slot_decode_step`` writes every row's K/V unconditionally (a
  masked write would cost a gather per layer) but advances ``pos``
  and replaces ``tok`` only where ``active``: an inactive row's cache
  may take garbage at its frozen pos, which is sound because inactive
  rows are only ever re-entered through ``slot_prefill``.
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

from ray_tpu.models.transformer import (EXPERTS, WINDOW, TransformerConfig,
                                        block, kind_rope, layer_runs,
                                        layer_stacks, scan_run, unembed)
from ray_tpu.ops.attention import flash_attention
from ray_tpu.ops.rotary import apply_rotary, rotate


# in a step's ``token`` row, in place of a token id (which is never
# negative): feed the row its own last pick, ``cache["tok"]``; and, where
# no ``active`` is given beside it, leave the row out of the step
CARRY, IDLE = -1, -2


def pick(logits, key=None, temperature=None):
    """The next token of each row of ``logits`` [.., V], int32: the
    likeliest, or one drawn at ``temperature`` where a key is given."""
    if key is None:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature).astype(jnp.int32)


def init_slot_cache(cfg: TransformerConfig, slots: int,
                    max_len: int) -> Dict:
    """KV cache with an independent decode offset per batch row: a
    tuple of arrays of K and one of V, one array a run of alike layers
    (one in all where the layers are all alike), a window run at
    ``cfg.window`` rows. Where every query head has a K/V head of its
    own a row is [H, Dh]; where G K/V heads serve more query heads each
    it is flat, [G * Dh], the heads side by side: rows of 768 or 1536
    values tile the chip's memory as they are, which [4, 192] or
    [8, 192] do not (the compiler's own layouts for those cost two
    copies of K a step)."""
    runs = layer_runs(cfg)

    def leaves(width):
        made = []
        for (attention, _), n in runs:
            G = cfg.kv_heads(attention)
            row = (G, width) if G == cfg.n_heads else (G * width,)
            made.append(jnp.zeros(
                (n, slots, cfg.window if attention == WINDOW else max_len)
                + row, cfg.dtype))
        return tuple(made)

    cache = {"k": leaves(cfg.head_dim), "v": leaves(cfg.v_dim),
             "pos": jnp.zeros((slots,), jnp.int32),
             "tok": jnp.zeros((slots,), jnp.int32)}
    if any(ffn == EXPERTS for (_, ffn), _ in runs):
        cache["load"] = jnp.zeros((3,), jnp.int32)
    return cache


def _cache_runs(cache: Dict, runs):
    """The cache's K and V arrays, one a run of alike layers."""
    ks, vs = cache["k"], cache["v"]
    if not (isinstance(ks, tuple) and isinstance(vs, tuple)
            and len(ks) == len(vs) == len(runs)):
        raise ValueError(
            f"the cache holds a tuple of K arrays and one of V, one "
            f"array for each of the model's {len(runs)} runs of alike "
            f"layers (init_slot_cache)")
    return ks, vs


def _max_len(cfg: TransformerConfig, runs, ks) -> int:
    """Rows of a full-attention run's cache: the longest sequence a slot
    holds (``cfg.max_seq`` where every layer has a window)."""
    return next((ck.shape[2] for ((attention, _), _), ck in zip(runs, ks)
                 if attention != WINDOW), cfg.max_seq)


def _cache_rows(t, like, window: Optional[int]):
    """A prompt's K or V, t [1, T0, G, D], as the rows the cache
    ``like`` holds: flat where its rows are, and of a ring of
    ``window`` the last ``window`` positions, position p in row
    p % window."""
    T0 = t.shape[1]
    if window is not None and T0 > window:
        t = jnp.roll(t[:, T0 - window:], (T0 - window) % window, axis=1)
    return t.reshape(t.shape[:2] + like.shape[3:]).astype(like.dtype)


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("cache",))
def slot_prefill(params, tokens, cache: Dict, slot,
                 cfg: TransformerConfig) -> Tuple[jnp.ndarray, Dict]:
    """Run one prompt [1, T0] through the stack, writing each layer's
    K/V into cache row ``slot`` (a traced index: one compiled program
    serves every slot). Returns (last-token logits [1, V], cache), the
    token picked from them in ``cache["tok"][slot]``; the cache given is
    consumed and the one returned is its memory, updated in place.
    Compiles once per distinct T0 — serving callers should bucket or
    pad prompt lengths if retrace cost matters."""
    _, T0 = tokens.shape
    runs = layer_runs(cfg)
    ks, vs = _cache_runs(cache, runs)
    max_len = _max_len(cfg, runs, ks)
    ropes = {}
    for attention in dict.fromkeys(attention for (attention, _), _ in runs):
        cos, sin = kind_rope(cfg, attention, max_len)
        ropes[attention] = functools.partial(
            apply_rotary, cos=cos, sin=sin, positions=jnp.arange(T0))
    x = params["embed"][tokens]

    def one_run(x, layers, ck, cv, attention):
        window = cfg.window if attention == WINDOW else None

        def body(carry, lp, i):
            x, ck, cv = carry  # ck/cv: the whole [L, slots, rows, G, Dh]

            def attend(q, k, v):
                # the training forward's local attention, so the last
                # token's logits are forward()'s; the roped k and v are
                # what a later step attends
                with jax.named_scope(f"{attention}_attention"):
                    return flash_attention(
                        q, k, v, causal=True, window=window,
                        sink=lp.get("sink")), (k, v)

            x, (k, v), _ = block(lp, x, ropes[attention], attend, cfg)
            ck = lax.dynamic_update_slice(
                ck, _cache_rows(k, ck, window)[None],
                (i, slot) + (0,) * (ck.ndim - 2))
            cv = lax.dynamic_update_slice(
                cv, _cache_rows(v, cv, window)[None],
                (i, slot) + (0,) * (cv.ndim - 2))
            return x, ck, cv

        return scan_run(body, (x, ck, cv), layers)

    new_k, new_v = [], []
    for ((attention, _), layers), ck, cv in zip(
            layer_stacks(params, cfg), ks, vs):
        x, ck, cv = one_run(x, layers, ck, cv, attention)
        new_k.append(ck)
        new_v.append(cv)
    logits = unembed(params, x, last=True, eps=cfg.norm_eps)
    return logits, dict(
        cache, k=tuple(new_k), v=tuple(new_v),
        pos=cache["pos"].at[slot].set(T0),
        tok=cache["tok"].at[slot].set(pick(logits)[0]))


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("cache",))
def slot_decode_step(params, cache: Dict, token, active,
                     cfg: TransformerConfig) -> Tuple[jnp.ndarray, Dict]:
    """One continuous-batching step: each ACTIVE row is fed one token,
    attends its own prefix (per-row position mask; in a window layer the
    last ``window`` positions of it), advances its own pos and picks its
    next token into ``cache["tok"]``. Inactive rows are free riders —
    their logits are garbage, their pos and tok frozen. ``token`` int32
    [B] says what a row is fed: a token id, or ``CARRY`` for the row's
    own last pick, which never left the device. The cache given is
    consumed and the one returned is its memory, with one position a
    row and layer written in place.

    Two callers, two forms. With ``active`` bool [B]: (next-token
    logits [B, V], cache), for a caller that draws the token itself
    (``generate``) or compares the logits. With ``active`` None, as
    served: ``token`` alone steers the rows (``IDLE`` leaves one out)
    and what comes back is (the row of picks, int32 [B], with the three
    counts of ``cache["load"]`` behind it where the model has expert
    layers, cache): a few bytes for the host to fetch whenever it gets
    to it, while the next step already runs on ``cache["tok"]``."""
    B = token.shape[0]
    served = active is None
    if served:
        active = token != IDLE
    token = jnp.where(token >= 0, token, cache["tok"])
    runs = layer_runs(cfg)
    ks, vs = _cache_runs(cache, runs)
    max_len = _max_len(cfg, runs, ks)
    pos = cache["pos"]  # [B]
    kinds = list(dict.fromkeys(attention for (attention, _), _ in runs))
    tables = {a: kind_rope(cfg, a, max_len) for a in kinds}
    x = params["embed"][token][:, None, :]  # [B, 1, D]
    sm_scale = cfg.head_dim ** -0.5
    # row r attends positions [0, pos[r]] (pos[r] is written this
    # step); of a ring, the rows filled so far, all once pos[r] has
    # passed the window
    valid = {a: (jnp.arange(cfg.window if a == WINDOW else max_len)
                 [None, None, :] <= pos[:, None, None])  # [B, 1, rows]
             for a in kinds}
    rows = jnp.arange(B)

    def one_run(x, load, layers, ck, cv, attention):
        cos, sin = tables[attention]
        window = cfg.window if attention == WINDOW else None
        at = pos if window is None else pos % window

        def rope(t):  # every row at its own position
            return rotate(t, cos[pos][:, None, None, :],
                          sin[pos][:, None, None, :])

        def body(carry, lp, i):
            x, ck, cv, load = carry  # ck/cv: the whole [L, B, rows, G, Dh]

            def attend(q, k, v):
                # write, then attend: the layer's K/V are read out of the
                # carry after the rows' new token is in it
                nk = ck.at[i, rows, at].set(
                    k[:, 0].reshape((B,) + ck.shape[3:]).astype(ck.dtype))
                nv = cv.at[i, rows, at].set(
                    v[:, 0].reshape((B,) + cv.shape[3:]).astype(cv.dtype))
                lk = lax.dynamic_index_in_dim(nk, i, keepdims=False)
                lv = lax.dynamic_index_in_dim(nv, i, keepdims=False)
                with jax.named_scope(f"{attention}_attention"):
                    return _attend_cached(q[:, 0], lk, lv, valid[attention],
                                          sm_scale, lp.get("sink")), (nk, nv)

            x, (ck, cv), got = block(lp, x, rope, attend, cfg)
            if got is not None:
                load = load + jnp.stack(
                    [jnp.sum(got > 0), jnp.sum(got), jnp.max(got)])
            return x, ck, cv, load

        return scan_run(body, (x, ck, cv, load), layers)

    load = jnp.zeros_like(cache["load"]) if "load" in cache else None
    new_k, new_v = [], []
    for ((attention, _), layers), ck, cv in zip(
            layer_stacks(params, cfg), ks, vs):
        x, ck, cv, load = one_run(x, load, layers, ck, cv, attention)
        new_k.append(ck)
        new_v.append(cv)
    logits = unembed(params, x[:, 0], eps=cfg.norm_eps)
    tok = jnp.where(active, pick(logits), cache["tok"])
    cache = dict(cache, k=tuple(new_k), v=tuple(new_v),
                 pos=jnp.where(active, pos + 1, pos), tok=tok)
    if load is not None:
        cache["load"] = load
    if not served:
        return logits, cache
    return (tok if load is None else jnp.concatenate([tok, load])), cache


def _attend_cached(q, lk, lv, valid, sm_scale, sink=None):
    """One new token a row against a layer's cached K/V: q [B, H, Dh],
    ``valid`` [B, 1, rows] the rows each batch row may attend, ``sink``
    [H] one more logit a head in the denominator. lk [B, rows, H, Dh]
    and lv [B, rows, H, Dv] where every query head has its own K/V
    head. Where G K/V heads serve H / G query heads each the rows are
    flat, lk [B, rows, G * Dh] and lv [B, rows, G * Dv]: each query is
    widened to a whole row, zero outside its own K/V head's part, so
    that both products are plain ones over rows as they lie in memory
    (G times the multiplications of the heads taken apart, which stay
    under the time the rows take to read), and the output keeps its own
    head's part. Accumulation dtypes as ops.attention's: softmax fp32,
    p cast to the value dtype, p@v accumulated in fp32."""
    B, H, D = q.shape
    grouped = lk.ndim == 3
    if grouped:
        G = lk.shape[2] // D
        own = (jnp.arange(H)[:, None] // (H // G)
               == jnp.arange(G)[None, :])[None, :, :, None]   # [1, H, G, 1]
        wide = jnp.where(own, q[:, :, None, :], 0).reshape(B, H, G * D)
        s = jnp.einsum("bhc,bkc->bhk", wide, lk,
                       preferred_element_type=jnp.float32) * sm_scale
    else:
        s = jnp.einsum("bhd,bkhd->bhk", q, lk,
                       preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(valid, s, -jnp.inf)
    if sink is None:
        p = jax.nn.softmax(s, axis=-1).astype(lv.dtype)
    else:
        column = jnp.broadcast_to(
            sink.astype(jnp.float32)[None, :, None], (B, H, 1))
        p = jax.nn.softmax(jnp.concatenate([s, column], axis=-1),
                           axis=-1)[..., :-1].astype(lv.dtype)
    if grouped:
        o = jnp.einsum("bhk,bkc->bhc", p, lv,
                       preferred_element_type=jnp.float32)
        o = jnp.sum(jnp.where(own, o.reshape(B, H, G, -1), 0), axis=2)
    else:
        o = jnp.einsum("bhk,bkhd->bhd", p, lv,
                       preferred_element_type=jnp.float32)
    return o.astype(q.dtype)


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

    def body(carry, i):
        logits, cache, key = carry
        key, sub = jax.random.split(key)
        tok = pick(logits, sub if sample else None, temperature)
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
