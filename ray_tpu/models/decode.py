"""Cached autoregressive decoding for the flagship transformer: a
slot's state between tokens, of the kinds its layers keep.

**Rows or a summary, one cache.** The cache is a dict of tuples, each
with one entry for each run of alike layers (``transformer.layer_runs``;
a model whose layers are all alike has one run), and a run's entry is
an array in the tuples of its own kind of state and ``None`` in the
others (in a run of periods of several layers the entry is itself a
tuple, one such array or ``None`` for each layer of the period):

* an attention run keeps *rows*: ``cache["k"][r]`` ``[L, slots, rows,
  H, Dh]`` and ``cache["v"][r]`` ``[.., Dv]`` (``[L, slots, rows,
  G * Dh]``, the K/V heads side by side in a row, where G of them serve
  more query heads each: ``init_slot_cache``), one row a position, read
  up to the slot's decode offset ``cache["pos"]`` ``[slots]``. A run of
  full-attention layers has ``max_len`` rows. A run of window layers
  has ``window`` rows, a ring: position p lives in row ``p % window``,
  ``slot_prefill`` writes a prompt's last ``window`` positions there,
  and ``slot_decode_step`` writes row ``pos % window`` and attends the
  rows filled so far (rope is applied before the write, so the order of
  the rows does not matter), with the layer's sink logit in the
  denominator where it has one;
* a Mamba run keeps a *summary*: ``cache["ssm"][r]`` ``[L, slots, N,
  C]`` float32, the selective scan's state after the slot's last token,
  and ``cache["conv"][r]`` ``[L, K - 1, slots, C]`` at the model's
  dtype, the last K - 1 inputs of its causal convolution (ops/ssm.py;
  the slots beside the channels, so that the two dimensions the chip
  tiles are whole multiples of a tile and not K - 1 = 3 rows). It has no rows and no position: it does not grow, a
  token's step replaces it whole, and what it was before cannot be read
  back. A model without Mamba layers has neither tuple;
* a retention run keeps a summary too, a matrix a K/V head:
  ``cache["ret"][r]`` ``[L, slots, G, Dv, D]`` float32, the head's
  state S after the slot's last token (D = ``ops.retention.feature_dim``
  of the head's width: 8,320 at 128), and ``cache["ret_z"][r]``
  ``[L, slots, G, D]``, its normaliser. **A model of such layers alone
  has no K/V at all**: no ``k``, no ``v``, no ring, and nothing in its
  cache is sized by ``max_len``.

**Layers that keep nothing.** A gated memory unit and a cross layer
have no entry anywhere: the first gates the *memory*, the scan output
(before its gate) of the last Mamba layer before it at the same
position, which both programs carry from layer to layer and from run to
run beside x and which no one keeps between tokens; the second
projects queries alone and attends **the K and V of the last
full-attention layer before it, in that layer's own cache**: one cache
that grows, written by one layer and read by every cross layer behind
it. ``slot_decode_step`` hands ``decode_attention`` the full layer's
run's arrays as they lie after that run's in-place write of the step's
token (the carry of another run's scan, closed over: no slice, no
copy), with that layer's index; ``slot_prefill`` hands a cross layer
the prompt's K and V as the full layer made them.

A model with expert layers carries ``cache["load"]``, int32 [3]: the
held experts that got a row, the rows routed to held experts and the
fullest expert's rows in the last decode step, each summed over the
expert layers, for the engine to fetch with the step's tokens.

Continuous batching (serve/decode_scheduler.py) needs exactly this
form: one sequence prefills into an open slot while the other slots
keep stepping, and a finished slot frees at once. Whole-batch
generation (``generate``) is its all-rows-active case. Every shape is
static, so serving is two compiled programs, each a ``lax.scan`` of
``transformer.block`` (the one definition of the layer) over each run's
stacked layers and their index, with the run's whole state as the
scan's carry:

* ``slot_prefill`` runs a prompt through the block with the training
  forward's rope, attention and scan (the flash and ``ssm_scan``
  kernels on TPU, XLA off it) and writes into the carry at ``(layer,
  slot)`` the roped K/V of the prompt's positions, or the state and the
  convolution's tail after its last one. Where the model ends in
  layers that mix nothing over the sequence (gated memory units, cross
  layers) it runs in two stages: the whole prompt up to the last layer
  that does mix, whose K and V alone are made at every position, and
  the last position alone from there on (``prefill_stages``);
* ``slot_decode_step`` feeds each row one token: an attention layer
  ropes it at the row's own position, scatters its K/V into the carry
  at ``[layer, rows, pos]`` and attends the layer's K/V, read out of
  the carry after that write; a Mamba layer reads the rows' state out
  of the carry, advances it by one position and writes it back (no
  recompute, no dynamic shapes); a retention layer hands the run's
  whole S and z to ``ops.retention.retention_step``, which on the TPU
  is one kernel that reads each live row's state once and writes it
  once where it lies, and touches no other row.

**Which runs attend through which form.** A run of full-attention
layers attends through
``ops.attention.decode_attention``: the run's whole K and V, as the
carry holds them after the write, with the layer's index and the rows'
``pos``. On the TPU that is the kernel ``decode_attend``, which copies
a row's K and V chunk by chunk up to the chunk that holds ``pos`` and
nothing past it (no slice of a layer is made to feed it: the carry is
its operand); elsewhere, for a shape the kernel has no chunk for and
for a layer with a sink, it is ``cached_attention`` over the layer's slice under a per-row mask,
which reads all ``max_len`` rows. A run of window layers is bounded by
its ring and keeps ``cached_attention`` (with the layer's sink) on
every platform. Both forms lean on the one invariant below: **a row
past a slot's ``pos`` is never attended**, so a reused slot's stale
tail and an idle row's garbage stay unread; the kernel does not even
copy them, and zeroes what its last chunk holds of them.

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
it donated, and every run's state is carried through the layers' scan,
not scanned over: XLA then aliases the result to the argument, the
only operations that produce K or V are the in-place writes of the new
rows, and a Mamba layer's state is read and written where it lies.
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
* ``slot_prefill`` makes its slot's state the prompt's and nothing
  else's, so a reused slot never sees its predecessor. Of rows: it
  rewrites rows [0, T0) (of a ring, the rows its last ``window``
  positions fall on) and resets the slot's pos; the stale tail beyond
  T0 is always overwritten (step s writes position pos BEFORE attending
  it) and never attended. Of a summary (a Mamba layer's state and
  tail, a retention layer's S and z): it replaces the slot's whole,
  computed from zeros.
* ``slot_decode_step`` advances ``pos``, replaces ``tok`` and advances
  a Mamba or retention layer's summary only where ``active``: **an
  inactive row's summary is bit for bit what it was** (a Mamba layer's
  step computes a new one for every row and writes back the old one
  there: the select costs no traffic, the layer's state is read and
  written whole either way; the retention kernel neither reads nor
  writes such a row, and its XLA form selects as the Mamba step does).
  Rows are not so guarded, and need not be: a step writes every
  row's K/V unconditionally (a masked write would cost a gather per
  layer), so an inactive row's cache may take garbage at its frozen
  pos, which no one reads before the next write there. A summary has
  no such place: garbage folded into it would stay. A row that is
  stepped when no one is owed its token (the engine's one step more
  for a finished request) does advance, rows and summary alike, which
  is sound because such a slot is only ever re-entered through
  ``slot_prefill``.
* A row at ``pos == max_len`` would have its write clamped onto the
  last position; the callers refuse before that (``generate``'s
  ``T0 + steps > max_len``, ``JaxSlotEngine.step``'s host mirror).
  ``max_len`` bounds a slot by its attention runs' rows; a model of
  Mamba or retention layers alone keeps nothing that grows, and its
  bound is ``cfg.max_seq``, the rope's table.

**Which part of the block an operation of a decode step computes** is
in the compiled step's metadata: every stretch of ``slot_decode_step``
is traced under the scope of its part (``transformer.PARTS``) and of its
run of layers, and :func:`program_parts` reads the compiled text into
``{instruction: [run, part]}``, the table a device trace is joined with
(``JaxSlotEngine.parts()``, serve/decode_scheduler.py). A prefill has
the block's own scopes and no table yet.

Oracle: greedy decoding must match the per-step argmax of the FULL
forward() on the growing prefix, which shares no cache code —
tests/test_ops.py, tests/test_decode_scheduler.py and
tests/test_mamba_block.py assert it, which pins the cache bookkeeping
(rope offsets, masking, row writes, the state's hand-over from prefill
to step) to the training forward's semantics.
"""

from __future__ import annotations

import functools
import re
from typing import Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.transformer import (BORROWERS, CROSS, EXPERTS,
                                        FROM_THE_START, FULL, GMU,
                                        LAYER_WEIGHTS, MAMBA,
                                        PARTS, RETENTION, SUMMARIES, WINDOW,
                                        TransformerConfig, block, kind_rope,
                                        last_position,
                                        layer_runs, layer_stacks,
                                        no_rotation, nothing_lent,
                                        one_period, period_of,
                                        retain_from_the_start, roped_kinds,
                                        run_layers, scan_run, unembed)
from ray_tpu.ops import retention, ssm
from ray_tpu.ops.attention import (cached_attention, decode_attention,
                                   decode_rows_fetched, flash_attention)
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


def _by_layer(runs, make) -> tuple:
    """One entry a run of ``make(mixer, layers in the run)``, and for a
    run of periods of several layers a tuple of them, one for each
    layer of the period: the form of each of the cache's tuples."""
    return tuple(
        make(kind[0], n) if isinstance(kind[0], str)
        else tuple(make(mixer, n) for mixer, _ in kind)
        for kind, n in runs)


def init_slot_cache(cfg: TransformerConfig, slots: int,
                    max_len: int) -> Dict:
    """The cache of ``slots`` sequences, each with a decode offset of
    its own: tuples with one entry a run of alike layers (one in all
    where the layers are all alike), ``None`` where the run keeps no
    state of that kind; in a run of periods of several layers the entry
    is a tuple, one for each layer of the period. ``k`` and ``v``: an
    attention run's rows, a
    window run at ``cfg.window`` of them. Where every query head has a
    K/V head of its own a row is [H, Dh]; where G K/V heads serve more
    query heads each it is flat, [G * Dh], the heads side by side: rows
    of 768 or 1536 values tile the chip's memory as they are, which
    [4, 192] or [8, 192] do not (the compiler's own layouts for those
    cost two copies of K a step). ``ssm`` and ``conv``, in a model with
    Mamba layers: a Mamba run's state [n, slots, N, C] float32 and its
    convolution's tail [n, K - 1, slots, C]. ``ret`` and ``ret_z``, in
    a model with retention layers: a retention run's state
    [n, slots, G, Dv, D] and normaliser [n, slots, G, D], float32. A
    model with no attention layer has no ``k`` and no ``v``; a gated
    memory unit and a cross layer keep nothing anywhere."""
    runs = layer_runs(cfg)

    def rows(width):
        def made(mixer, n):
            if mixer in SUMMARIES + BORROWERS:
                return None
            G = cfg.kv_heads(mixer)
            row = (G, width) if G == cfg.n_heads else (G * width,)
            return jnp.zeros(
                (n, slots, cfg.window if mixer == WINDOW else max_len)
                + row, cfg.dtype)
        return _by_layer(runs, made)

    def summaries(of, shape, dtype):
        return _by_layer(runs, lambda mixer, n: jnp.zeros(
            (n,) + shape, dtype) if mixer == of else None)

    mixers = {mixer for _, _, mixer, _ in run_layers(runs)}
    cache = {"pos": jnp.zeros((slots,), jnp.int32),
             "tok": jnp.zeros((slots,), jnp.int32)}
    if mixers & {FULL, WINDOW}:
        cache.update(k=rows(cfg.head_dim), v=rows(cfg.v_dim))
    if cfg.has_mamba:
        cache["ssm"] = summaries(
            MAMBA, (slots, cfg.ssm_state, cfg.ssm_inner), jnp.float32)
        cache["conv"] = summaries(
            MAMBA, (cfg.ssm_conv - 1, slots, cfg.ssm_inner), cfg.dtype)
    if cfg.has_retention:
        G, D = cfg.kv_heads(RETENTION), retention.feature_dim(cfg.head_dim)
        cache["ret"] = summaries(RETENTION, (slots, G, cfg.v_dim, D),
                                 jnp.float32)
        cache["ret_z"] = summaries(RETENTION, (slots, G, D), jnp.float32)
    if any(ffn == EXPERTS for _, _, _, ffn in run_layers(runs)):
        cache["load"] = jnp.zeros((3,), jnp.int32)
    return cache


# which two tuples of the cache hold a layer's state, by its mixer
ROWS, SUMMARY, RETAINED = ("k", "v"), ("ssm", "conv"), ("ret", "ret_z")


def _state_names(mixer: str):
    if mixer in BORROWERS:
        return ()
    return {MAMBA: SUMMARY, RETENTION: RETAINED}.get(mixer, ROWS)


def _cache_runs(cache: Dict, runs):
    """Each run's state: a tuple with one pair of arrays for each layer
    of the run's period (one pair, then, for a run of alike layers):
    (K, V) of an attention layer, (state, tail) of a Mamba layer,
    (S, z) of a retention layer, (None, None) of a layer that keeps
    nothing."""
    names = {name for _, _, mixer, _ in run_layers(runs)
             for name in _state_names(mixer)}
    for name in sorted(names):
        if not (isinstance(cache.get(name), tuple)
                and len(cache[name]) == len(runs)):
            raise ValueError(
                f"the cache holds a tuple cache[{name!r}], one array for "
                f"each of the model's {len(runs)} runs of alike layers "
                f"(None where a run keeps no such state: init_slot_cache)")

    def pair(mixer, held):
        names = _state_names(mixer)
        return tuple(held(name) for name in names) if names else (None, None)

    return [(pair(kind[0], lambda name: cache[name][r]),)
            if isinstance(kind[0], str)
            else tuple(pair(mixer, lambda name: cache[name][r][j])
                       for j, (mixer, _) in enumerate(kind))
            for r, (kind, _) in enumerate(runs)]


def _with_states(cache: Dict, runs, states, **more) -> Dict:
    """``cache`` with each run's state replaced (the form of
    ``_cache_runs``)."""
    new = {name: [None] * len(runs) for name in ROWS + SUMMARY + RETAINED
           if name in cache}
    for r, ((kind, _), state) in enumerate(zip(runs, states)):
        layers = [dict(zip(_state_names(mixer), pair))
                  for (mixer, _), pair in zip(period_of(kind), state)]
        for name in new:
            held = tuple(layer.get(name) for layer in layers)
            new[name][r] = held[0] if isinstance(kind[0], str) else held
    return dict(cache, **{name: tuple(held) for name, held in new.items()},
                **more)


def _layer_states(runs, states):
    """[(mixer, (first, second)), ...] of every layer of one period of
    every run, in the layers' order."""
    return [(mixer, pair) for (kind, _), state in zip(runs, states)
            for (mixer, _), pair in zip(period_of(kind), state)]


def _max_len(cfg: TransformerConfig, runs, states) -> int:
    """Rows of a full-attention run's cache: the longest sequence a slot
    holds (``cfg.max_seq`` where no layer keeps all its rows)."""
    return next((ck.shape[2] for mixer, (ck, _) in _layer_states(runs, states)
                 if mixer == FULL), cfg.max_seq)


def kv_rows_fetched(cfg: TransformerConfig, cache: Dict) -> Optional[int]:
    """How many positions of a slot a full-attention layer of
    ``slot_decode_step`` fetches at a time from this cache: the chunk
    ``ops.attention.decode_attention``'s kernel copies, all ``max_len``
    where its XLA form runs, None for a model without such a layer. A
    slot stepped at position p reads ``(p // n + 1) * n`` of its rows a
    layer, which is what ``JaxSlotEngine`` counts from its host mirror."""
    runs = layer_runs(cfg)
    for mixer, (ck, cv) in _layer_states(runs, _cache_runs(cache, runs)):
        if mixer == FULL:
            q = jax.ShapeDtypeStruct(
                (ck.shape[1], cfg.n_heads, cfg.head_dim), ck.dtype)
            return decode_rows_fetched(q, ck, cv,
                                       sink=mixer in cfg.sink_kinds)
    return None


def kv_readers(cfg: TransformerConfig) -> int:
    """How many layers of a decode step read a full-attention layer's
    rows, for each layer that holds such rows: 1, and with cross
    layers, which read the rows of the full layer before them, as many
    more as that layer lends to (8 where seven cross layers share one
    cache)."""
    mixers = [mixer for mixer, _ in cfg.layer_kinds or ()]
    return 1 + mixers.count(CROSS) // max(1, mixers.count(FULL))


def keeps_summaries(cfg: TransformerConfig) -> bool:
    """Whether a layer of ``cfg`` keeps a summary a slot (a Mamba
    layer's state, a retention layer's): every row a decode step steps
    then has that state read and written whole, whatever its length."""
    return cfg.has_mamba or cfg.has_retention


def _cache_rows(t, like, window: Optional[int]):
    """A prompt's K or V, t [1, T0, G, D], as the rows the cache
    ``like`` holds: flat where its rows are, and of a ring of
    ``window`` the last ``window`` positions, position p in row
    p % window."""
    T0 = t.shape[1]
    if window is not None and T0 > window:
        t = jnp.roll(t[:, T0 - window:], (T0 - window) % window, axis=1)
    return t.reshape(t.shape[:2] + like.shape[3:]).astype(like.dtype)


def _tally(load, got):
    """``load`` with an expert layer's counts added: held experts that
    got a row, rows routed to them, the fullest one's rows."""
    if got is None:
        return load
    with jax.named_scope("experts"):
        return load + jnp.stack([jnp.sum(got > 0), jnp.sum(got),
                                 jnp.max(got)])


def prefill_stages(cfg: TransformerConfig) -> Tuple[int, Optional[int]]:
    """Where a prefill stops running the whole prompt: (the first layer
    that runs at the last position alone, the layer handed ``last`` or
    None). Behind the last layer that mixes over the sequence (every
    layer from there on a gated memory unit or a cross layer) nothing
    needs any position's activations but the last's, whose logits the
    prefill is for. That last mixing layer itself, if it is an
    attention layer, makes its K and V at every position (the cache's,
    and what the cross layers attend) and everything else of itself at
    the last (``block``'s ``last``). A model with no such layers runs
    every layer at every position: (n_layers, None)."""
    mixers = [mixer for mixer, _ in cfg.layer_kinds or ()]
    tail = len(mixers)
    while tail and mixers[tail - 1] in BORROWERS:
        tail -= 1
    if tail == len(mixers) or tail == 0:
        return cfg.n_layers, None
    return tail, tail - 1 if mixers[tail - 1] in (FULL, WINDOW) else None


def _changes_stage(cfg: TransformerConfig, depth: int, n: int,
                   period: int) -> bool:
    """Whether the run of ``n`` periods of ``period`` layers whose
    first layer is layer ``depth`` is the one inside which a prefill
    goes on at the last position alone: it holds the change and is one
    period, which needs no scan."""
    tail, split = prefill_stages(cfg)
    last = depth + n * period - 1
    return n == 1 and tail < cfg.n_layers and (
        depth <= tail <= last or split == last)


def prefill_cross_rows(cfg: TransformerConfig, T0: int) -> Optional[int]:
    """How many positions of a prompt of ``T0`` the first layer behind
    the last mixing one runs over in ``slot_prefill``: 1 where the
    stage changes before it, ``T0`` where it lies in a run of several
    periods that holds the change, None for a model with no such
    layer."""
    tail, depth = prefill_stages(cfg)[0], 0
    if tail == cfg.n_layers:
        return None
    for kind, n in layer_runs(cfg):
        period = len(period_of(kind))
        if depth <= tail < depth + n * period:
            return 1 if depth == tail or _changes_stage(
                cfg, depth, n, period) else T0
        depth += n * period
    return None


def _either_stage(whole, cos, sin, T0: int):
    """``rope`` of a prefill in two stages: ``whole`` for the prompt's
    ``T0`` positions, and for the second stage's queries, which are the
    last position's alone, that position's rotation."""
    def rope(t):
        if t.shape[1] == T0:
            return whole(t)
        return apply_rotary(t, cos=cos, sin=sin, positions=jnp.arange(
            T0 - t.shape[1], T0))
    return rope


def _at_the_last(x, lent):
    """x [1, T, D] and the memory beside it (``lent``'s first) cut to
    the last position; the lent K and V stay whole."""
    return last_position(x), tuple(
        t if t is None or at else last_position(t)
        for at, t in enumerate(lent))


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("cache",))
def slot_prefill(params, tokens, cache: Dict, slot,
                 cfg: TransformerConfig) -> Tuple[jnp.ndarray, Dict]:
    """Run one prompt [1, T0] through the stack, leaving in cache row
    ``slot`` (a traced index: one compiled program serves every slot)
    each attention layer's K/V and each Mamba layer's state and tail
    after the prompt's last position. Returns (last-token logits
    [1, V], cache), the token picked from them in
    ``cache["tok"][slot]``; the cache given is consumed and the one
    returned is its memory, updated in place. Compiles once per
    distinct T0 — serving callers should bucket or pad prompt lengths
    if retrace cost matters.

    **Two stages, where the model ends in layers that mix nothing over
    the sequence** (``prefill_stages``): the whole prompt through the
    layers up to the last one that does, of which an attention layer
    makes its K and V at every position and the rest of itself at the
    last; then the last position alone, x [1, 1, D] and the memory
    beside it, through the gated memory units and cross layers behind
    it, each cross layer's one query attending the K and V just made.
    The stage changes inside a run of one period or between runs (a
    scan's carry keeps its shape): a run of several periods that holds
    the change is run whole at every position and cut behind it."""
    _, T0 = tokens.shape
    runs = layer_runs(cfg)
    states = _cache_runs(cache, runs)
    max_len = _max_len(cfg, runs, states)
    sm_scale = cfg.head_dim ** -0.5
    ropes = {}
    for attention in roped_kinds(cfg, runs):
        cos, sin = kind_rope(cfg, attention, max_len)
        ropes[attention] = functools.partial(
            apply_rotary, cos=cos, sin=sin, positions=jnp.arange(T0))
        if cfg.lends:
            ropes[attention] = _either_stage(ropes[attention], cos, sin, T0)
    x = params["embed"][tokens]

    # a layer of each kind: (x, lent, lp, i, first, second, last) ->
    # (x, lent, first, second); ``lent`` (the memory, the last full
    # layer's K, its V), ``first`` and ``second`` the layer's run's
    # whole state arrays, written at (i, slot)
    def attention_layer(attention):
        window = cfg.window if attention == WINDOW else None

        def layer(x, lent, lp, i, ck, cv, last=False):
            # ck/cv: the whole [L, slots, rows, G, Dh]
            def attend(q, k, v):
                # the training forward's local attention, so the last
                # token's logits are forward()'s; the roped k and v are
                # what a later step attends
                with jax.named_scope(f"{attention}_attention"):
                    return flash_attention(
                        q, k, v, causal=True, sm_scale=sm_scale,
                        window=window, sink=lp.get("sink")), (k, v)

            x, (k, v), _ = block(lp, x, ropes.get(attention, no_rotation),
                                 attend, cfg, last=last)
            ck = lax.dynamic_update_slice(
                ck, _cache_rows(k, ck, window)[None],
                (i, slot) + (0,) * (ck.ndim - 2))
            cv = lax.dynamic_update_slice(
                cv, _cache_rows(v, cv, window)[None],
                (i, slot) + (0,) * (cv.ndim - 2))
            if attention == FULL and cfg.has_cross:
                lent = (lent[0], k, v)
            return x, lent, ck, cv

        return layer

    def mamba_layer(x, lent, lp, i, cs, tails, last=False):
        # cs: the whole [L, slots, N, C]
        # the training forward's convolution and scan
        x, (tail, state, *memory), _ = block(lp, x, None, FROM_THE_START,
                                             cfg)
        cs = lax.dynamic_update_slice(
            cs, state[None].astype(cs.dtype), (i, slot, 0, 0))
        tails = lax.dynamic_update_slice(
            tails, tail.swapaxes(0, 1)[None].astype(tails.dtype),
            (i, 0, 0, 0))
        return x, (*memory, *lent[len(memory):]), cs, tails

    def retention_layer(x, lent, lp, i, cs, cz, last=False):
        # the whole [L, slots, G, Dv, D], [.., G, D]
        # the training forward's retention, from nothing before it;
        # the slot's state is the prompt's and nothing else's
        x, (S, z), _ = block(lp, x, ropes.get(RETENTION, no_rotation),
                             retain_from_the_start, cfg)
        cs = lax.dynamic_update_slice(cs, S[None], (i, slot, 0, 0, 0))
        cz = lax.dynamic_update_slice(cz, z[None], (i, slot, 0, 0))
        return x, lent, cs, cz

    def gmu_layer(x, lent, lp, i, first, second, last=False):
        return block(lp, x, None, lent[0], cfg)[0], lent, first, second

    def cross_layer(x, lent, lp, i, first, second, last=False):
        def attend(q, k, v):
            # every position its own prefix of the full layer's K/V, or
            # the last position, one query, all of it
            with jax.named_scope("cross_attention"):
                return flash_attention(q, lent[1], lent[2], causal=True,
                                       sm_scale=sm_scale), None

        x, _, _ = block(lp, x, ropes.get(CROSS, no_rotation), attend, cfg)
        return x, lent, first, second

    def layer_of(mixer):
        return {MAMBA: mamba_layer, RETENTION: retention_layer,
                GMU: gmu_layer, CROSS: cross_layer}.get(
                    mixer) or attention_layer(mixer)

    tail, split = prefill_stages(cfg)

    def run(x, lent, kind, layers, state, depth, n):
        """One run: its layers over x, its state written. ``depth`` the
        index of its first layer in the model. The carry holds the
        state as two tuples, each layer of the period's first array and
        its second."""
        period = period_of(kind)
        layer = [layer_of(mixer) for mixer, _ in period]
        firsts, held = zip(*state)
        # a Mamba layer's tails are gathered [L, K - 1, 1, C] and
        # written into the slot once, behind the scan: carried through
        # it, the tails' array (rows of the model's dtype, written at a
        # slot that is no multiple of a tile) is copied whole on the
        # way in and on the way out
        seconds = tuple(
            jnp.zeros(cc.shape[:2] + (1,) + cc.shape[3:], cc.dtype)
            if mixer == MAMBA else cc
            for (mixer, _), cc in zip(period, held))

        def body(carry, lps, i, staged=False):
            x, firsts, seconds, *lent = carry
            firsts, seconds, lent = list(firsts), list(seconds), tuple(lent)
            for j, lp in enumerate(lps if len(period) > 1 else (lps,)):
                at = depth + i * len(period) + j
                if cfg.differential:
                    lp = dict(lp, depth=at)
                if staged and at == tail and x.shape[1] > 1:
                    x, lent = _at_the_last(x, lent)
                x, lent, firsts[j], seconds[j] = layer[j](
                    x, lent, lp, i, firsts[j], seconds[j],
                    last=staged and at == split)
                if staged and at == split:
                    x, lent = _at_the_last(x, lent)
            return (x, tuple(firsts), tuple(seconds), *lent)

        if depth >= tail and x.shape[1] > 1:
            x, lent = _at_the_last(x, lent)
        carry = (x, firsts, seconds, *lent)
        if _changes_stage(cfg, depth, n, len(period)):
            # one period, no scan: the stage may change inside it
            x, firsts, seconds, *lent = body(
                carry, one_period(layers), 0, staged=True)
        else:
            x, firsts, seconds, *lent = scan_run(body, carry, layers)
        seconds = tuple(
            lax.dynamic_update_slice(cc, tails, (0, 0, slot, 0))
            if mixer == MAMBA else tails
            for (mixer, _), cc, tails in zip(period, held, seconds))
        return x, tuple(lent), tuple(zip(firsts, seconds))

    new, depth = [], 0
    lent = nothing_lent(cfg, x) if cfg.lends else (None, None, None)
    for ((kind, layers), (_, n)), state in zip(
            zip(layer_stacks(params, cfg), runs), states):
        x, lent, state = run(x, lent, kind, layers, state, depth, n)
        new.append(state)
        depth += n * len(period_of(kind))
    logits = unembed(params, x, last=True, eps=cfg.norm_eps)
    return logits, _with_states(
        cache, runs, new, pos=cache["pos"].at[slot].set(T0),
        tok=cache["tok"].at[slot].set(pick(logits)[0]))


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("cache",))
def slot_decode_step(params, cache: Dict, token, active,
                     cfg: TransformerConfig) -> Tuple[jnp.ndarray, Dict]:
    """One continuous-batching step: each ACTIVE row is fed one token,
    attends its own prefix (positions ``[0, pos]``, and of the cache no
    row past them: ``ops.attention.decode_attention``; in a window
    layer the last ``window`` positions of it, under a mask) and
    advances its Mamba layers'
    state by it, advances its own pos and picks its next token into
    ``cache["tok"]``. Inactive rows are free riders — their logits are
    garbage, their pos, tok and Mamba state frozen. ``token`` int32 [B]
    says what a row is fed: a token id, or ``CARRY`` for the row's own
    last pick, which never left the device. The cache given is consumed
    and the one returned is its memory, with one position a row and
    attention layer written in place and every Mamba layer's state
    replaced where it lay.

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
    runs = layer_runs(cfg)
    states = _cache_runs(cache, runs)
    max_len = _max_len(cfg, runs, states)
    pos = cache["pos"]  # [B]
    with jax.named_scope("embed"):
        if served:
            active = token != IDLE
        token = jnp.where(token >= 0, token, cache["tok"])
    with jax.named_scope("qkv"):
        tables = {a: kind_rope(cfg, a, max_len)
                  for a in roped_kinds(cfg, runs)}
    with jax.named_scope("embed"):
        x = params["embed"][token][:, None, :]  # [B, 1, D]
    sm_scale = cfg.head_dim ** -0.5
    # row r attends positions [0, pos[r]] (pos[r] is written this
    # step): a full-attention run hands ``decode_attention`` the
    # positions themselves; of a ring, the rows filled so far, all once
    # pos[r] has passed the window
    with jax.named_scope("window_attention"):
        filled = jnp.arange(cfg.window or 0)[None, None, :] \
            <= pos[:, None, None]                           # [B, 1, rows]
    with jax.named_scope("full_attention"):
        rows = jnp.arange(B)    # of the cache's in-place write, either kind

    def row_rope(mixer):
        if mixer not in tables:
            return no_rotation
        cos, sin = tables[mixer]

        def rope(t):  # every row at its own position
            return rotate(t, cos[pos][:, None, None, :],
                          sin[pos][:, None, None, :])
        return rope

    # a layer of each kind: (x, load, memory, shared, lp, i, first,
    # second) -> (x, load, memory, shared, first, second); ``memory``
    # the rows' memory [B, 1, C] (None in a model without gated memory
    # units), ``shared`` (K, V, layer) of the last full-attention layer
    # as they lie in its run's carry, behind that run's write
    def attention_layer(attention):
        window = cfg.window if attention == WINDOW else None
        at = pos if window is None else pos % window
        rope = row_rope(attention)

        def layer(x, load, memory, shared, lp, i, ck, cv):
            # ck/cv: the whole [L, B, rows, G, Dh]
            def attend(q, k, v):
                with jax.named_scope(f"{attention}_attention"):
                    # write, then attend: the layer's K/V are read out of
                    # the carry after the rows' new token is in it
                    nk = ck.at[i, rows, at].set(
                        k[:, 0].reshape((B,) + ck.shape[3:]).astype(ck.dtype))
                    nv = cv.at[i, rows, at].set(
                        v[:, 0].reshape((B,) + cv.shape[3:]).astype(cv.dtype))
                    if window is None:
                        # the carry itself is the operand: no slice of
                        # a layer feeds the kernel
                        o = decode_attention(
                            q[:, 0], nk, nv, i, pos, sm_scale=sm_scale,
                            sink=lp.get("sink"))
                    else:
                        o = cached_attention(
                            q[:, 0],
                            lax.dynamic_index_in_dim(nk, i, keepdims=False),
                            lax.dynamic_index_in_dim(nv, i, keepdims=False),
                            filled, sm_scale, lp.get("sink"))
                return o, (nk, nv)

            x, (ck, cv), got = block(lp, x, rope, attend, cfg)
            if attention == FULL and cfg.has_cross:
                shared = (ck, cv, i)
            return x, _tally(load, got), memory, shared, ck, cv

        return layer

    def mamba_layer(x, load, memory, shared, lp, i, cs, cc):
        # [L, B, N, C] and [L, K-1, B, C]
        with jax.named_scope("mamba_mixer"):
            tail = lax.dynamic_index_in_dim(
                cc, i, keepdims=False).swapaxes(0, 1)   # [B, K-1, C]

        def conv(u, w, b):
            return ssm.causal_conv(u, w, b, tail)

        def step(u, dt, A, b, c, D):
            with jax.named_scope("ssm_step"):
                # the carry itself is the operand: a row's state is
                # read once, advanced and written where it lies, an
                # inactive row's bit for bit what it was
                y, ns = ssm.carried_step(
                    u[:, 0], dt[:, 0], A, b[:, 0], c[:, 0], D, cs, i,
                    active)
            return y[:, None], ns

        x, (new_tail, cs, *made), got = block(
            lp, x, None, ssm.Recurrence(conv, step), cfg)
        # ... and so its convolution's tail
        with jax.named_scope("mamba_mixer"):
            cc = lax.dynamic_update_slice(cc, jnp.where(
                active[:, None, None], new_tail.astype(cc.dtype),
                tail).swapaxes(0, 1)[None], (i, 0, 0, 0))
        return (x, _tally(load, got), made[0] if made else memory, shared,
                cs, cc)

    def retention_layer(x, load, memory, shared, lp, i, cs, cz):
        # [L, B, G, Dv, D] and [L, B, G, D]
        def retain(q, k, v, g):
            with jax.named_scope("retention_step"):
                # the carry itself is the operand: a live row's
                # state is read and written where it lies, an
                # inactive row's not at all
                o, ns, nz = retention.retention_step(
                    q[:, 0], k[:, 0], v[:, 0], g[:, 0], cs, cz, i,
                    active)
            return o[:, None], (ns, nz)

        x, (cs, cz), got = block(lp, x, row_rope(RETENTION), retain, cfg)
        return x, _tally(load, got), memory, shared, cs, cz

    def gmu_layer(x, load, memory, shared, lp, i, first, second):
        x, _, got = block(lp, x, None, memory, cfg)
        return x, _tally(load, got), memory, shared, first, second

    def cross_layer(x, load, memory, shared, lp, i, first, second):
        def attend(q, k, v):
            with jax.named_scope("cross_attention"):
                # the full layer's carry is the operand, as it lies
                # behind that layer's write of this step's token
                sk, sv, layer = shared
                return decode_attention(q[:, 0], sk, sv, layer, pos,
                                        sm_scale=sm_scale), None

        x, _, got = block(lp, x, row_rope(CROSS), attend, cfg)
        return x, _tally(load, got), memory, shared, first, second

    def layer_of(mixer):
        return {MAMBA: mamba_layer, RETENTION: retention_layer,
                GMU: gmu_layer, CROSS: cross_layer}.get(
                    mixer) or attention_layer(mixer)

    def run(x, load, memory, shared, kind, layers, state, depth):
        period = period_of(kind)
        layer = [layer_of(mixer) for mixer, _ in period]

        def body(carry, lps, i):
            x, firsts, seconds, load, memory = carry
            firsts, seconds, within = list(firsts), list(seconds), shared
            for j, lp in enumerate(lps if len(period) > 1 else (lps,)):
                if cfg.differential:
                    lp = dict(lp, depth=depth + i * len(period) + j)
                x, load, memory, within, firsts[j], seconds[j] = layer[j](
                    x, load, memory, within, lp, i, firsts[j], seconds[j])
            return x, tuple(firsts), tuple(seconds), load, memory

        x, firsts, seconds, load, memory = scan_run(
            body, (x, *zip(*state), load, memory), layers)
        full = [j for j, (mixer, _) in enumerate(period) if mixer == FULL]
        if full and cfg.has_cross:
            # the run's last full layer, as the run leaves it
            shared = (firsts[full[-1]], seconds[full[-1]],
                      firsts[full[-1]].shape[0] - 1)
        return x, load, memory, shared, tuple(zip(firsts, seconds))

    load = jnp.zeros_like(cache["load"]) if "load" in cache else None
    with jax.named_scope("gmu"):
        memory = nothing_lent(cfg, x)[0] if cfg.has_gmu else None
    new, depth, shared = [], 0, None
    for r, (((kind, layers), (_, n)), state) in enumerate(zip(
            zip(layer_stacks(params, cfg), runs), states)):
        with jax.named_scope(f"run{r}"):
            x, load, memory, shared, state = run(
                x, load, memory, shared, kind, layers, state, depth)
        new.append(state)
        depth += n * len(period_of(kind))
    logits = unembed(params, x[:, 0], eps=cfg.norm_eps)
    with jax.named_scope("head"):
        tok = jnp.where(active, pick(logits), cache["tok"])
        cache = _with_states(cache, runs, new,
                             pos=jnp.where(active, pos + 1, pos), tok=tok)
        if load is not None:
            cache["load"] = load
        if not served:
            return logits, cache
        return (tok if load is None else jnp.concatenate([tok, load])), cache


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


# ------------------------------------ a compiled program, part by part

# opcodes that hold other computations' instructions and are no work
# of their own, and (in the order below) the attributes that name them
_CONTAINERS = {"while": ("body", "condition"), "call": ("to_apply",),
               "conditional": ("true_computation", "false_computation",
                               "branch_computations")}
# ... and opcodes that name memory or order and run nothing
_NO_EVENT = frozenset((
    "parameter", "tuple", "get-tuple-element", "constant", "bitcast",
    "after-all", "opt-barrier", "partition-id", "replica-id"))
_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_RUN = re.compile(r"^run\d+$")


def _scope_of(op_name: str) -> Tuple[Optional[str], Optional[str]]:
    """(the ``run<i>`` on an ``op_name`` path, the innermost name of
    ``PARTS`` on it), None where there is none."""
    run = part = None
    for name in op_name.split("/"):
        if _RUN.match(name):
            run = name
        elif name in PARTS:
            part = name
    return run, part


def decode_parts(cfg: TransformerConfig) -> List[str]:
    """The scopes that the compiled ``slot_decode_step`` of ``cfg``
    names somewhere: the parts its layers' kinds imply and every run."""
    runs = layer_runs(cfg)
    want = {"embed", "head"} | {f"run{r}" for r in range(len(runs))}
    for _, _, mixer, ffn in run_layers(runs):
        want |= ({"mamba_mixer", "ssm_step"} if mixer == MAMBA
                 else {"gmu"} if mixer == GMU
                 else {"qkv", "retention_step", "attn_out"}
                 if mixer == RETENTION
                 else {"qkv", f"{mixer}_attention", "attn_out"})
        want |= {"router", "experts"} if ffn == EXPERTS else {"mlp"}
    return sorted(want)


def program_parts(compiled_text: str, expect: Iterable[str] = ()
                  ) -> Optional[Dict[str, List[Optional[str]]]]:
    """``{instruction: [run, part]}`` of a compiled program's text
    (``jitted.lower(...).compile().as_text()``): for every instruction
    of the entry computation, and of the loop bodies, branches and
    called computations it reaches, that can be a device event of its
    own (fusions, custom calls, copies, dots, scatters ...; not
    ``while``, ``conditional`` or ``call``, which hold the others, not
    parameters, tuples and their like, and nothing inside a fused
    computation), the ``run<i>`` on its ``op_name`` path and the
    innermost name of ``transformer.PARTS`` there. The profiler names a
    device event by its instruction, so this is the join between a
    trace and the scopes of ``transformer.block``.

    **A fusion is counted where its own metadata puts it, which is its
    root's**: one that spans two parts (a norm fused into the product
    before it) reads whole under the part of what it ends in. Where an
    instruction's own path names no run or no part, it has those of the
    loop, branch or call that holds it, as that container's path names
    them. Inside a run and inside no part it is that run's
    ``LAYER_WEIGHTS``: the scan's slice of each stacked weight and what
    XLA hangs on it, which only a run that kept its loop has. Outside
    every run and part both are None: what the compiler made in the
    entry computation and gave no metadata (a parameter's copy into
    faster memory, the relaid weight of a single layer's run, which XLA
    unrolls) is in no part, and a reader counts it as unscoped.

    None, never a partial table, where one of ``expect`` (scope names,
    ``decode_parts(cfg)``) is nowhere in the text's metadata: the
    executable came from another tree's compile cache entry (jax's
    cache key leaves ``op_name`` out) and its scopes are not this
    tree's."""
    named = {name for op_name in set(_OP_NAME.findall(compiled_text))
             for name in op_name.split("/")}
    if not set(expect) <= named:
        return None
    # computation -> [(instruction, opcode, the rest of its line)]
    computations, entry, current = {}, None, None
    for line in compiled_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            current = computations.setdefault(head.group(2), [])
            entry = head.group(2) if head.group(1) else entry
        elif current is not None and (m := _INSTRUCTION.match(line)):
            opcode = _OPCODE.search(" " + m.group(2))
            if opcode:
                current.append((m.group(1), opcode.group(1), m.group(2)))

    def held(rest, key):
        names = re.search(key + r"=\{?([^}\s]+(?:, [^}\s]+)*)", rest)
        return [c.strip("%,") for c in names.group(1).split(", ")] \
            if names else []

    table, seen = {}, set()

    def walk(computation, run, part):
        if computation in seen:
            return
        seen.add(computation)
        for name, opcode, rest in computations.get(computation, ()):
            found = _OP_NAME.search(rest)
            own_run, own_part = _scope_of(found.group(1) if found else "")
            r, p = own_run or run, own_part or part
            if opcode in _CONTAINERS:
                for key in _CONTAINERS[opcode]:
                    for inner in held(rest, key):
                        walk(inner, r, p)
            elif opcode not in _NO_EVENT:
                table[name] = [r, p or (LAYER_WEIGHTS if r else None)]

    walk(entry, None, None)
    return table
