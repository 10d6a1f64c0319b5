"""Cached autoregressive decoding for the flagship transformer: a
slot's state between tokens, of the kinds its layers keep.

**One table, one entry a kind of mixer.** ``KINDS`` holds all that the
serving side knows of each kind of ``transformer.MIXERS`` (``Kind``):
the tuples of the cache its state lies in and their shapes, its layer
of each program, the scopes its decode step names, and what the
programs and the engine ask of it (rows or a summary, what it lends or
borrows). The cache is a dict of those tuples, each with one entry a
run of alike layers (``transformer.layer_runs``): an array where the
run keeps that state, ``None`` where it does not (in a run of periods
of several layers, a tuple of them, one a layer of the period). A model
with expert layers carries ``cache["load"]``, int32 [3], the last
decode step's counts (``_tally``), for the engine to fetch with the
step's tokens.

Continuous batching (serve/decode_scheduler.py) needs exactly this
form: one sequence prefills into an open slot while the other slots
keep stepping, and a finished slot frees at once. Whole-batch
generation (``generate``) is its all-rows-active case. Every shape is
static, so serving is two compiled programs, each a ``lax.scan`` of
``transformer.block`` (the one definition of the layer) over each run's
stacked layers and their index. ``cache["tok"]`` is each row's last
pick (:func:`pick`), so the serving engine dispatches step n + 1
before the host has seen step n's tokens (``slot_decode_step``).

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
is traced under the scope of its part (``transformer.PARTS``) and of
its run of layers (:func:`program_parts`). A prefill has the block's
own scopes and no table yet.

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
from typing import (Any, Callable, Dict, Iterable, List, NamedTuple,
                    Optional, Tuple)

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.transformer import (
    CROSS, EXPERTS, FROM_THE_START, FULL, GMU, LAYER_WEIGHTS, MAMBA, PARTS,
    RETENTION, WINDOW, TransformerConfig, block, kind_rope, last_position,
    layer_runs, layer_stacks, no_rotation, nothing_lent, one_period,
    period_of, retain_from_the_start, roped_kinds, run_layers, scan_run,
    unembed)
from ray_tpu.ops import retention, ssm
from ray_tpu.ops.attention import (decode_attention, decode_rows_fetched,
                                   flash_attention)
from ray_tpu.ops.rotary import apply_rotary, rotate


# in a step's ``token`` row, in place of a token id (which is never
# negative): feed the row its own last pick, ``cache["tok"]``; and, where
# no ``active`` is given beside it, leave the row out of the step
CARRY, IDLE = -1, -2


def pick(logits, key=None, temperature=None):
    """The next token of each row of ``logits`` [.., V], int32: the
    likeliest, or one drawn at ``temperature`` where a key is given. The
    one place a token is chosen: ``slot_prefill`` picks a prompt's first
    token into its slot's ``cache["tok"]``, ``slot_decode_step`` each
    active row's next one, ``generate`` draws through it."""
    if key is None:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature).astype(jnp.int32)


# ------------------------------------------- the table of mixer kinds

class Lent(NamedTuple):
    """What travels from layer to layer beside x, None where the model
    has no reader of it, and no one keeps between tokens: the *memory*,
    the last Mamba layer's scan output before its gate, which gated
    memory units read; the last full-attention layer's K and V, which
    cross layers attend (a prefill's: the prompt's; a step's: its run's
    whole arrays behind that run's in-place write, the carry of another
    run's scan, closed over: no slice, no copy) with ``layer``, its
    index in them; and a step's expert counts ``load``."""
    memory: Any = None
    k: Any = None
    v: Any = None
    layer: Any = None
    load: Any = None


class Context(NamedTuple):
    """What every layer of one call of a program shares, made once at
    its top: each roped kind's ``rope`` (a prefill's over the prompt's
    positions, a step's at each row's own), the score scale, a prefill's
    ``slot``; a step's rows' ``pos`` and ``active``, ``rows``, the
    in-place write's row index, and ``where``, its row in a layer's rows
    (``Kind.within``)."""
    cfg: TransformerConfig
    ropes: Dict[str, Callable]
    sm_scale: float
    slot: Any = None
    pos: Any = None
    active: Any = None
    rows: Any = None
    where: Any = None


class Kind:
    """All that the serving side knows of one kind of mixer, an entry of
    ``KINDS``; this base keeps, lends and borrows nothing. ``state``:
    the cache's tuples that hold its state, ``shapes`` a (shape, dtype)
    for each, of a run of ``n`` such layers. ``prefill`` and ``step``:
    its layer of each program, ``(at, mixer, x, lent, lp, i, first,
    second, last=False) -> (x, lent, first, second)``: ``at`` the call's
    ``Context``, ``lp`` layer i's weights, ``first`` and ``second`` its
    run's whole state arrays (None where it keeps none), written at
    layer i, ``last`` a prefill's stage change (``prefill_stages``).
    ``parts``: the scopes its decode step names. ``rows``: its state is
    a row a position, K and V, ``max_len`` of them where it ``grows``,
    a ring of the window where not. ``lends`` and ``borrows``: what it
    hands the layers behind it, what it reads of one before it in the
    same pass (keeping nothing, mixing nothing over the sequence)."""
    state: Tuple[str, ...] = ()
    parts: Tuple[str, ...] = ()
    rows = grows = False
    lends = borrows = ""

    def within(self, at, mixer):
        """A step's ``at`` for its layer, made before its run's scan."""
        return at

    def lend(self, cfg, lent, k, v, layer=None):
        """``lent`` with what the layer lends of its state ``k`` and
        ``v`` (a step's: ``layer``, where they lie in them)."""
        return lent

    def gather(self, second):
        """What a prefill's run carries through its scan in the place of
        the layer's second array, and ``put`` writes behind the scan."""
        return second

    def put(self, second, gathered, slot):
        return gathered


class _Attention(Kind):
    state, rows = ("k", "v"), True

    def shapes(self, cfg, mixer, n, slots, max_len):
        """``max_len`` rows a slot, or a ring of ``cfg.window``. Where
        every query head has a K/V head of its own a row is [H, Dh];
        where G K/V heads serve more query heads each it is flat,
        [G * Dh], the heads side by side: rows of 768 or 1536 values
        tile the chip's memory as they are, which [4, 192] or [8, 192]
        do not (the compiler's own layouts for those cost two copies of
        K a step)."""
        G = cfg.kv_heads(mixer)
        length = max_len if self.grows else cfg.window
        return tuple(((n, slots, length) + ((G, width) if G == cfg.n_heads
                                            else (G * width,)), cfg.dtype)
                     for width in (cfg.head_dim, cfg.v_dim))

    def prefill(self, at, attention, x, lent, lp, i, ck, cv, last=False):
        # ck/cv: the whole [L, slots, rows, G, Dh]
        window = None if self.grows else at.cfg.window

        def attend(q, k, v):
            # the training forward's local attention, so the last
            # token's logits are forward()'s; the roped k and v are
            # what a later step attends
            with jax.named_scope(f"{attention}_attention"):
                return flash_attention(
                    q, k, v, causal=True, sm_scale=at.sm_scale,
                    window=window, sink=lp.get("sink")), (k, v)

        x, (k, v), _ = block(lp, x, at.ropes.get(attention, no_rotation),
                             attend, at.cfg, last=last)
        ck = lax.dynamic_update_slice(
            ck, _cache_rows(k, ck, window)[None],
            (i, at.slot) + (0,) * (ck.ndim - 2))
        cv = lax.dynamic_update_slice(
            cv, _cache_rows(v, cv, window)[None],
            (i, at.slot) + (0,) * (cv.ndim - 2))
        return x, self.lend(at.cfg, lent, k, v), ck, cv

    def within(self, at, mixer):
        """The row a step writes each row's token at: its ``pos``, of a
        ring ``pos % window``."""
        return at._replace(
            where=at.pos if self.grows else at.pos % at.cfg.window)

    def step(self, at, attention, x, lent, lp, i, ck, cv, last=False):
        """A layer attends through ``ops.attention.decode_attention``,
        with its sink where it has one: on the TPU the kernel, which
        copies a row's K and V out of the carry up to the chunk that
        holds its position, ``decode_attend`` over a full layer's
        growing rows, ``decode_ring`` over a window layer's ring, whose
        rows ``[0, min(pos, window - 1)]`` hold the window. Both lean on
        the invariant that **a row past a slot's ``pos`` is never
        attended**, so a reused slot's stale tail and an idle row's
        garbage stay unread."""
        # ck/cv: the whole [L, B, rows, G, Dh]
        B = x.shape[0]

        def attend(q, k, v):
            with jax.named_scope(f"{attention}_attention"):
                # write, then attend: the layer's K/V are read out of
                # the carry after the rows' new token is in it; the
                # carry itself is the operand: no slice of a layer
                # feeds the kernel
                nk = ck.at[i, at.rows, at.where].set(
                    k[:, 0].reshape((B,) + ck.shape[3:]).astype(ck.dtype))
                nv = cv.at[i, at.rows, at.where].set(
                    v[:, 0].reshape((B,) + cv.shape[3:]).astype(cv.dtype))
                o = decode_attention(
                    q[:, 0], nk, nv, i, at.pos, sm_scale=at.sm_scale,
                    sink=lp.get("sink"), ring=not self.grows)
            return o, (nk, nv)

        x, (ck, cv), got = block(lp, x, at.ropes.get(attention, no_rotation),
                                 attend, at.cfg)
        lent = self.lend(at.cfg, lent, ck, cv, i)
        return x, lent._replace(load=_tally(lent.load, got)), ck, cv


class _Full(_Attention):
    parts = ("qkv", "full_attention", "attn_out")
    grows, lends = True, "kv"

    def lend(self, cfg, lent, k, v, layer=None):
        # where the model has cross layers, they attend these K and V
        return lent._replace(k=k, v=v, layer=layer) if cfg.has_cross else lent


class _Window(_Attention):
    parts = ("qkv", "window_attention", "attn_out")


class _Mamba(Kind):
    state, parts = ("ssm", "conv"), ("mamba_mixer", "ssm_step")

    def shapes(self, cfg, mixer, n, slots, max_len):
        """The state [n, slots, N, C] float32, the selective scan's
        after the slot's last token, and the convolution's tail [n,
        K - 1, slots, C] at the model's dtype, the last K - 1 inputs
        (ops/ssm.py; the slots beside the channels, so that the two
        dimensions the chip tiles are whole multiples of a tile and not
        K - 1 = 3 rows)."""
        return (((n, slots, cfg.ssm_state, cfg.ssm_inner), jnp.float32),
                ((n, cfg.ssm_conv - 1, slots, cfg.ssm_inner), cfg.dtype))

    def prefill(self, at, mixer, x, lent, lp, i, cs, tails, last=False):
        # cs: the whole [L, slots, N, C]
        # the training forward's convolution and scan
        x, (tail, state, *memory), _ = block(lp, x, None, FROM_THE_START,
                                             at.cfg)
        cs = lax.dynamic_update_slice(
            cs, state[None].astype(cs.dtype), (i, at.slot, 0, 0))
        tails = lax.dynamic_update_slice(
            tails, tail.swapaxes(0, 1)[None].astype(tails.dtype),
            (i, 0, 0, 0))
        return (x, lent._replace(memory=memory[0]) if memory else lent,
                cs, tails)

    # a prefill's tails are gathered [L, K - 1, 1, C] and written into
    # the slot once, behind the scan: carried through it, the tails'
    # array (rows of the model's dtype, written at a slot that is no
    # multiple of a tile) is copied whole on the way in and on the way
    # out
    def gather(self, tails):
        return jnp.zeros(tails.shape[:2] + (1,) + tails.shape[3:],
                         tails.dtype)

    def put(self, tails, gathered, slot):
        return lax.dynamic_update_slice(tails, gathered, (0, 0, slot, 0))

    def step(self, at, mixer, x, lent, lp, i, cs, cc, last=False):
        """The rows' state read out of the carry, advanced by one
        position and written back: no recompute, no dynamic shapes."""
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
                    at.active)
            return y[:, None], ns

        x, (new_tail, cs, *made), got = block(
            lp, x, None, ssm.Recurrence(conv, step), at.cfg)
        # ... and so its convolution's tail
        with jax.named_scope("mamba_mixer"):
            cc = lax.dynamic_update_slice(cc, jnp.where(
                at.active[:, None, None], new_tail.astype(cc.dtype),
                tail).swapaxes(0, 1)[None], (i, 0, 0, 0))
        return (x, lent._replace(load=_tally(lent.load, got),
                                 memory=made[0] if made else lent.memory),
                cs, cc)


class _Retention(Kind):
    state, parts = ("ret", "ret_z"), ("qkv", "retention_step", "attn_out")

    def shapes(self, cfg, mixer, n, slots, max_len):
        """The state, a matrix a K/V head, [n, slots, G, Dv, D] float32,
        the head's S after the slot's last token (D =
        ``ops.retention.feature_dim`` of the head's width: 8,320 at
        128), and its normaliser z [n, slots, G, D]. A model of such
        layers alone keeps nothing sized by ``max_len``."""
        G, D = cfg.kv_heads(mixer), retention.feature_dim(cfg.head_dim)
        return (((n, slots, G, cfg.v_dim, D), jnp.float32),
                ((n, slots, G, D), jnp.float32))

    def prefill(self, at, mixer, x, lent, lp, i, cs, cz, last=False):
        # the whole [L, slots, G, Dv, D], [.., G, D]
        # the training forward's retention, from nothing before it;
        # the slot's state is the prompt's and nothing else's
        x, (S, z), _ = block(lp, x, at.ropes.get(mixer, no_rotation),
                             retain_from_the_start, at.cfg)
        cs = lax.dynamic_update_slice(cs, S[None], (i, at.slot, 0, 0, 0))
        cz = lax.dynamic_update_slice(cz, z[None], (i, at.slot, 0, 0))
        return x, lent, cs, cz

    def step(self, at, mixer, x, lent, lp, i, cs, cz, last=False):
        # [L, B, G, Dv, D] and [L, B, G, D]
        def retain(q, k, v, g):
            with jax.named_scope("retention_step"):
                # the carry itself is the operand: a live row's
                # state is read and written where it lies, an
                # inactive row's not at all
                o, ns, nz = retention.retention_step(
                    q[:, 0], k[:, 0], v[:, 0], g[:, 0], cs, cz, i,
                    at.active)
            return o[:, None], (ns, nz)

        x, (cs, cz), got = block(lp, x, at.ropes.get(mixer, no_rotation),
                                 retain, at.cfg)
        return x, lent._replace(load=_tally(lent.load, got)), cs, cz


class _GatedMemory(Kind):
    parts, borrows = ("gmu",), "memory"

    def prefill(self, at, mixer, x, lent, lp, i, first, second, last=False):
        """Either program's layer, on the memory it is lent."""
        x, _, got = block(lp, x, None, lent.memory, at.cfg)
        return x, lent._replace(load=_tally(lent.load, got)), first, second

    step = prefill


class _Cross(Kind):
    parts, borrows = ("qkv", "cross_attention", "attn_out"), "kv"

    def prefill(self, at, mixer, x, lent, lp, i, first, second, last=False):
        def attend(q, k, v):
            # every position its own prefix of the full layer's K/V, or
            # the last position, one query, all of it
            with jax.named_scope(f"{mixer}_attention"):
                return flash_attention(q, lent.k, lent.v, causal=True,
                                       sm_scale=at.sm_scale), None

        x, _, _ = block(lp, x, at.ropes.get(mixer, no_rotation), attend,
                        at.cfg)
        return x, lent, first, second

    def step(self, at, mixer, x, lent, lp, i, first, second, last=False):
        def attend(q, k, v):
            with jax.named_scope(f"{mixer}_attention"):
                # the full layer's carry is the operand, as it lies
                # behind that layer's write of this step's token
                return decode_attention(q[:, 0], lent.k, lent.v,
                                        lent.layer, at.pos,
                                        sm_scale=at.sm_scale), None

        x, _, got = block(lp, x, at.ropes.get(mixer, no_rotation), attend,
                          at.cfg)
        return x, lent._replace(load=_tally(lent.load, got)), first, second


KINDS = {FULL: _Full(), WINDOW: _Window(), MAMBA: _Mamba(),
         RETENTION: _Retention(), GMU: _GatedMemory(), CROSS: _Cross()}


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
    got a row, rows routed to them, the fullest one's rows; summed over
    a step's expert layers (a prefill counts none: ``load`` None)."""
    if got is None or load is None:
        return load
    with jax.named_scope("experts"):
        return load + jnp.stack([jnp.sum(got > 0), jnp.sum(got),
                                 jnp.max(got)])


# ------------------------------------------------------------ the cache

def init_slot_cache(cfg: TransformerConfig, slots: int,
                    max_len: int) -> Dict:
    """The cache of ``slots`` sequences, each with a decode offset of
    its own: a tuple for each name of state that the kinds of the
    model's layers keep (``KINDS``), in the form the module says."""
    runs = layer_runs(cfg)

    def zeros(mixer, n):
        kind = KINDS[mixer]
        if not kind.state:
            return None, None
        return tuple(jnp.zeros(shape, dtype) for shape, dtype in
                     kind.shapes(cfg, mixer, n, slots, max_len))

    cache = _with_states(
        {"pos": jnp.zeros((slots,), jnp.int32),
         "tok": jnp.zeros((slots,), jnp.int32)}, runs,
        [tuple(zeros(mixer, n) for mixer, _ in period_of(kind))
         for kind, n in runs])
    if any(ffn == EXPERTS for _, _, _, ffn in run_layers(runs)):
        cache["load"] = jnp.zeros((3,), jnp.int32)
    return cache


def _cache_runs(cache: Dict, runs):
    """Each run's state: a tuple with one pair of arrays for each layer
    of the run's period (one pair, then, for a run of alike layers),
    its kind's ``state``, or (None, None)."""
    names = {name for _, _, mixer, _ in run_layers(runs)
             for name in KINDS[mixer].state}
    for name in sorted(names):
        if not (isinstance(cache.get(name), tuple)
                and len(cache[name]) == len(runs)):
            raise ValueError(
                f"the cache holds a tuple cache[{name!r}], one array for "
                f"each of the model's {len(runs)} runs of alike layers "
                f"(None where a run keeps no such state: init_slot_cache)")

    def pair(mixer, held):
        return tuple(map(held, KINDS[mixer].state)) or (None, None)

    return [(pair(kind[0], lambda name: cache[name][r]),)
            if isinstance(kind[0], str)
            else tuple(pair(mixer, lambda name: cache[name][r][j])
                       for j, (mixer, _) in enumerate(kind))
            for r, (kind, _) in enumerate(runs)]


def _with_states(cache: Dict, runs, states, **more) -> Dict:
    """``cache`` with each run's state (the form of ``_cache_runs``)
    in its tuples."""
    new = {name: [None] * len(runs) for _, _, mixer, _ in run_layers(runs)
           for name in KINDS[mixer].state}
    for r, ((kind, _), state) in enumerate(zip(runs, states)):
        layers = [dict(zip(KINDS[mixer].state, pair))
                  for (mixer, _), pair in zip(period_of(kind), state)]
        for name in new:
            held = tuple(layer.get(name) for layer in layers)
            new[name][r] = held[0] if isinstance(kind[0], str) else held
    return dict(cache, **{name: tuple(held) for name, held in new.items()},
                **more)


def _grown(runs, states):
    """(mixer, (K, V)) of the first layer whose rows grow with the
    sequence, a full-attention layer's; (None, None) where none does."""
    return next(((mixer, pair) for (kind, _), state in zip(runs, states)
                 for (mixer, _), pair in zip(period_of(kind), state)
                 if KINDS[mixer].grows), (None, None))


def _max_len(cfg: TransformerConfig, runs, states) -> int:
    """Rows of a full-attention run's cache: the longest sequence a slot
    holds (``cfg.max_seq`` where no layer keeps all its rows)."""
    _, grown = _grown(runs, states)
    return cfg.max_seq if grown is None else grown[0].shape[2]


def kv_rows_fetched(cfg: TransformerConfig, cache: Dict) -> Optional[int]:
    """How many positions of a slot a full-attention layer of
    ``slot_decode_step`` fetches at a time from this cache: the chunk
    ``ops.attention.decode_attention``'s kernel copies, all ``max_len``
    where its XLA form runs, None for a model without such a layer. A
    slot stepped at position p reads ``(p // n + 1) * n`` of its rows a
    layer, which is what ``JaxSlotEngine`` counts from its host mirror
    (growing caches only: a window layer's ring is not counted)."""
    runs = layer_runs(cfg)
    _, grown = _grown(runs, _cache_runs(cache, runs))
    if grown is None:
        return None
    ck, cv = grown
    q = jax.ShapeDtypeStruct((ck.shape[1], cfg.n_heads, cfg.head_dim),
                             ck.dtype)
    return decode_rows_fetched(q, ck, cv)


def kv_readers(cfg: TransformerConfig) -> int:
    """How many layers of a decode step read a full-attention layer's
    rows, for each layer that holds such rows: 1, and with cross
    layers, which read the rows of the full layer before them, as many
    more as that layer lends to (8 where seven cross layers share one
    cache)."""
    kinds = [KINDS[mixer] for mixer, _ in cfg.layer_kinds or ()]
    return 1 + sum(kind.borrows == "kv" for kind in kinds) // max(
        1, sum(kind.lends == "kv" for kind in kinds))


def keeps_summaries(cfg: TransformerConfig) -> bool:
    """Whether a layer of ``cfg`` keeps a summary a slot (a Mamba
    layer's state, a retention layer's): every row a decode step steps
    then has that state read and written whole, whatever its length."""
    return any(KINDS[mixer].state and not KINDS[mixer].rows
               for mixer, _ in cfg.layer_kinds or ())


# ------------------------------------------------------- the two programs

def prefill_stages(cfg: TransformerConfig) -> Tuple[int, Optional[int]]:
    """Where a prefill stops running the whole prompt: (the first layer
    that runs at the last position alone, the layer handed ``last`` or
    None). Behind the last layer that mixes over the sequence (every
    layer from there on one that borrows) nothing needs any position's
    activations but the last's, whose logits the prefill is for. That
    last mixing layer itself, if it keeps rows, makes its K and V at
    every position (the cache's, and what the cross layers attend) and
    everything else of itself at the last (``block``'s ``last``). A
    model with no such layers runs every layer at every position:
    (n_layers, None)."""
    kinds = [KINDS[mixer] for mixer, _ in cfg.layer_kinds or ()]
    tail = len(kinds)
    while tail and kinds[tail - 1].borrows:
        tail -= 1
    if tail == len(kinds) or tail == 0:
        return cfg.n_layers, None
    return tail, tail - 1 if kinds[tail - 1].rows else None


def _changes_stage(cfg: TransformerConfig, depth: int, n: int,
                   period: int) -> bool:
    """Whether the run of ``n`` periods of ``period`` layers whose
    first layer is layer ``depth`` is the one inside which a prefill
    goes on at the last position alone: it holds the change and is one
    period, which needs no scan. The stage changes inside such a run or
    between runs (a scan's carry keeps its shape): a run of several
    periods that holds the change is run whole at every position and
    cut behind it."""
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
    """x [1, T, D] and the memory beside it cut to the last position;
    the lent K and V stay whole."""
    return last_position(x), lent._replace(
        memory=None if lent.memory is None else last_position(lent.memory))


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("cache",))
def slot_prefill(params, tokens, cache: Dict, slot,
                 cfg: TransformerConfig) -> Tuple[jnp.ndarray, Dict]:
    """Run one prompt [1, T0] through the stack, leaving in cache row
    ``slot`` (a traced index: one compiled program serves every slot)
    each layer's state after the prompt's last position: the roped K/V
    of the prompt's positions (of a ring, its last ``window``), a
    summary computed from zeros. Returns (last-token logits
    [1, V], cache), the token picked from them in
    ``cache["tok"][slot]``; the cache given is consumed and the one
    returned is its memory, updated in place. Compiles once per
    distinct T0 — serving callers should bucket or pad prompt lengths
    if retrace cost matters.

    **Two stages, where the model ends in layers that mix nothing over
    the sequence** (``prefill_stages``): the whole prompt up to the last
    layer that does, then the last position alone, x [1, 1, D] and the
    memory beside it, each cross layer's one query attending the K and
    V just made (``_changes_stage``)."""
    _, T0 = tokens.shape
    runs = layer_runs(cfg)
    states = _cache_runs(cache, runs)
    max_len = _max_len(cfg, runs, states)
    ropes = {}
    for attention in roped_kinds(cfg, runs):
        cos, sin = kind_rope(cfg, attention, max_len)
        ropes[attention] = functools.partial(
            apply_rotary, cos=cos, sin=sin, positions=jnp.arange(T0))
        if cfg.lends:
            ropes[attention] = _either_stage(ropes[attention], cos, sin, T0)
    at = Context(cfg, ropes, cfg.head_dim ** -0.5, slot=slot)
    x = params["embed"][tokens]
    tail, split = prefill_stages(cfg)

    def run(x, lent, kind, layers, state, depth, n):
        """One run: its layers over x, its state written. ``depth`` the
        index of its first layer in the model. The carry holds the
        state as two tuples, each layer of the period's first array and
        its second."""
        period = period_of(kind)
        kinds = [KINDS[mixer] for mixer, _ in period]
        layer = [functools.partial(kind.prefill, at, mixer)
                 for kind, (mixer, _) in zip(kinds, period)]
        firsts, held = zip(*state)
        seconds = tuple(kind.gather(cc) for kind, cc in zip(kinds, held))

        def body(carry, lps, i, staged=False):
            x, firsts, seconds, lent = carry
            firsts, seconds = list(firsts), list(seconds)
            for j, lp in enumerate(lps if len(period) > 1 else (lps,)):
                here = depth + i * len(period) + j
                if cfg.differential:
                    lp = dict(lp, depth=here)
                if staged and here == tail and x.shape[1] > 1:
                    x, lent = _at_the_last(x, lent)
                x, lent, firsts[j], seconds[j] = layer[j](
                    x, lent, lp, i, firsts[j], seconds[j],
                    last=staged and here == split)
                if staged and here == split:
                    x, lent = _at_the_last(x, lent)
            return (x, tuple(firsts), tuple(seconds), lent)

        if depth >= tail and x.shape[1] > 1:
            x, lent = _at_the_last(x, lent)
        carry = (x, firsts, seconds, lent)
        if _changes_stage(cfg, depth, n, len(period)):
            # one period, no scan: the stage may change inside it
            x, firsts, seconds, lent = body(
                carry, one_period(layers), 0, staged=True)
        else:
            x, firsts, seconds, lent = scan_run(body, carry, layers)
        seconds = tuple(kind.put(cc, made, slot)
                        for kind, cc, made in zip(kinds, held, seconds))
        return x, lent, tuple(zip(firsts, seconds))

    new, depth = [], 0
    lent = Lent(*nothing_lent(cfg, x))
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
    attends its own prefix (positions ``[0, pos]``: ``_Attention.step``)
    and advances its layers' summaries by it, advances its own pos and
    picks its next token into ``cache["tok"]``. Inactive rows are free riders
    — their logits are garbage, their pos, tok and summaries frozen.
    ``token`` int32 [B] says what a row is fed: a token id, or ``CARRY``
    for the row's own last pick, which never left the device. The cache
    given is consumed and the one returned is its memory, written in
    place.

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
    # row r attends positions [0, pos[r]] (pos[r] is written this
    # step; ``decode_attention`` clips them to a ring's rows)
    with jax.named_scope("full_attention"):
        rows = jnp.arange(B)    # of the cache's in-place write, either kind

    def row_rope(cos, sin):
        def rope(t):  # every row at its own position
            return rotate(t, cos[pos][:, None, None, :],
                          sin[pos][:, None, None, :])
        return rope

    at = Context(cfg, {a: row_rope(*t) for a, t in tables.items()},
                 cfg.head_dim ** -0.5, pos=pos, active=active, rows=rows)

    def run(x, lent, kind, layers, state, depth, n):
        period = period_of(kind)
        kinds = [KINDS[mixer] for mixer, _ in period]
        layer = [functools.partial(kind.step, kind.within(at, mixer), mixer)
                 for kind, (mixer, _) in zip(kinds, period)]

        def body(carry, lps, i):
            x, firsts, seconds, load, memory = carry
            firsts, seconds = list(firsts), list(seconds)
            within = lent._replace(load=load, memory=memory)
            for j, lp in enumerate(lps if len(period) > 1 else (lps,)):
                if cfg.differential:
                    lp = dict(lp, depth=depth + i * len(period) + j)
                x, within, firsts[j], seconds[j] = layer[j](
                    x, within, lp, i, firsts[j], seconds[j])
            return x, tuple(firsts), tuple(seconds), within.load, \
                within.memory

        x, firsts, seconds, load, memory = scan_run(
            body, (x, *zip(*state), lent.load, lent.memory), layers)
        lent = lent._replace(load=load, memory=memory)
        for kind, first, second in zip(kinds, firsts, seconds):
            # what the run's layers lend, as the run leaves them
            lent = kind.lend(cfg, lent, first, second, n - 1)
        return x, lent, tuple(zip(firsts, seconds))

    load = jnp.zeros_like(cache["load"]) if "load" in cache else None
    with jax.named_scope("gmu"):
        lent = Lent(memory=nothing_lent(cfg, x)[0], load=load)
    new, depth = [], 0
    for r, (((kind, layers), (_, n)), state) in enumerate(zip(
            zip(layer_stacks(params, cfg), runs), states)):
        with jax.named_scope(f"run{r}"):
            x, lent, state = run(x, lent, kind, layers, state, depth, n)
        new.append(state)
        depth += n * len(period_of(kind))
    logits = unembed(params, x[:, 0], eps=cfg.norm_eps)
    load = lent.load
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
        want |= set(KINDS[mixer].parts)
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
