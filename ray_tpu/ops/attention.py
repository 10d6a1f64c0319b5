"""Attention: pure-JAX reference and a Pallas TPU flash kernel.

``attention`` is the XLA-fused reference (differential-test oracle and
CPU path). ``flash_attention`` is blockwise in BOTH q and k/v with an
online-softmax accumulator carried in VMEM scratch — the [Tq, Tk]
score matrix never materialises, so VMEM use is O(block_q * block_k),
independent of sequence length (the memory sense of "flash").

The backward pass is Pallas too: the forward emits per-row logsumexp,
and two blocked kernels recompute probabilities tile-by-tile — one
accumulating dK/dV (q-blocks innermost), one accumulating dQ
(k-blocks innermost) — so the backward never materialises [Tq, Tk]
either. ``delta = rowsum(dO * O)`` is precomputed by XLA (one fused
elementwise reduce). Shapes everywhere: [batch, seq, heads, head_dim].
What the forward hands the backward are the backward kernels' operands
as they read them (q, k, v and out heads first, lse a row a head), each
under a name (``SAVED``) that a ``jax.checkpoint`` around the caller
can keep by.

What one grid step is given is read from the input: the products take
their operands at the dtype they come in (probabilities and score
gradients are cast to it) and accumulate in float32, with the softmax
between them in float32; each kernel's blocks come from
``flash_blocks`` (multiples of 128 that divide the sequence, up to
caps swept on the chip), so every sequence that is a multiple of 128
takes the kernel; and under ``causal`` a block above the diagonal is
neither computed nor fetched (its index map names the block of the
nearest live step), a block below it skips the mask.

The forward kernel and ``attention`` also take what a model with layers
of several kinds needs: fewer K/V heads than query heads (query head i
attends K/V head ``i // (H / G)``), a value width other than the q.k
width, a causal ``window`` (position t attends ``(t - window, t]``; the
kernel's k-axis then spans only the blocks a q-block's band touches,
and fetches no other) and a per-head ``sink`` logit that joins the
softmax's denominator and has no value row. The backward kernels know
none of these: a gradient through such a call recomputes ``attention``
(the XLA form) and differentiates that.

A decode step has a kernel of its own, ``decode_attention`` (at the
end of this file): one new token a slot against a run's whole cache, a
full-attention run's growing rows or a window run's ring, each slot's K
and V copied chunk by chunk up to its own position and no further, a
sink folded into the denominator; ``cached_attention`` is its XLA form
and oracle.

Reference-parity note: the reference snapshot has no attention kernels
at all (SURVEY.md §5.7 — absent); this op underpins the TPU-native
long-context capability layered on the runtime.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

_NEG_INF = -1e30
_LANES = 128  # f32 VMEM lane width; the m/l scratch rows are as wide
_BLOCK = 128  # granule of a block along the sequence
# The three pallas_calls, by their ``name=`` (a device trace names them
# so), in the order ``_flash`` carries their blocks.
KERNELS = ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")
# Largest (block_q, block_k) of each: flash_blocks.
_BLOCK_CAPS = {"flash_fwd": (1024, 1024), "flash_bwd_dkv": (512, 512),
               "flash_bwd_dq": (1024, 1024)}
# The backward kernels' residuals, by the names ``_flash_fwd`` puts on
# them: what a ``jax.checkpoint`` around the caller keeps
# (``save_only_these_names``) so that its backward runs the two
# backward kernels and nothing of the forward again.
SAVED = ("flash_q", "flash_k", "flash_v", "flash_out", "flash_lse")


def attention(q, k, v, *, causal: bool = True,
              sm_scale: float | None = None, window: int | None = None,
              sink=None):
    """Reference softmax attention (fp32 accumulation). q [B, Tq, H, D],
    k [B, Tk, G, D], v [B, Tk, G, Dv] with G dividing H; ``window``
    (with ``causal``) keeps the last ``window`` positions up to each
    query's own; ``sink`` [H] is one more logit a head, in the
    denominator only."""
    B, Tq, H, D = q.shape
    Tk, G = k.shape[1], k.shape[2]
    sm_scale = sm_scale if sm_scale is not None else D ** -0.5
    if window is not None and not causal:
        raise ValueError("a window needs causal=True")
    if G == H:
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * sm_scale
    else:
        s = jnp.einsum("bqgrd,bkgd->bgrqk", q.reshape(B, Tq, G, H // G, D),
                       k, preferred_element_type=jnp.float32
                       ).reshape(B, H, Tq, Tk) * sm_scale
    if causal:
        # allow Tq != Tk (decode: q at the tail of the kv sequence)
        qpos = jnp.arange(Tq) + (Tk - Tq)
        mask = qpos[:, None] >= jnp.arange(Tk)[None, :]
        if window is not None:
            mask &= jnp.arange(Tk)[None, :] > qpos[:, None] - window
        s = jnp.where(mask[None, None], s, -jnp.inf)
    if sink is None:
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    else:
        column = jnp.broadcast_to(
            sink.astype(jnp.float32)[None, :, None, None], (B, H, Tq, 1))
        p = jax.nn.softmax(jnp.concatenate([s, column], axis=-1),
                           axis=-1)[..., :-1].astype(v.dtype)
    if G == H:
        return jnp.einsum("bhqk,bkhd->bqhd", p, v,
                          preferred_element_type=jnp.float32
                          ).astype(q.dtype)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", p.reshape(B, G, H // G, Tq, Tk), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Tq, H, v.shape[-1]).astype(q.dtype)


@functools.cache
def _on_tpu() -> bool:
    """Whether this process's default jax backend is the TPU, read
    once. A backend that cannot start raises here, as it would at the
    first array: picking a kernel is no place to absorb that."""
    return jax.default_backend() == "tpu"


def _on_live_tiles(tile, causal, qi, ki, block_q, block_k, window=None):
    """Run ``tile(masked)`` for the (q-block ``qi``, k-block ``ki``) grid
    step unless the mask takes the whole of it: ``masked`` False for a
    block wholly inside what ``causal`` and ``window`` let through (no
    compare, no select), True for one that an edge crosses."""
    import jax.experimental.pallas as pl

    if not causal:
        tile(False)
        return
    first_q, first_k = qi * block_q, ki * block_k
    last_q, last_k = first_q + block_q - 1, first_k + block_k - 1
    if window is None:
        pl.when(last_k <= first_q)(functools.partial(tile, False))
        pl.when((first_k <= last_q) & (last_k > first_q))(
            functools.partial(tile, True))
        return
    live = (first_k <= last_q) & (last_k > first_q - window)
    inside = (last_k <= first_q) & (first_k > last_q - window)
    pl.when(live & inside)(functools.partial(tile, False))
    pl.when(live & ~inside)(functools.partial(tile, True))


def _causal_mask(s, first_q, first_k, q_axis, window=None):
    """The score tile ``s`` with every pair q < k (and, under ``window``,
    k <= q - window) at _NEG_INF; q runs from ``first_q`` along
    ``q_axis`` of the tile, k from ``first_k`` along the other."""
    qpos = first_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    kpos = first_k + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                              1 - q_axis)
    keep = qpos >= kpos
    if window is not None:
        keep &= kpos > qpos - window
    return jnp.where(keep, s, _NEG_INF)


def _first_k_block(i, block_q, block_k, window):
    """The first k-block that q-block ``i`` attends under ``window``."""
    return jnp.maximum(i * block_q - (window - 1), 0) // block_k


def _kv_index(causal, block_q, block_k, window=None, group=1):
    """Index map of a K or V block on a (b, h, qi, ki) grid. Under
    ``causal`` ki is clamped to the last k-block the q-block attends to,
    so that a masked grid step names the block the last live step named
    and the pipeline issues no copy for it. Under ``window`` the k-axis
    counts from the first block of the q-block's band. ``group`` query
    heads share one K/V head."""
    def index(b, h, i, j):
        if window is not None:
            j = j + _first_k_block(i, block_q, block_k, window)
        if causal:
            j = jnp.minimum(j, (i * block_q + block_q - 1) // block_k)
        return (b, h // group if group > 1 else h, j, 0)
    return index


def _lanes(x, n):
    """A lane-replicated [rows, w] value at n lanes, or as one column to
    broadcast where n is not a multiple of w."""
    w = x.shape[1]
    if n <= w:
        return x[:, :n]
    return jnp.tile(x, (1, n // w)) if n % w == 0 else x[:, :1]


def _scratch_lanes(block_k):
    """Width of the forward's m/l scratch rows: a vreg's lanes where the
    k-block is made of whole ones, else the k-block."""
    return block_k if block_k % _LANES else _LANES


def _softmax_step(s, v, m_ref, l_ref, acc_ref):
    """One k-block of the online softmax: the score tile ``s`` [bq, bk]
    (float32, masked) and its values ``v`` [bk, Dv] folded into the
    running max ``m_ref`` [bq, w], denominator ``l_ref`` [bq, w] and
    numerator ``acc_ref`` [bq, Dv] (``_flash_kernel`` says how each is
    kept). p is cast to v's dtype, the product accumulates in float32."""
    block_k, w = s.shape[1], m_ref.shape[1]
    m_prev = m_ref[...]                           # [bq, w]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)               # rescale old state
    p = jnp.exp(s - _lanes(m_new, block_k))       # [bq, bk]
    l_ref[...] = alpha * l_ref[...] + sum(
        p[:, c:c + w] for c in range(0, block_k, w))
    acc_ref[...] = acc_ref[...] * _lanes(alpha, acc_ref.shape[1]) \
        + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _flash_kernel(q_ref, k_ref, v_ref, *rest, sm_scale, causal, block_q,
                  block_k, num_k, window=None, has_sink=False):
    """One (b, h, qi, ki) grid step of online-softmax attention.

    q_ref [1,1,bq,D]; k_ref [1,1,bk,D]; v_ref [1,1,bk,Dv]; with
    ``has_sink`` sink_ref [1,1,128], the head's sink logit in every
    lane; o_ref [1,1,bq,Dv];
    lse_ref [1,1,bq,1] per-row logsumexp (the backward's softmax key;
    the trailing singleton keeps the block's last-two dims Mosaic-legal:
    (bq, 1) = sublane-divisible x whole-array lane dim).
    Scratch (VMEM, persists across the innermost ki axis), w lanes wide
    (_scratch_lanes: a vreg's 128, or bk where that is no multiple):
      m_ref [bq, w] the running max, the same in every lane,
      l_ref [bq, w] the running denominator in w partial sums, lane j
        holding the columns j, j + w, ...: a step adds to it lane by
        lane and only _finish sums across the lanes,
      acc_ref [bq, Dv] running numerator.
    So a step reduces across lanes once (the max) and broadcasts along
    them once. The two products take q, k, v as they come (p is cast to
    v's dtype) and accumulate in float32; all between them is float32.
    Under ``window`` the k-axis of the grid spans the ``num_k`` blocks a
    q-block's band can touch, counted from its first.
    """
    import jax.experimental.pallas as pl

    if has_sink:
        sink_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, lse_ref, m_ref, l_ref, acc_ref = rest
    qi, ki = pl.program_id(2), pl.program_id(3)
    kb = ki if window is None else ki + _first_k_block(
        qi, block_q, block_k, window)       # the k-block of this step

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _tile(masked):
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [bq, bk]
        if masked:
            s = _causal_mask(s, qi * block_q, kb * block_k, q_axis=0,
                             window=window)
        _softmax_step(s, v, m_ref, l_ref, acc_ref)

    # Causal: blocks strictly above the diagonal contribute nothing;
    # under a window, nor do those wholly before the band.
    _on_live_tiles(_tile, causal, qi, kb, block_q, block_k, window)

    @pl.when(ki == num_k - 1)
    def _finish():
        # Fully masked rows (can't happen under causal) would have l=0;
        # guard the divide anyway so the kernel never emits NaN.
        l = jnp.sum(l_ref[...], axis=-1, keepdims=True)
        scale = m_all = None
        if has_sink:
            # one more logit in the denominator, with no value row
            sink = sink_ref[0][:, :1]                 # [1, 1]
            m_all = jnp.maximum(m_ref[:, :1], sink)
            scale = jnp.exp(m_ref[:, :1] - m_all)
            l = l * scale + jnp.exp(sink - m_all)
        l = jnp.where(l == 0.0, 1.0, l)
        acc = acc_ref[...] if scale is None else acc_ref[...] * scale
        o_ref[0, 0] = (acc / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_ref[:, :1] if m_all is None else m_all) \
            + jnp.log(l)


def _heads_first(t):
    """[B,T,H,D] ↔ [B,H,T,D]: the kernels want the MXU dims (T, D)
    trailing."""
    return t.transpose(0, 2, 1, 3)


def _flash_forward(q, k, v, *args, **kwargs):
    out, lse = _flash_forward_heads_first(
        _heads_first(q), _heads_first(k), _heads_first(v), *args, **kwargs)
    return _heads_first(out), lse


def _flash_forward_heads_first(qt, kt, vt, causal, sm_scale, block_q,
                               block_k, interpret, window=None, sink=None):
    """The forward kernel on [B,H,T,D] operands: (out [B,H,T,Dv], lse
    [B,H,T,1] float32)."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    B, H, T, D = qt.shape
    G, Dv = kt.shape[1], vt.shape[3]
    num_k = T // block_k
    if window is not None:
        # the most k-blocks any q-block's band touches
        num_k = max(
            (i * block_q + block_q - 1) // block_k
            - max(i * block_q - (window - 1), 0) // block_k + 1
            for i in range(T // block_q))
    grid = (B, H, T // block_q, num_k)  # ki innermost: scratch carries
    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, num_k=num_k, window=window,
        has_sink=sink is not None)
    kv_block = _kv_index(causal, block_q, block_k, window, H // G)
    in_specs = [
        pl.BlockSpec((1, 1, block_q, D),
                     lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, block_k, D), kv_block),
        pl.BlockSpec((1, 1, block_k, Dv), kv_block),
    ]
    operands = [qt, kt, vt]
    if sink is not None:
        in_specs.append(pl.BlockSpec((1, 1, _LANES),
                                     lambda b, h, i, j: (h, 0, 0)))
        operands.append(jnp.broadcast_to(
            sink.astype(jnp.float32)[:, None, None], (H, 1, _LANES)))
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((B, H, T, Dv), qt.dtype),
                   jax.ShapeDtypeStruct((B, H, T, 1), jnp.float32)),
        grid=grid,
        in_specs=in_specs,
        out_specs=(pl.BlockSpec((1, 1, block_q, Dv),
                                lambda b, h, i, j: (b, h, i, 0)),
                   pl.BlockSpec((1, 1, block_q, 1),
                                lambda b, h, i, j: (b, h, i, 0))),
        scratch_shapes=[
            pltpu.VMEM((block_q, _scratch_lanes(block_k)), jnp.float32),
            pltpu.VMEM((block_q, _scratch_lanes(block_k)), jnp.float32),
            pltpu.VMEM((block_q, Dv), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(*operands)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, sm_scale,
                          causal, block_q, block_k, num_q):
    """Grid (b, h, ki, qi), qi innermost: dK/dV accumulate over q.

    The tile is computed transposed, [bk, bq] (k along the sublanes, q
    along the lanes), so that all four products are plain row-by-column
    or row-by-row ones and no score tile is ever transposed; lse_ref and
    dl_ref are lane-dense rows [1,1,1,bq] for the same reason.
    """
    import jax.experimental.pallas as pl

    ki, qi = pl.program_id(2), pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _tile(masked):
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        st = jax.lax.dot_general(                      # k @ q^T  [bk, bq]
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if masked:
            st = _causal_mask(st, qi * block_q, ki * block_k, q_axis=1)
        pt = jnp.exp(st - lse_ref[0, 0])               # exact softmax tile
        dpt = jax.lax.dot_general(                     # v @ do^T  [bk, bq]
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dst = pt * (dpt - dl_ref[0, 0]) * sm_scale
        dv_acc[...] += jax.lax.dot_general(            # p^T @ do  [bk, D]
            pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[...] += jax.lax.dot_general(            # ds^T @ q  [bk, D]
            dst.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _on_live_tiles(_tile, causal, qi, ki, block_q, block_k)

    @pl.when(qi == num_q - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                         dq_ref, dq_acc, *, sm_scale, causal, block_q,
                         block_k, num_k):
    """Grid (b, h, qi, ki), ki innermost: dQ accumulates over k.
    lse_ref/dl_ref are columns [1,1,bq,1], as the forward writes lse."""
    import jax.experimental.pallas as pl

    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _tile(masked):
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        s = jax.lax.dot_general(                       # q @ k^T  [bq, bk]
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if masked:
            s = _causal_mask(s, qi * block_q, ki * block_k, q_axis=0)
        p = jnp.exp(s - lse_ref[0, 0])                 # exact softmax tile
        dp = jax.lax.dot_general(                      # do @ v^T  [bq, bk]
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - dl_ref[0, 0]) * sm_scale
        dq_acc[...] += jax.lax.dot_general(            # ds @ k  [bq, D]
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _on_live_tiles(_tile, causal, qi, ki, block_q, block_k)

    @pl.when(ki == num_k - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _flash_dkv(qt, kt, vt, dot, lse, delta, causal, sm_scale, block_q,
               block_k, interpret):
    """dK, dV of [B,H,T,D] operands; lse, delta as rows [B,H,1,T]."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    B, H, T, D = qt.shape
    num_q = T // block_q

    def q_index(i, j):
        # the masked steps of a k-block come first: clamped to the first
        # q-block that attends to it, they name the block the first live
        # step needs, fetched once
        return jnp.maximum(i, (j * block_k) // block_q) if causal else i

    qspec = pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, j, i: (b, h, q_index(i, j), 0))
    kspec = pl.BlockSpec((1, 1, block_k, D), lambda b, h, j, i: (b, h, j, 0))
    rowq = pl.BlockSpec((1, 1, 1, block_q),
                        lambda b, h, j, i: (b, h, 0, q_index(i, j)))
    return pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=block_q,
                          block_k=block_k, num_q=num_q),
        out_shape=(jax.ShapeDtypeStruct(kt.shape, kt.dtype),
                   jax.ShapeDtypeStruct(vt.shape, vt.dtype)),
        grid=(B, H, T // block_k, num_q),
        in_specs=[qspec, kspec, kspec, qspec, rowq, rowq],
        out_specs=(kspec, kspec),
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32)] * 2,
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qt, kt, vt, dot, lse, delta)


def _flash_dq(qt, kt, vt, dot, lse, delta, causal, sm_scale, block_q,
              block_k, interpret):
    """dQ of [B,H,T,D] operands; lse, delta as columns [B,H,T,1]."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    B, H, T, D = qt.shape
    num_k = T // block_k
    qspec = pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0))
    kspec = pl.BlockSpec((1, 1, block_k, D),
                         _kv_index(causal, block_q, block_k))
    colq = pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0))
    return pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=block_q,
                          block_k=block_k, num_k=num_k),
        out_shape=jax.ShapeDtypeStruct(qt.shape, qt.dtype),
        grid=(B, H, T // block_q, num_k),
        in_specs=[qspec, kspec, kspec, qspec, colq, colq],
        out_specs=qspec,
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(qt, kt, vt, dot, lse, delta)


def _flash_backward(qt, kt, vt, out, lse, g, causal, sm_scale, blocks,
                    interpret):
    """(dq, dk, dv) [B,T,H,D] from the residuals as ``_flash_fwd``
    keeps them, heads first, and g [B,T,H,D]."""
    dot = _heads_first(g)
    # delta_i = rowsum(dO_i * O_i): one fused XLA reduce, [B, H, T].
    delta = jnp.einsum("bhqd,bhqd->bhq", dot.astype(jnp.float32),
                       out.astype(jnp.float32))
    dk, dv = _flash_dkv(qt, kt, vt, dot, lse[:, :, None, :],
                        delta[:, :, None, :], causal, sm_scale,
                        *blocks[1], interpret)
    dq = _flash_dq(qt, kt, vt, dot, lse[..., None], delta[..., None], causal,
                   sm_scale, *blocks[2], interpret)
    return _heads_first(dq), _heads_first(dk), _heads_first(dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, sm_scale, blocks, interpret):
    out, _ = _flash_forward(q, k, v, causal, sm_scale, *blocks[0],
                            interpret)
    return out


def _flash_fwd(q, k, v, causal, sm_scale, blocks, interpret):
    """The residuals are the backward kernels' operands as they read
    them, each under its name in ``SAVED``: q (behind its rope), k and
    v heads first, the forward's out likewise, and lse as [B, H, T]
    (it leaves the kernel a column [B, H, T, 1], which HBM pads to 128
    lanes a value). A backward that is handed them runs no rope, no
    transpose of q, k or v and no forward kernel again. (The names are
    on the values the forward goes on with: a name on a copy beside
    them keeps the copy and computes the value again.)"""
    qt, kt, vt = (checkpoint_name(_heads_first(t), name)
                  for t, name in zip((q, k, v), SAVED))
    out, lse = _flash_forward_heads_first(qt, kt, vt, causal, sm_scale,
                                          *blocks[0], interpret)
    out, lse = (checkpoint_name(t, name)
                for t, name in zip((out, lse[..., 0]), SAVED[3:]))
    return _heads_first(out), (qt, kt, vt, out, lse)


def _flash_bwd(causal, sm_scale, blocks, interpret, res, g):
    return _flash_backward(*res, g, causal, sm_scale, blocks, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_blocks(T: int, head_dim: int, itemsize: int, kernel: str,
                 window: int | None = None) -> tuple[int, int]:
    """(block_q, block_k) of one of the three ``KERNELS`` for sequences
    of ``T`` (a multiple of 128): the largest multiples of 128 that
    divide ``T`` up to the kernel's caps in ``_BLOCK_CAPS``, so a ``T``
    below a cap is taken whole. The caps were swept on a TPU v5e at head
    dimension 128 in bfloat16 and hold for rows of up to 512 bytes
    (float32 at 128 and bfloat16 at 256 are faster with them than with
    smaller blocks: PERF.md section 6, PR 29); a wider row overflows
    the scoped VMEM with them and gets proportionally fewer rows a
    block. Under ``window`` a block is at most two windows long: a
    q-block's band is its own length plus the window, so longer blocks
    multiply pairs the mask throws away, and shorter ones grid steps."""
    shrink = max(1, head_dim * itemsize // 512)

    def largest(cap):
        cap = max(_BLOCK, cap // shrink)
        if window is not None:
            cap = max(_BLOCK, min(cap, 2 * _BLOCK * (-(-window // _BLOCK))))
        return max(b for b in range(_BLOCK, min(cap, T) + 1, _BLOCK)
                   if T % b == 0)

    cap_q, cap_k = _BLOCK_CAPS[kernel]
    return largest(cap_q), largest(cap_k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_forward_only(q, k, v, sink, causal, sm_scale, window, blocks,
                        interpret):
    """The forward kernel for what the backward kernels do not know
    (grouped K/V heads, a value width of its own, a window, a sink);
    its gradient is ``attention``'s, recomputed."""
    out, _ = _flash_forward(q, k, v, causal, sm_scale, *blocks, interpret,
                            window=window, sink=sink)
    return out


def _flash_forward_only_fwd(q, k, v, sink, causal, sm_scale, window, blocks,
                            interpret):
    return _flash_forward_only(q, k, v, sink, causal, sm_scale, window,
                               blocks, interpret), (q, k, v, sink)


def _flash_forward_only_bwd(causal, sm_scale, window, blocks, interpret,
                            res, g):
    def xla(q, k, v, sink):
        return attention(q, k, v, causal=causal, sm_scale=sm_scale,
                         window=window, sink=sink)

    return jax.vjp(xla, *res)[1](g)


_flash_forward_only.defvjp(_flash_forward_only_fwd, _flash_forward_only_bwd)


def flash_attention(q, k, v, *, causal: bool = True,
                    sm_scale: float | None = None,
                    window: int | None = None, sink=None,
                    block_q: int | None = None,
                    block_k: int | None = None, interpret: bool = False):
    """Blockwise online-softmax attention (Pallas on TPU).

    Which implementation runs is decided by two things the caller can
    see: the platform — the Mosaic kernel exists only for the TPU, so a
    process whose default backend is anything else runs ``attention``
    unless it asks for the kernel under ``interpret`` — and the shape:
    decode steps (Tq != Tk) and sequences that are not a multiple of
    128 (with explicit blocks: of those blocks) run ``attention`` on
    every platform; ``interpret`` alone takes such a sequence as one
    block. A caller that must know the kernel ran looks for the Mosaic
    custom call in its compiled program (chip_smoke.py does).

    The kernels read the rest from their input as well: the products run
    at the operands' dtype and accumulate in float32, and each of the
    three kernels takes its blocks from ``flash_blocks``. An explicit
    ``block_q`` and ``block_k`` (both, or neither) win, for all three.

    K/V heads fewer than the query's, a value width other than the q.k
    width, ``window`` and ``sink`` (as ``attention`` takes them) run the
    forward kernel alone: asked for a gradient, such a call recomputes
    ``attention`` and differentiates that.
    """
    B, T, H, D = q.shape
    sm_scale = sm_scale if sm_scale is not None else D ** -0.5
    if (block_q is None) != (block_k is None):
        raise ValueError("give both block_q and block_k, or neither")
    plain = (window is None and sink is None and k.shape[2] == H
             and v.shape[3] == D)
    reference = functools.partial(attention, causal=causal,
                                  sm_scale=sm_scale, window=window, sink=sink)
    if k.shape[1] != T or not (interpret or _on_tpu()):
        return reference(q, k, v)
    if block_q is None and T % _BLOCK == 0:
        blocks = tuple(flash_blocks(T, D, q.dtype.itemsize, kernel, window)
                       for kernel in KERNELS)
    elif block_q is None and not interpret:
        return reference(q, k, v)
    else:
        if interpret:
            # interpret mode exists to exercise the kernel: blocks are
            # clamped so it runs even at small T, and a T the table has
            # no blocks for is taken whole (no Mosaic tiling constraints
            # on CPU).
            block_q, block_k = min(block_q or T, T), min(block_k or T, T)
        blocks = ((block_q, block_k),) * len(KERNELS)
    # Unaligned sequences use the XLA reference — Mosaic blocks come in
    # sublane 8 / lane 128 granules.
    if any(T % b for pair in blocks for b in pair):
        return reference(q, k, v)
    if plain:
        return _flash(q, k, v, causal, sm_scale, blocks, interpret)
    return _flash_forward_only(q, k, v, sink, causal, sm_scale, window,
                               blocks[0], interpret)


# ------------------------------------------------ one new token a row

# the pallas_call's ``name=``: over a growing cache, over a window's ring
DECODE_KERNEL, DECODE_RING = "decode_attend", "decode_ring"
# the least K and V, in bytes, of a row's chunk; chunks in VMEM at a time
_DECODE_CHUNK_BYTES = 2 ** 18
_DECODE_DEPTH = 3


def _widen(q, G):
    """Queries q [B, H, D] of H heads on G K/V heads, each as a whole
    flat row [G * D] that is zero outside its own K/V head's part, and
    ``own`` [1, H, G, 1], which part that is."""
    B, H, D = q.shape
    own = (jnp.arange(H)[:, None] // (H // G)
           == jnp.arange(G)[None, :])[None, :, :, None]
    return jnp.where(own, q[:, :, None, :], 0).reshape(B, H, G * D), own


def _own_part(o, own):
    """o [B, H, G * Dv], a product with whole flat rows, cut to each
    head's own K/V head's part [B, H, Dv]."""
    B, H, G = o.shape[0], own.shape[1], own.shape[2]
    return jnp.sum(jnp.where(own, o.reshape(B, H, G, -1), 0), axis=2)


def cached_attention(q, lk, lv, valid, sm_scale, sink=None):
    """One new token a row against a layer's cached K/V, in XLA: q
    [B, H, Dh], ``valid`` [B, 1, rows] the rows each batch row may
    attend, ``sink`` [H] one more logit a head in the denominator. lk
    [B, rows, H, Dh] and lv [B, rows, H, Dv] where every query head has
    its own K/V head. Where G K/V heads serve H / G query heads each
    the rows are flat, lk [B, rows, G * Dh] and lv [B, rows, G * Dv]:
    each query is widened to a whole row, zero outside its own K/V
    head's part, so that both products are plain ones over rows as they
    lie in memory (G times the multiplications of the heads taken
    apart, which stay under the time the rows take to read), and the
    output keeps its own head's part. Accumulation dtypes as
    ``attention``'s: softmax fp32, p cast to the value dtype, p@v
    accumulated in fp32.

    Every row of lk and lv is read, whatever ``valid`` says, and a row
    that is not valid weighs exactly 0: what lies there must be finite
    (0 times NaN is NaN), which a cache that is only ever written with
    K and V is. This is the form of a run's cache, growing rows or a
    window's ring, wherever ``decode_attention`` does not take the
    kernel, and the kernel's oracle."""
    B, H, D = q.shape
    grouped = lk.ndim == 3
    if grouped:
        wide, own = _widen(q, lk.shape[2] // D)
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
        o = _own_part(jnp.einsum("bhk,bkc->bhc", p, lv,
                                 preferred_element_type=jnp.float32), own)
    else:
        o = jnp.einsum("bhk,bkhd->bhd", p, lv,
                       preferred_element_type=jnp.float32)
    return o.astype(q.dtype)


def _decode_kernel(layer_ref, pos_ref, q_ref, k_hbm, v_hbm, *refs, sm_scale,
                   chunk, stride, has_sink=False, per=None):
    """Grid step b of a decode step's attention over the carried cache:
    row b up to its own position. Prefetched: layer_ref [1], the layer
    of the run, and pos_ref [slots], the last position each slot
    attends (its new token's; of a ring, that clipped to the ring's
    last row).

    q_ref [H, C] and o_ref [H, Cv] are the row's blocks of the grid;
    with ``has_sink`` sink_ref [H, 128] comes before o_ref, each head's
    sink logit in every lane;
    k_hbm [L, slots, N, C] and v_hbm [L, slots, N, Cv] are the run's
    whole arrays where they lie, and the kernel copies a row's chunks of
    n = chunk * stride of its rows itself: ``stride`` 1 for flat rows
    (C the whole row, q widened to it), H where a position is H rows of
    one head each (column c of the scores is then position c // H of
    head c % H, and a query head keeps its own head's columns). With
    ``per`` (query heads a K/V head) the kernel widens the queries
    itself: q_ref is [H, D], each row's queries are widened once into
    the scratch qw_ref [H, C] (last of the scratch), and o_ref [H, Dv]
    takes each head's own part of the [H, G * Dv] the products make.

    The kernel takes the chunks in one order, row after row, each row's
    from its first to ``pos // chunk`` and none past it, and keeps
    ``depth`` of them in VMEM (k_buf [depth, n, C], v_buf [depth, n,
    Cv], a DMA semaphore each; ``_DECODE_DEPTH``): before a chunk is
    multiplied, the copy of the one ``depth - 1`` places after it in
    that order is started, the next row's first chunks under a row's
    last ones, so that the copies run back to back from the first row
    to the last, across the grid's steps. ``count_ref`` counts the
    chunks taken, from one grid step to the next; chunk c of the order
    lies in buffer c % depth. Online softmax across the row's chunks in
    m_ref, l_ref [H, w] and acc_ref [H, Cv] as the forward kernel keeps
    them; only the last chunk masks the columns past the position, and
    zeroes the value rows there, so that nothing a stale tail holds, NaN
    included, reaches the output. The sink joins the float32 state once,
    after the last chunk, as ``_flash_kernel``'s ``_finish`` has it."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    if has_sink:
        sink_ref, *refs = refs
    o_ref, k_buf, v_buf, sems, count_ref, m_ref, l_ref, acc_ref, *qw = refs
    b, slots = pl.program_id(0), pl.num_programs(0)
    n, depth = chunk * stride, k_buf.shape[0]

    def copies(row, i, slot):
        at = pl.ds(pl.multiple_of(i * n, n), n)
        return [pltpu.make_async_copy(hbm.at[layer_ref[0], row, at],
                                      buf.at[slot], sems.at[kv, slot])
                for kv, (hbm, buf) in enumerate(((k_hbm, k_buf),
                                                 (v_hbm, v_buf)))]

    def after(row, i):
        """The chunk after chunk i of ``row`` in the order (row
        ``slots`` once the last row's are done)."""
        more = i < pos_ref[jnp.minimum(row, slots - 1)] // chunk
        return jnp.where(more, row, row + 1), jnp.where(more, i + 1, 0)

    def issue(row, i, slot):
        @pl.when(row < slots)
        def _():
            for copy in copies(row, i, slot):
                copy.start()

    @pl.when(b == 0)
    def _first():
        count_ref[0] = 0
        row, i = 0, 0
        for c in range(depth - 1):
            issue(row, i, c)
            row, i = after(row, i)

    pos = pos_ref[b]

    def attend(i, c, edge):
        """The row's chunk i, chunk c of the order, folded into its
        softmax, the copy of the chunk ``depth - 1`` after it started
        first."""
        row, ahead = b, i
        for _ in range(depth - 1):
            row, ahead = after(row, ahead)
        issue(row, ahead, (c + depth - 1) % depth)
        for copy in copies(b, i, c % depth):
            copy.wait()
        q = (qw[0] if qw else q_ref)[...]
        k, v = k_buf[c % depth], v_buf[c % depth]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [H, n]
        column = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = None
        if stride > 1:
            keep = column % stride == jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
        if edge:
            # rows of this chunk at or before the position
            live = (pos + 1 - i * chunk) * stride
            keep = column < live if keep is None else keep & (column < live)
            v = jnp.where(jax.lax.broadcasted_iota(
                jnp.int32, (n, 1), 0) < live, v, jnp.zeros_like(v))
        if keep is not None:
            s = jnp.where(keep, s, _NEG_INF)
        _softmax_step(s, v, m_ref, l_ref, acc_ref)
        return c + 1

    if per is not None:
        # query head h is K/V head h // per's: its row of the widened
        # queries is zero outside that head's D columns
        G = k_buf.shape[-1] // q_ref.shape[-1]
        head = jax.lax.broadcasted_iota(jnp.int32, (q_ref.shape[0], 1), 0)
        own = [(head >= g * per) & (head < (g + 1) * per) for g in range(G)]
        q = q_ref[...]
        qw[0][...] = jnp.concatenate(
            [jnp.where(own[g], q, jnp.zeros_like(q)) for g in range(G)],
            axis=1)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    last = pos // chunk
    c = jax.lax.fori_loop(0, last, lambda i, c: attend(i, c, False),
                          count_ref[0])
    count_ref[0] = attend(last, c, True)
    l = jnp.sum(l_ref[...], axis=-1, keepdims=True)
    acc = acc_ref[...]
    if per is not None:     # each head's own part of the whole rows
        Dv = acc.shape[1] // G
        acc = sum(jnp.where(own[g], acc[:, g * Dv:(g + 1) * Dv], 0.0)
                  for g in range(G))
    if has_sink:
        # one more logit in the denominator, with no value row
        sink, m = sink_ref[:, :1], m_ref[:, :1]             # [H, 1]
        m_all = jnp.maximum(m, sink)
        scale = jnp.exp(m - m_all)
        l = l * scale + jnp.exp(sink - m_all)
        acc = acc * scale
    o_ref[...] = (acc / l).astype(o_ref.dtype)


def _decode_forward(q, k, v, sink, layer, pos, sm_scale, chunk, stride,
                    name, interpret, per=None):
    """q [B, H, C] (or [B, H, D] with ``per``, which the kernel widens);
    k [L, B, N, C] and v [L, B, N, Cv], the run's whole arrays, N =
    rows * stride; sink [H] or None; returns [B, H, Cv] (Cv // G with
    ``per``) at q's dtype."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    B, H = q.shape[:2]
    C, Cv = k.shape[3], v.shape[3]
    Co = Cv if per is None else Cv * q.shape[2] // C
    n = chunk * stride

    def row(b, layer_ref, pos_ref):
        return (b, 0, 0)

    in_specs = [pl.BlockSpec((None, H, q.shape[2]), row),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)]
    operands = [q, k, v]
    if sink is not None:
        # the same block at every grid step: copied in once
        in_specs.append(pl.BlockSpec((H, _LANES),
                                     lambda b, layer_ref, pos_ref: (0, 0)))
        operands.append(jnp.broadcast_to(
            sink.astype(jnp.float32)[:, None], (H, _LANES)))
    w = _scratch_lanes(n)
    return pl.pallas_call(
        functools.partial(_decode_kernel, sm_scale=sm_scale, chunk=chunk,
                          stride=stride, has_sink=sink is not None,
                          per=per),
        out_shape=jax.ShapeDtypeStruct((B, H, Co), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, H, Co), row),
            scratch_shapes=[pltpu.VMEM((_DECODE_DEPTH, n, C), k.dtype),
                            pltpu.VMEM((_DECODE_DEPTH, n, Cv), v.dtype),
                            pltpu.SemaphoreType.DMA((2, _DECODE_DEPTH)),
                            pltpu.SMEM((1,), jnp.int32),
                            pltpu.VMEM((H, w), jnp.float32),
                            pltpu.VMEM((H, w), jnp.float32),
                            pltpu.VMEM((H, Cv), jnp.float32)]
            + ([] if per is None else [pltpu.VMEM((H, C), q.dtype)])),
        # the rows follow one another: a row starts the next one's
        # copies; three chunks of a row of 32 KB a position and their
        # scores outgrow the 16 MiB of VMEM a kernel is given by default
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret,
        name=name,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), pos.astype(jnp.int32),
      *operands)


def decode_chunks(rows: int, row_bytes: int) -> int | None:
    """Positions in a chunk of the decode kernel for a cache of ``rows``
    positions whose K and V together take ``row_bytes`` a position, or
    None where the kernel has no chunk for the shape (``rows`` no
    multiple of 128).

    A chunk is the smallest multiple of 128 that divides ``rows`` and
    whose K and V take at least ``_DECODE_CHUNK_BYTES`` (what a row
    copies past its position is under one chunk), but at most a quarter
    of ``rows`` where that is 128 or more. ``_DECODE_DEPTH`` chunks are
    in VMEM at a time, two of them in flight while one is multiplied.

    Swept on a TPU v5e in bfloat16, microseconds a call, each the
    median of a scan of many, rows at the serving cells' positions, as
    (chunk, depth) against the parent's kernel (PR 37's grid of blocks;
    PERF.md section 6, PR 45). 8 x 1024 of 8 KB (16 x 128 heads) at a
    mean of 251: 31.9 (128, 2), 31.8 (128, 3), 32.1 (128, 4), 37.9
    (256, 3), the copies alone 31.5, against 49.7. 64 x 6144 of 5 KB
    at 2,886: 1,434, 1,344, 1,345 at 128 and depths 2, 3, 4, 1,372 and
    1,370 at 256, 1,394 at 384 (3), the copies alone 1,343, against
    1,558. 128 x 3200 of 2.5 KB at 1,730: 1,293, 999 and 1,012 at 128,
    1,007 and 1,016 at 640 (2, 3), the copies alone 909, against 1,188.
    256 x 2048 of 512 bytes at 806: 351 and 271 at 512 (2, 3), 329 and
    293 at 1024, 412 at 256 (3), against 397. So a chunk of a quarter
    MB or more keeps the copy engine near its rate with two copies
    queued (one is not enough below a MB), a smaller one loses to the
    copies' own cost and a larger one to the rows copied past each
    position; with rows of 5 KB and more the kernel is its copies
    alone. Up to 8 rows a grid step moved no shape by more than half a
    percent: the copies run on across the grid's steps."""
    fit = [c for c in range(_BLOCK, rows + 1, _BLOCK) if rows % c == 0]
    if not fit:
        return None
    room = [c for c in fit if c <= max(_BLOCK, rows // 4)]
    return next((c for c in room if c * row_bytes >= _DECODE_CHUNK_BYTES),
                room[-1])


def _decode_plan(q, k, v, chunk, interpret):
    """(chunk, stride) of the kernel for these operands, or None where
    the XLA form runs (``decode_attention`` says when)."""
    rows, H = k.shape[2], q.shape[1]
    stride = 1 if k.ndim == 4 else H
    if not (interpret or _on_tpu()):
        return None
    if chunk is None:
        row_bytes = k.dtype.itemsize * (
            math.prod(k.shape[3:]) + math.prod(v.shape[3:]))
        chunk = decode_chunks(rows, row_bytes)
        if chunk is None and not interpret:
            return None
    if interpret:   # exercises the kernel at any size: no Mosaic tiling
        chunk = min(chunk or rows, rows)
    else:
        tile = 8 * 4 // k.dtype.itemsize    # rows of a tile in memory
        if (chunk % _BLOCK or k.shape[-1] % _LANES or v.shape[-1] % _LANES
                or (stride > 1 and (stride % tile or stride & (stride - 1)))):
            return None
    if rows % chunk:
        return None
    return chunk, stride


def decode_rows_fetched(q, k, v, *, interpret: bool = False) -> int:
    """How many positions of a slot ``decode_attention`` copies at a
    time for these operands (arrays or their shapes' structs; a ring's
    too): the kernel's chunk, or all ``rows`` where the XLA form runs. A
    slot at position p costs ``(p // n + 1) * n`` of them a layer, which
    is what the kernel copies for it."""
    plan = _decode_plan(q, k, v, None, interpret)
    return k.shape[2] if plan is None else plan[0]


def decode_attention(q, k, v, layer, pos, *, sm_scale: float | None = None,
                     sink=None, ring: bool = False, chunk: int | None = None,
                     interpret: bool = False):
    """A decode step's attention for a run of attention layers, over the
    run's cache where it lies: one new token a slot, q [B, H, D],
    against layer ``layer`` (a traced index) of k [L, B, rows, H, D] and
    v [L, B, rows, H, Dv], or of the flat k [L, B, rows, G * D] and v
    [L, B, rows, G * Dv] where G K/V heads serve H / G query heads each;
    slot b attends positions ``[0, pos[b]]`` (its new token is in the
    cache already) and nothing past them; ``sink`` [H] is one more logit
    a head, in the denominator only. Under ``ring`` the rows are a
    window's ring, position p in row ``p % rows``: a slot attends rows
    ``[0, min(pos[b], rows - 1)]``, which hold the last ``rows``
    positions in some order (softmax does not care which; K is stored
    after its rope). Returns [B, H, Dv] at q's dtype. Softmax in
    float32, p cast to v's dtype, p@v accumulated in float32, as
    ``attention``.

    Two forms behind the one name, as ``flash_attention`` has. On the
    TPU (or under ``interpret``) the kernel, named ``decode_attend``
    (``decode_ring`` under ``ring``): k and v are its operands whole,
    where they lie, ``layer`` and ``pos`` prefetched scalars, and the
    kernel copies a slot's K and V itself, in chunks of positions **up
    to the chunk that holds ``pos[b]`` and no further**: what lies past
    a slot's position, in the chunks not copied or in the tail of its
    last one, is neither attended nor able to reach the output (NaN
    included). Rows are taken as they lie: flat ones with q widened to a
    whole row (``cached_attention``; of a ring, by the kernel itself in
    VMEM, which also keeps each head's own part of the output: a ring's
    few rows move about as many bytes as widened queries and outputs
    would), [H, D] ones as H rows a position of which a query head
    keeps its own, so both products are plain matrix products of [H, C]
    with a chunk. A ``sink`` joins the float32 softmax state once, after
    a slot's last chunk. Chunks come from the shape (``decode_chunks``);
    an explicit ``chunk`` wins.

    The XLA form, ``cached_attention`` over the layer's slice under a
    mask: off the TPU, and on the TPU where the shape has no chunk
    (``rows`` no multiple of 128, a row's width no multiple of 128
    lanes, [H, D] rows whose H is no power of two of whole tiles). It
    reads all ``rows`` of every slot.
    ``decode_rows_fetched`` says which a shape gets."""
    B, H, D = q.shape
    sm_scale = sm_scale if sm_scale is not None else D ** -0.5
    if ring:
        pos = jnp.minimum(pos, k.shape[2] - 1)
    plan = _decode_plan(q, k, v, chunk, interpret)
    if plan is None:
        lk = jax.lax.dynamic_index_in_dim(k, layer, keepdims=False)
        lv = jax.lax.dynamic_index_in_dim(v, layer, keepdims=False)
        valid = jnp.arange(k.shape[2])[None, None, :] <= pos[:, None, None]
        return cached_attention(q, lk, lv, valid, sm_scale, sink)
    chunk, stride = plan
    own = per = None
    if stride > 1:  # a position's H rows follow one another: a bitcast
        k = k.reshape(k.shape[:2] + (-1, D))
        v = v.reshape(v.shape[:2] + (-1, v.shape[-1]))
    elif ring:
        # a ring's few rows a slot move about as many bytes as queries
        # widened to whole rows and outputs of whole rows would, each
        # written and read back (MiMo-V2-Flash's rings of 128 rows at 128
        # slots: 84 MB a layer against 25 + 17 MB, each twice):
        # the kernel widens them in VMEM and writes each head's own part
        per = H // (k.shape[3] // D)
    else:
        q, own = _widen(q, k.shape[3] // D)
    # whole tiles of query rows (a padded head's output is dropped)
    padded = -H % (8 * 4 // q.dtype.itemsize)
    q = jnp.pad(q, ((0, 0), (0, padded), (0, 0)))
    if sink is not None:
        sink = jnp.pad(sink, (0, padded))
    o = _decode_forward(q, k, v, sink, layer, pos, sm_scale, chunk, stride,
                        DECODE_RING if ring else DECODE_KERNEL, interpret,
                        per)[:, :H]
    return o if own is None else _own_part(o, own)
