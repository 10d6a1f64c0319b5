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

Reference-parity note: the reference snapshot has no attention kernels
at all (SURVEY.md §5.7 — absent); this op underpins the TPU-native
long-context capability layered on the runtime.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_NEG_INF = -1e30
_LANES = 128  # f32 VMEM lane width; the m/l scratch rows are as wide
_BLOCK = 128  # granule of a block along the sequence
# The three pallas_calls, by their ``name=`` (a device trace names them
# so), in the order ``_flash`` carries their blocks.
KERNELS = ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")
# Largest (block_q, block_k) of each: flash_blocks.
_BLOCK_CAPS = {"flash_fwd": (1024, 1024), "flash_bwd_dkv": (512, 512),
               "flash_bwd_dq": (1024, 1024)}


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
    w = m_ref.shape[1]

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


def _flash_forward(q, k, v, causal, sm_scale, block_q, block_k,
                   interpret, window=None, sink=None):
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    B, T, H, D = q.shape
    G, Dv = k.shape[2], v.shape[3]
    # [B,T,H,D] → [B,H,T,D] so the MXU dims (T, D) are trailing.
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
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
    out, lse = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((B, H, T, Dv), q.dtype),
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
    return out.transpose(0, 2, 1, 3), lse


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


def _flash_backward(q, k, v, out, lse, g, causal, sm_scale, blocks,
                    interpret):
    B, T, H, D = q.shape
    qt, kt, vt, dot = (x.transpose(0, 2, 1, 3) for x in (q, k, v, g))
    # delta_i = rowsum(dO_i * O_i): one fused XLA reduce, [B, H, T].
    delta = jnp.einsum("bqhd,bqhd->bhq", g.astype(jnp.float32),
                       out.astype(jnp.float32))
    dk, dv = _flash_dkv(qt, kt, vt, dot, lse.reshape(B, H, 1, T),
                        delta[:, :, None, :], causal, sm_scale,
                        *blocks[1], interpret)
    dq = _flash_dq(qt, kt, vt, dot, lse, delta[..., None], causal,
                   sm_scale, *blocks[2], interpret)
    return (dq.transpose(0, 2, 1, 3), dk.transpose(0, 2, 1, 3),
            dv.transpose(0, 2, 1, 3))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, sm_scale, blocks, interpret):
    out, _ = _flash_forward(q, k, v, causal, sm_scale, *blocks[0],
                            interpret)
    return out


def _flash_fwd(q, k, v, causal, sm_scale, blocks, interpret):
    out, lse = _flash_forward(q, k, v, causal, sm_scale, *blocks[0],
                              interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, sm_scale, blocks, interpret, res, g):
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse, g, causal, sm_scale,
                           blocks, interpret)


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
