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

Reference-parity note: the reference snapshot has no attention kernels
at all (SURVEY.md §5.7 — absent); this op underpins the TPU-native
long-context capability layered on the runtime.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_NEG_INF = -1e30
_LANES = 128  # f32 VMEM lane width; m/l scratch rows are lane-replicated


def attention(q, k, v, *, causal: bool = True,
              sm_scale: float | None = None):
    """Reference softmax attention (fp32 accumulation)."""
    D = q.shape[-1]
    sm_scale = sm_scale if sm_scale is not None else D ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        Tq, Tk = q.shape[1], k.shape[1]
        # allow Tq != Tk (decode: q at the tail of the kv sequence)
        qpos = jnp.arange(Tq) + (Tk - Tq)
        mask = qpos[:, None] >= jnp.arange(Tk)[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


@functools.cache
def _on_tpu() -> bool:
    """Whether this process's default jax backend is the TPU, read
    once. A backend that cannot start raises here, as it would at the
    first array: picking a kernel is no place to absorb that."""
    return jax.default_backend() == "tpu"


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                  acc_ref, *, sm_scale, causal, block_q, block_k, num_k):
    """One (b, h, qi, ki) grid step of online-softmax attention.

    q_ref [1,1,bq,D]; k_ref/v_ref [1,1,bk,D]; o_ref [1,1,bq,D];
    lse_ref [1,1,bq,1] per-row logsumexp (the backward's softmax key;
    the trailing singleton keeps the block's last-two dims Mosaic-legal:
    (bq, 1) = sublane-divisible x whole-array lane dim).
    Scratch (VMEM, persists across the innermost ki axis):
      m_ref/l_ref [bq, _LANES] lane-replicated running max / denom,
      acc_ref [bq, D] running numerator.
    """
    import jax.experimental.pallas as pl

    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Causal: blocks strictly above the diagonal contribute nothing.
    run = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)          # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)          # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [bq, bk]
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)

        m_prev = m_ref[:, :1]                         # [bq, 1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)    # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)               # rescale old state
        p = jnp.exp(s - m_new)                        # [bq, bk]
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == num_k - 1)
    def _finish():
        # Fully masked rows (can't happen under causal) would have l=0;
        # guard the divide anyway so the kernel never emits NaN.
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[:, :1] + jnp.log(l)


def _flash_forward(q, k, v, causal, sm_scale, block_q, block_k,
                   interpret):
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    B, T, H, D = q.shape
    # [B,T,H,D] → [B,H,T,D] so the MXU dims (T, D) are trailing.
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    num_k = T // block_k
    grid = (B, H, T // block_q, num_k)  # ki innermost: scratch carries
    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, num_k=num_k)
    out, lse = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct(qt.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, H, T, 1), jnp.float32)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=(pl.BlockSpec((1, 1, block_q, D),
                                lambda b, h, i, j: (b, h, i, 0)),
                   pl.BlockSpec((1, 1, block_q, 1),
                                lambda b, h, i, j: (b, h, i, 0))),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3), lse


def _bwd_tiles(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, *,
               sm_scale, causal, block_q, block_k, qi, ki):
    """Shared recompute for one (q-block, k-block) tile of the backward:
    returns (p, ds) — the probability tile and the score gradient tile
    (sm_scale folded into ds)."""
    q = q_ref[0, 0].astype(jnp.float32)               # [bq, D]
    k = k_ref[0, 0].astype(jnp.float32)               # [bk, D]
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]                               # [bq, 1]
    delta = dl_ref[0, 0]                              # [bq, 1]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale     # [bq, bk]
    if causal:
        qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                       s.shape, 0)
        kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                       s.shape, 1)
        s = jnp.where(qpos >= kpos, s, _NEG_INF)
    p = jnp.exp(s - lse)                              # exact softmax tile
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                # [bq, bk]
    ds = p * (dp - delta) * sm_scale
    return q, k, do, p, ds


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, sm_scale,
                          causal, block_q, block_k, num_q):
    """Grid (b, h, ki, qi), qi innermost: dK/dV accumulate over q."""
    import jax.experimental.pallas as pl

    ki, qi = pl.program_id(2), pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    run = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(run)
    def _step():
        q, _k, do, p, ds = _bwd_tiles(
            q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
            sm_scale=sm_scale, causal=causal, block_q=block_q,
            block_k=block_k, qi=qi, ki=ki)
        dv_acc[...] += jax.lax.dot_general(            # p^T @ do  [bk, D]
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[...] += jax.lax.dot_general(            # ds^T @ q  [bk, D]
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == num_q - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                         dq_ref, dq_acc, *, sm_scale, causal, block_q,
                         block_k, num_k):
    """Grid (b, h, qi, ki), ki innermost: dQ accumulates over k."""
    import jax.experimental.pallas as pl

    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    run = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(run)
    def _step():
        _q, k, _do, _p, ds = _bwd_tiles(
            q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
            sm_scale=sm_scale, causal=causal, block_q=block_q,
            block_k=block_k, qi=qi, ki=ki)
        dq_acc[...] += jax.lax.dot_general(            # ds @ k  [bq, D]
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == num_k - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, causal, sm_scale, block_q,
                    block_k, interpret):
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    B, T, H, D = q.shape
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    dot = g.transpose(0, 2, 1, 3)
    # delta_i = rowsum(dO_i * O_i): one fused XLA reduce, [B, H, T, 1]
    # (trailing singleton matches the lse layout; see _flash_kernel doc).
    delta = jnp.einsum("bqhd,bqhd->bhq", g.astype(jnp.float32),
                       out.astype(jnp.float32))[..., None]
    num_q, num_k = T // block_q, T // block_k

    qspec = pl.BlockSpec((1, 1, block_q, D), lambda b, h, j, i: (b, h, i, 0))
    kspec = pl.BlockSpec((1, 1, block_k, D), lambda b, h, j, i: (b, h, j, 0))
    rowq = pl.BlockSpec((1, 1, block_q, 1), lambda b, h, j, i: (b, h, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=block_q,
                          block_k=block_k, num_q=num_q),
        out_shape=(jax.ShapeDtypeStruct(kt.shape, k.dtype),
                   jax.ShapeDtypeStruct(vt.shape, v.dtype)),
        grid=(B, H, num_k, num_q),
        in_specs=[qspec, kspec, kspec, qspec, rowq, rowq],
        out_specs=(pl.BlockSpec((1, 1, block_k, D),
                                lambda b, h, j, i: (b, h, j, 0)),) * 2,
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32)] * 2,
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qt, kt, vt, dot, lse, delta)

    qspec2 = pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0))
    kspec2 = pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h, j, 0))
    rowq2 = pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0))
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=block_q,
                          block_k=block_k, num_k=num_k),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        grid=(B, H, num_q, num_k),
        in_specs=[qspec2, kspec2, kspec2, qspec2, rowq2, rowq2],
        out_specs=qspec2,
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(qt, kt, vt, dot, lse, delta)
    return (dq.transpose(0, 2, 1, 3), dk.transpose(0, 2, 1, 3),
            dv.transpose(0, 2, 1, 3))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    out, _ = _flash_forward(q, k, v, causal, sm_scale, block_q, block_k,
                            interpret)
    return out


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    out, lse = _flash_forward(q, k, v, causal, sm_scale, block_q,
                              block_k, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, sm_scale, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse, g, causal, sm_scale,
                           block_q, block_k, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True,
                    sm_scale: float | None = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """Blockwise online-softmax attention (Pallas on TPU).

    Which implementation runs is decided by two things the caller can
    see: the platform — the Mosaic kernel exists only for the TPU, so a
    process whose default backend is anything else runs ``attention``
    unless it asks for the kernel under ``interpret`` — and the shape:
    decode steps (Tq != Tk) and sequences not divisible by the block
    sizes run ``attention`` on every platform. A caller that must know
    the kernel ran looks for the Mosaic custom call in its compiled
    program (chip_smoke.py does).
    """
    B, T, H, D = q.shape
    sm_scale = sm_scale if sm_scale is not None else D ** -0.5
    if interpret:
        # interpret mode exists to exercise the kernel: clamp blocks so
        # it runs even at small T (no Mosaic tiling constraints on CPU).
        block_q = min(block_q, T)
        block_k = min(block_k, T)
    # Shape: short / unaligned sequences use the XLA reference — Mosaic
    # blocks come in sublane 8 / lane 128 granules.
    unaligned = (T < block_q or T % block_q or T % block_k
                 or k.shape[1] != T)
    if unaligned or not (interpret or _on_tpu()):
        return attention(q, k, v, causal=causal, sm_scale=sm_scale)
    return _flash(q, k, v, causal, sm_scale, block_q, block_k,
                  interpret)
