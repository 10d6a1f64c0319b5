"""Ring attention over a mesh axis.

Sequence/context parallelism is absent from the reference snapshot
(SURVEY.md §5.7) — this is the TPU-native capability that replaces it:
K/V shards rotate around the ``sp`` axis ring via ``lax.ppermute``
(nearest-neighbor ICI hops) while each device keeps a blockwise
online-softmax accumulator over its local Q shard, so attention over a
sequence of length ``n_sp * T_local`` never materializes on one chip.

Call inside ``jax.shard_map`` with q/k/v sharded on dim 1 (seq) over
``axis``. Shapes: [batch, seq_local, heads, head_dim].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.parallel.collectives import axis_size


def _block_attn(q, k, v, q_pos, kv_pos, causal, sm_scale):
    # q: [B,Tq,H,D] k,v: [B,Tk,H,D] → scores [B,H,Tq,Tk]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        mask = q_pos[:, None] >= kv_pos[None, :]      # [Tq,Tk]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    m = jnp.max(s, axis=-1)                            # [B,H,Tq]
    # Fully-masked rows (no visible keys yet in the ring) → avoid -inf.
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    l = jnp.sum(p, axis=-1)                            # [B,H,Tq]
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o, m_safe, l


def ring_attention(q, k, v, *, axis: str = "sp", causal: bool = True,
                   sm_scale: float | None = None):
    """Blockwise ring attention. Returns [B, T_local, H, D] in q.dtype."""
    n = axis_size(axis)
    my = lax.axis_index(axis)
    B, T, H, D = q.shape
    sm_scale = sm_scale if sm_scale is not None else D ** -0.5
    q_pos = my * T + jnp.arange(T)

    q32 = q.astype(jnp.float32)

    def step(carry, i):
        o_acc, m_acc, l_acc, k_cur, v_cur = carry
        src = (my - i) % n                       # whose K/V block we hold
        kv_pos = src * T + jnp.arange(T)
        o, m, l = _block_attn(q32, k_cur, v_cur, q_pos, kv_pos,
                              causal, sm_scale)
        # online softmax merge
        m_new = jnp.maximum(m_acc, m)
        alpha = jnp.exp(m_acc - m_new)
        beta = jnp.exp(m - m_new)
        l_new = l_acc * alpha + l * beta
        o_new = (o_acc * alpha.transpose(0, 2, 1)[..., None]
                 + o * beta.transpose(0, 2, 1)[..., None])
        # rotate K/V to the next rank (skip after the final block; the
        # ppermute still runs — the scan carries it — but is cheap and
        # keeps the loop body static for XLA)
        perm = [(r, (r + 1) % n) for r in range(n)]
        k_nxt = lax.ppermute(k_cur, axis_name=axis, perm=perm)
        v_nxt = lax.ppermute(v_cur, axis_name=axis, perm=perm)
        return (o_new, m_new, l_new, k_nxt, v_nxt), None

    o0 = jnp.zeros((B, T, H, D), jnp.float32)
    m0 = jnp.full((B, H, T), -1e30)  # finite "-inf" sentinel
    l0 = jnp.zeros((B, H, T))
    (o, m, l, _, _), _ = lax.scan(
        step, (o0, m0, l0, k.astype(jnp.float32), v.astype(jnp.float32)),
        jnp.arange(n))
    l = jnp.maximum(l, 1e-30)
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)
