"""Ulysses-style sequence parallelism: head↔sequence all-to-all.

Alternative SP schedule to ring attention (SURVEY.md §5.7): instead of
rotating K/V, re-shard — an all-to-all over the ``sp`` axis converts
seq-sharded/head-full activations into seq-full/head-sharded ones, runs
ordinary (full-sequence) attention on the local heads, then converts
back. Two all-to-alls per attention; wins when heads ≥ sp and the
sequence fits per-device once head-sharded.

Call inside ``jax.shard_map``; q/k/v: [B, T_local, H, D], H % sp == 0.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def _seq_to_heads(x, axis):
    # [B, T/n, H, D] → [B, T, H/n, D]
    return lax.all_to_all(x, axis_name=axis, split_axis=2, concat_axis=1,
                          tiled=True)


def _heads_to_seq(x, axis):
    # [B, T, H/n, D] → [B, T/n, H, D]
    return lax.all_to_all(x, axis_name=axis, split_axis=1, concat_axis=2,
                          tiled=True)


def ulysses_attention(q, k, v, *, axis: str = "sp", causal: bool = True,
                      sm_scale: float | None = None,
                      attn_fn=None):
    """Returns [B, T_local, H, D]. ``attn_fn(q,k,v,causal,sm_scale)``
    runs full attention on head-sharded tensors (defaults to a fused
    softmax-attention; swap in a Pallas flash kernel on TPU)."""
    D = q.shape[-1]
    sm_scale = sm_scale if sm_scale is not None else D ** -0.5
    qh = _seq_to_heads(q, axis)
    kh = _seq_to_heads(k, axis)
    vh = _seq_to_heads(v, axis)
    if attn_fn is None:
        from ray_tpu.ops.attention import attention as attn_fn  # lazy
    oh = attn_fn(qh, kh, vh, causal=causal, sm_scale=sm_scale)
    return _heads_to_seq(oh, axis)
