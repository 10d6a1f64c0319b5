"""Rotary position embeddings (RoPE)."""

from __future__ import annotations

import jax.numpy as jnp


def rope_frequencies(head_dim: int, max_seq: int, *,
                     theta: float = 10000.0):
    """Precompute cos/sin tables [max_seq, head_dim//2] (fp32)."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                      dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_seq, dtype=jnp.float32)
    ang = jnp.outer(t, inv)
    return jnp.cos(ang), jnp.sin(ang)


def rotate(x, c, s):
    """x: [B, T, H, D] rotated pairwise (first half against second) by
    the angles whose cosines and sines ``c``, ``s`` broadcast against
    [B, T, H, R//2]. R = D rotates the whole head; a narrower table
    rotates the first R dimensions so and passes the rest through.
    Arithmetic in float32, result at x's dtype."""
    rotary = 2 * c.shape[-1]
    if rotary < x.shape[-1]:
        return jnp.concatenate(
            [rotate(x[..., :rotary], c, s), x[..., rotary:]], axis=-1)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def apply_rotary(x, cos, sin, *, positions=None):
    """x: [B, T, H, D]; cos/sin: [max_seq, R//2], R <= D the leading
    dimensions rotated (``rotate``). positions: [T] global
    token positions, shared by the batch (for sequence-parallel shards /
    prefill); a batch whose rows stand at different positions gathers
    its own rows of the tables and calls ``rotate``."""
    T = x.shape[1]
    if positions is None:
        c, s = cos[:T], sin[:T]
    else:
        c, s = cos[positions], sin[positions]
    return rotate(x, c[None, :, None, :], s[None, :, None, :])
