"""Normalization ops."""

from __future__ import annotations

import jax.numpy as jnp


def rmsnorm(x, weight, *, eps: float = 1e-6):
    """RMSNorm in fp32, cast back to input dtype (XLA fuses this into
    the adjacent matmul; no Pallas needed — it is bandwidth-bound and
    fusion already eliminates the HBM round-trip)."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jnp.reciprocal(jnp.sqrt(var + eps))
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def layernorm(x, weight, bias, *, eps: float = 1e-5):
    """LayerNorm with scale and bias over the last dimension, in fp32,
    cast back to the input dtype."""
    x32 = x.astype(jnp.float32)
    centred = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(centred * centred, axis=-1, keepdims=True)
    y = centred * jnp.reciprocal(jnp.sqrt(var + eps))
    return (y * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)
