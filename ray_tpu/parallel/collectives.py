"""Named-axis collective wrappers.

TPU-native equivalent of ``ray.util.collective``'s op surface
(reference: python/ray/util/collective/collective.py — allreduce :244,
allgather :409, reducescatter :457, broadcast :358, send/recv :514+),
expressed as XLA collectives over mesh axis names so they compile onto
ICI instead of going through NCCL communicators. Used inside
``jax.shard_map``/``pjit`` bodies.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


# Re-exported so every SPMD body in the repo names one symbol.
shard_map = jax.shard_map


def psum(x, axis: str):
    """All-reduce-sum over a mesh axis (ray.util.collective.allreduce)."""
    return lax.psum(x, axis_name=axis)


def pmean(x, axis: str):
    return lax.pmean(x, axis_name=axis)


def all_gather(x, axis: str, *, tiled: bool = True, gather_dim: int = 0):
    """Gather shards along a mesh axis (collective.allgather)."""
    return lax.all_gather(x, axis_name=axis, axis=gather_dim, tiled=tiled)


def psum_scatter(x, axis: str, *, scatter_dim: int = 0, tiled: bool = True):
    """Reduce-scatter (collective.reducescatter)."""
    return lax.psum_scatter(x, axis_name=axis,
                            scatter_dimension=scatter_dim, tiled=tiled)


def all_to_all(x, axis: str, *, split_dim: int, concat_dim: int,
               tiled: bool = True):
    """All-to-all over a mesh axis — the Ulysses/MoE primitive."""
    return lax.all_to_all(x, axis_name=axis, split_axis=split_dim,
                          concat_axis=concat_dim, tiled=tiled)


def ppermute_ring(x, axis: str, *, shift: int = 1):
    """Rotate shards around the axis ring (ring attention's hop)."""
    n = axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name=axis, perm=perm)


def axis_index(axis: str):
    return lax.axis_index(axis)


def axis_size(axis: str) -> int:
    """Static (Python int) size of a mesh axis, for Python-level
    control flow (pipeline step counts, ring permutations)."""
    return lax.axis_size(axis)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def tp_copy(x, axis: str):
    """Identity forward, psum backward (Megatron's "f" operator).

    Place on a tp-replicated activation right before column-parallel
    (output-sharded) matmuls: each tp rank backpropagates only its
    shard's contribution to the activation cotangent, so the cotangents
    must be summed over tp to stay consistent with the replicated
    forward value.
    """
    return x


def _tp_copy_fwd(x, axis):
    return x, None


def _tp_copy_bwd(axis, _, g):
    return (lax.psum(g, axis_name=axis),)


tp_copy.defvjp(_tp_copy_fwd, _tp_copy_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def tp_allreduce(x, axis: str):
    """psum forward, identity backward (Megatron's "g" operator).

    Place on a row-parallel matmul's partial output. ``lax.psum``'s own
    transpose SUMS cotangents across ranks, which is right only when
    every rank's cotangent is a distinct contribution; here the
    downstream compute is replicated on ``axis`` (every rank holds the
    same loss copy and produces the same cotangent), so the true
    cotangent of each rank's partial is that single copy — identity.
    Requires: the output must be consumed by tp-replicated computation.
    """
    return lax.psum(x, axis_name=axis)


def _tp_allreduce_fwd(x, axis):
    return lax.psum(x, axis_name=axis), None


def _tp_allreduce_bwd(axis, _, g):
    return (g,)


tp_allreduce.defvjp(_tp_allreduce_fwd, _tp_allreduce_bwd)


def broadcast_from(x, axis: str, root: int = 0):
    """Broadcast the root shard's value to all ranks on the axis
    (collective.broadcast): select root's contribution, all-reduce."""
    idx = lax.axis_index(axis)
    contrib = jnp.where(idx == root, x, jnp.zeros_like(x))
    return lax.psum(contrib, axis_name=axis)
