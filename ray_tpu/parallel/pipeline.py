"""SPMD pipeline parallelism (GPipe schedule over a mesh axis).

Pipeline parallelism is absent from the reference (SURVEY.md §2.4).
TPU-native design: each ``pp`` rank holds one stage's params (the
stacked-stage leading dim sharded over ``pp``); microbatch activations
hop between neighbor ranks with ``lax.ppermute`` inside a ``lax.scan``
— a static-shape loop XLA compiles once, with the bubble cost
``(n_stages - 1) / n_microbatches``. Differentiable: jax.grad through
the scan yields the reverse (backward) schedule automatically.

Call inside ``jax.shard_map`` over the ``pp`` axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.parallel.collectives import axis_size


# AD note (verified empirically, jax 0.9 shard_map check_vma=False):
# the transpose of lax.psum SUMS cotangents across ranks, so per-rank
# grads equal ∂(Σ_ranks loss_r)/∂(local params). The final
# psum-broadcast below hands every pp rank an identical copy of the
# output; if every rank then computes the same loss, stage-param grads
# come out n_pp-fold inflated. Callers must divide their per-rank loss
# (or the resulting grads) by the pp axis size — the model train step
# does this uniformly (models/transformer.py make_train_step).


def pipeline_spmd(stage_fn, stage_params, x, *, axis: str = "pp",
                  num_microbatches: int | None = None):
    """Run ``stage_fn(stage_params, mb)`` as a pipeline.

    x: [B, ...] full (pp-replicated) batch; returns [B, ...] outputs,
    valid on every rank (last stage's results are psum-broadcast).
    num_microbatches defaults to the pipeline depth.
    """
    n = axis_size(axis)
    rank = lax.axis_index(axis)
    B = x.shape[0]
    M = num_microbatches or n
    if B % M:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    mb = B // M
    xs = x.reshape((M, mb) + x.shape[1:])
    steps = M + n - 1
    perm = [(r, (r + 1) % n) for r in range(n)]

    def body(carry, t):
        recv, out_buf = carry
        # stage 0 reads microbatch t (clamped; masked out when t >= M)
        feed = lax.dynamic_index_in_dim(
            xs, jnp.clip(t, 0, M - 1), axis=0, keepdims=False)
        inp = jnp.where(rank == 0, feed.astype(recv.dtype), recv)
        out = stage_fn(stage_params, inp)
        # last rank finished microbatch t-(n-1) at this step
        mb_idx = jnp.clip(t - (n - 1), 0, M - 1)
        valid = jnp.logical_and(rank == n - 1, t >= n - 1)
        cur = lax.dynamic_index_in_dim(out_buf, mb_idx, 0, keepdims=False)
        upd = jnp.where(valid, out, cur)
        out_buf = lax.dynamic_update_index_in_dim(out_buf, upd, mb_idx, 0)
        recv_next = lax.ppermute(out, axis_name=axis, perm=perm)
        return (recv_next, out_buf), None

    probe = jax.eval_shape(stage_fn, stage_params,
                           jax.ShapeDtypeStruct((mb,) + x.shape[1:],
                                                x.dtype))
    recv0 = jnp.zeros(probe.shape, probe.dtype)
    buf0 = jnp.zeros((M,) + probe.shape, probe.dtype)
    (_, out_buf), _ = lax.scan(body, (recv0, buf0), jnp.arange(steps))
    # broadcast last rank's results to all pp ranks
    out_buf = lax.psum(
        jnp.where(rank == n - 1, out_buf, jnp.zeros_like(out_buf)),
        axis_name=axis)
    return out_buf.reshape((B,) + out_buf.shape[2:])
