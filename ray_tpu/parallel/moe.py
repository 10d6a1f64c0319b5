"""Expert parallelism: top-1 MoE dispatch/combine via all-to-all.

EP does not exist in the reference (SURVEY.md §2.4). TPU-native design
(Mesh-TensorFlow-style einsum routing): experts are sharded over a mesh
axis; tokens are routed with a capacity-bounded one-hot dispatch tensor
and exchanged with a single tiled ``lax.all_to_all`` each way, which XLA
lowers to ICI all-to-all. Static shapes throughout (dropped tokens pass
through on the residual path, standard Switch-Transformer behavior).

Call inside ``jax.shard_map``; x: [T_local, D]; experts sharded so each
rank owns E_local = E / axis_size experts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.parallel.collectives import axis_size


def moe_dispatch_combine(x, gate_logits, expert_fn, expert_params, *,
                         axis: str = "tp", capacity_factor: float = 1.25):
    """Returns [T_local, D] combined expert outputs (0 for dropped).

    gate_logits: [T_local, E] (E = global expert count).
    expert_fn(params, xs): params for E_local experts with leading dim
    E_local; xs [E_local, cap_total, D] → [E_local, cap_total, D].
    """
    n = axis_size(axis)
    T, D = x.shape
    E = gate_logits.shape[-1]
    if E % n:
        raise ValueError(f"{E} experts not divisible by axis size {n}")
    cap = max(1, int(capacity_factor * T / E))

    gates = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    expert_idx = jnp.argmax(gates, axis=-1)                  # [T]
    gate_val = jnp.max(gates, axis=-1)                       # [T]
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)  # [T,E]
    # position of each token within its expert's buffer
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0          # [T,E]
    keep = (pos < cap) & (onehot > 0)
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), cap,
                            dtype=jnp.float32) * keep[..., None]
    dispatch = pos_oh                                        # [T,E,cap]
    combine = dispatch * gate_val[:, None, None]             # [T,E,cap]

    xe = jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32))
    # [E,cap,D] → exchange so each rank holds its E_local experts'
    # buffers from every rank: after the all-to-all the leading dim
    # indexes the SOURCE rank, so transpose to [E_local, n, cap, D]
    # before flattening the per-expert token dim.
    xe = xe.reshape(n, E // n, cap, D)
    xe = lax.all_to_all(xe, axis_name=axis, split_axis=0, concat_axis=0,
                        tiled=False)
    xe = xe.transpose(1, 0, 2, 3).reshape(E // n, n * cap, D)
    ye = expert_fn(expert_params, xe.astype(x.dtype))        # [E_l,n*cap,D]
    ye = (ye.astype(jnp.float32)
          .reshape(E // n, n, cap, D).transpose(1, 0, 2, 3))
    ye = lax.all_to_all(ye, axis_name=axis, split_axis=0, concat_axis=0,
                        tiled=False)
    ye = ye.reshape(E, cap, D)
    out = jnp.einsum("tec,ecd->td", combine, ye)
    return out.astype(x.dtype)


def load_balance_loss(gate_logits, axis: str | None = None):
    """Switch-Transformer auxiliary loss: E * Σ_e f_e · p_e."""
    E = gate_logits.shape[-1]
    gates = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    frac = jnp.mean(
        jax.nn.one_hot(jnp.argmax(gates, -1), E, dtype=jnp.float32),
        axis=tuple(range(gates.ndim - 1)))
    prob = jnp.mean(gates, axis=tuple(range(gates.ndim - 1)))
    return E * jnp.sum(frac * prob)
