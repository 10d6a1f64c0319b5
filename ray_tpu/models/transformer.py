"""Decoder-only transformer (Llama-style) over a dp/pp/sp/tp mesh.

**One block.** ``block`` is the only definition of the layer: norms,
projections, feed-forward and residuals. What differs between training,
prefill and decode is handed to it: ``rope`` (how q and k are rotated)
and ``attend`` (what the queries attend, and the state that comes
back). ``forward`` here and ``slot_prefill`` / ``slot_decode_step`` in
models/decode.py each scan it over the stacked layers; ``unembed`` is
their shared final norm and tied head. A change to the architecture is
a change to ``block``, ``init_params`` and ``param_specs``.

``forward`` runs in two modes sharing every line of math:

* **oracle** — ``ParallelConfig()`` with all axes ``None``: plain
  single-device forward (the differential-test reference).
* **SPMD** — inside ``jax.shard_map`` over the 4-axis mesh
  (``ray_tpu.parallel.mesh``): Megatron-style tensor parallelism on
  ``tp`` (column-parallel QKV/gate/up, row-parallel O/down + ``psum``;
  backward fixed up by ``tp_copy``), ring or Ulysses attention on
  ``sp``, a GPipe microbatch pipeline on ``pp``
  (``parallel.pipeline_spmd``), and gradient ``psum`` over the data
  axes (``dp``/``sp``).

Design notes for TPU: params live in bf16 MXU-aligned blocks, layers
are stacked on a leading dim and scanned (one compiled layer body),
fp32 accumulation everywhere that matters, optional per-layer
``jax.checkpoint`` to trade FLOPs for HBM.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import flash_attention
from ray_tpu.ops.norms import rmsnorm
from ray_tpu.ops.rotary import apply_rotary, rope_frequencies
from ray_tpu.parallel.collectives import (axis_size, shard_map,
                                           tp_allreduce, tp_copy)
from ray_tpu.parallel.pipeline import pipeline_spmd
from ray_tpu.parallel.ring_attention import ring_attention
from ray_tpu.parallel.ulysses import ulysses_attention

from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 256
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 4
    d_ff: int = 512
    max_seq: int = 256
    rope_theta: float = 10000.0
    dtype: jnp.dtype = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


# The dense decoder the repo serves and trains at full width on one
# v5e chip: 168M parameters, head_dim 64, bf16. B16 x T1024 with
# remat fits the chip's HBM; without remat it does not.
DENSE_168M = TransformerConfig(
    vocab=32768, d_model=1024, n_heads=16, n_layers=8, d_ff=4096,
    max_seq=1024, dtype=jnp.bfloat16)


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    """Mesh axis names (None = that parallelism disabled)."""
    dp: Optional[str] = None
    pp: Optional[str] = None
    sp: Optional[str] = None
    tp: Optional[str] = None
    attn: str = "auto"          # auto | local | ring | ulysses
    remat: bool = False         # jax.checkpoint around each block
    num_microbatches: Optional[int] = None

    def data_axes(self):
        return tuple(a for a in (self.dp, self.sp) if a)


def init_params(key, cfg: TransformerConfig):
    """Pytree of params; layer weights stacked on a leading L dim."""
    k = jax.random.split(key, 8)
    D, H, Dh, F, L, V = (cfg.d_model, cfg.n_heads, cfg.head_dim,
                         cfg.d_ff, cfg.n_layers, cfg.vocab)
    dt = cfg.dtype
    init = jax.nn.initializers.normal(0.02)

    def w(kk, shape):
        return init(kk, shape, jnp.float32).astype(dt)

    return {
        "embed": w(k[0], (V, D)),
        "layers": {
            "attn_norm": jnp.ones((L, D), dt),
            "wq": w(k[1], (L, D, H * Dh)),
            "wk": w(k[2], (L, D, H * Dh)),
            "wv": w(k[3], (L, D, H * Dh)),
            "wo": w(k[4], (L, H * Dh, D)),
            "mlp_norm": jnp.ones((L, D), dt),
            "w_gate": w(k[5], (L, D, F)),
            "w_up": w(k[6], (L, D, F)),
            "w_down": w(k[7], (L, F, D)),
        },
        "final_norm": jnp.ones((D,), dt),
    }


def param_specs(pcfg: ParallelConfig):
    """PartitionSpec pytree matching ``init_params`` output."""
    pp, tp = pcfg.pp, pcfg.tp
    return {
        "embed": P(None, None),
        "layers": {
            "attn_norm": P(pp, None),
            "wq": P(pp, None, tp),
            "wk": P(pp, None, tp),
            "wv": P(pp, None, tp),
            "wo": P(pp, tp, None),
            "mlp_norm": P(pp, None),
            "w_gate": P(pp, None, tp),
            "w_up": P(pp, None, tp),
            "w_down": P(pp, tp, None),
        },
        "final_norm": P(None),
    }


def _attend(q, k, v, pcfg: ParallelConfig):
    impl = pcfg.attn
    if impl == "auto":
        impl = "ring" if pcfg.sp else "local"
    if impl == "local" or not pcfg.sp:
        # Pallas blocked online-softmax kernel when the default backend
        # is the TPU and T is a multiple of 128 (the kernel chooses its
        # blocks from T and multiplies at q's dtype); the XLA reference
        # otherwise (ops.attention.flash_attention).
        return flash_attention(q, k, v, causal=True)
    if impl == "ring":
        return ring_attention(q, k, v, axis=pcfg.sp, causal=True)
    if impl == "ulysses":
        return ulysses_attention(q, k, v, axis=pcfg.sp, causal=True)
    raise ValueError(f"unknown attn impl {impl!r}")


def block(lp, x, rope, attend, cfg: TransformerConfig,
          pcfg: ParallelConfig = ParallelConfig()):
    """The transformer block, on local shards. x: [B_l, T_l, D]
    (tp-replicated); ``lp`` one layer's weights. ``rope(t)`` rotates q
    and k; ``attend(q, k, v) -> (o, state)`` takes them as [B, T,
    H_local, Dh] and gives the attention output, which is flattened
    here to [B, T, H_local * Dh], and whatever the caller keeps of the
    layer (the K/V a cache holds; None in training). Returns
    (x, state)."""
    B, T, D = x.shape
    Dh = cfg.head_dim

    h = rmsnorm(x, lp["attn_norm"])
    if pcfg.tp:
        h = tp_copy(h, pcfg.tp)
    q = (h @ lp["wq"]).reshape(B, T, -1, Dh)      # H_local heads
    k = (h @ lp["wk"]).reshape(B, T, -1, Dh)
    v = (h @ lp["wv"]).reshape(B, T, -1, Dh)
    o, state = attend(rope(q), rope(k), v)
    o = o.reshape(B, T, -1) @ lp["wo"]             # row-parallel
    if pcfg.tp:
        o = tp_allreduce(o, pcfg.tp)
    x = x + o.astype(x.dtype)

    h = rmsnorm(x, lp["mlp_norm"])
    if pcfg.tp:
        h = tp_copy(h, pcfg.tp)
    g = jax.nn.silu((h @ lp["w_gate"]).astype(jnp.float32))
    u = (h @ lp["w_up"]).astype(jnp.float32)
    d = (g * u).astype(x.dtype) @ lp["w_down"]     # row-parallel
    if pcfg.tp:
        d = tp_allreduce(d, pcfg.tp)
    return x + d.astype(x.dtype), state


def unembed(params, x, *, last=False):
    """Final norm and the tied unembed of x [..., D], or with ``last``
    of the last position alone of x [B, T, D] (a prefill). The matmul
    runs at x's dtype and the logits are float32: a stable
    softmax-xent, and one rounding for every caller, so that a greedy
    decode agrees with the full forward's argmax."""
    x = rmsnorm(x, params["final_norm"])
    if last:
        x = x[:, -1]
    return (x @ params["embed"].T.astype(x.dtype)).astype(jnp.float32)


def _stack_fn(cfg, pcfg, rope):
    """Scan the (locally held) layer stack over one activation."""
    def layer(lp, x):
        return block(lp, x, rope,
                     lambda q, k, v: (_attend(q, k, v, pcfg), None),
                     cfg, pcfg)

    if pcfg.remat:
        layer = jax.checkpoint(layer)

    def run(layers, x):
        return lax.scan(lambda h, lp: layer(lp, h), x, layers)[0]
    return run


def forward(params, tokens, cfg: TransformerConfig,
            pcfg: ParallelConfig = ParallelConfig()):
    """tokens: [B_local, T_local] int32 → logits [B_l, T_l, V] (fp32).

    Call directly for the oracle, or inside shard_map for SPMD.
    """
    T = tokens.shape[1]
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq,
                                theta=cfg.rope_theta)
    if pcfg.sp:
        positions = lax.axis_index(pcfg.sp) * T + jnp.arange(T)
    else:
        positions = jnp.arange(T)

    x = params["embed"][tokens]                    # [B,T,D]
    stack = _stack_fn(cfg, pcfg, functools.partial(
        apply_rotary, cos=cos, sin=sin, positions=positions))
    if pcfg.pp:
        x = pipeline_spmd(stack, params["layers"], x, axis=pcfg.pp,
                          num_microbatches=pcfg.num_microbatches)
    else:
        x = stack(params["layers"], x)
    return unembed(params, x)


def loss_fn(params, batch, cfg: TransformerConfig,
            pcfg: ParallelConfig = ParallelConfig()):
    """Mean next-token cross-entropy over the GLOBAL batch.

    batch: dict(tokens=[B_l, T_l], targets=[B_l, T_l]); inside
    shard_map the per-rank mean is pmean'd over the data axes.
    """
    logits = forward(params, batch["tokens"], cfg, pcfg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(
        logp, batch["targets"][..., None], axis=-1)[..., 0]
    return jnp.mean(nll)  # LOCAL mean; train step reduces over axes


def make_train_step(cfg: TransformerConfig, pcfg: ParallelConfig,
                    mesh=None, optimizer=None):
    """Build a jitted ``step(params, opt_state, batch) → (params,
    opt_state, loss)``. With a mesh, wraps the per-rank step in
    shard_map over all four axes with real param/batch shardings."""
    import optax

    optimizer = optimizer or optax.adamw(3e-4)

    pspecs_for_grads = param_specs(pcfg)

    def local_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch, cfg,
                                                  pcfg)
        # Gradient calculus under shard_map AD (lax.psum transposes to
        # psum, i.e. per-rank grads equal ∂(Σ_ranks loss_r)/∂leaf):
        # * tp — the layer uses tp_copy/tp_allreduce (Megatron f/g with
        #   JAX-correct transposes), so every tp rank's grads are
        #   already the true single-counted gradient: no reduction.
        # * pp — the pipeline's output broadcast sums the n_pp
        #   redundant loss copies' cotangents into every path, so
        #   divide by n_pp; pp-replicated leaves (embed, final_norm)
        #   then need their per-rank halves psum'd over pp.
        # * dp/sp — distinct data shards: pmean.
        redundancy = float(axis_size(pcfg.pp)) if pcfg.pp else 1.0

        def reduce_leaf(g, spec):
            g = g / redundancy
            sharded = set(a for a in spec if a)
            if pcfg.pp and pcfg.pp not in sharded:
                g = lax.psum(g, axis_name=pcfg.pp)
            for ax in pcfg.data_axes():
                g = lax.pmean(g, axis_name=ax)
            return g

        grads = jax.tree.map(
            reduce_leaf, grads, pspecs_for_grads,
            is_leaf=lambda x: isinstance(x, jax.Array))
        for ax in pcfg.data_axes():
            loss = lax.pmean(loss, axis_name=ax)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    if mesh is None:
        return jax.jit(local_step), optimizer

    pspecs = param_specs(pcfg)
    opt_specs = _opt_state_specs(optimizer, cfg, pspecs)
    batch_spec = {"tokens": P(pcfg.dp, pcfg.sp),
                  "targets": P(pcfg.dp, pcfg.sp)}
    step = shard_map(
        local_step, mesh=mesh,
        in_specs=(pspecs, opt_specs, batch_spec),
        out_specs=(pspecs, opt_specs, P()),
        check_vma=False)
    return jax.jit(step), optimizer


def init_train_state(key, cfg: TransformerConfig, pcfg: ParallelConfig,
                     mesh, optimizer):
    """(params, opt_state) for ``make_train_step(cfg, pcfg, mesh)``,
    each leaf created in its mesh sharding — no device ever holds the
    whole tree."""
    from jax.sharding import NamedSharding

    def named(specs):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, P))

    pspecs = param_specs(pcfg)
    params = jax.jit(functools.partial(init_params, cfg=cfg),
                     out_shardings=named(pspecs))(key)
    opt_state = jax.jit(
        optimizer.init,
        out_shardings=named(_opt_state_specs(optimizer, cfg, pspecs)),
    )(params)
    return params, opt_state


def _opt_state_specs(optimizer, cfg: TransformerConfig, pspecs):
    """Opt-state PartitionSpecs: any subtree shaped like the param tree
    (adam's mu/nu, etc.) shards like the params; scalars replicate."""
    param_shapes = jax.eval_shape(
        functools.partial(init_params, cfg=cfg), jax.random.key(0))
    param_treedef = jax.tree.structure(param_shapes)
    state_shapes = jax.eval_shape(optimizer.init, param_shapes)

    def walk(st):
        if jax.tree.structure(st) == param_treedef:
            return pspecs
        if isinstance(st, tuple):
            mapped = tuple(walk(s) for s in st)
            return (type(st)(*mapped) if hasattr(st, "_fields")
                    else mapped)
        if isinstance(st, list):
            return [walk(s) for s in st]
        if isinstance(st, dict):
            return {kk: walk(vv) for kk, vv in st.items()}
        return P()

    return walk(state_shapes)
