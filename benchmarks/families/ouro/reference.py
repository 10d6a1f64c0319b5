"""The plain reference of the ``ouro`` family: the block Ouro-2.6B's
``config.json`` describes, run once (``total_ut_steps`` 1): RMSNorm ->
MHA with rotate-half RoPE -> residual -> RMSNorm -> SwiGLU -> residual;
final RMSNorm; tied unembedding. Departures from the published model are
listed in the configuration files under ``assumed``.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision: no kernels, no cache, no batching of requests. It imports
nothing of ``ray_tpu`` and nothing of this family's ``program.py``. What
every family's reference shares (the seed's key, the matmul with its
lower-precision controls, the comparisons) is ``benchmarks/reference.py``,
which drives this file's ``forward`` for serving and for training.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference import HIGHEST, mm, seed_key

LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm",
                "w_gate", "w_up", "w_down")


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    d_model: int
    n_heads: int
    head_dim: int
    d_ff: int
    n_layers: int
    rope_theta: float
    eps: float
    dtype: str


def sizes_of(config: dict) -> Sizes:
    """The sizes a configuration file states, under its published
    (Hugging Face) key names. Refuses what this block cannot express."""
    heads = int(config["num_attention_heads"])
    problems = []
    if int(config["num_key_value_heads"]) != heads:
        problems.append("grouped-query attention")
    if int(config["head_dim"]) * heads != int(config["hidden_size"]):
        problems.append("head_dim * heads != hidden_size")
    if config.get("hidden_act") != "silu":
        problems.append(f"hidden_act {config.get('hidden_act')!r}")
    if config.get("rope_scaling") or config.get("use_sliding_window"):
        problems.append("rope scaling or a sliding window")
    if int(config.get("total_ut_steps", 1)) != 1:
        problems.append("a looped stack (total_ut_steps > 1)")
    if not config.get("tie_word_embeddings", False):
        problems.append("untied embeddings")
    if problems:
        raise ValueError("the reference block cannot express: "
                         + "; ".join(problems))
    return Sizes(
        vocab=int(config["vocab_size"]), d_model=int(config["hidden_size"]),
        n_heads=heads, head_dim=int(config["head_dim"]),
        d_ff=int(config["intermediate_size"]),
        n_layers=int(config["num_hidden_layers"]),
        rope_theta=float(config["rope_theta"]),
        eps=float(config["rms_norm_eps"]),
        dtype=str(config.get("torch_dtype", "bfloat16")))


@functools.partial(jax.jit, static_argnames=("sz",))
def _params(key, sz: Sizes):
    k = jax.random.split(key, 8)
    D, HD, F, L = sz.d_model, sz.n_heads * sz.head_dim, sz.d_ff, sz.n_layers
    dt = jnp.dtype(sz.dtype)

    def w(kk, shape):
        return (0.02 * jax.random.normal(kk, shape, jnp.float32)).astype(dt)

    return {
        "embed": w(k[0], (sz.vocab, D)),
        "layers": {
            "attn_norm": jnp.ones((L, D), dt),
            "wq": w(k[1], (L, D, HD)), "wk": w(k[2], (L, D, HD)),
            "wv": w(k[3], (L, D, HD)), "wo": w(k[4], (L, HD, D)),
            "mlp_norm": jnp.ones((L, D), dt),
            "w_gate": w(k[5], (L, D, F)), "w_up": w(k[6], (L, D, F)),
            "w_down": w(k[7], (L, F, D)),
        },
        "final_norm": jnp.ones((D,), dt),
    }


def seeded_params(seed: int, sz: Sizes):
    """The model's weights from the seed, made on the device in one
    jitted call, in the type they are served and trained in. Layer
    weights are stacked on a leading layer dimension."""
    return _params(seed_key(seed), sz)


# ------------------------------------------------------------------ block

def _rms(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * weight


def _rope(x, theta):
    """x [B, T, H, Dh]; rotate-half convention, positions 0..T-1."""
    T, Dh = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, lp, sz: Sizes, quant):
    B, T, _ = x.shape
    lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
    h = _rms(x, lp["attn_norm"], sz.eps)
    q, k, v = (mm(h, lp[n], quant).reshape(B, T, sz.n_heads, sz.head_dim)
               for n in ("wq", "wk", "wv"))
    q, k = _rope(q, sz.rope_theta), _rope(k, sz.rope_theta)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST)
    s = s / math.sqrt(sz.head_dim)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)
    x = x + mm(o.reshape(B, T, -1), lp["wo"], quant)
    h = _rms(x, lp["mlp_norm"], sz.eps)
    gated = jax.nn.silu(mm(h, lp["w_gate"], quant)) * mm(h, lp["w_up"],
                                                           quant)
    return x + mm(gated, lp["w_down"], quant)


def forward(params, tokens, sz: Sizes, quant=None, remat=False):
    """tokens [B, T] int32 -> logits [B, T, V] float32."""
    layer = functools.partial(_layer, sz=sz, quant=quant)
    if remat:
        layer = jax.checkpoint(layer)
    embed = params["embed"].astype(jnp.float32)
    x, _ = lax.scan(lambda x, lp: (layer(x, lp), None), embed[tokens],
                    params["layers"])
    x = _rms(x, params["final_norm"].astype(jnp.float32), sz.eps)
    return mm(x, embed.T, quant)


# ------------------------------------------------- the leaves compared

def by_leaf(tree):
    """{"embed": leaf, "wq.0": layer 0's slice, ...}: the stacked layer
    leaves split by layer."""
    out = {"embed": tree["embed"], "final_norm": tree["final_norm"]}
    for name in LAYER_LEAVES:
        leaf = tree["layers"][name]
        for i in range(leaf.shape[0]):
            out[f"{name}.{i}"] = leaf[i]
    return out
