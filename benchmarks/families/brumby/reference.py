"""The plain reference of the ``brumby`` family: the block that
Brumby-14B-Base's ``config.json`` and its release describe, a Qwen3-14B
block whose every attention layer is a power-retention layer (Manifest
AI, "Scaling Context Requires Rethinking Attention", arXiv 2507.04239;
degree 2). Every layer is RMSNorm -> retention -> residual -> RMSNorm
-> dense SwiGLU -> residual; then a final RMSNorm and an untied head.

For one sequence and one layer, h_t the normed input, H query heads on
J K/V heads (query head i reads K/V head ``i // (H / J)``) of width d::

    q_t = rope_t(rmsnorm_d(W_q h_t; w_qn))     k_t = rope_t(rmsnorm_d(W_k h_t; w_kn))
    v_t = W_v h_t                              g_t = log sigmoid(w_g . h_t + b_g)   (a K/V head's, <= 0)
    G_t = sum_{s <= t} g_s
    a_ts = exp(G_t - G_s) (q_t . k_s)^2   for s <= t, else 0
    o_t  = sum_s a_ts v_s / sum_s a_ts         (0 where the denominator is 0)
    x <- x + W_o [o_t^1 .. o_t^H]

**This is the attention form**: the [T, T] weights under the causal
mask, no state, no feature map, no chunks. The program computes the
same function through a matrix state a K/V head (its chunked form in a
prefill, its one-token recurrence in a decode step), so the two are
different derivations and the comparison checks the program against
the definition, not against itself. What the published row does not
state (the degree, the gate's form, the q and k norms) is listed in the
configuration file under ``assumed``.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision: no kernels, no cache, no batching of requests. Only the
memory is minded, because the check runs it beside the served weights
on the chip over sequences of some 9,000 positions: a layer is upcast
when it runs, the weights of a block of queries at a time, the
feed-forward a block of positions at a time and the head a block of
the vocabulary at a time. It imports nothing of ``ray_tpu`` and nothing
of this family's ``program.py``.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference import HIGHEST, mm, seed_key

HEAD_BLOCKS = 16        # the vocabulary in as many blocks
# a fresh gate's decay e^g has a half-life log-uniform between these
# many positions (see seeded_params)
HALF_LIFE_FLOOR, HALF_LIFE_CEILING = 64.0, 8192.0
LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
                "w_g", "b_g", "mlp_norm", "w_gate", "w_up", "w_down")


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    d_model: int
    n_layers: int
    n_heads: int
    head_dim: int
    kv_heads: int
    d_ff: int
    rope_theta: float
    eps: float
    dtype: str


def sizes_of(config: dict) -> Sizes:
    """The sizes a configuration file states, under its published
    (Hugging Face) key names. Refuses what this block cannot express."""
    problems = []
    if config.get("hidden_act") != "silu":
        problems.append(f"hidden_act {config.get('hidden_act')!r}")
    if config.get("use_sliding_window") or config.get("sliding_window"):
        problems.append("a sliding window")
    if config.get("rope_scaling"):
        problems.append("rope scaling")
    if config.get("attention_bias"):
        problems.append("biased q, k, v or o projections")
    if config.get("tie_word_embeddings"):
        problems.append("tied embeddings")
    heads, groups = (int(config["num_attention_heads"]),
                     int(config["num_key_value_heads"]))
    if heads % groups or int(config["head_dim"]) % 2:
        problems.append("K/V heads that do not divide the heads, or heads "
                        "of odd width")
    if problems:
        raise ValueError("the reference block cannot express: "
                         + "; ".join(problems))
    return Sizes(
        vocab=int(config["vocab_size"]), d_model=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]), n_heads=heads,
        head_dim=int(config["head_dim"]), kv_heads=groups,
        d_ff=int(config["intermediate_size"]),
        rope_theta=float(config["rope_theta"]),
        eps=float(config["rms_norm_eps"]),
        dtype=str(config.get("torch_dtype", "bfloat16")))


# ------------------------------------------------------------- weights

def _normal(key, shape, dtype, std=0.02):
    """A leaf drawn a slice of its leading dimension at a time, so that
    the float32 draw of a large leaf is never whole beside the weights
    (a [rows, columns] matrix goes in 8 blocks of rows)."""
    def draw(k, part):
        return (std * jax.random.normal(k, part, jnp.float32)).astype(dtype)

    if len(shape) < 2 or (len(shape) == 2 and shape[0] % 8):
        return draw(key, shape)
    if len(shape) == 2:
        return _normal(key, (8, shape[0] // 8, shape[1]), dtype,
                       std).reshape(shape)
    return lax.map(lambda k: draw(k, shape[1:]),
                   jax.random.split(key, shape[0]))


@functools.partial(jax.jit, static_argnames=("sz",))
def _params(key, sz: Sizes):
    dt = jnp.dtype(sz.dtype)
    w = functools.partial(_normal, dtype=dt)
    n, D, F = sz.n_layers, sz.d_model, sz.d_ff
    H, G, Dh = sz.n_heads, sz.kv_heads, sz.head_dim
    k = jax.random.split(jax.random.fold_in(key, 2), 10)
    half = jnp.exp(jax.random.uniform(k[8], (n, G), jnp.float32) * (
        math.log(HALF_LIFE_CEILING) - math.log(HALF_LIFE_FLOOR))
        + math.log(HALF_LIFE_FLOOR))
    decay = jnp.exp2(-1.0 / half)
    stack = {
        "attn_norm": jnp.ones((n, D), dt), "mlp_norm": jnp.ones((n, D), dt),
        "wq": w(k[0], (n, D, H * Dh)), "wk": w(k[1], (n, D, G * Dh)),
        "wv": w(k[2], (n, D, G * Dh)), "wo": w(k[3], (n, H * Dh, D)),
        "q_norm": jnp.ones((n, Dh), dt), "k_norm": jnp.ones((n, Dh), dt),
        "w_g": w(k[7], (n, D, G)),
        # sigmoid's inverse of the decay the head starts at
        "b_g": jnp.log(decay) - jnp.log1p(-decay),
        "w_gate": w(k[4], (n, D, F)), "w_up": w(k[5], (n, D, F)),
        "w_down": w(k[6], (n, F, D)),
    }
    return {
        "embed": _normal(jax.random.fold_in(key, 0), (sz.vocab, D), dt),
        "layers": (stack,),
        "final_norm": jnp.ones((D,), dt),
        "head": _normal(jax.random.fold_in(key, 1), (D, sz.vocab), dt),
    }


def seeded_params(seed: int, sz: Sizes):
    """The model's weights from the seed, made on the device in one
    jitted call, in the type they are served in: the embedding, the
    untied head and one stack of layer weights (a tuple of one: the
    program's layout for a model with ``layer_kinds``). normal(0, 0.02)
    for every matrix, the gate's projection among them; ones for the
    norm scales, the q and k norms' among them. **The gate's bias**
    (float32) is drawn so that the per-step decay sigmoid(b_g) has a
    half-life log-uniform between 64 and 8,192 positions: under random
    weights the projection adds next to nothing, and a bias of 0 would
    halve the state at every token."""
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


def _block_of(T: int) -> int:
    """Positions in a block: 256, 128 or 64 where that divides a longer
    T, else all of T."""
    return next((b for b in (256, 128, 64) if T > b and T % b == 0), T)


def _retention(q, k, v, g):
    """The attention form. q [B, T, H, Dh], k and v [B, T, J, Dh], g
    [B, T, J] (log decay) -> [B, T, H, Dh]: each query head on K/V head
    ``h // (H / J)``, the weights ``exp(G_t - G_s) (q_t . k_s)^2`` under
    the causal mask, normalised by their sum. A block of queries at a
    time."""
    B, T, H, Dh = q.shape
    J, R = k.shape[2], H // k.shape[2]
    G = jnp.cumsum(g, axis=1).transpose(0, 2, 1)            # [B, J, T]
    block = _block_of(T)
    blocks = q.reshape(B, T // block, block, J, R, Dh).swapaxes(0, 1)
    kpos = jnp.arange(T)

    def attend(args):
        qb, first = args                        # [B, block, J, R, Dh]
        at = first + jnp.arange(block)
        s = jnp.einsum("bqjrd,bkjd->bjrqk", qb, k, precision=HIGHEST)
        keep = kpos[None, :] <= at[:, None]                 # [block, T]
        fall = lax.dynamic_slice_in_dim(G, first, block, axis=2)[
            ..., :, None] - G[..., None, :]                 # [B, J, block, T]
        decay = jnp.where(keep, jnp.exp(jnp.where(keep, fall, 0.0)), 0.0)
        a = s * s * decay[:, :, None]
        num = jnp.einsum("bjrqk,bkjd->bqjrd", a, v, precision=HIGHEST)
        den = jnp.sum(a, axis=-1).transpose(0, 3, 1, 2)[..., None]
        return jnp.where(den > 0, num / jnp.where(den > 0, den, 1.0), 0.0)

    out = lax.map(attend, (blocks, jnp.arange(0, T, block)))
    return out.swapaxes(0, 1).reshape(B, T, H, Dh)


def _layer(x, lp, sz: Sizes, quant):
    B, T, _ = x.shape
    lp = {n: a.astype(jnp.float32) for n, a in lp.items()}
    h = _rms(x, lp["attn_norm"], sz.eps)
    q = mm(h, lp["wq"], quant).reshape(B, T, sz.n_heads, sz.head_dim)
    k = mm(h, lp["wk"], quant).reshape(B, T, sz.kv_heads, sz.head_dim)
    v = mm(h, lp["wv"], quant).reshape(B, T, sz.kv_heads, sz.head_dim)
    q = _rope(_rms(q, lp["q_norm"], sz.eps), sz.rope_theta)
    k = _rope(_rms(k, lp["k_norm"], sz.eps), sz.rope_theta)
    g = jax.nn.log_sigmoid(mm(h, lp["w_g"], quant) + lp["b_g"])
    x = x + mm(_retention(q, k, v, g).reshape(B, T, -1), lp["wo"], quant)
    h = _rms(x, lp["mlp_norm"], sz.eps)

    def ffn(hb):                                # [B, block, D]
        gated = jax.nn.silu(mm(hb, lp["w_gate"], quant)) * mm(
            hb, lp["w_up"], quant)
        return mm(gated, lp["w_down"], quant)

    block = _block_of(T) if T > 1024 else T
    d = lax.map(ffn, h.reshape(B, T // block, block, -1).swapaxes(0, 1))
    return x + d.swapaxes(0, 1).reshape(B, T, -1)


def forward(params, tokens, sz: Sizes, quant=None, remat=False):
    """tokens [B, T] int32 -> logits [B, T, V] float32."""
    layer = functools.partial(_layer, sz=sz, quant=quant)
    if remat:
        layer = jax.checkpoint(layer)
    x = params["embed"][tokens].astype(jnp.float32)
    for stack in params["layers"]:
        x, _ = lax.scan(lambda x, lp: (layer(x, lp), None), x, stack)
    x = _rms(x, params["final_norm"].astype(jnp.float32), sz.eps)
    # a block of the vocabulary at a time, each written where it belongs
    # in the one [B, T, V] result: concatenated, the blocks and their
    # copy (5.4 GB each at 9,000 positions) do not fit beside the weights
    edges = [sz.vocab * i // HEAD_BLOCKS for i in range(HEAD_BLOCKS + 1)]
    logits = jnp.zeros(x.shape[:2] + (sz.vocab,), jnp.float32)
    for a, b in zip(edges, edges[1:]):
        logits = lax.dynamic_update_slice_in_dim(
            logits, mm(x, params["head"][:, a:b].astype(jnp.float32), quant),
            a, axis=2)
    return logits


# ------------------------------------------------- the leaves compared

def by_leaf(tree):
    """{"embed": leaf, "head": leaf, "wq.0": layer 0's slice, ...}: the
    stacked layer leaves split by layer."""
    out = {name: tree[name] for name in ("embed", "final_norm", "head")}
    layer = 0
    for stack in tree["layers"]:
        n = stack["attn_norm"].shape[0]
        for name, leaf in stack.items():
            for i in range(n):
                out[f"{name}.{layer + i}"] = leaf[i]
        layer += n
    return out
