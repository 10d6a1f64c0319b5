"""The plain reference of the ``jamba`` family: the block that
AI21-Jamba2-3B's ``config.json`` and the family's published modeling
code describe. Every layer is RMSNorm -> mixer -> residual -> RMSNorm ->
dense SwiGLU -> residual (``num_experts`` 1: no layer is an expert
layer); then a final RMSNorm and the logits by the embedding, tied.
Layer i's mixer is attention where ``i % attn_layer_period ==
attn_layer_offset``, else a Mamba (Mamba-1) mixer.

* Attention: ``num_attention_heads`` query heads of ``hidden_size /
  heads`` over ``num_key_value_heads`` K/V heads (query head i attends
  K/V head ``i // (H / G)``), no biases, causal softmax at scale
  ``head_dim ** -0.5``, and **no rope or any other positional term**:
  the Mamba layers carry the order.
* Mamba, with C = ``mamba_expand * hidden_size`` channels, a state of
  N = ``mamba_d_state`` a channel, R = ``mamba_dt_rank``, K =
  ``mamba_d_conv``::

      u, z   = split(h @ W_in)                              # no bias
      u      = silu(causal_depthwise_conv(u, w_conv) + b_conv)
      dt,B,C = split(u @ W_x)                               # no bias
      dt, B, C = rmsnorm(dt), rmsnorm(B), rmsnorm(C)        # each its own
      dt     = softplus(dt @ W_dt + b_dt)
      A      = -exp(A_log)
      s_t    = exp(dt_t (x) A) * s_{t-1} + (dt_t * u_t) (x) B_t    # s_0 = 0
      y_t    = s_t . C_t + D * u_t
      out    = (y * silu(z)) @ W_out                        # no bias

Departures and readings of the published config are listed in the
configuration file under ``assumed``. One is of layout alone: the
state, ``A_log`` and the convolution's weights lie with the channels
last (``[N, C]``, ``[K, C]``), as the program holds them, where the
published code has the channels first; no equation changes.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision: no kernels, no cache, no batching of requests; the scan is a
plain ``lax.scan`` over positions. Only the memory is minded, because
the check runs it beside the served weights on the chip: a layer is
upcast when it runs, attention goes a block of queries at a time and
the head a block of the vocabulary at a time. It imports nothing of
``ray_tpu`` and nothing of this family's ``program.py``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference import HIGHEST, mm, seed_key

MAMBA, ATTENTION = "mamba", "full"      # a layer's mixer
HEAD_BLOCKS = 8         # the vocabulary in as many blocks
DT_FLOOR, DT_CEILING = 1e-3, 1e-1       # see seeded_params


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    d_model: int
    n_heads: int
    head_dim: int
    kv_heads: int
    d_ff: int
    mixers: Tuple[str, ...]             # each layer's, in their order
    ssm_inner: int                      # C
    ssm_state: int                      # N
    ssm_dt_rank: int                    # R
    ssm_conv: int                       # K
    eps: float
    dtype: str

    @property
    def n_layers(self) -> int:
        return len(self.mixers)


def runs_of(mixers) -> Tuple[Tuple[str, int], ...]:
    """The layers in their order as runs of alike ones: ((mixer, how
    many), ...). Each run's weights are one stack."""
    runs = []
    for mixer in mixers:
        if runs and runs[-1][0] == mixer:
            runs[-1][1] += 1
        else:
            runs.append([mixer, 1])
    return tuple((mixer, n) for mixer, n in runs)


def sizes_of(config: dict) -> Sizes:
    """The sizes a configuration file states, under its published
    (Hugging Face) key names. Refuses what this block cannot express."""
    problems = []
    if config.get("hidden_act") != "silu":
        problems.append(f"hidden_act {config.get('hidden_act')!r}")
    if int(config.get("num_experts", 1)) != 1 or int(
            config.get("num_experts_per_tok", 1)) != 1:
        problems.append("expert layers (num_experts > 1)")
    if config.get("mamba_proj_bias") or not config.get("mamba_conv_bias"):
        problems.append("a Mamba mixer with biased projections or an "
                        "unbiased convolution")
    if config.get("sliding_window") is not None:
        problems.append("a sliding window")
    if not config.get("tie_word_embeddings"):
        problems.append("untied embeddings")
    hidden, heads = int(config["hidden_size"]), int(
        config["num_attention_heads"])
    if hidden % heads or heads % int(config["num_key_value_heads"]):
        problems.append("heads that do not divide the hidden size, or K/V "
                        "heads that do not divide the heads")
    period, offset = int(config["attn_layer_period"]), int(
        config["attn_layer_offset"])
    if not 0 <= offset < period:
        problems.append("an attention offset outside its period")
    if problems:
        raise ValueError("the reference block cannot express: "
                         + "; ".join(problems))
    return Sizes(
        vocab=int(config["vocab_size"]), d_model=hidden, n_heads=heads,
        head_dim=hidden // heads,
        kv_heads=int(config["num_key_value_heads"]),
        d_ff=int(config["intermediate_size"]),
        mixers=tuple(ATTENTION if i % period == offset else MAMBA
                     for i in range(int(config["num_hidden_layers"]))),
        ssm_inner=int(config["mamba_expand"]) * hidden,
        ssm_state=int(config["mamba_d_state"]),
        ssm_dt_rank=int(config["mamba_dt_rank"]),
        ssm_conv=int(config["mamba_d_conv"]),
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


def _run_params(key, sz: Sizes, mixer: str, n: int):
    k = jax.random.split(key, 10)
    D, F, dt = sz.d_model, sz.d_ff, jnp.dtype(sz.dtype)
    w = functools.partial(_normal, dtype=dt)
    if mixer == ATTENTION:
        H, G, Dh = sz.n_heads, sz.kv_heads, sz.head_dim
        run = {"wq": w(k[0], (n, D, H * Dh)), "wk": w(k[1], (n, D, G * Dh)),
               "wv": w(k[2], (n, D, G * Dh)), "wo": w(k[3], (n, H * Dh, D))}
    else:
        C, N, R, K = (sz.ssm_inner, sz.ssm_state, sz.ssm_dt_rank,
                      sz.ssm_conv)
        start = jnp.exp(jax.random.uniform(k[9], (n, C), jnp.float32) * (
            math.log(DT_CEILING) - math.log(DT_FLOOR)) + math.log(DT_FLOOR))
        run = {
            "w_in": w(k[0], (n, D, 2 * C)),
            "conv_w": w(k[7], (n, K, C)), "conv_b": w(k[8], (n, C)),
            "w_x": w(k[1], (n, C, R + 2 * N)),
            "dt_norm": jnp.ones((n, R), dt), "b_norm": jnp.ones((n, N), dt),
            "c_norm": jnp.ones((n, N), dt),
            "w_dt": w(k[2], (n, R, C)),
            # softplus's inverse of the step the channel starts at
            "dt_bias": start + jnp.log(-jnp.expm1(-start)),
            "a_log": jnp.broadcast_to(jnp.log(jnp.arange(
                1, N + 1, dtype=jnp.float32))[None, :, None], (n, N, C)),
            "d_skip": jnp.ones((n, C), jnp.float32),
            "w_out": w(k[3], (n, C, D)),
        }
    run.update(attn_norm=jnp.ones((n, D), dt), mlp_norm=jnp.ones((n, D), dt),
               w_gate=w(k[4], (n, D, F)), w_up=w(k[5], (n, D, F)),
               w_down=w(k[6], (n, F, D)))
    return run


@functools.partial(jax.jit, static_argnames=("sz",))
def _params(key, sz: Sizes):
    dt = jnp.dtype(sz.dtype)
    return {
        "embed": _normal(jax.random.fold_in(key, 0),
                         (sz.vocab, sz.d_model), dt),
        "layers": tuple(
            _run_params(jax.random.fold_in(key, 2 + r), sz, mixer, n)
            for r, (mixer, n) in enumerate(runs_of(sz.mixers))),
        "final_norm": jnp.ones((sz.d_model,), dt),
    }


def seeded_params(seed: int, sz: Sizes):
    """The model's weights from the seed, made on the device in one
    jitted call, in the type they are served in: the embedding (which
    is the head too) and a tuple of stacks of layer weights, one for
    each run of alike layers. normal(0, 0.02) for every matrix, the
    convolution's weights and bias among them; ones for the norm
    scales. What feeds the recurrence is float32 and starts as the
    family starts it, so that the state neither dies at once nor
    stands still under random weights: ``a_log`` log(1..N) down every
    channel, ``d_skip`` ones, ``dt_bias`` such that softplus(dt_bias)
    is log-uniform in [1e-3, 1e-1]."""
    return _params(seed_key(seed), sz)


# ------------------------------------------------------------------ block

def _rms(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * weight


def _query_block(T: int) -> int:
    return next((b for b in (256, 128) if T > b and T % b == 0), T)


def _attention(q, k, v):
    """q [B, T, H, Dh], k and v [B, T, G, Dh] -> [B, T, H, Dh]: causal
    softmax attention, each query head on K/V head ``h // (H / G)``,
    nothing positional. A block of queries at a time."""
    B, T, H, Dh = q.shape
    G, R = k.shape[2], H // k.shape[2]
    block = _query_block(T)
    blocks = q.reshape(B, T // block, block, G, R, Dh).swapaxes(0, 1)
    kpos = jnp.arange(T)

    def attend(args):
        qb, first = args                        # [B, block, G, R, Dh]
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qb, k, precision=HIGHEST)
        s = s / math.sqrt(Dh)
        keep = kpos[None, :] <= (first + jnp.arange(block))[:, None]
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return jnp.einsum("bgrqk,bkgd->bqgrd", p, v, precision=HIGHEST)

    out = lax.map(attend, (blocks, jnp.arange(0, T, block)))
    return out.swapaxes(0, 1).reshape(B, T, H, Dh)


def _mamba(h, lp, sz: Sizes, quant):
    """The Mamba mixer over h [B, T, D], position by position."""
    B, T, _ = h.shape
    C, N, R, K = sz.ssm_inner, sz.ssm_state, sz.ssm_dt_rank, sz.ssm_conv
    u, z = jnp.split(mm(h, lp["w_in"], quant), 2, axis=-1)
    rows = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    u = jax.nn.silu(lp["conv_b"] + sum(
        rows[:, k:k + T] * lp["conv_w"][k] for k in range(K)))
    dt, b, c = jnp.split(mm(u, lp["w_x"], quant), (R, R + N), axis=-1)
    dt = _rms(dt, lp["dt_norm"], sz.eps)
    b, c = _rms(b, lp["b_norm"], sz.eps), _rms(c, lp["c_norm"], sz.eps)
    dt = jax.nn.softplus(mm(dt, lp["w_dt"], quant) + lp["dt_bias"])
    A = -jnp.exp(lp["a_log"])                           # [N, C]

    def position(s, at):
        u_t, dt_t, b_t, c_t = at                # [B, C], [B, C], [B, N] x 2
        s = (jnp.exp(dt_t[:, None, :] * A) * s
             + (dt_t * u_t)[:, None, :] * b_t[:, :, None])
        return s, jnp.sum(s * c_t[:, :, None], axis=1) + lp["d_skip"] * u_t

    _, y = lax.scan(position, jnp.zeros((B, N, C), jnp.float32),
                    tuple(t.swapaxes(0, 1) for t in (u, dt, b, c)))
    return mm(y.swapaxes(0, 1) * jax.nn.silu(z), lp["w_out"], quant)


def _layer(x, lp, sz: Sizes, mixer, quant):
    B, T, _ = x.shape
    lp = {n: a.astype(jnp.float32) for n, a in lp.items()}
    h = _rms(x, lp["attn_norm"], sz.eps)
    if mixer == ATTENTION:
        q = mm(h, lp["wq"], quant).reshape(B, T, sz.n_heads, sz.head_dim)
        k = mm(h, lp["wk"], quant).reshape(B, T, sz.kv_heads, sz.head_dim)
        v = mm(h, lp["wv"], quant).reshape(B, T, sz.kv_heads, sz.head_dim)
        x = x + mm(_attention(q, k, v).reshape(B, T, -1), lp["wo"], quant)
    else:
        x = x + _mamba(h, lp, sz, quant)
    h = _rms(x, lp["mlp_norm"], sz.eps)
    gated = jax.nn.silu(mm(h, lp["w_gate"], quant)) * mm(h, lp["w_up"],
                                                           quant)
    return x + mm(gated, lp["w_down"], quant)


def forward(params, tokens, sz: Sizes, quant=None, remat=False):
    """tokens [B, T] int32 -> logits [B, T, V] float32."""
    x = params["embed"][tokens].astype(jnp.float32)
    for (mixer, _), stack in zip(runs_of(sz.mixers), params["layers"]):
        layer = functools.partial(_layer, sz=sz, mixer=mixer, quant=quant)
        if remat:
            layer = jax.checkpoint(layer)
        x, _ = lax.scan(lambda x, lp: (layer(x, lp), None), x, stack)
    x = _rms(x, params["final_norm"].astype(jnp.float32), sz.eps)
    edges = [sz.vocab * i // HEAD_BLOCKS for i in range(HEAD_BLOCKS + 1)]
    return jnp.concatenate(
        [mm(x, params["embed"][a:b].astype(jnp.float32).T, quant)
         for a, b in zip(edges, edges[1:])], axis=-1)


# ------------------------------------------------- the leaves compared

def by_leaf(tree):
    """{"embed": leaf, "w_in.0": layer 0's slice, ...}: the stacked
    layer leaves split by layer, numbered in the layers' published
    order."""
    out = {name: tree[name] for name in ("embed", "final_norm")}
    layer = 0
    for stack in tree["layers"]:
        n = stack["attn_norm"].shape[0]
        for name, leaf in stack.items():
            for i in range(n):
                out[f"{name}.{layer + i}"] = leaf[i]
        layer += n
    return out
