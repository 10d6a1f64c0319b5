"""The plain reference of the ``phi4flash`` family: the decoder-hybrid-
decoder that Phi-4-mini-flash-reasoning's ``config.json`` and the
family's published modeling code describe (SambaY, arXiv:2507.06607).
Every layer is LayerNorm -> mixer -> residual -> LayerNorm -> dense
SwiGLU -> residual (LayerNorm with scale and bias, eps
``layer_norm_eps``); then a final LayerNorm and the logits by the
embedding, tied. Nothing positional anywhere: the Mamba layers carry
the order. With L = ``num_hidden_layers``, layer i's mixer is

* even i, i <= L / 2 (the self-decoder and the layer behind it): Mamba-1
  with C = 2 x hidden channels, a state of N a channel, dt rank R, a
  convolution of K positions::

      u, z   = split(h @ W_in)                              # no bias
      u      = silu(causal_depthwise_conv(u, w_conv) + b_conv)
      dt,B,C = split(u @ W_x)                               # no bias
      dt     = softplus(dt @ W_dt + b_dt)
      A      = -exp(A_log)
      s_t    = exp(dt_t (x) A) * s_{t-1} + (dt_t * u_t) (x) B_t    # s_0 = 0
      y_t    = s_t . C_t + D * u_t
      out    = (y * silu(z)) @ W_out                        # no bias

  The last of them, layer L / 2, also hands on y, before the gate: the
  *memory* m;
* odd i < L / 2: differential attention over a window (position t
  attends (t - ``sliding_window``, t]); i = L / 2 + 1: the same, full
  and causal. q has ``num_attention_heads`` heads of hidden / heads, k
  and v ``num_key_value_heads``, projections with bias. Query pair p is
  (q_2p, q_2p+1); it reads K/V pair g = p // (query pairs a K/V pair):
  keys k_2g and k_2g+1, value V_g = [v_2g; v_2g+1]::

      A1  = softmax(q_2p k_2g^T / sqrt(Dh)),  A2 = softmax(q_2p+1 k_2g+1^T / sqrt(Dh))
      o_p = RMSNorm((A1 - lambda A2) V_g; gamma) * (1 - lambda_init)
      lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
      lambda_init(i) = 0.8 - 0.6 exp(-0.3 i)

  and the pairs' outputs side by side through W_o (with bias);
* even i > L / 2: a gated memory unit, ``out = (silu(h @ W_in) * m) @
  W_out``, no bias, no state, no scan;
* odd i > L / 2 + 1: cross differential attention: ``q = h @ W_q + b``
  and nothing else projected; K and V are layer L / 2 + 1's, at
  positions <= t; lambda's vectors, gamma and W_o of its own.

What the row has no key for is listed in the configuration file under
``assumed``; the Mamba sizes among it are read from its
``assumed_sizes``. One departure is of layout alone: the state,
``A_log`` and the convolution's weights lie with the channels last, as
the program holds them; the feed-forward's one input matrix is held as
its two halves. No equation changes.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision: no kernels, no cache, no batching of requests; every layer
runs at every position; the scan is a plain ``lax.scan`` over
positions. Only the memory is minded, because the served weights stay
alive beside it: a layer is upcast at a time, attention runs a block
of queries at a time, the head in blocks of the vocabulary. It imports
nothing of ``ray_tpu`` and nothing of this family's ``program.py``.
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

MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"
HEAD_BLOCKS = 16        # the vocabulary in as many blocks
DT_FLOOR, DT_CEILING = 1e-3, 1e-1       # see seeded_params
LAMBDA_STD = 0.1


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    d_model: int
    n_heads: int
    head_dim: int
    kv_heads: int
    d_ff: int
    window: int
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


def mixers_of(layers: int, every: int) -> Tuple[str, ...]:
    """Each layer's mixer: the rule of the family's published code."""
    half = layers // 2

    def mixer(i):
        if i % every == 0:
            return MAMBA if i <= half else GMU
        return WINDOW if i < half else FULL if i == half + 1 else CROSS

    return tuple(mixer(i) for i in range(layers))


def runs_of(mixers) -> Tuple[Tuple[Tuple[str, ...], int], ...]:
    """The layers in their order as runs of alike pairs of layers:
    ((the pair's mixers, how many), ...). Each run's weights are a pair
    of stacks."""
    runs = []
    for at in range(0, len(mixers), 2):
        pair = tuple(mixers[at:at + 2])
        if runs and runs[-1][0] == pair:
            runs[-1][1] += 1
        else:
            runs.append([pair, 1])
    return tuple((pair, n) for pair, n in runs)


def sizes_of(config: dict) -> Sizes:
    """The sizes a configuration file states, under its published
    (Hugging Face) key names. Refuses what this block cannot express."""
    problems = []
    if config.get("hidden_act") != "silu":
        problems.append(f"hidden_act {config.get('hidden_act')!r}")
    if config.get("mlp_bias") or config.get("lm_head_bias"):
        problems.append("a biased feed-forward or head")
    if not config.get("tie_word_embeddings"):
        problems.append("untied embeddings")
    if int(config.get("mb_per_layer", 2)) != 2:
        problems.append("Mamba-class layers other than every second")
    if config.get("embd_pdrop") or config.get("resid_pdrop"):
        problems.append("dropout")
    hidden, heads = int(config["hidden_size"]), int(
        config["num_attention_heads"])
    kv, layers = int(config["num_key_value_heads"]), int(
        config["num_hidden_layers"])
    if hidden % heads or heads % kv or heads % 2 or kv % 2:
        problems.append("heads that do not divide the hidden size, K/V "
                        "heads that do not divide the heads, or heads "
                        "that do not pair")
    if layers % 2 or layers < 8 or (layers // 2) % 2:
        problems.append("fewer than 8 layers, or a half of them that is "
                        "not a whole number of (Mamba, attention) pairs")
    if not config.get("sliding_window"):
        problems.append("no sliding window")
    if problems:
        raise ValueError("the reference block cannot express: "
                         + "; ".join(problems))
    assumed = config["assumed_sizes"]
    return Sizes(
        vocab=int(config["vocab_size"]), d_model=hidden, n_heads=heads,
        head_dim=hidden // heads, kv_heads=kv,
        d_ff=int(config["intermediate_size"]),
        window=int(config["sliding_window"]),
        mixers=mixers_of(layers, int(config["mb_per_layer"])),
        ssm_inner=int(assumed["mamba_expand"]) * hidden,
        ssm_state=int(assumed["mamba_d_state"]),
        ssm_dt_rank=int(assumed["mamba_dt_rank"]),
        ssm_conv=int(assumed["mamba_d_conv"]),
        eps=float(config["layer_norm_eps"]),
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


def _layer_params(key, sz: Sizes, mixer: str, n: int):
    """``n`` stacked layers of one mixer."""
    k = jax.random.split(key, 20)
    D, F, dt = sz.d_model, sz.d_ff, jnp.dtype(sz.dtype)
    w = functools.partial(_normal, dtype=dt)
    if mixer == MAMBA:
        C, N, R, K = (sz.ssm_inner, sz.ssm_state, sz.ssm_dt_rank,
                      sz.ssm_conv)
        start = jnp.exp(jax.random.uniform(k[9], (n, C), jnp.float32) * (
            math.log(DT_CEILING) - math.log(DT_FLOOR)) + math.log(DT_FLOOR))
        run = {
            "w_in": w(k[0], (n, D, 2 * C)),
            "conv_w": w(k[7], (n, K, C)), "conv_b": w(k[8], (n, C)),
            "w_x": w(k[1], (n, C, R + 2 * N)),
            "w_dt": w(k[2], (n, R, C)),
            # softplus's inverse of the step the channel starts at
            "dt_bias": start + jnp.log(-jnp.expm1(-start)),
            "a_log": jnp.broadcast_to(jnp.log(jnp.arange(
                1, N + 1, dtype=jnp.float32))[None, :, None], (n, N, C)),
            "d_skip": jnp.ones((n, C), jnp.float32),
            "w_out": w(k[3], (n, C, D)),
        }
    elif mixer == GMU:
        run = {"w_mem": w(k[0], (n, D, sz.ssm_inner)),
               "w_out": w(k[3], (n, sz.ssm_inner, D))}
    else:
        H, G, Dh = sz.n_heads, sz.kv_heads, sz.head_dim
        run = {"wq": w(k[0], (n, D, H * Dh)), "bq": w(k[10], (n, H * Dh)),
               "wo": w(k[3], (n, H * Dh, D)), "bo": w(k[13], (n, D)),
               "sub_norm": jnp.ones((n, 2 * Dh), dt)}
        if mixer != CROSS:
            run.update(wk=w(k[1], (n, D, G * Dh)), bk=w(k[11], (n, G * Dh)),
                       wv=w(k[2], (n, D, G * Dh)), bv=w(k[12], (n, G * Dh)))
        for name, kk in zip(("lambda_q1", "lambda_k1", "lambda_q2",
                             "lambda_k2"), k[14:18]):
            run[name] = LAMBDA_STD * jax.random.normal(kk, (n, Dh),
                                                       jnp.float32)
    run.update(attn_norm=jnp.ones((n, D), dt), attn_norm_b=w(k[18], (n, D)),
               mlp_norm=jnp.ones((n, D), dt), mlp_norm_b=w(k[19], (n, D)),
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
            tuple(_layer_params(
                jax.random.fold_in(jax.random.fold_in(key, 2 + r), j), sz,
                mixer, n) for j, mixer in enumerate(pair))
            for r, (pair, n) in enumerate(runs_of(sz.mixers))),
        "final_norm": jnp.ones((sz.d_model,), dt),
        "final_norm_b": _normal(jax.random.fold_in(key, 1), (sz.d_model,),
                                dt),
    }


def seeded_params(seed: int, sz: Sizes):
    """The model's weights from the seed, made on the device in one
    jitted call, in the type they are served in: the embedding (which
    is the head too) and a tuple of runs, each a pair of stacks of layer
    weights, one for each layer of the run's pairs. normal(0, 0.02) for
    every matrix and every bias, a LayerNorm's and the convolution's
    weights among them; ones for the norm scales; lambda's four vectors
    a layer normal(0, 0.1), float32, so that lambda is near lambda_init
    and the second softmax matters. What feeds the recurrence is
    float32 and starts as the family starts it, so that the state
    neither dies at once nor stands still under random weights:
    ``a_log`` log(1..N) down every channel, ``d_skip`` ones, ``dt_bias``
    such that softplus(dt_bias) is log-uniform in [1e-3, 1e-1]."""
    return _params(seed_key(seed), sz)


# ------------------------------------------------------------------ block

def _layer_norm(x, weight, bias, eps):
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(centred * centred, axis=-1, keepdims=True)
    return centred * lax.rsqrt(var + eps) * weight + bias


def _rms(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * weight


def lambda_init(depth):
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(depth, jnp.float32))


def _query_block(T: int) -> int:
    return next((b for b in (256, 128) if T > b and T % b == 0), T)


def _differential(q, k, v, lp, depth, sz: Sizes, window):
    """q [B, T, H, Dh], k and v [B, T, G, Dh] -> the pairs' outputs side
    by side [B, T, H * Dh]. A block of queries at a time."""
    B, T, H, Dh = q.shape
    pairs = k.shape[2] // 2             # K/V pairs
    each = H // 2 // pairs              # query pairs on one K/V pair
    block = _query_block(T)
    q = q.reshape(B, T // block, block, pairs, each, 2, Dh).swapaxes(0, 1)
    k = k.reshape(B, T, pairs, 2, Dh)
    v = v.reshape(B, T, pairs, 2 * Dh)  # the pair's values side by side
    kpos = jnp.arange(T)
    start = lambda_init(depth)
    lam = (jnp.exp(jnp.sum(lp["lambda_q1"] * lp["lambda_k1"]))
           - jnp.exp(jnp.sum(lp["lambda_q2"] * lp["lambda_k2"])) + start)

    def attend(args):
        qb, first = args                # [B, block, pairs, each, 2, Dh]
        s = jnp.einsum("bqgrhd,bkghd->bgrhqk", qb, k, precision=HIGHEST)
        s = s / math.sqrt(Dh)
        qpos = (first + jnp.arange(block))[:, None]
        keep = kpos[None, :] <= qpos
        if window is not None:
            keep &= kpos[None, :] > qpos - window
        a = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bgrhqk,bkgc->bqgrhc", a, v, precision=HIGHEST)
        out = _rms(o[..., 0, :] - lam * o[..., 1, :], lp["sub_norm"], sz.eps)
        return out * (1.0 - start)      # [B, block, pairs, each, 2 Dh]

    out = lax.map(attend, (q, jnp.arange(0, T, block)))
    return out.swapaxes(0, 1).reshape(B, T, H * Dh)


def _mamba(h, lp, sz: Sizes, quant):
    """The Mamba mixer over h [B, T, D], position by position: (the
    mixer's output, the scan's output before the gate)."""
    B, T, _ = h.shape
    C, N, R, K = sz.ssm_inner, sz.ssm_state, sz.ssm_dt_rank, sz.ssm_conv
    u, z = jnp.split(mm(h, lp["w_in"], quant), 2, axis=-1)
    rows = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    u = jax.nn.silu(lp["conv_b"] + sum(
        rows[:, k:k + T] * lp["conv_w"][k] for k in range(K)))
    dt, b, c = jnp.split(mm(u, lp["w_x"], quant), (R, R + N), axis=-1)
    dt = jax.nn.softplus(mm(dt, lp["w_dt"], quant) + lp["dt_bias"])
    A = -jnp.exp(lp["a_log"])                           # [N, C]

    def position(s, at):
        u_t, dt_t, b_t, c_t = at                # [B, C], [B, C], [B, N] x 2
        s = (jnp.exp(dt_t[:, None, :] * A) * s
             + (dt_t * u_t)[:, None, :] * b_t[:, :, None])
        return s, jnp.sum(s * c_t[:, :, None], axis=1) + lp["d_skip"] * u_t

    _, y = lax.scan(position, jnp.zeros((B, N, C), jnp.float32),
                    tuple(t.swapaxes(0, 1) for t in (u, dt, b, c)))
    y = y.swapaxes(0, 1)
    return mm(y * jax.nn.silu(z), lp["w_out"], quant), y


def _layer(carry, lp, depth, sz: Sizes, mixer, quant):
    """One layer over (x, the memory, the full layer's K, its V)."""
    x, memory, lent_k, lent_v = carry
    B, T, _ = x.shape
    lp = {n: a.astype(jnp.float32) for n, a in lp.items()}
    h = _layer_norm(x, lp["attn_norm"], lp["attn_norm_b"], sz.eps)
    if mixer == MAMBA:
        out, memory = _mamba(h, lp, sz, quant)
    elif mixer == GMU:
        out = mm(jax.nn.silu(mm(h, lp["w_mem"], quant)) * memory,
                 lp["w_out"], quant)
    else:
        q = (mm(h, lp["wq"], quant) + lp["bq"]).reshape(
            B, T, sz.n_heads, sz.head_dim)
        if mixer == CROSS:
            k, v = lent_k, lent_v
        else:
            k = (mm(h, lp["wk"], quant) + lp["bk"]).reshape(
                B, T, sz.kv_heads, sz.head_dim)
            v = (mm(h, lp["wv"], quant) + lp["bv"]).reshape(
                B, T, sz.kv_heads, sz.head_dim)
        if mixer == FULL:
            lent_k, lent_v = k, v
        o = _differential(q, k, v, lp, depth, sz,
                          sz.window if mixer == WINDOW else None)
        out = mm(o, lp["wo"], quant) + lp["bo"]
    x = x + out
    h = _layer_norm(x, lp["mlp_norm"], lp["mlp_norm_b"], sz.eps)
    gated = jax.nn.silu(mm(h, lp["w_gate"], quant)) * mm(h, lp["w_up"],
                                                           quant)
    return x + mm(gated, lp["w_down"], quant), memory, lent_k, lent_v


def forward(params, tokens, sz: Sizes, quant=None, remat=False):
    """tokens [B, T] int32 -> logits [B, T, V] float32."""
    x = params["embed"][tokens].astype(jnp.float32)
    B, T, _ = x.shape
    carry = (x, jnp.zeros((B, T, sz.ssm_inner), jnp.float32),
             jnp.zeros((B, T, sz.kv_heads, sz.head_dim), jnp.float32),
             jnp.zeros((B, T, sz.kv_heads, sz.head_dim), jnp.float32))
    depth = 0
    for (pair, n), stacks in zip(runs_of(sz.mixers), params["layers"]):
        layers = [functools.partial(_layer, sz=sz, mixer=mixer, quant=quant)
                  for mixer in pair]
        if remat:
            layers = [jax.checkpoint(layer) for layer in layers]

        def both(carry, at, layers=layers, depth=depth):
            lps, i = at
            for j, (layer, lp) in enumerate(zip(layers, lps)):
                carry = layer(carry, lp, depth + 2 * i + j)
            return carry, None

        carry, _ = lax.scan(both, carry, (stacks, jnp.arange(n)))
        depth += 2 * n
    x = _layer_norm(carry[0], params["final_norm"].astype(jnp.float32),
                    params["final_norm_b"].astype(jnp.float32), sz.eps)
    edges = [sz.vocab * i // HEAD_BLOCKS for i in range(HEAD_BLOCKS + 1)]
    return jnp.concatenate(
        [mm(x, params["embed"][a:b].astype(jnp.float32).T, quant)
         for a, b in zip(edges, edges[1:])], axis=-1)


# ------------------------------------------------- the leaves compared

def by_leaf(tree):
    """{"embed": leaf, "w_in.0": layer 0's slice, ...}: the stacked
    layer leaves split by layer, numbered in the layers' published
    order."""
    out = {name: tree[name]
           for name in ("embed", "final_norm", "final_norm_b")}
    layer = 0
    for pair in tree["layers"]:
        n = pair[0]["attn_norm"].shape[0]
        for j, stack in enumerate(pair):
            for name, leaf in stack.items():
                for i in range(n):
                    out[f"{name}.{layer + 2 * i + j}"] = leaf[i]
        layer += 2 * n
    return out
