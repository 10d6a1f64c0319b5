"""The plain reference of the ``mimo_v2_flash`` family: the block that
MiMo-V2-Flash's ``config.json`` describes, as one chip of an
expert-parallel deployment holds it. Each layer is RMSNorm -> attention
-> residual -> RMSNorm -> feed-forward -> residual; then a final RMSNorm
and an untied head.

* Attention: 64 query heads of q.k width 192 and value width 128 over
  fewer K/V heads (query head i attends K/V head ``i // (64 / G)``), G
  by the layer's kind: full layers (``hybrid_layer_pattern`` 0) attend
  causally with ``num_key_value_heads`` and ``rope_theta``; window
  layers (1) attend ``(t - sliding_window, t]`` with
  ``swa_num_key_value_heads`` and ``swa_rope_theta``, and their heads
  have a learned sink logit in the softmax's denominator, with no value
  row. Rotate-half rope on the first ``int(head_dim *
  partial_rotary_factor)`` dimensions of q and k. v is scaled by
  ``attention_value_scale`` before the product.
* Feed-forward: dense SwiGLU where ``moe_layer_freq`` is 0; else a
  sigmoid router over all ``router_experts``, the ``num_experts_per_tok``
  largest of score + correction bias, weights the chosen scores over
  their sum, and the sum over the chosen experts THIS CHIP HOLDS (the
  contiguous range ``experts_held``) of weight * SwiGLU_e. What the
  absent experts would add is another chip's part and is left out.

Departures and readings of the published config are listed in the
configuration file under ``assumed``.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision: no kernels, no cache, no batching of requests; the experts
are the dense sum over the held ones with a zero weight where a row did
not choose them. Only the memory is minded, because the check runs it
beside the served weights on the chip: a layer is upcast when it runs,
an expert when its turn comes, attention goes a block of queries at a
time and the head a block of the vocabulary at a time. It imports
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

FULL, WINDOW, DENSE, EXPERTS = "full", "window", "dense", "experts"
HEAD_BLOCKS = 8         # the vocabulary in as many blocks
SINK_MEAN = 5.0         # see seeded_params


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    d_model: int
    n_heads: int
    qk_dim: int
    v_dim: int
    rotary_dim: int
    kv_heads: int               # of a full layer
    window_kv_heads: int
    window: int
    rope_theta: float
    window_rope_theta: float
    sink_kinds: Tuple[str, ...]
    value_scale: float
    d_ff: int
    d_expert: int
    router_experts: int         # the router's width: every expert
    experts_first: int          # held here: [first, first + held)
    experts_held: int
    experts_per_token: int
    kinds: Tuple[Tuple[str, str], ...]      # (attention, feed-forward)
    eps: float
    dtype: str

    @property
    def n_layers(self) -> int:
        return len(self.kinds)


def runs_of(kinds) -> Tuple[Tuple[Tuple[str, str], int], ...]:
    """The layers in their order as runs of alike ones: ((kind, how
    many), ...). Each run's weights are one stack."""
    runs = []
    for kind in kinds:
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    return tuple((kind, n) for kind, n in runs)


def sizes_of(config: dict) -> Sizes:
    """The sizes a configuration file states, under its published
    (Hugging Face) key names and the keys that state the chip's share
    (``router_experts``, ``experts_held``). Refuses what this block
    cannot express."""
    problems = []
    if config.get("hidden_act") != "silu":
        problems.append(f"hidden_act {config.get('hidden_act')!r}")
    if config.get("scoring_func") != "sigmoid" or config.get(
            "topk_method") != "noaux_tc" or not config.get("norm_topk_prob"):
        problems.append("a router other than sigmoid scores, noaux_tc "
                        "choice and normalised weights")
    if int(config.get("n_group") or 1) != 1 or int(
            config.get("topk_group") or 1) != 1:
        problems.append("group-limited routing")
    if config.get("n_shared_experts"):
        problems.append("shared experts")
    if config.get("routed_scaling_factor") not in (None, 1, 1.0):
        problems.append("a routed scaling factor")
    if config.get("attention_bias") or config.get("tie_word_embeddings"):
        problems.append("attention bias or tied embeddings")
    if config.get("rope_scaling"):
        problems.append("rope scaling")
    for full, window in (("head_dim", "swa_head_dim"),
                         ("v_head_dim", "swa_v_head_dim"),
                         ("num_attention_heads", "swa_num_attention_heads")):
        if int(config[full]) != int(config[window]):
            problems.append(f"{window} != {full}")
    if int(config["sliding_window"]) != int(config["sliding_window_size"]):
        problems.append("two different windows")
    layers = int(config["num_hidden_layers"])
    pattern, freq = config["hybrid_layer_pattern"], config["moe_layer_freq"]
    if len(pattern) != layers or len(freq) != layers:
        problems.append("layer lists that do not name every layer")
    first, held = (int(n) for n in config["experts_held"])
    if held != int(config["n_routed_experts"]) or first < 0 or (
            first + held > int(config["router_experts"])):
        problems.append("experts_held is not n_routed_experts experts "
                        "inside the router's width")
    if problems:
        raise ValueError("the reference block cannot express: "
                         + "; ".join(problems))
    head_dim = int(config["head_dim"])
    return Sizes(
        vocab=int(config["vocab_size"]), d_model=int(config["hidden_size"]),
        n_heads=int(config["num_attention_heads"]), qk_dim=head_dim,
        v_dim=int(config["v_head_dim"]),
        rotary_dim=int(head_dim * float(config["partial_rotary_factor"])),
        kv_heads=int(config["num_key_value_heads"]),
        window_kv_heads=int(config["swa_num_key_value_heads"]),
        window=int(config["sliding_window"]),
        rope_theta=float(config["rope_theta"]),
        window_rope_theta=float(config["swa_rope_theta"]),
        sink_kinds=tuple(
            kind for kind, key in ((FULL, "add_full_attention_sink_bias"),
                                   (WINDOW, "add_swa_attention_sink_bias"))
            if config.get(key)),
        value_scale=float(config["attention_value_scale"]),
        d_ff=int(config["intermediate_size"]),
        d_expert=int(config["moe_intermediate_size"]),
        router_experts=int(config["router_experts"]),
        experts_first=first, experts_held=held,
        experts_per_token=int(config["num_experts_per_tok"]),
        kinds=tuple((WINDOW if w else FULL, EXPERTS if e else DENSE)
                    for w, e in zip(pattern, freq)),
        eps=float(config["layernorm_epsilon"]),
        dtype=str(config.get("torch_dtype", "bfloat16")))


# ------------------------------------------------------------- weights

def _normal(key, shape, dtype, mean=0.0, std=0.02):
    """A leaf drawn a slice of its leading dimension at a time, so that
    the float32 draw of a 2 GB leaf is never whole beside the weights
    (a [rows, columns] matrix goes in 8 blocks of rows)."""
    def draw(k, part):
        return (mean + std * jax.random.normal(k, part, jnp.float32)
                ).astype(dtype)

    if len(shape) < 2 or (len(shape) == 2 and shape[0] % 8):
        return draw(key, shape)
    if len(shape) == 2:
        blocks = _normal(key, (8, shape[0] // 8, shape[1]), dtype, mean, std)
        return blocks.reshape(shape)
    return lax.map(lambda k: draw(k, shape[1:]),
                   jax.random.split(key, shape[0]))


def _run_params(key, sz: Sizes, kind, n: int):
    attention, ffn = kind
    k = jax.random.split(key, 10)
    D, H, dt = sz.d_model, sz.n_heads, jnp.dtype(sz.dtype)
    G = sz.window_kv_heads if attention == WINDOW else sz.kv_heads
    w = functools.partial(_normal, dtype=dt)
    run = {
        "attn_norm": jnp.ones((n, D), dt),
        "wq": w(k[0], (n, D, H * sz.qk_dim)),
        "wk": w(k[1], (n, D, G * sz.qk_dim)),
        "wv": w(k[2], (n, D, G * sz.v_dim)),
        "wo": w(k[3], (n, H * sz.v_dim, D)),
        "mlp_norm": jnp.ones((n, D), dt),
    }
    if attention in sz.sink_kinds:
        run["sink"] = _normal(k[7], (n, H), jnp.float32, SINK_MEAN, 1.0)
    if ffn == EXPERTS:
        E, F = sz.experts_held, sz.d_expert
        run.update(
            router=w(k[8], (n, D, sz.router_experts)),
            router_bias=_normal(k[9], (n, sz.router_experts), jnp.float32),
            w_gate=w(k[4], (n, E, D, F)), w_up=w(k[5], (n, E, D, F)),
            w_down=w(k[6], (n, E, F, D)))
    else:
        F = sz.d_ff
        run.update(w_gate=w(k[4], (n, D, F)), w_up=w(k[5], (n, D, F)),
                   w_down=w(k[6], (n, F, D)))
    return run


@functools.partial(jax.jit, static_argnames=("sz",))
def _params(key, sz: Sizes):
    dt = jnp.dtype(sz.dtype)
    return {
        "embed": _normal(jax.random.fold_in(key, 0),
                         (sz.vocab, sz.d_model), dt),
        "head": _normal(jax.random.fold_in(key, 1),
                        (sz.d_model, sz.vocab), dt),
        "layers": tuple(
            _run_params(jax.random.fold_in(key, 2 + r), sz, kind, n)
            for r, (kind, n) in enumerate(runs_of(sz.kinds))),
        "final_norm": jnp.ones((sz.d_model,), dt),
    }


def seeded_params(seed: int, sz: Sizes):
    """The chip's share of the model's weights from the seed, made on
    the device in one jitted call, in the type they are served in: the
    embedding, the untied head, and a tuple of stacks of layer weights,
    one for each run of alike layers, of an expert layer the held
    experts alone. normal(0, 0.02) for every matrix and the router's
    correction bias; ones for the norm scales; the sinks normal(5, 1):
    a window's 128 scores of spread 1.6 sum to some exp(6) in the
    softmax's denominator, so a sink near 5 takes a fifth to a third of
    it, and one near 0 would be a rounding error that no comparison
    could hold the program to."""
    return _params(seed_key(seed), sz)


# ------------------------------------------------------------------ block

def _rms(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * weight


def _rope(x, theta, rotary):
    """x [B, T, H, Dh]; rotate-half on the first ``rotary`` dimensions,
    positions 0..T-1; the rest pass through."""
    T = x.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, rotary, 2, dtype=jnp.float32)
                           / rotary))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x[..., :rotary], 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rotary:]], -1)


def _query_block(T: int) -> int:
    return next((b for b in (256, 128) if T > b and T % b == 0), T)


def _attention(q, k, v, window, sink):
    """q [B, T, H, Dh], k [B, T, G, Dh], v [B, T, G, Dv] -> [B, T, H, Dv]:
    causal softmax attention, each query head on K/V head ``h // (H/G)``,
    ``window`` (or None) positions back, ``sink`` [H] (or None) one more
    logit a head in the denominator. A block of queries at a time."""
    B, T, H, Dh = q.shape
    G, R = k.shape[2], H // k.shape[2]
    block = _query_block(T)
    blocks = q.reshape(B, T // block, block, G, R, Dh).swapaxes(0, 1)
    kpos = jnp.arange(T)

    def attend(args):
        qb, first = args                        # [B, block, G, R, Dh]
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qb, k, precision=HIGHEST)
        s = s / math.sqrt(Dh)
        qpos = first + jnp.arange(block)
        keep = kpos[None, :] <= qpos[:, None]
        if window is not None:
            keep &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(keep, s, -jnp.inf)
        if sink is not None:
            column = jnp.broadcast_to(
                sink.reshape(1, G, R, 1, 1), (B, G, R, block, 1))
            s = jnp.concatenate([s, column], axis=-1)
        p = jax.nn.softmax(s, axis=-1)[..., :T]
        return jnp.einsum("bgrqk,bkgd->bqgrd", p, v, precision=HIGHEST)

    out = lax.map(attend, (blocks, jnp.arange(0, T, block)))
    return out.swapaxes(0, 1).reshape(B, T, H, v.shape[-1])


def _route(h, lp, sz: Sizes, quant):
    """[B, T, N] float32: each row's weight on every expert, zero on the
    ones it did not choose."""
    scores = jax.nn.sigmoid(mm(h, lp["router"].astype(jnp.float32), quant))
    _, chosen = lax.top_k(scores + lp["router_bias"], sz.experts_per_token)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(chosen, sz.router_experts)
                   * weights[..., None], axis=-2)


def _held_experts(h, lp, sz: Sizes, quant):
    """The held experts' part of the layer: sum over e held of
    weight[.., e] * SwiGLU_e(h), one expert at a time."""
    weights = _route(h, lp, sz, quant)[
        ..., sz.experts_first:sz.experts_first + sz.experts_held]

    def add(total, expert):
        gate, up, down, weight = expert
        gate, up, down = (m.astype(jnp.float32) for m in (gate, up, down))
        out = mm(jax.nn.silu(mm(h, gate, quant)) * mm(h, up, quant),
                 down, quant)
        return total + weight[..., None] * out, None

    return lax.scan(add, jnp.zeros_like(h), (
        lp["w_gate"], lp["w_up"], lp["w_down"],
        jnp.moveaxis(weights, -1, 0)))[0]


def _layer(x, lp, sz: Sizes, kind, quant):
    B, T, _ = x.shape
    attention, ffn = kind
    experts = {n: lp[n] for n in ("w_gate", "w_up", "w_down")}
    lp = {n: a.astype(jnp.float32) for n, a in lp.items()
          if ffn == DENSE or n not in experts}
    window = sz.window if attention == WINDOW else None
    theta = sz.window_rope_theta if attention == WINDOW else sz.rope_theta
    G = sz.window_kv_heads if attention == WINDOW else sz.kv_heads

    h = _rms(x, lp["attn_norm"], sz.eps)
    q = mm(h, lp["wq"], quant).reshape(B, T, sz.n_heads, sz.qk_dim)
    k = mm(h, lp["wk"], quant).reshape(B, T, G, sz.qk_dim)
    v = mm(h, lp["wv"], quant).reshape(B, T, G, sz.v_dim) * sz.value_scale
    o = _attention(_rope(q, theta, sz.rotary_dim),
                   _rope(k, theta, sz.rotary_dim), v, window, lp.get("sink"))
    x = x + mm(o.reshape(B, T, -1), lp["wo"], quant)

    h = _rms(x, lp["mlp_norm"], sz.eps)
    if ffn == EXPERTS:
        return x + _held_experts(h, dict(lp, **experts), sz, quant)
    gated = jax.nn.silu(mm(h, lp["w_gate"], quant)) * mm(h, lp["w_up"],
                                                           quant)
    return x + mm(gated, lp["w_down"], quant)


def forward(params, tokens, sz: Sizes, quant=None, remat=False):
    """tokens [B, T] int32 -> logits [B, T, V] float32."""
    x = params["embed"][tokens].astype(jnp.float32)
    for (kind, _), stack in zip(runs_of(sz.kinds), params["layers"]):
        layer = functools.partial(_layer, sz=sz, kind=kind, quant=quant)
        if remat:
            layer = jax.checkpoint(layer)
        x, _ = lax.scan(lambda x, lp: (layer(x, lp), None), x, stack)
    x = _rms(x, params["final_norm"].astype(jnp.float32), sz.eps)
    edges = [sz.vocab * i // HEAD_BLOCKS for i in range(HEAD_BLOCKS + 1)]
    return jnp.concatenate(
        [mm(x, params["head"][:, a:b].astype(jnp.float32), quant)
         for a, b in zip(edges, edges[1:])], axis=-1)


# ------------------------------------------------- the leaves compared

def by_leaf(tree):
    """{"embed": leaf, "wq.0": layer 0's slice, ...}: the stacked layer
    leaves split by layer, numbered in the layers' published order."""
    out = {name: tree[name] for name in ("embed", "head", "final_norm")}
    layer = 0
    for stack in tree["layers"]:
        n = stack["attn_norm"].shape[0]
        for name, leaf in stack.items():
            for i in range(n):
                out[f"{name}.{layer + i}"] = leaf[i]
        layer += n
    return out
