"""Decoder-only transformer over a dp/pp/sp/tp mesh: pre-norm blocks of
a mixer over the sequence and a feed-forward (dense SwiGLU, or a chip's
share of sparse experts), whose layers may be of several kinds in one
model. The mixer is attention (all heads alike, or fewer K/V heads than
query heads; rope on the whole head, on its first dimensions or not at
all; full or windowed, each with its own K/V heads and rope base, with
or without a sink logit), a Mamba layer: a gated selective
state-space recurrence behind a short causal convolution
(ops/ssm.py), which carries the order of the sequence itself, or a
power-retention layer: attention's projections, heads and rope with
``(q.k)^2`` in the place of ``exp(q.k)`` and a learned decay, whose
whole past is a matrix a K/V head (ops/retention.py). Two mixers keep
nothing of their own and read what an earlier layer made inside the
same pass: a gated memory unit, which gates the scan's output (before
its own gate) of the last Mamba layer before it, the *memory*, and a
cross-attention layer, which projects queries alone and attends the
K/V of the last full-attention layer before it. Attention of any kind
may be *differential*: query heads in pairs, two softmaxes over one
doubled value head, the second taken from the first times a learned
scalar, a norm over each pair's output.

**One block.** ``block`` is the only definition of the layer: norms,
projections, feed-forward and residuals. What differs between training,
prefill and decode is handed to it: ``rope`` (how q and k are rotated)
and ``attend`` (what the queries attend, or how a Mamba layer's
convolution and scan run, and the state that comes back); what differs
between layers is in the layer's own weights (a router makes it an
expert layer, ``w_in`` a Mamba layer, ``w_g`` a retention layer,
``w_mem`` a gated memory unit, ``wq`` without ``wk`` a cross layer,
``lambda_q1`` differential attention, ``attn_norm_b`` LayerNorm where
the others have RMSNorm, ``bq`` biased projections) and
in the closures its caller
builds for its kind. ``forward`` here and ``slot_prefill`` /
``slot_decode_step`` in models/decode.py each scan it over the stacked
layers; ``unembed`` is their shared final norm and head. A change to
the architecture is a change to ``block``, ``init_params`` and
``param_specs``.

**Layers of several kinds.** Layers of unlike shape cannot share one
scan. ``TransformerConfig.layer_kinds`` gives each layer's kind; the
published order is cut into runs of alike *periods* (``layer_runs``):
of alike layers where the kinds change rarely (a period of one layer,
which is every model of one kind), of alike groups of layers where they
alternate ((Mamba, window) x 8 is one run of eight periods of two, not
sixteen runs of one). Each run's weights are stacked on a leading
dimension of their own (``params["layers"]`` is then a tuple of such
stacks, one a run, where a model of one kind keeps the one stack; a run
of periods of several layers holds a tuple of stacks, one for each
layer of the period), and every caller scans run after run, the
period's layers one after the other inside the scan's body. Unrolling
instead would compile one body a layer where this compiles one for
each layer of a run's period.

**What travels beside x.** A model with gated memory units or cross
layers carries two more values from layer to layer inside a pass: the
memory (``[B, T, ssm_inner]``, replaced by every Mamba layer) and the
K/V of the last full-attention layer. ``forward`` carries them through
its runs beside x; models/decode.py's two programs do the same with the
memory, and hand a cross layer the full layer's cache itself.

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
``jax.checkpoint`` (``ParallelConfig.remat``) that keeps a layer's
matrix products and what its flash backward kernels read for the
backward and computes the elementwise rest again (``KEPT``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops import ssm
from ray_tpu.ops.attention import SAVED as FLASH_SAVED, flash_attention
from ray_tpu.ops.retention import retention
from ray_tpu.ops.norms import layernorm, rmsnorm
from ray_tpu.ops.rotary import apply_rotary, rope_frequencies
from ray_tpu.parallel.collectives import (axis_size, shard_map,
                                           tp_allreduce, tp_copy)
from ray_tpu.parallel.experts import expert_ffn, expert_tile, route
from ray_tpu.parallel.pipeline import pipeline_spmd
from ray_tpu.parallel.ring_attention import ring_attention
from ray_tpu.parallel.ulysses import ulysses_attention

from jax.sharding import PartitionSpec as P


# A layer's kind: (mixer, feed-forward). The mixer is one of the two
# kinds of attention, a Mamba layer, a power-retention layer (of
# degree 2: a pair scores (q.k)^2, the feature map of ops/retention.py;
# no other degree is expressed, so the kind has no field for one), a
# gated memory unit or a cross-attention layer.
FULL, WINDOW, MAMBA, RETENTION = "full", "window", "mamba", "retention"
GMU, CROSS = "gmu", "cross"
MIXERS = (FULL, WINDOW, MAMBA, RETENTION, GMU, CROSS)
# the mixers that keep no row a position but a summary that does not
# grow: nothing of theirs is sharded over tp, sp or pp
SUMMARIES = (MAMBA, RETENTION)
# the mixers that keep nothing between tokens and mix nothing over the
# sequence themselves: a gated memory unit reads the memory of the last
# Mamba layer before it (that layer's scan output, before its gate), a
# cross layer the K/V of the last full-attention layer before it
BORROWERS = (GMU, CROSS)
DENSE, EXPERTS = "dense", "experts"
LayerKind = Tuple[str, str]

# The parts of a step, by name: ``block`` and ``unembed`` trace each
# stretch of what they compute inside a ``jax.named_scope`` of one of
# them, the callers' ``attend`` closures inside the attention's, so
# that a compiled program's ``op_name`` metadata says which part an
# instruction computes. ``slot_decode_step`` scopes what it adds around
# the block as well, because its table is read
# (``models.decode.program_parts``); ``forward`` and ``slot_prefill``
# carry the block's and the head's scopes and no more until something
# reads theirs. Metadata alone: XLA fuses as before and a step pays
# nothing. Where scopes nest, the innermost names the operation: a
# decode step's recurrence reads as ``ssm_step``, the mixer around it
# as ``mamba_mixer``.
PARTS = (
    "embed",                # the token's select and the embedding lookup
    "qkv",                  # attention norm, wq / wk / wv, the q and k norms,
                            # rope, value scale, a retention layer's gate
    "full_attention",       # scores, softmax, p.V (or the kernel's call)
    "window_attention",     # ... and the cache's in-place write beside them
    "cross_attention",      # ... of a cross layer, which writes nothing
    "attn_out",             # wo and the residual add
    "mamba_mixer",          # norm, projections, convolution, gate, residual
    "gmu",                  # a gated memory unit: norm, both projections,
                            # the gate on the memory, residual
    "ssm_step",             # a decode step's recurrence, its state read and
                            # written where it lies
    "ssm_scan",             # the recurrence over a whole sequence
    "retention_step",       # a decode step's retention: the state read,
                            # advanced and written where it lies
    "retention_chunk",      # retention over a whole sequence, chunk by chunk
    "router",               # feed-forward norm and the router
    "experts",              # the held experts' products and the residual
    "mlp",                  # the dense feed-forward, its norm and residual
    "head",                 # final norm, unembed and the pick
)
# Around each run's scan in the decode step (``layer_runs``' order) lies
# a scope ``run<i>``. What a run's loop body holds outside every part,
# the scan's slice of each stacked weight and the copy XLA hangs on it
# for a product's layout, is read as this part of that run.
LAYER_WEIGHTS = "layer_weights"


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
    norm_eps: float = 1e-6
    tie_embeddings: bool = True
    # attention: None means "as the field above it says" (all heads
    # alike, head_dim = d_model / n_heads, rope on the whole head)
    n_kv_heads: Optional[int] = None
    qk_head_dim: Optional[int] = None
    v_head_dim: Optional[int] = None
    rotary_dim: Optional[int] = None    # leading dims of a head rotated
    # False: q and k are not rotated and attention has no positional
    # term at all (a model whose Mamba layers carry the order)
    rope: bool = True
    value_scale: float = 1.0            # on v, before the product
    # each layer's (mixer, feed-forward) kind; None: all full, dense
    layer_kinds: Optional[Tuple[LayerKind, ...]] = None
    # window layers: position t attends (t - window, t]
    window: Optional[int] = None
    window_kv_heads: Optional[int] = None
    window_rope_theta: Optional[float] = None
    # attention kinds whose heads have a learned sink logit
    sink_kinds: Tuple[str, ...] = ()
    # expert layers: the router scores n_experts, a row goes to
    # experts_per_token of them, and this chip holds the contiguous
    # range [experts_first, experts_first + experts_held) at width
    # d_expert
    n_experts: int = 0
    experts_per_token: int = 0
    experts_first: int = 0
    experts_held: int = 0
    d_expert: int = 0
    # Mamba layers: ssm_inner channels (the published expansion times
    # d_model), each with a state of ssm_state values; dt is projected
    # through ssm_dt_rank; the causal convolution spans ssm_conv
    # positions
    ssm_inner: int = 0
    ssm_state: int = 0
    ssm_dt_rank: int = 0
    ssm_conv: int = 0
    # an RMSNorm over each head of q and of k, before rope (weights
    # q_norm, k_norm [head_dim], shared by the heads)
    qk_norm: bool = False
    # differential attention, in every attention and cross layer: query
    # heads 2p and 2p + 1 are a pair on K/V pair p // (pairs a K/V
    # pair): each softmaxes over its own key head of that pair, both
    # weigh the pair's two value heads side by side, and the pair's
    # output is RMSNorm(first - lambda * second) * (1 - lambda_init),
    # lambda learned (four vectors a layer) around lambda_init(depth)
    differential: bool = False
    # biases on wq, wk, wv and wo
    attn_bias: bool = False
    # every norm of the block and the final one a LayerNorm with scale
    # and bias, where False has RMSNorm
    layer_norm: bool = False
    # a Mamba layer's dt, B and C each through an RMSNorm of its own
    # between the two projections (Jamba's); False: Mamba-1 as published
    ssm_inner_norms: bool = True

    def __post_init__(self):
        def refuse(key, why):
            raise ValueError(f"TransformerConfig.{key}: {why}")

        kinds = self.layer_kinds
        if kinds is not None:
            if len(kinds) != self.n_layers:
                refuse("layer_kinds", f"{len(kinds)} kinds for "
                       f"{self.n_layers} layers")
            for at, (mixer, ffn) in enumerate(kinds):
                if mixer not in MIXERS or ffn not in (DENSE, EXPERTS):
                    refuse("layer_kinds", f"unknown kind "
                           f"{(mixer, ffn)!r}")
                before = [m for m, _ in kinds[:at]]
                if mixer == CROSS and FULL not in before:
                    refuse("layer_kinds", f"layer {at} is a cross layer "
                           f"with no full-attention layer before it "
                           f"whose K/V it could attend")
                if mixer == GMU and MAMBA not in before:
                    refuse("layer_kinds", f"layer {at} is a gated memory "
                           f"unit with no Mamba layer before it whose "
                           f"memory it could gate")
        if any(a == WINDOW for a, _ in kinds or ()) and not self.window:
            refuse("window", "window layers need a window")
        for key in ("n_kv_heads", "window_kv_heads"):
            heads = getattr(self, key)
            if heads is not None and self.n_heads % heads:
                refuse(key, f"{heads} K/V heads do not divide "
                       f"{self.n_heads} query heads")
        if self.rotary_dim == 0:
            refuse("rotary_dim", "0 dimensions rotated is said with "
                   "rope=False, not with a width of nothing")
        if self.rotary_dim is not None and (
                self.rotary_dim % 2 or self.rotary_dim > self.head_dim):
            refuse("rotary_dim", f"{self.rotary_dim} is odd or wider "
                   f"than the head ({self.head_dim})")
        if any(f == EXPERTS for _, f in kinds or ()):
            if not (0 < self.experts_per_token <= self.n_experts
                    and self.d_expert > 0 and self.experts_held > 0
                    and 0 <= self.experts_first
                    and self.experts_first + self.experts_held
                    <= self.n_experts):
                refuse("n_experts", "expert layers need n_experts, "
                       "experts_per_token, d_expert and a held range "
                       "inside the router's width")
        if self.has_mamba and not (
                self.ssm_inner > 0 and self.ssm_state > 0
                and self.ssm_dt_rank > 0 and self.ssm_conv > 1):
            refuse("ssm_inner", "Mamba layers need ssm_inner, ssm_state, "
                   "ssm_dt_rank and a convolution of at least 2 positions")
        if self.has_retention and self.head_dim % 2:
            refuse("qk_head_dim", f"retention layers need heads of even "
                   f"width, not {self.head_dim}")
        if self.differential:
            for key in ("n_heads", "n_kv_heads", "window_kv_heads"):
                heads = getattr(self, key)
                if heads is not None and heads % 2:
                    refuse(key, f"differential attention pairs its heads: "
                           f"{heads} is odd")
            if self.has_retention or self.sink_kinds:
                refuse("differential", "no differential form of a "
                       "retention layer or of a sink logit is expressed")
        if self.has_cross:
            for kind, n in layer_runs(self):
                mixers = [mixer for mixer, _ in period_of(kind)]
                if n > 1 and FULL in mixers and CROSS in mixers[
                        :len(mixers) - mixers[::-1].index(FULL)]:
                    refuse("layer_kinds", f"a cross layer before the "
                           f"full-attention layer of its own period "
                           f"({mixers} x {n}) would attend the period "
                           f"before's K/V, which no serving program "
                           f"carries from one period to the next")

    @property
    def head_dim(self) -> int:
        """Width of a head in q and k."""
        return self.qk_head_dim or self.d_model // self.n_heads

    @property
    def v_dim(self) -> int:
        """Width of a head in v and in the attention output."""
        return self.v_head_dim or self.head_dim

    @property
    def rope_dim(self) -> int:
        return self.head_dim if self.rotary_dim is None else self.rotary_dim

    @property
    def has_mamba(self) -> bool:
        return any(mixer == MAMBA for mixer, _ in self.layer_kinds or ())

    @property
    def has_retention(self) -> bool:
        return any(mixer == RETENTION for mixer, _ in self.layer_kinds or ())

    @property
    def has_gmu(self) -> bool:
        return any(mixer == GMU for mixer, _ in self.layer_kinds or ())

    @property
    def has_cross(self) -> bool:
        return any(mixer == CROSS for mixer, _ in self.layer_kinds or ())

    @property
    def lends(self) -> bool:
        """Whether a layer reads what an earlier one made inside the
        same pass: the memory, or a full-attention layer's K/V."""
        return self.has_gmu or self.has_cross

    def kv_heads(self, attention: str = FULL) -> int:
        heads = self.n_kv_heads or self.n_heads
        if attention == WINDOW:
            heads = self.window_kv_heads or heads
        return heads

    def theta(self, attention: str = FULL) -> float:
        if attention == WINDOW and self.window_rope_theta is not None:
            return self.window_rope_theta
        return self.rope_theta


def layer_runs(cfg: TransformerConfig) -> tuple:
    """The layers in their order as runs of alike periods: ((kind, how
    many), ...). Each run is one stack of weights and one scan. The
    layers are cut into periods of p layers each and neighbouring
    periods that are alike make a run; **p is the one that leaves the
    fewest layer bodies to compile**, p times the number of runs (the
    smaller p where two tie; p divides the layers). Where the kinds
    change rarely that is p = 1 and a run is a run of alike layers,
    ``kind`` the layers' ``(mixer, feed-forward)``: every model of one
    kind, a dense layer before five window layers and a full one, a
    Mamba model with an attention layer every fourteenth. Where they
    alternate it is the alternation's length and ``kind`` is the
    period, a tuple of its layers' kinds: (Mamba, window) x 8, (Mamba,
    full) x 1, (gated memory, cross) x 7 are three runs at p = 2 (six
    bodies) where p = 1 gives thirty-two. ``period_of`` reads either
    form."""
    kinds = tuple(tuple(kind) for kind in (
        cfg.layer_kinds or ((FULL, DENSE),) * cfg.n_layers))
    best = None
    for p in range(1, len(kinds) + 1):
        if best is not None and p >= p_best * len(best):
            break
        if len(kinds) % p:
            continue
        runs = []
        for at in range(0, len(kinds), p):
            if runs and runs[-1][0] == kinds[at:at + p]:
                runs[-1][1] += 1
            else:
                runs.append([kinds[at:at + p], 1])
        if best is None or p * len(runs) < p_best * len(best):
            best, p_best = runs, p
    return tuple((period[0] if len(period) == 1 else period, n)
                 for period, n in best)


def period_of(kind) -> Tuple[LayerKind, ...]:
    """The kinds of the layers of one period of a run, from the run's
    ``kind`` as ``layer_runs`` gives it: a layer's own kind is a period
    of one."""
    return (kind,) if isinstance(kind[0], str) else tuple(kind)


def run_layers(runs) -> list:
    """[(run, layer of its period, mixer, feed-forward), ...] of every
    layer of one period of every run, in the layers' order."""
    return [(r, j, mixer, ffn) for r, (kind, _) in enumerate(runs)
            for j, (mixer, ffn) in enumerate(period_of(kind))]


def layer_stacks(params, cfg: TransformerConfig):
    """[(kind, that run's stacked weights), ...] in the layers' order:
    the one place that reads ``params["layers"]``. A model of one kind
    keeps it as the one stack, the form its checkpoints, its callers
    and the benchmark's ``ouro`` family hold; one with ``layer_kinds``
    holds a tuple of stacks, one a run (a run of periods of several
    layers: a tuple of stacks, one for each layer of the period)."""
    runs, layers = layer_runs(cfg), params["layers"]
    if cfg.layer_kinds is None:
        layers = (layers,)
    if len(layers) != len(runs):
        raise ValueError(f"{len(layers)} stacks of layers for "
                         f"{len(runs)} runs of alike layers")
    return [(kind, stack) for (kind, _), stack in zip(runs, layers)]


# The dense decoder the repo serves and trains at full width on one
# v5e chip: 168M parameters, head_dim 64, bf16. B16 x T1024 with
# remat keeps 28,672 bytes a token and layer (``KEPT``: 3.8 GB over
# its 8 layers) beside 1 GB of state; without remat it does not fit.
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
    # jax.checkpoint around each block under the one rule ``KEPT``: a
    # layer's matrix products and what its flash backward kernels read
    # are held from the forward to the backward, the elementwise rest
    # is computed again. In bytes a token and layer, for a dense layer
    # of heads all alike: itemsize x (6 d_model + 2 d_ff) + 4 n_heads
    # (the layer's input, q, k, v, out, wo's result; gate and up; lse),
    # where keeping nothing held itemsize x d_model: 47,168 against
    # 4,096 at Ouro's widths. A step that fitted only because
    # everything was computed again needs a smaller batch.
    remat: bool = False
    num_microbatches: Optional[int] = None

    def data_axes(self):
        return tuple(a for a in (self.dp, self.sp) if a)


def mamba_dt_bias(key, shape, low: float = 1e-3, high: float = 1e-1):
    """The Mamba family's own start for the bias of dt's projection:
    such that softplus(bias) is log-uniform in [low, high], so that a
    channel's decay exp(dt A) neither dies at once nor stands still
    under fresh weights. float32."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                 * (jnp.log(high) - jnp.log(low)) + jnp.log(low))
    return dt + jnp.log(-jnp.expm1(-dt))       # softplus's inverse


def retention_gate_bias(key, shape, low: float = 64.0, high: float = 8192.0):
    """A start for the bias of a retention layer's gate: such that the
    per-step decay sigmoid(bias) has a half-life log-uniform between
    ``low`` and ``high`` positions, so that under fresh weights (whose
    projection adds next to nothing) a head's state neither dies in a
    few tokens (bias 0 halves it every step) nor stands still.
    float32."""
    half = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                   * (jnp.log(high) - jnp.log(low)) + jnp.log(low))
    decay = jnp.exp2(-1.0 / half)
    return jnp.log(decay) - jnp.log1p(-decay)       # sigmoid's inverse


def _init_mamba(keys, cfg: TransformerConfig, n: int):
    """The mixer of ``n`` stacked Mamba layers. Matrices normal(0.02)
    at the model's dtype; what feeds the recurrence is float32 and
    starts as the family starts it: ``a_log`` log(1..N) down every
    channel ([N, C], the state's layout: ops/ssm.py), ``d_skip`` ones,
    ``dt_bias`` by :func:`mamba_dt_bias`."""
    D, C, N = cfg.d_model, cfg.ssm_inner, cfg.ssm_state
    R, K, dt = cfg.ssm_dt_rank, cfg.ssm_conv, cfg.dtype
    init = jax.nn.initializers.normal(0.02)

    def w(kk, shape):
        return init(kk, shape, jnp.float32).astype(dt)

    inner = {"dt_norm": jnp.ones((n, R), dt), "b_norm": jnp.ones((n, N), dt),
             "c_norm": jnp.ones((n, N), dt)} if cfg.ssm_inner_norms else {}
    return {
        "w_in": w(keys[0], (n, D, 2 * C)),
        "conv_w": w(keys[7], (n, K, C)),
        "conv_b": w(keys[8], (n, C)),
        "w_x": w(keys[1], (n, C, R + 2 * N)),
        **inner,
        "w_dt": w(keys[2], (n, R, C)),
        "dt_bias": mamba_dt_bias(keys[9], (n, C)),
        "a_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[None, :, None],
            (n, N, C)),
        "d_skip": jnp.ones((n, C), jnp.float32),
        "w_out": w(keys[3], (n, C, D)),
    }


def _init_run(keys, cfg: TransformerConfig, kind: LayerKind, n: int):
    """One run of ``n`` alike layers, stacked on a leading dim. ``keys``
    holds ten keys, the first seven in the order of a dense layer's
    matrices."""
    attention, ffn = kind
    D, H, G = cfg.d_model, cfg.n_heads, cfg.kv_heads(attention)
    Dh, Dv, dt = cfg.head_dim, cfg.v_dim, cfg.dtype
    init = jax.nn.initializers.normal(0.02)

    def w(kk, shape):
        return init(kk, shape, jnp.float32).astype(dt)

    if attention == MAMBA:
        run = dict(_init_mamba(keys, cfg, n),
                   attn_norm=jnp.ones((n, D), dt),
                   mlp_norm=jnp.ones((n, D), dt))
    elif attention == GMU:
        run = {"attn_norm": jnp.ones((n, D), dt),
               "w_mem": w(keys[0], (n, D, cfg.ssm_inner)),
               "w_out": w(keys[3], (n, cfg.ssm_inner, D)),
               "mlp_norm": jnp.ones((n, D), dt)}
    else:
        run = {
            "attn_norm": jnp.ones((n, D), dt),
            "wq": w(keys[0], (n, D, H * Dh)),
            "wk": w(keys[1], (n, D, G * Dh)),
            "wv": w(keys[2], (n, D, G * Dv)),
            "wo": w(keys[3], (n, H * Dv, D)),
            "mlp_norm": jnp.ones((n, D), dt),
        }
        if attention == CROSS:      # the queries alone are its own
            del run["wk"], run["wv"]
        if cfg.attn_bias:
            run.update({"b" + name[1:]: jnp.zeros(run[name].shape[::2], dt)
                        for name in ("wq", "wk", "wv", "wo") if name in run})
        if cfg.differential:
            # lambda's four vectors at normal(0.1), so that lambda is
            # near lambda_init and the second softmax matters
            lam = jax.random.split(jax.random.fold_in(keys[0], 1), 4)
            run.update({
                name: 0.1 * jax.random.normal(kk, (n, Dh), jnp.float32)
                for name, kk in zip(("lambda_q1", "lambda_k1", "lambda_q2",
                                     "lambda_k2"), lam)},
                sub_norm=jnp.ones((n, 2 * Dv), dt))
    if cfg.layer_norm:
        run.update(attn_norm_b=jnp.zeros((n, D), dt),
                   mlp_norm_b=jnp.zeros((n, D), dt))
    if attention not in (MAMBA, GMU) and cfg.qk_norm:
        run.update(q_norm=jnp.ones((n, Dh), dt), k_norm=jnp.ones((n, Dh), dt))
    if attention == RETENTION:
        run.update(w_g=w(keys[7], (n, D, G)),
                   b_g=retention_gate_bias(keys[8], (n, G)))
    if attention in cfg.sink_kinds:
        run["sink"] = init(keys[7], (n, H), jnp.float32)
    if ffn == EXPERTS:
        E, F = cfg.experts_held, cfg.d_expert
        run.update(
            router=w(keys[8], (n, D, cfg.n_experts)),
            router_bias=init(keys[9], (n, cfg.n_experts), jnp.float32),
            w_gate=w(keys[4], (n, E, D, F)), w_up=w(keys[5], (n, E, D, F)),
            w_down=w(keys[6], (n, E, F, D)))
    else:
        F = cfg.d_ff
        run.update(w_gate=w(keys[4], (n, D, F)), w_up=w(keys[5], (n, D, F)),
                   w_down=w(keys[6], (n, F, D)))
    return run


def init_params(key, cfg: TransformerConfig):
    """Pytree of params; layer weights stacked on a leading L dim, or,
    with ``layer_kinds``, a tuple of such stacks, one for each run of
    alike periods (``layer_runs``; for a period of several layers a
    tuple of stacks, one for each of them)."""
    k = jax.random.split(key, 8)
    runs = layer_runs(cfg)
    if cfg.layer_kinds is None:
        (kind, n), = runs
        layers = _init_run(list(k[1:]) + [None] * 3, cfg, kind, n)
    else:
        def run(r, kind, n):
            at = jax.random.fold_in(key, 1 + r)
            if isinstance(kind[0], str):
                return _init_run(jax.random.split(at, 10), cfg, kind, n)
            return tuple(
                _init_run(jax.random.split(jax.random.fold_in(at, j), 10),
                          cfg, one, n) for j, one in enumerate(kind))

        layers = tuple(run(r, kind, n) for r, (kind, n) in enumerate(runs))
    init = jax.nn.initializers.normal(0.02)
    params = {
        "embed": init(k[0], (cfg.vocab, cfg.d_model),
                      jnp.float32).astype(cfg.dtype),
        "layers": layers,
        "final_norm": jnp.ones((cfg.d_model,), cfg.dtype),
    }
    if cfg.layer_norm:
        params["final_norm_b"] = jnp.zeros((cfg.d_model,), cfg.dtype)
    if not cfg.tie_embeddings:
        params["head"] = init(jax.random.fold_in(key, 0),
                              (cfg.d_model, cfg.vocab),
                              jnp.float32).astype(cfg.dtype)
    return params


def param_specs(pcfg: ParallelConfig,
                cfg: Optional[TransformerConfig] = None):
    """PartitionSpec pytree matching ``init_params`` output: of a model
    of one kind without ``cfg``, else of ``cfg``'s. Heads and the
    feed-forward's width (an expert's own, inside each expert) go over
    ``tp``; a stack's layers over ``pp``, which only a model of one
    kind can have. A model with Mamba or retention layers, or one whose
    layers read an earlier layer's memory or K/V, is refused ``tp``,
    ``sp`` and ``pp``: no sharding of those mixers is expressed."""
    pp, tp = pcfg.pp, pcfg.tp
    if cfg is not None:
        _refuse_a_sharded_summary(cfg, pcfg)

    def run_specs(kind):
        attention, ffn = kind
        def whole(*leaves):     # refused tp and pp: replicated
            return {name: P(*(None,) * rank) for name, rank in leaves}

        if attention == MAMBA:
            specs = whole(
                ("attn_norm", 2), ("mlp_norm", 2), ("w_in", 3),
                ("conv_w", 3), ("conv_b", 2), ("w_x", 3), ("w_dt", 3),
                ("dt_bias", 2), ("a_log", 3), ("d_skip", 2), ("w_out", 3))
            if cfg.ssm_inner_norms:
                specs.update(whole(("dt_norm", 2), ("b_norm", 2),
                                   ("c_norm", 2)))
        elif attention == GMU:
            specs = whole(("attn_norm", 2), ("mlp_norm", 2), ("w_mem", 3),
                          ("w_out", 3))
        else:
            specs = {
                "attn_norm": P(pp, None),
                "wq": P(pp, None, tp),
                "wk": P(pp, None, tp),
                "wv": P(pp, None, tp),
                "wo": P(pp, tp, None),
                "mlp_norm": P(pp, None),
            }
        if attention == CROSS:
            del specs["wk"], specs["wv"]
        attends = attention not in (MAMBA, GMU)
        if cfg is not None and attends and cfg.attn_bias:
            specs.update({"b" + name[1:]: P(pp, tp if name != "wo" else None)
                          for name in ("wq", "wk", "wv", "wo")
                          if name in specs})
        if cfg is not None and attends and cfg.differential:
            specs.update(whole(("lambda_q1", 2), ("lambda_k1", 2),
                               ("lambda_q2", 2), ("lambda_k2", 2),
                               ("sub_norm", 2)))
        if cfg is not None and cfg.layer_norm:
            specs.update(attn_norm_b=P(pp, None), mlp_norm_b=P(pp, None))
        if cfg is not None and cfg.qk_norm and attends:
            specs.update(q_norm=P(pp, None), k_norm=P(pp, None))
        if attention == RETENTION:      # refused tp and pp: replicated
            specs.update(w_g=P(None, None, None), b_g=P(None, None))
        if cfg is not None and attention in cfg.sink_kinds:
            specs["sink"] = P(pp, tp)
        if ffn == EXPERTS:
            specs.update(router=P(pp, None, None), router_bias=P(pp, None),
                         w_gate=P(pp, None, None, tp),
                         w_up=P(pp, None, None, tp),
                         w_down=P(pp, None, tp, None))
        else:
            specs.update(w_gate=P(pp, None, tp), w_up=P(pp, None, tp),
                         w_down=P(pp, tp, None))
        return specs

    specs = {"embed": P(None, None), "final_norm": P(None)}
    if cfg is None or cfg.layer_kinds is None:
        specs["layers"] = run_specs((FULL, DENSE))
    else:
        if pp:
            raise ValueError("a pipeline over layers of several kinds is "
                             "not expressed: pp needs one stack")
        specs["layers"] = tuple(
            run_specs(kind) if isinstance(kind[0], str)
            else tuple(run_specs(one) for one in kind)
            for kind, _ in layer_runs(cfg))
    if cfg is not None and not cfg.tie_embeddings:
        specs["head"] = P(None, None)
    if cfg is not None and cfg.layer_norm:
        specs["final_norm_b"] = P(None)
    return specs


def _refuse_a_sharded_summary(cfg: TransformerConfig,
                              pcfg: ParallelConfig) -> None:
    sharded = [axis for axis in ("tp", "sp", "pp") if getattr(pcfg, axis)]
    for has, layers, what in (
            (cfg.has_mamba, "Mamba", "its channels for tp, its scan for sp, "
             "its runs of unlike layers for pp"),
            (cfg.has_retention, "retention", "its K/V heads and their "
             "states for tp, its chunks' carried state for sp, a stack "
             "with a state a layer for pp"),
            (cfg.lends, "gated-memory or cross", "the memory's channels and "
             "the lent K/V's heads for tp, positions that an earlier layer "
             "made on another device for sp, a value handed from one stage's "
             "layer to another stage's for pp"),
            (cfg.differential, "differential-attention", "head pairs that "
             "one device must hold whole for tp, the pair's two softmaxes "
             "over one ring for sp; pp has no stack of layers that take "
             "their own depth")):
        if has and sharded:
            raise ValueError(
                f"a model with {layers} layers runs on one device or under "
                f"dp alone: no sharding of that mixer over "
                f"{', '.join(sharded)} is expressed ({what})")


def _attend(q, k, v, pcfg: ParallelConfig, window=None, sink=None,
            sm_scale=None):
    impl = pcfg.attn
    if impl == "auto":
        impl = "ring" if pcfg.sp else "local"
    if impl == "local" or not pcfg.sp:
        # Pallas blocked online-softmax kernel when the default backend
        # is the TPU and T is a multiple of 128 (the kernel chooses its
        # blocks from T and multiplies at q's dtype); the XLA reference
        # otherwise (ops.attention.flash_attention).
        return flash_attention(q, k, v, causal=True, sm_scale=sm_scale,
                               window=window, sink=sink)
    if window is not None or sink is not None or k.shape[2] != q.shape[2] \
            or v.shape[3] != q.shape[3]:
        raise ValueError("sequence-parallel attention takes heads all "
                         "alike, one width, no window and no sink")
    if impl == "ring":
        return ring_attention(q, k, v, axis=pcfg.sp, causal=True)
    if impl == "ulysses":
        return ulysses_attention(q, k, v, axis=pcfg.sp, causal=True)
    raise ValueError(f"unknown attn impl {impl!r}")


def _norm(x, lp, name: str, eps: float):
    """The norm ``name`` of a layer (or of the model, ``lp`` the
    parameters): a LayerNorm with scale and bias where ``lp`` holds
    ``<name>_b``, else an RMSNorm."""
    if name + "_b" in lp:
        return layernorm(x, lp[name], lp[name + "_b"], eps=eps)
    return rmsnorm(x, lp[name], eps=eps)


def _heads(h, w, width: int, bias=None):
    """h [B, T, D] @ w [D, H * width] (+ bias), as heads: [B, T, H,
    width]. XLA
    folds the reshape into the product, which then wants the weight as
    [H, width, D], the transpose of what is stored: a copy of the
    matrix a layer, and before it a slice of its own out of the layers'
    stack into fast memory, since the copy stands between the scan's
    slice and the product (65 of a decode layer's 243 us on a v5e at 8
    rows of 2048: PERF.md section 6, PR 39). Where the rows are many,
    that copy is small beside the product and the folded form is the
    better one (kept flat, the rows' own results are copied instead:
    the train step's). Where they are fewer than the weight's own rows
    (a decode step's token a row, one prompt's prefill) the weight is
    the larger operand: the flat result is kept from the reshape
    (``lax.optimization_barrier``: the identity, no arithmetic), and
    the product reads its layer's matrix where it lies in the stack,
    as ``wo``'s and the feed-forward's do."""
    B, T, D = h.shape
    flat = h @ w
    if bias is not None:
        flat = flat + bias
    if B * T < D:
        flat = lax.optimization_barrier(flat)
    return flat.reshape(B, T, -1, width)


def _paired(q, k, v):
    """Differential attention's heads as one call of plain grouped
    attention serves them. q [B, T, H, Dh]: query heads 2p and 2p + 1
    are a pair; k and v [B, T, G, Dh] (None in a cross layer): K/V
    heads 2g and 2g + 1 are a pair, which serves the H / G query pairs
    p with p // (H / G) == g. The first query of a pair scores against
    the first key of its K/V pair, the second against the second, and
    both weigh the two value heads side by side. So: a K/V pair is one
    head of twice the width (a reshape: the heads lie side by side in a
    row already), and a query is widened to that width with zeros in
    the half that is the other key's, so that its product with the
    doubled key is its product with its own. Grouped attention over
    [B, T, H, 2 Dh] on [B, T, G / 2, 2 Dh] (query head h on K/V head
    h // (2 H / G), at the scale of Dh) then gives every query's
    softmax over its own key times the doubled value. Twice the
    multiplications in q.k for no copy of K or V and no second call."""
    B, T, H, Dh = q.shape
    own = (jnp.arange(H) % 2)[:, None, None] == jnp.arange(2)[None, :, None]
    q = jnp.where(own, q[..., None, :], 0).reshape(B, T, H, 2 * Dh)

    def pair(t):
        return None if t is None else t.reshape(
            t.shape[:2] + (t.shape[2] // 2, 2 * t.shape[3]))
    return q, pair(k), pair(v)


def last_position(t):
    """t [B, T, ..] at its last position alone: [B, 1, ..]."""
    return t[:, -1:]


def lambda_init(depth):
    """Differential attention's lambda_init at a layer's depth (its
    index in the model)."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(depth, jnp.float32))


def _differ(o, lp, eps: float):
    """o [.., H, 2 Dv], each query's softmax times its K/V pair's
    doubled value, to the pairs' outputs [.., H / 2, 2 Dv]:
    RMSNorm(first - lambda second) (1 - lambda_init), in float32, at
    o's dtype."""
    (H, W), dtype = o.shape[-2:], o.dtype
    start = lambda_init(lp["depth"])
    lam = (jnp.exp(jnp.sum(lp["lambda_q1"] * lp["lambda_k1"]))
           - jnp.exp(jnp.sum(lp["lambda_q2"] * lp["lambda_k2"])) + start)
    o = o.astype(jnp.float32).reshape(o.shape[:-2] + (H // 2, 2, W))
    return (rmsnorm(o[..., 0, :] - lam * o[..., 1, :], lp["sub_norm"],
                    eps=eps) * (1.0 - start)).astype(dtype)


def block(lp, x, rope, attend, cfg: TransformerConfig,
          pcfg: ParallelConfig = ParallelConfig(), last: bool = False):
    """The transformer block, on local shards. x: [B_l, T_l, D]
    (tp-replicated); ``lp`` one layer's weights. ``rope(t)`` rotates q
    and k; ``attend(q, k, v) -> (o, state)`` takes them as [B, T,
    H_local, Dh] (k and v at the layer's K/V heads, v at the value
    width) and gives the attention output, which is flattened here to
    [B, T, H_local * Dv], and whatever the caller keeps of the layer
    (the K/V a cache holds; None in training). A layer that has
    ``w_g`` is a retention layer: the same projections, heads and rope,
    and beside them the gate, g [B, T, G] float32, the log of each K/V
    head's decay at each position (log sigmoid of a projection of the
    normed input and a bias, <= 0); its caller's ``attend(q, k, v, g)``
    runs the retention from whatever state it starts from and the
    state that comes back is (S, z) (ops/retention.py). Where
    ``cfg.qk_norm``, q and k of either pass through an RMSNorm over
    each head before rope. A layer that has
    ``w_in`` is a Mamba layer: ``rope`` is not called and ``attend`` is
    an ``ops.ssm.Recurrence``, the convolution and the scan as its
    caller runs them (:func:`mamba_mixer`); the state that comes back
    is (the convolution's tail, the scan's state) and, in a model with
    gated memory units, behind them the *memory*: the scan's output
    before the gate, [B, T, ssm_inner], which its caller carries to
    the layers that read it. A layer that has ``w_mem`` is such a
    unit: ``attend`` is the memory itself (of the same rows as x), the
    mixer is ``w_out(silu(w_mem h) * memory)`` and no state comes
    back. A layer that has ``wq`` and no ``wk`` is a cross layer: it
    projects queries alone, ``attend(q, None, None)`` attends the K/V
    its caller has from the last full-attention layer. A layer that
    has ``lambda_q1`` has differential attention (:func:`_paired`):
    ``attend`` is handed the widened queries and the doubled K/V heads
    (which are what a cache keeps, flat rows either way) and must
    score at ``cfg.head_dim ** -0.5``, the scale of the head before it
    was widened; ``lp["depth"]`` is the layer's index in the model.
    Biases and a LayerNorm's are applied where ``lp`` holds them. A
    layer that has a
    router is an expert layer: its feed-forward is the part the experts
    held here give (parallel/experts.py). With ``last`` (an attention
    or cross layer of a prefill, behind which nothing mixes over the
    sequence any more) the layer's K and V are made at every position
    and everything else of it at the last one alone: what comes back
    is x [B, 1, D]. Returns (x, state, load):
    ``load`` the rows each held expert got, int32 [experts_held], None
    for a dense layer. Each stretch of it is traced under the scope of
    its part (``PARTS``); ``attend`` brings its own, the attention's
    kind."""
    B, T, D = x.shape

    if "w_in" in lp:
        with jax.named_scope("mamba_mixer"):
            h = _norm(x, lp, "attn_norm", cfg.norm_eps)
            o, state = mamba_mixer(lp, h, attend, cfg)
            x = x + o.astype(x.dtype)
    elif "w_mem" in lp:
        with jax.named_scope("gmu"):
            h = _norm(x, lp, "attn_norm", cfg.norm_eps)
            gate = jax.nn.silu((h @ lp["w_mem"]).astype(jnp.float32))
            o = (gate * attend.astype(jnp.float32)).astype(x.dtype) \
                @ lp["w_out"]
            x, state = x + o.astype(x.dtype), None
    else:
        with jax.named_scope("qkv"):
            h = _norm(x, lp, "attn_norm", cfg.norm_eps)
            if pcfg.tp:
                h = tp_copy(h, pcfg.tp)
            if last:
                x, T = last_position(x), 1
            q = _heads(last_position(h) if last else h, lp["wq"],
                       cfg.head_dim, lp.get("bq"))      # H_local of them
            k = v = None
            if "wk" in lp:
                k = _heads(h, lp["wk"], cfg.head_dim, lp.get("bk"))
                v = _heads(h, lp["wv"], cfg.v_dim, lp.get("bv"))
                if cfg.value_scale != 1.0:
                    v = v * cfg.value_scale
            if cfg.qk_norm:
                q = rmsnorm(q, lp["q_norm"], eps=cfg.norm_eps)
                if k is not None:
                    k = rmsnorm(k, lp["k_norm"], eps=cfg.norm_eps)
            q = rope(q)
            if k is not None:
                k = rope(k)
            if "lambda_q1" in lp:
                q, k, v = _paired(q, k, v)
            gate = ()
            if "w_g" in lp:
                gate = (jax.nn.log_sigmoid(jnp.matmul(
                    h, lp["w_g"], preferred_element_type=jnp.float32)
                    + lp["b_g"]),)
        o, state = attend(q, k, v, *gate)   # the caller's scope, by kind
        with jax.named_scope("attn_out"):
            if "lambda_q1" in lp:
                o = _differ(o, lp, cfg.norm_eps)
            o = o.reshape(B, T, -1) @ lp["wo"]         # row-parallel
            if "bo" in lp:
                o = o + lp["bo"]
            if pcfg.tp:
                o = tp_allreduce(o, pcfg.tp)
            x = x + o.astype(x.dtype)

    load = None
    if "router" in lp:
        with jax.named_scope("router"):
            h = _norm(x, lp, "mlp_norm", cfg.norm_eps)
            if pcfg.tp:
                h = tp_copy(h, pcfg.tp)
            chosen, weights = route(h.reshape(B * T, D), lp["router"],
                                    lp["router_bias"],
                                    cfg.experts_per_token)
        with jax.named_scope("experts"):
            d, load = expert_ffn(
                h.reshape(B * T, D), chosen, weights, lp["w_gate"],
                lp["w_up"], lp["w_down"], first=cfg.experts_first,
                held=cfg.experts_held, base=lp.get("expert_base", 0),
                tile=expert_tile(B * T, cfg.experts_per_token,
                                 cfg.n_experts))
            d = d.reshape(B, T, D)
            if pcfg.tp:
                d = tp_allreduce(d, pcfg.tp)
            x = x + d.astype(x.dtype)
    else:
        with jax.named_scope("mlp"):
            h = _norm(x, lp, "mlp_norm", cfg.norm_eps)
            if pcfg.tp:
                h = tp_copy(h, pcfg.tp)
            g = jax.nn.silu((h @ lp["w_gate"]).astype(jnp.float32))
            u = (h @ lp["w_up"]).astype(jnp.float32)
            d = (g * u).astype(x.dtype) @ lp["w_down"]     # row-parallel
            if pcfg.tp:
                d = tp_allreduce(d, pcfg.tp)
            x = x + d.astype(x.dtype)
    return x, state, load


def mamba_mixer(lp, h, recur: ssm.Recurrence, cfg: TransformerConfig):
    """A Mamba layer's mixer over h [B, T, D], the normed input: the
    projection to the channels u and their gate z; the causal
    convolution and its silu (``recur.conv``); dt, B and C projected
    from the result, each through its own RMSNorm where the layer has
    them (``dt_norm``), dt widened to the
    channels and through a softplus; the selective scan
    (``recur.scan``); the gate; the projection back. The matrix
    products run at h's dtype; dt, A and the state are float32. Returns
    (the mixer's output [B, T, D], (the convolution's tail [B, K - 1,
    C], the scan's state [B, N, C])), and behind the two, in a model
    with gated memory units, the scan's output y [B, T, C] before the
    gate: the memory."""
    C, N, R = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_dt_rank
    uz = h @ lp["w_in"]
    z = uz[..., C:]
    u, tail = recur.conv(uz[..., :C], lp["conv_w"], lp["conv_b"])
    low = u @ lp["w_x"]
    dt, b, c = (low[..., a:z_] if name not in lp
                else rmsnorm(low[..., a:z_], lp[name], eps=cfg.norm_eps)
                for name, a, z_ in (("dt_norm", 0, R), ("b_norm", R, R + N),
                                    ("c_norm", R + N, R + 2 * N)))
    dt = jax.nn.softplus(
        jnp.matmul(dt, lp["w_dt"], preferred_element_type=jnp.float32)
        + lp["dt_bias"])
    y, state = recur.scan(u, dt, -jnp.exp(lp["a_log"]), b, c, lp["d_skip"])
    gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    memory = (y,) if cfg.has_gmu else ()
    return gated.astype(h.dtype) @ lp["w_out"], (tail, state) + memory


EXPERT_MATRICES = ("w_gate", "w_up", "w_down")


def scan_run(body, carry, layers, index: bool = True):
    """``lax.scan`` of ``body(carry, lp, i) -> carry`` over one run's
    stacked layers: ``lp`` layer i's weights, or, where the run is of
    periods of several layers (``layers`` a tuple of stacks), the
    tuple of period i's layers' weights. (``index`` False scans the
    layers alone and gives ``i`` None.) An expert run's matrices are
    not sliced a layer at a time: the slice would be a copy of every
    held expert (0.8 GB a layer at 16 experts of 4096 x 2048), made so
    that the loop over the tiles could index it. ``lp`` holds them
    whole, the layers' experts flattened to one leading dimension, and
    ``expert_base``, where layer i's begin (``block`` hands both to
    ``expert_ffn``)."""
    stacks = layers if isinstance(layers, tuple) else (layers,)
    n = stacks[0]["attn_norm"].shape[0]
    if not any("router" in stack for stack in stacks):
        if not index:
            return lax.scan(lambda c, lp: (body(c, lp, None), None), carry,
                            layers)[0]
        return lax.scan(lambda c, xs: (body(c, *xs), None), carry,
                        (layers, jnp.arange(n)))[0]

    def split(stack):
        """(what the scan slices, what the body adds to a slice)."""
        if "router" not in stack:
            return stack, lambda i: {}
        held = stack["w_gate"].shape[1]
        whole = {k: stack[k].reshape((n * held,) + stack[k].shape[2:])
                 for k in EXPERT_MATRICES}
        return ({k: v for k, v in stack.items() if k not in EXPERT_MATRICES},
                lambda i: dict(whole, expert_base=i * held))

    sliced, rest = zip(*(split(stack) for stack in stacks))

    period = isinstance(layers, tuple)

    def step(c, xs):
        lps, i = xs
        lps = tuple(dict(lp, **more(i))
                    for lp, more in zip(lps if period else (lps,), rest))
        return body(c, lps if period else lps[0], i), None

    return lax.scan(step, carry, (sliced if period else sliced[0],
                                  jnp.arange(n)))[0]


def one_period(layers):
    """The weights of a run of one period as ``scan_run``'s body gets
    them at i = 0, with no scan: such a run has nothing to scan, and a
    body that is no loop's may change the shape of what it carries (a
    prefill that goes on at the last position alone)."""
    def first(stack):
        lp = {k: v[0] for k, v in stack.items()}
        if "router" in stack:   # experts whole, as the scan's body has them
            lp.update({k: stack[k].reshape((-1,) + stack[k].shape[2:])
                       for k in EXPERT_MATRICES}, expert_base=0)
        return lp
    return tuple(first(stack) for stack in layers) \
        if isinstance(layers, tuple) else first(layers)


def unembed(params, x, *, last=False, eps: float = 1e-6):
    """Final norm and the unembed of x [..., D] (the head where the
    model has one, else the embedding, tied), or with ``last`` of the
    last position alone of x [B, T, D] (a prefill). The matmul runs at
    x's dtype and the logits are float32: a stable softmax-xent, and
    one rounding for every caller, so that a greedy decode agrees with
    the full forward's argmax."""
    with jax.named_scope("head"):
        x = _norm(x, params, "final_norm", eps)
        if last:
            x = x[:, -1]
        head = params["head"] if "head" in params else params["embed"].T
        return (x @ head.astype(x.dtype)).astype(jnp.float32)


def kind_rope(cfg: TransformerConfig, attention: str, max_seq: int):
    """(cos, sin) tables [max_seq, rope_dim / 2] of an attention kind:
    each kind has its own base."""
    return rope_frequencies(cfg.rope_dim, max_seq,
                            theta=cfg.theta(attention))


def roped_kinds(cfg: TransformerConfig, runs) -> list:
    """The kinds of mixer among ``runs`` whose q and k are rotated: the
    attention kinds, unless the model has no rotation at all."""
    if not cfg.rope:
        return []
    return list(dict.fromkeys(
        mixer for kind, *_ in runs for mixer, _ in period_of(kind)
        if mixer not in (MAMBA, GMU)))


def retain_from_the_start(q, k, v, g):
    """A retention layer's ``attend`` over a whole sequence from nothing
    before it: training's, which drops the state that comes back, and a
    prefill's, which keeps it."""
    with jax.named_scope("retention_chunk"):
        return retention(q, k, v, g)


def no_rotation(t):
    """``rope`` of a model whose attention has no positional term."""
    return t


def _scan_from_the_start(*args):
    with jax.named_scope("ssm_scan"):
        return ssm.selective_scan(*args)


# a Mamba layer's recurrence over a whole sequence from nothing before
# it: training's, which drops what comes back, and a prefill's, which
# keeps it
FROM_THE_START = ssm.Recurrence(ssm.causal_conv, _scan_from_the_start)


# What ``remat=True`` keeps of a layer from the forward to the backward:
# the results of its matrix products that the backward reads (``wo``'s,
# gate, up; a Mamba or retention layer's likewise) and what the flash
# backward kernels read (``ops.attention.SAVED``: q behind its rope, k,
# v and the forward's out, heads first, and lse; these stand in for the
# q, k and v products, which nothing then reads). The rest (norms, silu,
# the gate's product with up, any other kernel's result; rope and the
# q, k and v products themselves where the attention is not the flash
# kernel's) the backward computes again from these.
KEPT = jax.checkpoint_policies.save_from_both_policies(
    jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    jax.checkpoint_policies.save_only_these_names(*FLASH_SAVED))


def _stack_fn(cfg, pcfg, ropes, kind=(FULL, DENSE), first: int = 0):
    """Scan one run of (locally held) alike layers, or alike periods
    of layers, over one activation. ``ropes``: each roped mixer's
    ``rope``; ``first``: the depth of the run's first layer. Where the
    model lends (``cfg.lends``) the activation travels with what is
    lent: (x, the memory, the last full-attention layer's K, its V)."""
    period = period_of(kind)

    def layer(mixer, lp, carry):
        x, memory, *lent = carry if cfg.lends else (carry, None)
        window = cfg.window if mixer == WINDOW else None

        def attend(q, k, v):
            with jax.named_scope(f"{mixer}_attention"):
                if mixer == CROSS:
                    k, v = lent
                return (_attend(q, k, v, pcfg, window, lp.get("sink"),
                                cfg.head_dim ** -0.5),
                        (k, v) if cfg.lends else None)

        how = {MAMBA: FROM_THE_START, RETENTION: retain_from_the_start,
               GMU: memory}
        x, state, _ = block(lp, x, ropes.get(mixer, no_rotation),
                            how.get(mixer, attend), cfg, pcfg)
        if not cfg.lends:
            return x
        if mixer == MAMBA and cfg.has_gmu:
            memory = state[2]
        if mixer == FULL and cfg.has_cross:
            lent = state
        return (x, memory, *lent)

    layer = [functools.partial(layer, mixer) for mixer, _ in period]
    if pcfg.remat:
        # (a layer runs inside ``scan_run``'s loop and nowhere else, so
        # XLA cannot merge what the backward computes again with the
        # forward's; the barrier that would forbid it makes every kept
        # value a copy out of the loop's stack before the backward reads
        # it)
        layer = [jax.checkpoint(one, policy=KEPT, prevent_cse=False)
                 for one in layer]

    def body(carry, lps, i):
        if len(period) == 1:
            lps = (lps,)
        for j, (one, lp) in enumerate(zip(layer, lps)):
            if cfg.differential:
                lp = dict(lp, depth=first + i * len(period) + j)
            carry = one(lp, carry)
        return carry

    def run(layers, carry):
        return scan_run(body, carry, layers, index=cfg.differential)
    return run


def nothing_lent(cfg: TransformerConfig, x) -> tuple:
    """What travels beside x [B, T, D] in a model that lends, before
    any layer has made it: (the memory, the lent K, the lent V), zeros
    of their shapes where the model has a layer that reads them, None
    where it has none."""
    B, T, _ = x.shape
    width = 2 if cfg.differential else 1
    memory = jnp.zeros((B, T, cfg.ssm_inner), x.dtype) if cfg.has_gmu \
        else None
    if not cfg.has_cross:
        return memory, None, None
    G = cfg.kv_heads(FULL) // width
    return (memory, jnp.zeros((B, T, G, width * cfg.head_dim), x.dtype),
            jnp.zeros((B, T, G, width * cfg.v_dim), x.dtype))


def forward(params, tokens, cfg: TransformerConfig,
            pcfg: ParallelConfig = ParallelConfig()):
    """tokens: [B_local, T_local] int32 → logits [B_l, T_l, V] (fp32).
    Every layer at every position.

    Call directly for the oracle, or inside shard_map for SPMD.
    """
    T = tokens.shape[1]
    _refuse_a_sharded_summary(cfg, pcfg)
    stacks = layer_stacks(params, cfg)
    if pcfg.pp and len(stacks) > 1:
        raise ValueError("a pipeline over layers of several kinds is not "
                         "expressed: pp needs one stack")
    tables = {a: kind_rope(cfg, a, cfg.max_seq)
              for a in roped_kinds(cfg, stacks)}
    if pcfg.sp:
        positions = lax.axis_index(pcfg.sp) * T + jnp.arange(T)
    else:
        positions = jnp.arange(T)
    ropes = {a: functools.partial(apply_rotary, cos=cos, sin=sin,
                                  positions=positions)
             for a, (cos, sin) in tables.items()}

    x = params["embed"][tokens]                    # [B,T,D]
    if cfg.lends:
        x = (x, *nothing_lent(cfg, x))
    first = 0
    for (kind, layers), (_, n) in zip(stacks, layer_runs(cfg)):
        stack = _stack_fn(cfg, pcfg, ropes, kind, first)
        if pcfg.pp:
            x = pipeline_spmd(stack, layers, x, axis=pcfg.pp,
                              num_microbatches=pcfg.num_microbatches)
        else:
            x = stack(layers, x)
        first += len(period_of(kind)) * n
    if cfg.lends:
        x = x[0]
    return unembed(params, x, eps=cfg.norm_eps)


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
    shard_map over all four axes with real param/batch shardings.
    **The step consumes its state**: ``params`` and ``opt_state`` are
    donated, with and without a mesh, so the device holds them once
    (the results take their place) and the arrays passed in are deleted
    when the call returns; go on with the ones it returns."""
    import optax

    optimizer = optimizer or optax.adamw(3e-4)

    pspecs_for_grads = param_specs(pcfg, cfg)

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
        return jax.jit(local_step, donate_argnums=(0, 1)), optimizer

    pspecs = param_specs(pcfg, cfg)
    opt_specs = _opt_state_specs(optimizer, cfg, pspecs)
    batch_spec = {"tokens": P(pcfg.dp, pcfg.sp),
                  "targets": P(pcfg.dp, pcfg.sp)}
    step = shard_map(
        local_step, mesh=mesh,
        in_specs=(pspecs, opt_specs, batch_spec),
        out_specs=(pspecs, opt_specs, P()),
        check_vma=False)
    return jax.jit(step, donate_argnums=(0, 1)), optimizer


def init_train_state(key, cfg: TransformerConfig, pcfg: ParallelConfig,
                     mesh, optimizer):
    """(params, opt_state) for ``make_train_step(cfg, pcfg, mesh)``,
    each leaf created in its mesh sharding — no device ever holds the
    whole tree."""
    from jax.sharding import NamedSharding

    def named(specs):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, P))

    pspecs = param_specs(pcfg, cfg)
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
