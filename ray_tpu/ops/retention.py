"""Power retention (degree 2): a linear-attention mixer whose score of
a query and a key is ``(q.k)^2`` in the place of ``exp(q.k)``, with a
learned decay of the past. For one sequence and one K/V head (H query
heads on J K/V heads, query head i on K/V head ``i // (H / J)``), with
``g_t <= 0`` the log of the step's decay and ``G_t`` its running sum::

    attention form   a_ts = exp(G_t - G_s) (q_t.k_s)^2   for s <= t
                     o_t  = sum_s a_ts v_s / sum_s a_ts
    recurrent form   S_t = e^{g_t} S_{t-1} + v_t phi(k_t)^T     [dv, D]
                     z_t = e^{g_t} z_{t-1} + phi(k_t)           [D]
                     o_t = S_t phi(q_t) / (z_t . phi(q_t))

because ``(x.y)^2 = phi(x).phi(y)`` for the feature map below: the
whole past of a K/V head is a matrix and a normaliser that do not grow
with the context. Where the denominator is 0 the output is 0. No scale
is put on ``q.k`` (any constant cancels between numerator and
denominator).

**The feature map and the layout of its D rows.** ``power_features``
holds the upper triangle of ``x x^T`` by wrapped diagonals: group r
(r = 0 .. d/2) is ``x * roll(x, r)``, d values, scaled so that every
unordered pair {i, j} weighs what ``(x.y)^2`` gives it (1 on the
diagonal, 2 off it: group 0 as it is, groups 1 .. d/2 - 1 by sqrt 2,
and group d/2, which holds each of its pairs twice, as it is). **D =
(d/2 + 1) d: 8,320 for d = 128**, 65 whole groups of 128 lanes, 64 rows
more than the exact triangle's 8,256 (group d/2's second half) and half
of the full product's 16,384. A group is one lane rotation and one
multiply of a tile that is already in registers, so a kernel builds phi
of a tile of q or k where it needs it and never writes it to memory.

**The state lies [dv, D]**, a head's value dimensions down the
sublanes and the features along the lanes (the transpose of the S of
the equations above): both kernels then multiply it as it lies, a
chunk's ``phi(Q) S^T`` and ``V^T phi(K)`` as plain matrix products and
a decode step's update as a row of phi broadcast down the sublanes.
State, z, G and every accumulation are float32 (a decay near 1
multiplied in over thousands of steps does not survive bfloat16); q, k
and v keep the model's dtype and the matrix products run at it.

Three forms of the one function:

* ``retention_quadratic``: the attention form over a whole sequence
  from no state, [T, T] weights. The oracle of the tests.
* ``retention``: the chunked form, ``(o, (S, z))`` from a state (None:
  nothing before). Two forms behind the one name, as
  ``flash_attention`` and ``selective_scan`` have: XLA, a ``lax.scan``
  over chunks (every platform, every T, the gradient), and the Pallas
  kernel ``retention_chunk`` on the TPU where T is a multiple of its
  chunk: grid (row, K/V head, chunk in order), the head's state the
  kernel's own output block, resident in VMEM from the first chunk to
  the last; a chunk's intra part two products under the causal decay
  mask, its inter part and the state's update a loop over the groups,
  phi built a group at a time in VMEM. A gradient through the kernel
  recomputes the XLA form and differentiates that.
* ``retention_step``: one token a row against the run's whole carried
  state ``[L, B, J, dv, D]``. On the TPU the kernel ``retention_step``:
  the state arrays are its operands where they lie (aliased to its
  results), the layer's index and the rows' liveness prefetched
  scalars; **a live row's state is read once and written once, a row
  left out is neither read nor written** (its grid steps name the block
  the step before them held, so the pipeline copies nothing in or out).
  Elsewhere ``retention_step_xla`` over the layer's slice.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import _on_tpu

CHUNK_KERNEL = "retention_chunk"    # the pallas_calls' ``name=``
STEP_KERNEL = "retention_step"
_CHUNK = 64                 # positions in a chunk of the XLA form
_BLOCK_T = 128              # positions in a grid step of the chunk kernel
_BLOCK_C = 128              # value rows of the state in a grid step of
_SUB_C = 32                 # the step kernel, and in a pass inside it
_VMEM_LIMIT = 64 * 2 ** 20  # the chunk kernel keeps a head's state (4.3
                            # MB at d 128, twice: it is an output block)
_ROOT4 = 2.0 ** 0.25


def feature_dim(d: int) -> int:
    """D of ``power_features`` for heads of width d."""
    return (d // 2 + 1) * d


def power_features(x):
    """phi of x [..., d] (d even) -> [..., D] float32, D = (d/2 + 1) d,
    such that ``phi(x) . phi(y) == (x . y) ** 2``: group r holds
    ``x_i x_{i-r}`` (indices mod d), the module docstring's weights."""
    d = x.shape[-1]
    if d % 2:
        raise ValueError(f"a head of odd width {d} has no such layout")
    x = x.astype(jnp.float32)
    xs = x * _ROOT4
    return jnp.concatenate(
        [x * x] + [xs * jnp.roll(xs, r, -1) for r in range(1, d // 2)]
        + [x * jnp.roll(x, d // 2, -1)], axis=-1)


def _grouped(q, J):
    """q [B, T, H, d] as [B, T, J, H / J, d]: a K/V head's query heads
    side by side."""
    B, T, H, d = q.shape
    return q.reshape(B, T, J, H // J, d)


def _ratio(num, den):
    """num / den where den > 0, else 0 (``den`` broadcasts a last
    dimension onto num)."""
    den = den[..., None]
    return jnp.where(den > 0, num / jnp.where(den > 0, den, 1.0), 0.0)


def retention_quadratic(q, k, v, g):
    """The attention form from no state: q [B, T, H, d], k [B, T, J, d],
    v [B, T, J, dv], g [B, T, J] (log decay, <= 0) -> o [B, T, H, dv] at
    q's dtype. float32 at the highest matmul precision throughout."""
    B, T, H, _ = q.shape
    J = k.shape[2]
    hi = lax.Precision.HIGHEST
    G = jnp.cumsum(g.astype(jnp.float32), axis=1).transpose(0, 2, 1)
    s = jnp.einsum("btjrd,bsjd->bjrts", _grouped(q, J).astype(jnp.float32),
                   k.astype(jnp.float32), precision=hi)
    keep = jnp.tril(jnp.ones((T, T), bool))
    decay = jnp.where(keep, jnp.exp(jnp.where(
        keep, G[..., :, None] - G[..., None, :], 0.0)), 0.0)    # [B,J,T,T]
    a = s * s * decay[:, :, None]
    num = jnp.einsum("bjrts,bsjc->btjrc", a, v.astype(jnp.float32),
                     precision=hi)
    den = jnp.sum(a, axis=-1).transpose(0, 3, 1, 2)             # [B,T,J,R]
    return _ratio(num, den).reshape(B, T, H, -1).astype(q.dtype)


def zero_state(B: int, J: int, d: int, dv: int):
    """(S [B, J, dv, D], z [B, J, D]) of a sequence with nothing before
    it, float32."""
    D = feature_dim(d)
    return (jnp.zeros((B, J, dv, D), jnp.float32),
            jnp.zeros((B, J, D), jnp.float32))


def _retention_chunked(q, k, v, g, S, z, chunk: int = _CHUNK):
    """The XLA form. Positions beyond T in the last chunk are fed k = 0
    and g = 0, under which the state stands still."""
    B, T, H, d = q.shape
    J, dv = k.shape[2], v.shape[3]
    chunk = min(chunk, T)
    n = -(-T // chunk)
    mm = q.dtype                # the matrix products' operand type

    def chunks(t):
        t = jnp.pad(t, ((0, 0), (0, n * chunk - T)) + ((0, 0),) * (t.ndim - 2))
        return t.reshape((B, n, chunk) + t.shape[2:]).swapaxes(0, 1)

    keep = jnp.tril(jnp.ones((chunk, chunk), bool))

    def one(state, xs):
        S, z = state                                # [B,J,dv,D], [B,J,D]
        qc, kc, vc, gc = xs                         # [B, chunk, ...]
        G = jnp.cumsum(gc, axis=1).transpose(0, 2, 1)           # [B,J,C]
        qg = _grouped(qc, J)
        s = jnp.einsum("btjrd,bsjd->bjrts", qg, kc,
                       preferred_element_type=jnp.float32)
        decay = jnp.where(keep, jnp.exp(jnp.where(
            keep, G[..., :, None] - G[..., None, :], 0.0)), 0.0)
        a = s * s * decay[:, :, None]                           # [B,J,R,C,C]
        num = jnp.einsum("bjrts,bsjc->btjrc", a.astype(mm), vc,
                         preferred_element_type=jnp.float32)
        den = jnp.sum(a, axis=-1).transpose(0, 3, 1, 2)         # [B,C,J,R]
        # what came before the chunk, decayed to each position
        fq = power_features(qg)                                 # [B,C,J,R,D]
        before = jnp.exp(G).transpose(0, 2, 1)[..., None]       # [B,C,J,1]
        num = num + before[..., None] * jnp.einsum(
            "btjrf,bjcf->btjrc", fq.astype(mm), S.astype(mm),
            preferred_element_type=jnp.float32)
        den = den + before * jnp.einsum("btjrf,bjf->btjr", fq, z)
        # ... and the chunk folded into it, each position decayed to
        # the chunk's end
        fk = power_features(kc)                                 # [B,C,J,D]
        after = jnp.exp(G[..., -1:] - G).transpose(0, 2, 1)     # [B,C,J]
        last = jnp.exp(G[..., -1])                              # [B,J]
        S = last[..., None, None] * S + jnp.einsum(
            "bsjc,bsjf->bjcf",
            (vc.astype(jnp.float32) * after[..., None]).astype(mm),
            fk.astype(mm), preferred_element_type=jnp.float32)
        z = last[..., None] * z + jnp.einsum("bsj,bsjf->bjf", after, fk)
        return (S, z), _ratio(num, den).astype(q.dtype)

    (S, z), o = lax.scan(one, (S, z), (chunks(q), chunks(k), chunks(v),
                                       chunks(g.astype(jnp.float32))))
    return o.swapaxes(0, 1).reshape(B, n * chunk, H, dv)[:, :T], (S, z)


def retention_step_xla(q, k, v, g, S, z):
    """One position a row: q [B, H, d], k [B, J, d], v [B, J, dv], g
    [B, J], S [B, J, dv, D], z [B, J, D] -> (o [B, H, dv] at q's dtype,
    S, z)."""
    B, H, d = q.shape
    J = k.shape[1]
    decay = jnp.exp(g.astype(jnp.float32))
    fk = power_features(k)                                      # [B,J,D]
    S = decay[..., None, None] * S \
        + v.astype(jnp.float32)[..., :, None] * fk[..., None, :]
    z = decay[..., None] * z + fk
    fq = power_features(q.reshape(B, J, H // J, d))             # [B,J,R,D]
    num = jnp.einsum("bjrf,bjcf->bjrc", fq, S,
                     precision=lax.Precision.HIGHEST)
    den = jnp.einsum("bjrf,bjf->bjr", fq, z,
                     precision=lax.Precision.HIGHEST)
    return _ratio(num, den).reshape(B, H, -1).astype(q.dtype), S, z


# ------------------------------------------------- the kernels' groups

def _groups(x, roll):
    """phi of a tile x [rows, d] float32, a group at a time: yields
    (r, x * roll(x, r) at its weight) for r = 0 .. d / 2, each
    [rows, d]. ``roll(x, r)`` rotates along the lanes."""
    d = x.shape[-1]
    xs = x * _ROOT4
    for r in range(d // 2 + 1):
        if r == 0:
            yield r, x * x
        elif r == d // 2:
            yield r, x * roll(x, r)
        else:
            yield r, xs * roll(xs, r)


def _lane_roll(interpret):
    if interpret:
        return lambda x, r: jnp.roll(x, r, axis=-1)
    import jax.experimental.pallas.tpu as pltpu

    return lambda x, r: pltpu.roll(x, r, x.ndim - 1)


# ------------------------------------------------------ the chunk kernel

def _chunk_kernel(*refs, heads: int, from_zero: bool, interpret: bool):
    """One (row, K/V head, chunk) grid step. Blocks: q and o
    [C, heads * d] (the head's query heads side by side), k [C, d], v
    [C, dv], the chunk's running log decay as a column [C, 1] and as a
    row [1, C]; the head's state S [dv, D] and z [1, D] are output
    blocks that stay where they are from the first chunk to the last
    (and start from ``s0``/``z0``, or from zeros)."""
    import jax.experimental.pallas as pl

    if from_zero:
        q_ref, k_ref, v_ref, gc_ref, gr_ref, o_ref, s_ref, z_ref = refs
    else:
        (q_ref, k_ref, v_ref, gc_ref, gr_ref, s0_ref, z0_ref,
         o_ref, s_ref, z_ref) = refs
    roll = _lane_roll(interpret)
    C, d = k_ref.shape
    mm = k_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _start():
        if from_zero:
            s_ref[...] = jnp.zeros_like(s_ref)
            z_ref[...] = jnp.zeros_like(z_ref)
        else:
            s_ref[...] = s0_ref[...]
            z_ref[...] = z0_ref[...]

    G_col, G_row = gc_ref[...], gr_ref[...]             # [C, 1], [1, C]
    keep = (lax.broadcasted_iota(jnp.int32, (C, C), 0)
            >= lax.broadcasted_iota(jnp.int32, (C, C), 1))
    decay = jnp.where(keep, jnp.exp(jnp.where(keep, G_col - G_row, 0.0)),
                      0.0)
    G_last = G_col[C - 1:C, :]                          # [1, 1]
    before = jnp.exp(G_col)                             # [C, 1]
    after = jnp.exp(G_last - G_col)                     # [C, 1]
    last = jnp.exp(G_last)                              # [1, 1]
    k, v = k_ref[...], v_ref[...]
    # the same down the state's sublanes, [dv, 1] (G only falls, so its
    # last is its least): a [1, 1] broadcast in both directions at once
    # is more than the compiler takes
    last_rows = jnp.exp(jnp.min(jnp.broadcast_to(G_row, (v.shape[1], C)),
                                axis=1, keepdims=True))

    # inside the chunk: two plain products under the causal decay mask
    qs = [q_ref[:, u * d:(u + 1) * d] for u in range(heads)]
    nums, dens = [], []
    for q in qs:
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        a = s * s * decay
        dens.append(jnp.sum(a, axis=1, keepdims=True))
        nums.append(jnp.dot(a.astype(mm), v,
                            preferred_element_type=jnp.float32))

    # what came before the chunk, and the chunk folded into the state:
    # one pass over the state's groups, phi a group at a time
    q_all = jnp.concatenate([q.astype(jnp.float32) for q in qs],
                            axis=0)                     # [heads * C, d]
    vw = (v.astype(jnp.float32) * after).T.astype(mm)   # [dv, C]
    num_all = jnp.zeros((heads * C, v.shape[1]), jnp.float32)
    den_all = jnp.zeros((heads * C, d), jnp.float32)
    for (r, fq), (_, fk) in zip(_groups(q_all, roll),
                                _groups(k.astype(jnp.float32), roll)):
        at = slice(r * d, (r + 1) * d)
        s_r, z_r = s_ref[:, at], z_ref[:, at]           # [dv, d], [1, d]
        num_all = num_all + lax.dot_general(
            fq.astype(mm), s_r.astype(mm), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        den_all = den_all + fq * z_r
        s_ref[:, at] = last_rows * s_r + jnp.dot(
            vw, fk.astype(mm), preferred_element_type=jnp.float32)
        z_ref[:, at] = last * z_r + jnp.sum(fk * after, axis=0,
                                            keepdims=True)

    for u in range(heads):
        rows = slice(u * C, (u + 1) * C)
        num = nums[u] + before * num_all[rows]
        den = dens[u] + before * jnp.sum(den_all[rows], axis=1,
                                         keepdims=True)
        o_ref[:, u * v.shape[1]:(u + 1) * v.shape[1]] = jnp.where(
            den > 0, num / jnp.where(den > 0, den, 1.0),
            0.0).astype(o_ref.dtype)


def _chunk_pallas(q, k, v, g, state, block_t, interpret):
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    B, T, H, d = q.shape
    J, dv = k.shape[2], v.shape[3]
    R, D, n = H // J, feature_dim(d), T // block_t
    # the running log decay inside each chunk, as a column and as a row
    G = jnp.cumsum(g.astype(jnp.float32).reshape(B, n, block_t, J),
                   axis=2).transpose(0, 3, 1, 2)        # [B, J, n, C]

    def over_time(width):
        return pl.BlockSpec((None, block_t, width), lambda b, j, i: (b, i, j))

    def per_head(rows):
        return pl.BlockSpec((None, None, rows, D),
                            lambda b, j, i: (b, j, 0, 0))

    in_specs = [over_time(R * d), over_time(d), over_time(dv),
                pl.BlockSpec((None, None, None, block_t, 1),
                             lambda b, j, i: (b, j, i, 0, 0)),
                pl.BlockSpec((None, None, None, 1, block_t),
                             lambda b, j, i: (b, j, i, 0, 0))]
    args = [q.reshape(B, T, H * d), k.reshape(B, T, J * d),
            v.reshape(B, T, J * dv), G[..., None], G[..., None, :]]
    if state is not None:
        in_specs += [per_head(dv), per_head(1)]
        args += [state[0].astype(jnp.float32),
                 state[1].astype(jnp.float32)[:, :, None]]
    o, S, z = pl.pallas_call(
        functools.partial(_chunk_kernel, heads=R, from_zero=state is None,
                          interpret=interpret),
        grid=(B, J, n),
        in_specs=in_specs,
        out_specs=[over_time(R * dv), per_head(dv), per_head(1)],
        out_shape=[jax.ShapeDtypeStruct((B, T, H * dv), q.dtype),
                   jax.ShapeDtypeStruct((B, J, dv, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, J, 1, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=CHUNK_KERNEL,
    )(*args)
    return o.reshape(B, T, H, dv), (S, z[:, :, 0])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _chunk_forward_only(q, k, v, g, state, block_t, interpret):
    return _chunk_pallas(q, k, v, g, state, block_t, interpret)


def _chunk_forward_only_fwd(q, k, v, g, state, block_t, interpret):
    return (_chunk_pallas(q, k, v, g, state, block_t, interpret),
            (q, k, v, g, state))


def _chunk_forward_only_bwd(block_t, interpret, res, ct):
    q, k, v, g, state = res
    start = state if state is not None else zero_state(
        q.shape[0], k.shape[2], q.shape[3], v.shape[3])
    grads = jax.vjp(_retention_chunked, q, k, v, g, *start)[1](ct)
    return grads[:4] + (None if state is None else tuple(grads[4:]),)


_chunk_forward_only.defvjp(_chunk_forward_only_fwd, _chunk_forward_only_bwd)


def retention(q, k, v, g, state=None, *, block_t: int | None = None,
              interpret: bool = False):
    """Power retention over a sequence: q [B, T, H, d], k [B, T, J, d],
    v [B, T, J, dv], g [B, T, J] the log of each position's decay
    (<= 0), ``state`` (S [B, J, dv, D], z [B, J, D]) float32, what came
    before the first position (None: nothing). Returns (o [B, T, H, dv]
    at q's dtype, (S, z) after the last position).

    Which form runs is decided by what the caller can see, as in
    ``flash_attention``: the kernel ``retention_chunk`` where the
    default backend is the TPU (or under ``interpret``), T is a
    multiple of its chunk and the heads are whole lane tiles; the
    chunked XLA form otherwise. The kernel's gradient is the XLA
    form's, recomputed."""
    B, T, H, d = q.shape
    J, dv = k.shape[2], v.shape[3]
    block_t = block_t or _BLOCK_T
    if interpret:   # exercises the kernel at any size: no Mosaic tiling
        block_t, tiled = min(block_t, T), True
    else:
        tiled = _on_tpu() and not (d % 128 or dv % 128 or block_t % 128)
    if tiled and T % block_t == 0:
        return _chunk_forward_only(q, k, v, g, state, block_t, interpret)
    return _retention_chunked(q, k, v, g,
                              *(state or zero_state(B, J, d, dv)))


# ------------------------------------------------------- the step kernel

def _step_kernel(layer_ref, row_ref, park_ref, x_ref, xz_ref, v_ref, d_ref,
                 dz_ref, s_in, z_in, o_ref, den_ref, s_out, z_out, phi, *,
                 heads: int, sub: int, interpret: bool):
    """One (row b, K/V head j, tile of value rows c) grid step of a
    decode step's state pass. Prefetched: layer_ref [1]; row_ref [B],
    the row whose state block this row's steps name (its own where it
    is live); park_ref [B], 0 for a live row, else where its steps wait
    (1: on the first block of the first live row, 2: on the last block
    of the live row before it), so that the pipeline copies nothing for
    them.

    x_ref [8, d] float32: the head's ``heads`` queries, then its key,
    then zeros; xz_ref [heads + 1, J, d] the same of every K/V head of
    the row, a tile of heads each. v_ref [block_c, 1] the value's rows
    of this tile; d_ref [1, d] the head's decay on every lane and
    dz_ref [J, d] every head's. s_in / s_out [block_c, D], z_in / z_out
    [J, D]. o_ref [block_c, 8]: column u the numerator of query head u
    at this tile's value rows; den_ref [heads, J, d]: the denominators'
    partial sums a lane, made with z at the row's first step. ``phi``
    [8, D] scratch: phi of x_ref's rows, made with the head's first
    tile and read by the others."""
    import jax.experimental.pallas as pl

    b, j, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    roll = _lane_roll(interpret)
    d = x_ref.shape[1]
    live = park_ref[b] == 0

    @pl.when(live & (j == 0) & (c == 0))
    def _row():     # z of every head of the row, and the denominators
        decay = dz_ref[...]
        dens = [jnp.zeros(decay.shape, jnp.float32) for _ in range(heads)]
        # the key's groups, and each query's beside them
        for (r, fk), *fqs in zip(*(_groups(xz_ref[u], roll)
                                   for u in (heads, *range(heads)))):
            at = slice(r * d, (r + 1) * d)
            z = decay * z_in[:, at] + fk
            z_out[:, at] = z
            dens = [den + fq * z for den, (_, fq) in zip(dens, fqs)]
        for u, den in enumerate(dens):
            den_ref[u] = den

    @pl.when(live & (c == 0))
    def _head():
        for r, f in _groups(x_ref[...], roll):
            phi[:, r * d:(r + 1) * d] = f

    @pl.when(live)
    def _tile():
        decay = d_ref[...]                              # [1, d]
        lane = lax.broadcasted_iota(jnp.int32, (sub, 8), 1)

        def rows_of(i, _):      # ``sub`` value rows at a time: their
            # sums stay in registers over the groups
            rows = pl.ds(pl.multiple_of(i * sub, sub), sub)
            value = v_ref[rows, :]                      # [sub, 1]
            nums = [jnp.zeros((sub, d), jnp.float32) for _ in range(heads)]
            for r in range(d // 2 + 1):
                at = slice(r * d, (r + 1) * d)
                new = decay * s_in[rows, at] \
                    + value * phi[heads:heads + 1, at]
                s_out[rows, at] = new
                for u in range(heads):
                    nums[u] = nums[u] + new * phi[u:u + 1, at]
            out = jnp.zeros((sub, 8), jnp.float32)
            for u, num in enumerate(nums):
                out = jnp.where(lane == u,
                                jnp.sum(num, axis=1, keepdims=True), out)
            o_ref[rows, :] = out

        lax.fori_loop(0, s_in.shape[0] // sub, rows_of, None)

    # no live row at all: the one block every step names goes back as
    # it came
    @pl.when((b == 0) & (j == 0) & (c == 0) & jnp.logical_not(live))
    def _untouched():
        s_out[...] = s_in[...]
        z_out[...] = z_in[...]


def _step_pallas(q, k, v, g, S, z, layer, active, block_c, interpret):
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    B, H, d = q.shape
    L, _, J, dv, D = S.shape
    R, tiles = H // J, dv // block_c
    # the head's queries, then its key, then zeros: one tile of rows
    x = jnp.concatenate(
        [q.reshape(B, J, R, d), k[:, :, None],
         jnp.zeros((B, J, 8 - R - 1, d), q.dtype)],
        axis=2).astype(jnp.float32)
    value = v.astype(jnp.float32)[..., None]                    # [B,J,dv,1]
    decay = jnp.broadcast_to(
        jnp.exp(g.astype(jnp.float32))[..., None], (B, J, d))
    # a row left out names a block the pipeline already holds: the last
    # of the live row before it, or the first of the first live row
    rows = jnp.arange(B)
    before = lax.cummax(jnp.where(active, rows, -1))
    first = jnp.argmax(active)
    row = jnp.where(before >= 0, before, first).astype(jnp.int32)
    park = jnp.where(active, 0, jnp.where(before >= 0, 2, 1)).astype(
        jnp.int32)

    def state_index(b, j, c, layer_ref, row_ref, park_ref):
        parked = park_ref[b]
        return (layer_ref[0], row_ref[b],
                jnp.where(parked == 0, j, (parked - 1) * (J - 1)),
                jnp.where(parked == 0, c, (parked - 1) * (tiles - 1)), 0)

    def z_index(b, j, c, layer_ref, row_ref, park_ref):
        return (layer_ref[0], row_ref[b], 0, 0)

    def head_index(b, j, c, *_):
        return (b, j, 0, 0)

    def row_index(b, j, c, *_):
        return (b, 0, 0, 0)

    def decays_index(b, j, c, *_):
        return (b, 0, 0)

    def tile_index(b, j, c, *_):
        return (b, j, c, 0)

    s_spec = pl.BlockSpec((None, None, None, block_c, D), state_index)
    z_spec = pl.BlockSpec((None, None, J, D), z_index)
    num, den, S, z = pl.pallas_call(
        functools.partial(_step_kernel, heads=R, sub=min(_SUB_C, block_c),
                          interpret=interpret),
        out_shape=[jax.ShapeDtypeStruct((B, J, dv, 8), jnp.float32),
                   jax.ShapeDtypeStruct((B, R, J, d), jnp.float32),
                   jax.ShapeDtypeStruct(S.shape, S.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, J, tiles),
            in_specs=[pl.BlockSpec((None, None, 8, d), head_index),
                      pl.BlockSpec((None, R + 1, J, d), row_index),
                      pl.BlockSpec((None, None, block_c, 1), tile_index),
                      pl.BlockSpec((None, None, 1, d), head_index),
                      pl.BlockSpec((None, J, d), decays_index),
                      s_spec, z_spec],
            out_specs=[pl.BlockSpec((None, None, block_c, 8), tile_index),
                       pl.BlockSpec((None, R, J, d), row_index),
                       s_spec, z_spec],
            scratch_shapes=[pltpu.VMEM((8, D), jnp.float32)]),
        # the state arrays are written where they lie (operands 8 and 9,
        # the three prefetched scalars counted)
        input_output_aliases={8: 2, 9: 3},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=STEP_KERNEL,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), row, park, x,
      x[:, :, :R + 1].swapaxes(1, 2), value, decay[:, :, None], decay, S, z)
    num = num[..., :R].swapaxes(2, 3)                           # [B,J,R,dv]
    den = jnp.sum(den, axis=-1).swapaxes(1, 2)                  # [B,J,R]
    return _ratio(num, den).reshape(B, H, dv).astype(q.dtype), S, z


def step_block(dv: int, d: int, heads: int, interpret: bool = False):
    """Value rows of the state in a grid step of the step kernel, None
    where this shape runs the XLA form: off the TPU, heads that are no
    whole lane tiles, more query heads a K/V head than a tile of rows
    holds beside the key."""
    block = next((b for b in (_BLOCK_C, _SUB_C) if dv % b == 0), None)
    if interpret:
        return (block or dv) if heads < 8 else None
    if not _on_tpu() or d % 128 or block is None or heads >= 8:
        return None
    return block


def retention_step(q, k, v, g, S, z, layer, active, *,
                   interpret: bool = False):
    """A decode step's retention for one layer of a run, over the run's
    carried state where it lies: one token a row, q [B, H, d], k
    [B, J, d], v [B, J, dv], g [B, J] (log decay), against layer
    ``layer`` (a traced index) of S [L, B, J, dv, D] and z [L, B, J, D];
    ``active`` bool [B]. Returns (o [B, H, dv] at q's dtype, S, z): an
    active row's state advanced by its token, **a row left out kept bit
    for bit** (its o is garbage).

    On the TPU (or under ``interpret``) the kernel ``retention_step``
    (module docstring): S and z are its operands whole and its results
    in place; elsewhere ``retention_step_xla`` over the layer's slice,
    selected and written back whole."""
    block_c = step_block(S.shape[3], q.shape[2], q.shape[1] // k.shape[1],
                         interpret)
    if block_c is not None:
        return _step_pallas(q, k, v, g, S, z, layer, active, block_c,
                            interpret)
    old_S = lax.dynamic_index_in_dim(S, layer, keepdims=False)
    old_z = lax.dynamic_index_in_dim(z, layer, keepdims=False)
    o, new_S, new_z = retention_step_xla(q, k, v, g, old_S, old_z)
    S = lax.dynamic_update_slice(S, jnp.where(
        active[:, None, None, None], new_S, old_S)[None], (layer, 0, 0, 0, 0))
    z = lax.dynamic_update_slice(z, jnp.where(
        active[:, None, None], new_z, old_z)[None], (layer, 0, 0, 0))
    return o, S, z
