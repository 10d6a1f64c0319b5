"""The recurrent half of a Mamba (selective state-space) layer: the
causal depthwise convolution, the selective scan over a sequence, and
the one-token recurrence of a decode step.

For one sequence, with C channels and a state of N values a channel::

    s_t = exp(dt_t (x) A) * s_{t-1} + (dt_t * u_t) (x) B_t      s: [N, C]
    y_t = sum_n s_t[n] * C_t[n] + D * u_t

``u`` and ``dt`` are [.., T, C], ``B`` and ``C`` [.., T, N], ``A``
[N, C] (negative), ``D`` [C]. The state lies **[N, C]**, channels
along the lanes: C is thousands and N is 16, and an array whose last
dimension is 16 is padded eightfold in the chip's tiled memory. The
state and everything that feeds it are float32 (a decay ``exp(dt A)``
near 1 multiplied in over hundreds of steps does not survive
bfloat16); ``u``, ``B``, ``C`` and ``y`` keep the model's dtype.

``selective_scan`` has two forms behind one function, as
``ops.attention.flash_attention`` has:

* XLA, chunked over T (``_scan_chunked``): a ``lax.scan`` over chunks
  with an associative scan inside each, so that no array is longer
  than a chunk. Every platform, every T, and the gradient.
* a Pallas kernel named ``ssm_scan`` (a device trace names it so): the
  grid walks chunks of T in order and, inside a chunk, tiles of C; the
  whole state stays in VMEM scratch from the first chunk to the last;
  a tile's steps run one after another on the vector unit with the
  tile's state in registers. On the TPU where T is a multiple of 128
  and C of the channel tile. A gradient through it recomputes the
  chunked form and differentiates that.

No step of the recurrence is a matrix product (the decay is per
channel *and* per state index), so the kernel is bound by the vector
unit, not by memory: what it saves over XLA is a loop iteration's
overhead a position (a ``lax.scan`` over T) or several passes over
``[T, N, C]`` float32 in memory (an associative scan over T).

A decode step is the other way round: one position a row, and the
state of every row, ``[rows, N, C]`` float32 a layer, to be read,
advanced and written. ``carried_step`` takes a run's whole carried
state ``[L, rows, N, C]`` and the layer's index, as
``retention.retention_step`` does, and has two forms too:

* XLA: the layer's slice through ``selective_step``, the one statement
  of a step's mathematics, selected by ``active`` and written back with
  a ``dynamic_update_slice``. XLA makes the sum over N a fusion of its
  own, which reads the state a second time.
* a Pallas kernel named ``ssm_step``: the state array is its operand
  where it lies (aliased to its result), the layer's index and the
  rows' liveness prefetched scalars; the grid walks blocks of whole
  rows, and **each state value is read once, advanced,
  multiplied into y and written back where it lay**; a row left out
  goes back as it came. Bound by memory: the state's bytes, once each
  way.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import _on_tpu

KERNEL = "ssm_scan"         # the pallas_calls' ``name=``
STEP_KERNEL = "ssm_step"
_CHUNK = 64                 # positions in a chunk of the XLA form
_BLOCK_T = 128              # positions in a grid step of the kernel
_BLOCK_C = 512              # channels in a grid step of the kernel
# the step kernel: rows of the state in a grid step (a tile of two-byte
# rows, two of four-byte ones), each with all its channels, so that a
# block is one stretch of memory: [16, 16, 5120] float32 is 5.2 MB, in
# and out and each twice in flight, which with the rows' small operands
# has to fit the kernel's share of fast memory; channels in a pass
# inside it, whose values stay in registers
_STEP_ROWS = 16
_STEP_SUB_C = 256
_LANES = 128
_VMEM_LIMIT = 32 * 2 ** 20


class Recurrence(NamedTuple):
    """What a Mamba layer's caller hands ``transformer.block`` in the
    place of ``attend``: the two parts of the mixer that carry state
    from one position to the next. ``conv(u, w, b) -> (x, tail)`` is the
    causal convolution with its bias and silu over u [B, T, C] and the
    last K - 1 rows that went into it; ``scan(x, dt, A, B, C, D) -> (y, s)``
    the selective scan and the state after the last position. Training
    starts both from nothing and drops what comes back; a prefill keeps
    it; a decode step starts from what the cache holds."""
    conv: Callable
    scan: Callable


def causal_conv(u, w, b, tail=None):
    """Depthwise causal convolution over time and the silu behind it
    (the two are one operation in the family's own kernels, and one
    rounding here). u [B, T, C] at the model's dtype, w [K, C]
    (``w[K - 1]`` multiplies the position itself), b [C]; ``tail``
    [B, K - 1, C] the rows of u before the first (None: zeros, the
    start of a sequence). Returns (silu(conv(u) + b) [B, T, C] at u's
    dtype, computed in float32; the last K - 1 rows of tail + u, which
    a next call continues from)."""
    B, T, C = u.shape
    K = w.shape[0]
    if tail is None:
        tail = jnp.zeros((B, K - 1, C), u.dtype)
    rows = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
    x = b.astype(jnp.float32)
    for k in range(K):
        x = x + rows[:, k:k + T].astype(jnp.float32) * w[k].astype(
            jnp.float32)
    return jax.nn.silu(x).astype(u.dtype), rows[:, T:]


def selective_step(u, dt, A, B, C, D, state):
    """One position a row. u, dt [R, C], B, C [R, N], state [R, N, C]
    float32 -> (y [R, C] at u's dtype, the new state)."""
    u32, dt = u.astype(jnp.float32), dt.astype(jnp.float32)
    state = (jnp.exp(dt[:, None, :] * A) * state
             + (dt * u32)[:, None, :] * B.astype(jnp.float32)[:, :, None])
    y = jnp.sum(state * C.astype(jnp.float32)[:, :, None], axis=1)
    return (y + D.astype(jnp.float32) * u32).astype(u.dtype), state


def _scan_chunked(u, dt, A, B, C, D, state, chunk: int = _CHUNK):
    """The XLA form. Positions beyond T in the last chunk are fed
    dt = 0, under which the state stands still."""
    Bt, T, Cn = u.shape
    N = A.shape[0]
    chunk = min(chunk, T)
    n = -(-T // chunk)

    def chunks(t):
        t = jnp.pad(t.astype(jnp.float32),
                    ((0, 0), (0, n * chunk - T), (0, 0)))
        return t.reshape(Bt, n, chunk, t.shape[-1]).swapaxes(0, 1)

    def combine(earlier, later):
        return (earlier[0] * later[0], later[0] * earlier[1] + later[1])

    def one(s, xs):
        uc, dtc, bc, cc = xs                            # [Bt, chunk, .]
        decay = jnp.exp(dtc[:, :, None, :] * A)         # [Bt, chunk, N, C]
        fed = (dtc * uc)[:, :, None, :] * bc[..., None]
        through, added = lax.associative_scan(combine, (decay, fed), axis=1)
        states = through * s[:, None] + added
        y = jnp.einsum("bqnc,bqn->bqc", states, cc) + D * uc
        return states[:, -1], y

    state, ys = lax.scan(one, state.astype(jnp.float32),
                         (chunks(u), chunks(dt), chunks(B), chunks(C)))
    y = ys.swapaxes(0, 1).reshape(Bt, n * chunk, Cn)[:, :T]
    return y.astype(u.dtype), state


def _scan_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, s0_ref,
                 y_ref, s_ref, state, y_rows, *, sub: int):
    """One grid step: ``block_t`` positions of one tile of channels.
    Blocks: u, dt, y [1, block_t, block_c]; b, c [1, block_t, N]; a, s0,
    s [.., N, block_c]; d [1, block_c]. ``state`` [tiles, N, block_c]
    float32 is every tile's state between its chunks; ``y_rows``
    [sub, block_c] gathers ``sub`` positions' outputs, the rows of one
    tile of y's dtype, so that y is stored a whole tile at a time."""
    import jax.experimental.pallas as pl

    chunk, tile = pl.program_id(1), pl.program_id(2)
    block_t, n = b_ref.shape[1], b_ref.shape[2]

    @pl.when(chunk == 0)
    def _start():
        state[tile] = s0_ref[0]

    A, D = a_ref[...], d_ref[...]
    # a position's B and C come as a row [1, N] and are needed down the
    # sublanes [N, 1]: the row over the diagonal, summed along the lanes
    diagonal = (lax.broadcasted_iota(jnp.int32, (n, n), 0)
                == lax.broadcasted_iota(jnp.int32, (n, n), 1))

    def column(row):
        return jnp.sum(jnp.where(diagonal, row, 0.0), axis=1, keepdims=True)

    def rows(i, s):
        at = pl.multiple_of(i * sub, sub)
        u = u_ref[0, pl.ds(at, sub), :].astype(jnp.float32)
        dt = dt_ref[0, pl.ds(at, sub), :].astype(jnp.float32)
        b = b_ref[0, pl.ds(at, sub), :].astype(jnp.float32)
        c = c_ref[0, pl.ds(at, sub), :].astype(jnp.float32)
        for j in range(sub):
            u_t, dt_t = u[j:j + 1], dt[j:j + 1]                 # [1, C]
            s = jnp.exp(dt_t * A) * s + (dt_t * u_t) * column(b[j:j + 1])
            y_rows[j:j + 1, :] = (
                jnp.sum(s * column(c[j:j + 1]), axis=0, keepdims=True)
                + D * u_t)
        y_ref[0, pl.ds(at, sub), :] = y_rows[...].astype(y_ref.dtype)
        return s

    s = lax.fori_loop(0, block_t // sub, rows, state[tile])
    state[tile] = s
    # every visit leaves the state so far: the last one's is the result
    s_ref[0] = s


def _tile_rows(dtype) -> int:
    """Rows of one tile of ``dtype`` in the chip's memory (8 of four
    bytes, 16 of two): the positions the kernel stores together."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def _scan_pallas(u, dt, A, B, C, D, state, block_t, block_c, interpret):
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    Bt, T, Cn = u.shape
    N = A.shape[0]
    sub = _tile_rows(u.dtype)
    grid = (Bt, T // block_t, Cn // block_c)

    def over_time(width):
        return pl.BlockSpec((1, block_t, width), lambda b, i, j: (b, i, 0))

    seq = pl.BlockSpec((1, block_t, block_c), lambda b, i, j: (b, i, j))
    per_row = pl.BlockSpec((1, N, block_c), lambda b, i, j: (b, 0, j))
    y, s = pl.pallas_call(
        functools.partial(_scan_kernel, sub=sub),
        grid=grid,
        in_specs=[seq, seq,
                  pl.BlockSpec((N, block_c), lambda b, i, j: (0, j)),
                  over_time(N), over_time(N),
                  pl.BlockSpec((1, block_c), lambda b, i, j: (0, j)),
                  per_row],
        out_specs=[seq, per_row],
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype),
                   jax.ShapeDtypeStruct((Bt, N, Cn), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((Cn // block_c, N, block_c), jnp.float32),
                        pltpu.VMEM((sub, block_c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name=KERNEL,
    )(u, dt.astype(jnp.float32), A.astype(jnp.float32), B, C,
      D.astype(jnp.float32).reshape(1, Cn), state.astype(jnp.float32))
    return y, s


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _scan_forward_only(u, dt, A, B, C, D, state, block_t, block_c,
                       interpret):
    return _scan_pallas(u, dt, A, B, C, D, state, block_t, block_c,
                        interpret)


def _scan_forward_only_fwd(u, dt, A, B, C, D, state, block_t, block_c,
                           interpret):
    return (_scan_pallas(u, dt, A, B, C, D, state, block_t, block_c,
                         interpret), (u, dt, A, B, C, D, state))


def _scan_forward_only_bwd(block_t, block_c, interpret, res, g):
    return jax.vjp(_scan_chunked, *res)[1](g)


_scan_forward_only.defvjp(_scan_forward_only_fwd, _scan_forward_only_bwd)


def selective_scan(u, dt, A, B, C, D, state=None, *,
                   block_t: int | None = None, block_c: int | None = None,
                   interpret: bool = False):
    """The selective scan over a sequence: u [Bt, T, C], dt [Bt, T, C]
    (after its softplus), A [N, C], B and C [Bt, T, N], D [C], ``state``
    [Bt, N, C] float32 the state before the first position (None:
    zeros). Returns (y [Bt, T, C] at u's dtype, the state after the last
    position, float32).

    Which form runs is decided by what the caller can see, as in
    ``flash_attention``: the kernel ``ssm_scan`` where the default
    backend is the TPU (or under ``interpret``), T is a multiple of its
    block of positions and C of its tile of channels; the chunked XLA
    form otherwise. The kernel's gradient is the XLA form's,
    recomputed."""
    Bt, T, Cn = u.shape
    if state is None:
        state = jnp.zeros((Bt, A.shape[0], Cn), jnp.float32)
    block_t, block_c = block_t or _BLOCK_T, block_c or _BLOCK_C
    if interpret:   # exercises the kernel at any size: no Mosaic tiling
        block_t, block_c = min(block_t, T), min(block_c, Cn)
    if (not (interpret or _on_tpu()) or T % block_t or Cn % block_c
            or block_t % _tile_rows(u.dtype)):
        return _scan_chunked(u, dt, A, B, C, D, state)
    return _scan_forward_only(u, dt, A, B, C, D, state, block_t, block_c,
                              interpret)


# ------------------------------------------------------- the step kernel

def _step_kernel(layer_ref, live_ref, u_ref, dt_ref, a_ref, b_ref, c_ref,
                 d_ref, s_in, y_ref, s_out, y_rows, *, sub_c: int):
    """One grid step of a decode step's state pass: a block of rows,
    whole. Prefetched: layer_ref [1] (the index map's alone), live_ref
    [rows of the state] int32. Blocks: u, dt, y [rows, C]; a [N, C]; d
    [1, C]; b, c [rows, N, lanes], a row's B and C down the sublanes and
    the same on every lane; s_in, s_out [rows, N, C]. ``y_rows``
    [rows, sub_c] float32 gathers the rows' outputs, so that y is
    stored whole tiles at a time."""
    import jax.experimental.pallas as pl

    rows, _, Cn = s_in.shape
    first = pl.program_id(0) * rows
    wide = sub_c // b_ref.shape[2]

    def channels(j, _):
        at = pl.ds(pl.multiple_of(j * sub_c, sub_c), sub_c)
        A = a_ref[:, at]
        u = u_ref[:, at].astype(jnp.float32)
        dt = dt_ref[:, at]
        fed = dt * u
        for r in range(rows):
            old = s_in[r, :, at]                                # [N, sub_c]
            b = jnp.tile(b_ref[r].astype(jnp.float32), (1, wide))
            c = jnp.tile(c_ref[r].astype(jnp.float32), (1, wide))
            new = jnp.exp(dt[r:r + 1] * A) * old + fed[r:r + 1] * b
            y_rows[r:r + 1, :] = jnp.sum(new * c, axis=0, keepdims=True)
            # a row left out goes back bit for bit as it came
            s_out[r, :, at] = jnp.where(live_ref[first + r] != 0, new, old)
        y_ref[:, at] = (y_rows[...] + d_ref[:, at] * u).astype(y_ref.dtype)

    lax.fori_loop(0, Cn // sub_c, channels, None)


def _step_pallas(u, dt, A, B, C, D, states, layer, active, block_r, sub_c,
                 interpret):
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    R, Cn = u.shape
    N = A.shape[0]
    lanes = min(_LANES, sub_c)

    def down_the_sublanes(t):   # [R, N] -> [R, N, lanes], lane-dense
        return jnp.broadcast_to(t[:, :, None], (R, N, lanes))

    by_row = pl.BlockSpec((block_r, Cn), lambda i, *_: (i, 0))
    per_row = pl.BlockSpec((block_r, N, lanes), lambda i, *_: (i, 0, 0))
    s_spec = pl.BlockSpec((None, block_r, N, Cn),
                          lambda i, layer_ref, live_ref: (layer_ref[0], i,
                                                          0, 0))
    y, states = pl.pallas_call(
        functools.partial(_step_kernel, sub_c=sub_c),
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(R // block_r,),
            in_specs=[by_row, by_row,
                      pl.BlockSpec((N, Cn), lambda i, *_: (0, 0)),
                      per_row, per_row,
                      pl.BlockSpec((1, Cn), lambda i, *_: (0, 0)),
                      s_spec],
            out_specs=[by_row, s_spec],
            scratch_shapes=[pltpu.VMEM((block_r, sub_c), jnp.float32)]),
        # the state array is written where it lies (operand 8, the two
        # prefetched scalars counted)
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=STEP_KERNEL,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), active.astype(jnp.int32),
      u, dt.astype(jnp.float32), A.astype(jnp.float32),
      down_the_sublanes(B), down_the_sublanes(C),
      D.astype(jnp.float32).reshape(1, Cn), states)
    return y, states


def step_blocks(rows: int, N: int, Cn: int, interpret: bool = False):
    """(rows of the state in a grid step of the step kernel, channels
    in a pass inside it), None where this shape runs the XLA form: off
    the TPU, a state that is no whole number of sublane tiles, channels
    that are no whole passes, rows that are no whole blocks, a block of
    rows of which fast memory does not hold four and the small operands
    beside them (reckoned as a fifth)."""
    if interpret:   # exercises the kernel at any size: no Mosaic tiling
        block_r, sub_c = min(_STEP_ROWS, rows), min(_STEP_SUB_C, Cn)
        return None if rows % block_r or Cn % sub_c else (block_r, sub_c)
    if (not _on_tpu() or N % 8 or Cn % _STEP_SUB_C or rows % _STEP_ROWS
            or 5 * 4 * _STEP_ROWS * N * Cn > _VMEM_LIMIT):
        return None
    return _STEP_ROWS, _STEP_SUB_C


def carried_step(u, dt, A, B, C, D, states, layer, active, *,
                 interpret: bool = False):
    """A decode step's recurrence for one layer of a run, over the run's
    carried state where it lies: one position a row, u and dt [R, C], B
    and C [R, N], against layer ``layer`` (a traced index) of ``states``
    [L, R, N, C] float32; ``active`` bool [R]. Returns (y [R, C] at u's
    dtype, ``states``): an active row's state advanced by its token, **a
    row left out kept bit for bit** (its y is garbage).

    On the TPU (or under ``interpret``) the kernel ``ssm_step`` (module
    docstring): the state array is its operand whole and its result in
    place; elsewhere ``selective_step`` over the layer's slice, selected
    and written back whole."""
    blocks = step_blocks(u.shape[0], A.shape[0], u.shape[1], interpret)
    if blocks is not None:
        return _step_pallas(u, dt, A, B, C, D, states, layer, active,
                            *blocks, interpret)
    old = lax.dynamic_index_in_dim(states, layer, keepdims=False)
    y, new = selective_step(u, dt, A, B, C, D, old)
    return y, lax.dynamic_update_slice(states, jnp.where(
        active[:, None, None], new, old)[None], (layer, 0, 0, 0))
