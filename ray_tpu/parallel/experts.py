"""One chip's share of a sparse-expert layer: route over every expert
the model has, compute the part of the result that the experts held
here give.

The layout is the wide expert-parallel one: a layer's experts are spread
over the chips that share it, a contiguous range to each, and the
router, which every chip holds whole, scores all of them. ``route``
picks each row's ``k`` experts and their weights, normalised over all
``k`` chosen, held here or not. ``expert_ffn`` computes, for the rows
routed to the experts held here, the weighted SwiGLU of each and sums
them by row; what the absent experts would add is another chip's part.
On several chips the same layer would exchange rows before and sums
after; this module has no exchange and nothing that stands in for one.

No capacity and no dropped row, and two forms of the same sums, as
``flash_attention`` has two:

- **The kernel** ``expert_ffn`` (a Pallas call, on the TPU where the
  shape fits, ``expert_block``: a decode step's rows): the rows ``h``,
  their choices and the float32 result stay in VMEM for the whole call,
  and a grid of (held expert, chunk of the expert width) streams each
  expert's gate, up and down matrices from where they lie in the
  layers' stack, back to back and double buffered. **An expert's
  matrices are read once, an unhit expert's not at all**: its grid
  steps name the blocks of the last live expert before it, which the
  pipeline already holds. A live expert's steps multiply every row
  (up to 128 rows' products take less time than the weights' copies)
  and sum the down product over the chunks in float32; after the last
  chunk the rows routed to the expert add it, weighted, into theirs.
  Nothing is sorted, gathered or scattered.
- **The tile loop** (XLA): the assignments (row, expert) held here are
  sorted by expert and cut into tiles of ``tile`` rows, each of one
  expert alone, and a ``fori_loop`` runs over the tiles that exist.
  Shapes are static (at most rows x k assignments, so at most ``rows *
  k / tile + experts`` tiles) and the work is in proportion to the rows
  routed here, never experts x rows; a tile reads its expert's three
  matrices once. It runs on every other platform and shape (a
  prefill's thousands of rows), in the gradient of both, and as the
  kernel's oracle.

Both round alike: bfloat16 operands, float32 products and sums, the
gate and up products and the SwiGLU rounded to the model's dtype before
the down product, experts added into a row in ascending order. They
differ only in the order of the down product's float32 partial sums.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import _on_tpu

KERNEL = "expert_ffn"


def route(h, router, bias, k: int):
    """Which ``k`` experts each row of h [T, D] goes to, and with what
    weights. ``router`` [D, N] scores all N experts through a sigmoid;
    the choice is the ``k`` largest of score + ``bias`` [N] (a
    correction that steers the load and carries no weight); the weights
    are the chosen scores over their sum. The product and the sigmoid
    are float32. Returns (chosen [T, k] int32, weights [T, k]
    float32)."""
    scores = jax.nn.sigmoid(jnp.dot(
        h, router, precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32))
    _, chosen = lax.top_k(scores + bias.astype(jnp.float32), k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, picked / jnp.sum(picked, axis=-1, keepdims=True)


def expert_tile(rows: int, k: int, n_experts: int) -> int:
    """Rows in a tile of ``expert_ffn``: about twice what an expert
    expects of ``rows`` rows that each choose ``k`` of ``n_experts``, a
    power of two from 16 (a bfloat16 tile's sublanes) to 128 (the
    matrix unit's rows), so that most experts take one tile and read
    their weights once."""
    expected = max(1, 2 * rows * k // n_experts)
    return max(16, min(128, 1 << (expected - 1).bit_length()))


# Columns of the expert width in a grid step of the kernel; a width it
# does not divide takes the tile loop. The kernel multiplies every row
# by each hit expert: at most ``_ROWS`` rows, so that a step's products
# (as many operations a byte of its weights as rows) stay under its
# copies (a v5e does 240 a byte); what it holds in VMEM for the whole
# call, a row of h, of the result and of the down product's sums (h and
# the result double buffered), at most ``_RESIDENT_BYTES``; and the VMEM
# the call may take, beside two sets of weight blocks (3 x 4 MB at 4096
# x 512 in bfloat16, 24 MB double buffered).
_FFN_BLOCK = 512
_ROWS = 128
_RESIDENT_BYTES = 16 * 2 ** 20
_VMEM_LIMIT = 64 * 2 ** 20


def expert_block(rows: int, width: int, ffn: int, dtype) -> int | None:
    """Columns of the expert width ``ffn`` in a grid step of
    ``expert_ffn``'s kernel for ``rows`` rows of ``width`` at ``dtype``,
    or None where the shape takes the tile loop: an operand type other
    than bfloat16, rows that are no whole tiles of 8 or more than
    ``_ROWS``, a width no whole tiles of 128 lanes, an expert width
    that is no whole blocks of ``_FFN_BLOCK``, or rows that do not stay
    in VMEM beside the weights (a decode step's 128 rows of 4096 hold
    8 MB there; a prefill's 512 and more take the loop)."""
    resident = rows * width * (2 * jnp.dtype(dtype).itemsize + 2 * 4 + 4)
    if (jnp.dtype(dtype) != jnp.bfloat16 or rows % 8 or rows > _ROWS
            or width % 128 or ffn % _FFN_BLOCK
            or resident > _RESIDENT_BYTES):
        return None
    return _FFN_BLOCK


def expert_ffn(h, chosen, weights, w_gate, w_up, w_down, *, first: int,
               held: int, tile: int, base=0, interpret: bool = False):
    """The held experts' part of the layer for h [T, D]: the sum over
    the chosen experts e in [first, first + held) of weights *
    SwiGLU_e(h), as float32 [T, D], and the rows each held expert got,
    int32 [held]. ``chosen``/``weights`` are ``route``'s. ``w_gate``/
    ``w_up`` [.., D, F] and ``w_down`` [.., F, D] hold the layer's
    held experts at rows [base, base + held): a caller that stacks
    layers hands the stack whole with the layer's ``base`` (traced), so
    that each expert's matrices are read where they lie; a layer's
    slice taken first would be a copy of all of them. ``tile`` is the
    loop's rows a tile (``expert_tile``).

    The kernel on the TPU (or under ``interpret``) where
    ``expert_block`` gives the shape a block, else the tile loop
    (module docstring); the gradient is the loop's either way."""
    block = expert_block(h.shape[0], h.shape[1], w_gate.shape[-1], h.dtype)
    counts = _counts(chosen, first, held)
    if block is None or not (interpret or _on_tpu()):
        return _tile_loop(h, weights, w_gate, w_up, w_down, base,
                          _order(chosen, first, held), counts,
                          chosen.shape[1], tile), counts
    local = (chosen - first).astype(jnp.int32)
    return _stream(h, local, weights, w_gate, w_up, w_down,
                   jnp.asarray(base, jnp.int32), counts, tile, block,
                   interpret), counts


def _counts(chosen, first: int, held: int):
    """The rows each held expert got, int32 [held]."""
    local = chosen.reshape(-1, 1) - first
    return jnp.sum(local == jnp.arange(held), axis=0, dtype=jnp.int32)


def _order(chosen, first: int, held: int):
    """The assignment ids [T * k] sorted by held expert, those of absent
    ones last."""
    local = chosen.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < held), local, held)
    return jnp.argsort(key, stable=True)


def _tile_loop(h, weights, w_gate, w_up, w_down, base, order, counts,
               k: int, tile: int):
    """The XLA form: a loop over the tiles that exist, each gathering
    its rows, computing its expert's SwiGLU and adding the weighted
    result into its rows."""
    A, E = order.shape[0], counts.shape[0]
    tiles_of = (counts + tile - 1) // tile
    tile_end = jnp.cumsum(tiles_of)
    row_start = jnp.cumsum(counts) - counts
    flat_weights = weights.reshape(A)
    lane = jnp.arange(tile)

    def one_tile(j, out):
        e = jnp.sum(tile_end <= j)          # whose tile the j-th is
        within = (j - (tile_end[e] - tiles_of[e])) * tile + lane
        valid = within < counts[e]
        ids = order[jnp.minimum(row_start[e] + within, A - 1)]
        rows = ids // k
        x = h[rows]                                             # [tile, D]
        gate, up, down = (lax.dynamic_index_in_dim(w, base + e, keepdims=False)
                          for w in (w_gate, w_up, w_down))
        g = jax.nn.silu((x @ gate).astype(jnp.float32))
        u = (x @ up).astype(jnp.float32)
        y = ((g * u).astype(h.dtype) @ down).astype(jnp.float32)
        weight = jnp.where(valid, flat_weights[ids], 0.0)
        return out.at[rows].add(y * weight[:, None])

    return lax.fori_loop(0, tile_end[-1], one_tile,
                         jnp.zeros(h.shape, jnp.float32))


def _parking(counts):
    """(src, park) [E] of held experts with ``counts`` rows: an unhit
    expert's grid steps name blocks the pipeline already holds, the
    last chunk of the live expert before it (park 2), or the first
    chunk of the first live one (park 1); a live one its own (0)."""
    live = counts > 0
    before = lax.cummax(jnp.where(live, jnp.arange(counts.shape[0]), -1))
    src = jnp.where(before >= 0, before, jnp.argmax(live))
    park = jnp.where(live, 0, jnp.where(before >= 0, 2, 1))
    return src.astype(jnp.int32), park.astype(jnp.int32)


def _named_block(e, f, src, park, chunks: int):
    """(expert, chunk) whose weight blocks grid step (e, f) names."""
    parked = park[e]
    return src[e], jnp.where(parked == 0, f, (parked - 1) * (chunks - 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def _stream(h, local, weights, w_gate, w_up, w_down, base, counts,
            tile: int, block: int, interpret: bool):
    """The kernel's form, ``local`` [T, k] the chosen experts' ids among
    those held here: find where each held expert's grid steps wait, and
    call the kernel. Its gradient is the tile loop's (of ``tile``
    rows)."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    T, D = h.shape
    E, chunks = counts.shape[0], w_gate.shape[-1] // block
    src, park = _parking(counts)

    def across(e, f, base_ref, src_ref, park_ref):      # gate, up
        expert, chunk = _named_block(e, f, src_ref, park_ref, chunks)
        return base_ref[0] + expert, 0, chunk

    def down(e, f, base_ref, src_ref, park_ref):
        expert, chunk = _named_block(e, f, src_ref, park_ref, chunks)
        return base_ref[0] + expert, chunk, 0

    def whole(e, f, *_):
        return 0, 0

    return pl.pallas_call(
        _stream_kernel,
        out_shape=jax.ShapeDtypeStruct((T, D), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(E, chunks),
            in_specs=[pl.BlockSpec((T, D), whole),
                      pl.BlockSpec(local.shape, whole),
                      pl.BlockSpec(local.shape, whole),
                      pl.BlockSpec((None, D, block), across),
                      pl.BlockSpec((None, D, block), across),
                      pl.BlockSpec((None, block, D), down)],
            out_specs=pl.BlockSpec((T, D), whole),
            scratch_shapes=[pltpu.VMEM((T, D), jnp.float32)]),
        # the result is summed over every grid step
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=KERNEL,
    )(jnp.reshape(base, (1,)), src, park, h, local,
      weights.astype(jnp.float32), w_gate, w_up, w_down)


def _stream_fwd(*args):
    return _stream(*args), args[:8]


def _stream_bwd(tile, block, interpret, saved, d_out):
    h, local, weights, w_gate, w_up, w_down, base, counts = saved
    order = _order(local, 0, counts.shape[0])
    _, pull = jax.vjp(
        lambda h, weights, *mats: _tile_loop(
            h, weights, *mats, base, order, counts, local.shape[1], tile),
        h, weights, w_gate, w_up, w_down)
    d_h, d_weights, *d_mats = pull(d_out)
    return (d_h, None, d_weights, *d_mats, None, None)


_stream.defvjp(_stream_fwd, _stream_bwd)


def _stream_kernel(base_ref, src_ref, park_ref, h_ref, local_ref, wts_ref,
                   gate_ref, up_ref, down_ref, out_ref, acc_ref):
    """Grid step (held expert e, chunk f of its width). Prefetched:
    base_ref [1], the layer's first expert in the stack; src_ref and
    park_ref [E], whose blocks e's steps name and where they wait (0:
    its own, e live; 1: the first chunk of src; 2: its last). h_ref
    [T, D] and out_ref [T, D] float32 stay for the whole call, as do
    local_ref and wts_ref [T, k], the rows' chosen experts (ids among
    those held) and their weights; gate_ref and up_ref [D, block],
    down_ref [block, D]: e's chunk f. acc_ref [T, D] float32: e's down
    product, summed over the chunks. Every row is multiplied, and only
    those routed to e are added: a step's products stay under its
    copies (``_ROWS``), and no row is gathered or scattered."""
    import jax.experimental.pallas as pl

    e, f = pl.program_id(0), pl.program_id(1)
    dtype = h_ref.dtype

    @pl.when((e == 0) & (f == 0))
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    def rounded(x, w):      # as the loop's ``x @ w`` at the model's dtype
        return jnp.dot(x, w, preferred_element_type=jnp.float32
                       ).astype(dtype).astype(jnp.float32)

    @pl.when(park_ref[e] == 0)
    def _expert():
        x = h_ref[...]
        g = jax.nn.silu(rounded(x, gate_ref[...]))
        u = rounded(x, up_ref[...])
        part = jnp.dot((g * u).astype(dtype), down_ref[...],
                       preferred_element_type=jnp.float32)

        @pl.when(f == 0)
        def _first():
            acc_ref[...] = part

        @pl.when(f > 0)
        def _more():
            acc_ref[...] += part

        @pl.when(f == pl.num_programs(1) - 1)
        def _add():
            routed = local_ref[...] == e                        # [T, k]
            weight = jnp.sum(jnp.where(routed, wts_ref[...], 0.0), axis=1,
                             keepdims=True)
            hit = jnp.sum(routed.astype(jnp.int32), axis=1,
                          keepdims=True) > 0
            y = acc_ref[...].astype(dtype).astype(jnp.float32)
            out_ref[...] += jnp.where(hit, y * weight, 0.0)
