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

No capacity and no dropped row: the assignments (row, expert) held here
are sorted by expert and cut into tiles of ``tile`` rows, each of one
expert alone, and a loop runs over the tiles that exist. Shapes are
static (at most rows x k assignments, so at most ``rows * k / tile +
experts`` tiles) and the trip count is not, so the work is in proportion
to the rows routed here, never experts x rows. A tile reads its
expert's three matrices once.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


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


def expert_ffn(h, chosen, weights, w_gate, w_up, w_down, *, first: int,
               held: int, tile: int, base=0):
    """The held experts' part of the layer for h [T, D]: the sum over
    the chosen experts e in [first, first + held) of weights *
    SwiGLU_e(h), as float32 [T, D], and the rows each held expert got,
    int32 [held]. ``chosen``/``weights`` are ``route``'s. ``w_gate``/
    ``w_up`` [.., D, F] and ``w_down`` [.., F, D] hold the layer's
    held experts at rows [base, base + held): a caller that stacks
    layers hands the stack whole with the layer's ``base`` (traced), so
    that a tile reads its expert's matrices where they lie; a layer's
    slice taken first would be a copy of all of them."""
    T, _ = h.shape
    k, E = chosen.shape[1], held
    A = T * k                               # assignments, held or not
    local = chosen.reshape(A) - first
    key = jnp.where((local >= 0) & (local < E), local, E)
    # assignment ids sorted by held expert; those of absent ones last
    order = jnp.argsort(key, stable=True)
    counts = jnp.zeros(E + 1, jnp.int32).at[key].add(1)[:E]
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

    out = lax.fori_loop(0, tile_end[-1], one_tile,
                        jnp.zeros(h.shape, jnp.float32))
    return out, counts
