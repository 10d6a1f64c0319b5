"""``expert_ffn``'s kernel (``parallel/experts.py``) in interpret mode on
the CPU against the tile loop, its oracle, at small widths in bfloat16:
an expert with no row, an expert with more rows than a tile, rows routed
to experts held elsewhere, a layer's experts at ``base`` > 0 in a stack,
every row to one expert, and an expert width of one chunk and of
several. Beside them: where an unhit expert's grid steps wait (no block
of its own fetched), the gradient (the loop's), and the shape rule that
gives a decode step the kernel and a prefill the loop.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.parallel import experts
from ray_tpu.parallel.experts import expert_block, expert_ffn, route

D_, N, K = 256, 32, 2


def weights_of(key, n, F_):
    """Gate, up and down of ``n`` experts in bfloat16, scaled so that
    the results are of order one."""
    kg, ku, kd = jax.random.split(key, 3)
    return tuple(
        (jax.random.normal(k_, shape) * shape[1] ** -0.5).astype(jnp.bfloat16)
        for k_, shape in ((kg, (n, D_, F_)), (ku, (n, D_, F_)),
                          (kd, (n, F_, D_))))


def routed(T, bias):
    """h [T, D] and ``route``'s choice of K of N experts under ``bias``."""
    h = jax.random.normal(jax.random.key(0), (T, D_)).astype(jnp.bfloat16)
    router = jax.random.normal(jax.random.key(1), (D_, N)).astype(jnp.bfloat16)
    return (h, *route(h, router, bias, K))


def both(h, chosen, weights, mats, **kw):
    """(the kernel's (out, counts), the loop's)."""
    return (expert_ffn(h, chosen, weights, *mats, interpret=True, **kw),
            expert_ffn(h, chosen, weights, *mats, **kw))


# name: (rows, expert width, first, held, bias, layers in the stack, base)
CASES = {
    # experts 3 and 6 of the eight held are never chosen
    "unhit": (32, 1024, 0, 8, {3: -10.0, 6: -10.0}, 1, 0),
    # every row chooses expert 2: 32 rows, two tiles of 16
    "past_a_tile": (32, 1024, 0, 8, {2: 10.0}, 1, 0),
    # experts 12 to 19 held here, most assignments elsewhere
    "not_held": (32, 1024, 12, 8, {}, 1, 0),
    # the third layer's experts of a stack of three
    "stacked_base": (16, 1024, 0, 4, {}, 3, 8),
    # every row to experts 5 and 6, of which 2 to 5 are held: expert 5
    # gets them all, the others none
    "one_expert": (32, 1024, 2, 4, {5: 10.0, 6: 10.0}, 1, 0),
    # an expert width of one chunk
    "one_chunk": (32, 512, 0, 8, {}, 1, 0),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_the_tile_loop(case):
    T, F_, first, held, steer, layers, base = CASES[case]
    bias = jnp.zeros(N)
    for e, b in steer.items():
        bias = bias.at[e].set(b)
    h, chosen, weights = routed(T, bias)
    mats = weights_of(jax.random.key(2), layers * held, F_)
    (got, counts), (want, want_counts) = both(
        h, chosen, weights, mats, first=first, held=held, tile=16,
        base=jnp.int32(base))
    np.testing.assert_array_equal(counts, want_counts)
    np.testing.assert_array_equal(counts, [
        int(jnp.sum(chosen == first + e)) for e in range(held)])
    # the same roundings; the down product's float32 sums in chunks
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 0.1
    np.testing.assert_allclose(got, want, atol=1e-2 * scale, rtol=0)
    # a row routed to no held expert gets exactly nothing
    none_here = ~((chosen >= first) & (chosen < first + held)).any(axis=1)
    assert (got[none_here] == 0).all()
    if case == "unhit":
        assert counts[3] == counts[6] == 0 and (counts > 0).sum() == 6
    if case == "past_a_tile":
        assert counts[2] == T
    if case == "one_expert":
        np.testing.assert_array_equal(counts, [0, 0, 0, T])


def fetched(counts, chunks):
    """The weight blocks the pipeline copies over the grid, in order: a
    step copies where the block it names differs from the last one's."""
    src, park = experts._parking(jnp.asarray(counts, jnp.int32))
    named = [tuple(int(x) for x in experts._named_block(e, f, src, park,
                                                         chunks))
             for e in range(len(counts)) for f in range(chunks)]
    return [b for i, b in enumerate(named) if i == 0 or b != named[i - 1]]


@pytest.mark.parametrize("counts", [
    [0, 3, 0, 0, 5, 1, 0, 0], [4, 0, 0, 0, 0, 0, 0, 0],
    [0, 0, 0, 0, 0, 0, 0, 2], [1, 1, 1, 1, 1, 1, 1, 1],
    [0, 2, 2, 0, 2, 0, 2, 0]])
@pytest.mark.parametrize("chunks", [1, 4])
def test_an_unhit_expert_fetches_no_block(counts, chunks):
    """Each hit expert's chunks are copied once, in order, and nothing
    else: an unhit expert's steps name the block the pipeline holds."""
    assert fetched(counts, chunks) == [
        (e, f) for e, n in enumerate(counts) if n for f in range(chunks)]


def test_no_expert_hit_fetches_one_block():
    assert fetched([0] * 8, 4) == [(0, 0)]


def test_the_gradient_is_the_tile_loops():
    T, F_, held = 32, 1024, 8
    h, chosen, weights = routed(T, jnp.zeros(N))
    mats = weights_of(jax.random.key(2), held, F_)
    probe = jax.random.normal(jax.random.key(3), (T, D_))

    def loss(interpret):
        def f(h, weights, *mats):
            out, _ = expert_ffn(h, chosen, weights, *mats, first=0,
                                held=held, tile=16, interpret=interpret)
            return jnp.sum(out * probe)
        return jax.grad(f, argnums=(0, 1, 2, 3, 4))(h, weights, *mats)

    for got, want in zip(loss(True), loss(False)):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=1e-6, atol=1e-6)


def test_the_shape_rule_gives_a_decode_step_the_kernel():
    """Cell 4's decode step (128 rows of 4096, experts of 2048) takes
    the kernel; its prefills (512 to 2048 rows) keep the loop, as do
    more rows than the products hide under the copies, float32 rows,
    and rows or widths that are no whole tiles."""
    bf16 = jnp.bfloat16
    assert expert_block(128, 4096, 2048, bf16) is not None
    assert 2048 % expert_block(128, 4096, 2048, bf16) == 0
    for rows in (256, 512, 1024, 2048):
        assert expert_block(rows, 4096, 2048, bf16) is None
    assert expert_block(128, 4096, 2048, jnp.float32) is None
    assert expert_block(100, 4096, 2048, bf16) is None
    assert expert_block(128, 4000, 2048, bf16) is None
    assert expert_block(128, 4096, 100, bf16) is None
    assert expert_block(128, 4096, 384, bf16) is None


def kernels(T, interpret, monkeypatch, on_tpu):
    """The names of the ``pallas_call`` equations in ``expert_ffn``'s
    program for T rows of cell 4's widths (traced from shapes, nothing
    runs)."""
    monkeypatch.setattr(importlib.import_module("ray_tpu.parallel.experts"),
                        "_on_tpu", lambda: on_tpu)
    E, F_ = 16, 2048
    arg = jax.ShapeDtypeStruct

    def program(h, chosen, weights, *mats):
        return expert_ffn(h, chosen, weights, *mats, first=0, held=E,
                          tile=16, interpret=interpret)

    jaxpr = jax.make_jaxpr(program)(
        arg((T, 4096), jnp.bfloat16), arg((T, 8), jnp.int32),
        arg((T, 8), jnp.float32), arg((E, 4096, F_), jnp.bfloat16),
        arg((E, 4096, F_), jnp.bfloat16), arg((E, F_, 4096), jnp.bfloat16))

    def found(eqns):
        for eqn in eqns:
            if eqn.primitive.name == "pallas_call":
                yield str(eqn.params["name"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from found(sub.eqns)

    return list(found(jaxpr.eqns))


def test_the_kernel_runs_where_the_platform_and_the_shape_say(monkeypatch):
    assert kernels(128, False, monkeypatch, True) == [experts.KERNEL]
    assert kernels(128, True, monkeypatch, False) == [experts.KERNEL]
    assert kernels(128, False, monkeypatch, False) == []
    assert kernels(512, False, monkeypatch, True) == []
