"""ops/ssm.py at small sizes on the CPU: the selective scan's three
forms against each other (one position at a time, the chunked XLA form,
the Pallas kernel in interpret mode), the state that comes back
included; a sequence cut in two and carried over the cut; the causal
convolution's tail across such an edge; the kernel's gradient, which
is the chunked form's; and a decode step's two forms over a run's
carried state (the layer's slice through ``selective_step``, the
``ssm_step`` kernel in interpret mode) against ``selective_step``, a
row left out kept bit for bit and the other layers untouched.

Tolerances: float32 throughout, the same sums in another order. The
chunked form multiplies decays together before it applies them and the
kernel keeps the order of the recurrence, so they differ from the
step-by-step form by rounding alone: 2e-6 is what they read on values
of order 1 to 10; 2e-5 leaves ten times of room. With bfloat16 inputs
the state is float32 still and the forms agree on it as closely; y is
rounded to bfloat16 once, so two forms may land on neighbouring values:
an eighth of the largest y's last place, 2 ** -8 of its magnitude.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import ssm

TOLERANCE = 2e-5


def inputs(T, C=256, N=8, rows=2, dtype=jnp.float32, seed=0):
    k = jax.random.split(jax.random.key(seed), 6)
    u = jax.random.normal(k[0], (rows, T, C)).astype(dtype)
    # steps from 0.003 to 1: decays from 0.99 down to nothing
    dt = jax.nn.softplus(3.0 * jax.random.normal(k[1], (rows, T, C)) - 2.0)
    A = -jnp.broadcast_to(jnp.arange(1.0, N + 1)[:, None], (N, C))
    B = jax.random.normal(k[2], (rows, T, N)).astype(dtype)
    Cm = jax.random.normal(k[3], (rows, T, N)).astype(dtype)
    D = 1.0 + 0.1 * jax.random.normal(k[4], (C,))
    state = jax.random.normal(k[5], (rows, N, C))
    return u, dt, A, B, Cm, D, state


def position_by_position(u, dt, A, B, C, D, state):
    ys = []
    for t in range(u.shape[1]):
        y, state = ssm.selective_step(u[:, t], dt[:, t], A, B[:, t],
                                      C[:, t], D, state)
        ys.append(y)
    return jnp.stack(ys, axis=1), state


def gap(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


FORMS = {
    # T, then how the scan is run on it
    "chunked, one chunk": (24, lambda a: ssm._scan_chunked(*a, chunk=64)),
    "chunked, chunks of 16": (48, lambda a: ssm._scan_chunked(*a, chunk=16)),
    "chunked, a ragged last chunk": (
        41, lambda a: ssm._scan_chunked(*a, chunk=16)),
    "the dispatcher off the TPU": (40, lambda a: ssm.selective_scan(*a)),
    "kernel, one block": (16, lambda a: ssm.selective_scan(
        *a, block_t=16, block_c=256, interpret=True)),
    "kernel, blocks of 16 x 128": (48, lambda a: ssm.selective_scan(
        *a, block_t=16, block_c=128, interpret=True)),
    "kernel, blocks of 8 x 128": (24, lambda a: ssm.selective_scan(
        *a, block_t=8, block_c=128, interpret=True)),
}


@pytest.mark.parametrize("form", sorted(FORMS))
def test_every_form_of_the_scan_is_the_recurrence(form):
    T, run = FORMS[form]
    args = inputs(T)
    want_y, want_s = position_by_position(*args)
    y, s = run(args)
    assert y.shape == want_y.shape and s.dtype == jnp.float32
    assert gap(y, want_y) < TOLERANCE and gap(s, want_s) < TOLERANCE
    assert float(jnp.max(jnp.abs(want_y))) > 1.0    # values worth the name


@pytest.mark.parametrize("form", ["chunked", "kernel"])
def test_bfloat16_inputs_keep_a_float32_state(form):
    args = inputs(32, dtype=jnp.bfloat16)
    want_y, want_s = position_by_position(*args)
    if form == "chunked":
        y, s = ssm._scan_chunked(*args, chunk=16)
    else:
        y, s = ssm.selective_scan(*args, block_t=16, block_c=128,
                                  interpret=True)
    assert y.dtype == jnp.bfloat16 and s.dtype == jnp.float32
    assert gap(s, want_s) < TOLERANCE
    assert gap(y, want_y) <= 2 ** -8 * float(jnp.max(jnp.abs(
        want_y.astype(jnp.float32))))


@pytest.mark.parametrize("form", ["chunked", "kernel"])
def test_a_sequence_cut_in_two_carries_its_state_over_the_cut(form):
    """What ``slot_prefill`` leaves and a later scan would pick up: the
    state after the first part is the state before the second."""
    def run(u, dt, A, B, C, D, state):
        if form == "chunked":
            return ssm._scan_chunked(u, dt, A, B, C, D, state, chunk=16)
        return ssm.selective_scan(u, dt, A, B, C, D, state, block_t=16,
                                  block_c=128, interpret=True)

    u, dt, A, B, C, D, state = inputs(64, seed=3)
    whole_y, whole_s = run(u, dt, A, B, C, D, state)
    cut = 32
    y1, s1 = run(u[:, :cut], dt[:, :cut], A, B[:, :cut], C[:, :cut], D,
                 state)
    y2, s2 = run(u[:, cut:], dt[:, cut:], A, B[:, cut:], C[:, cut:], D, s1)
    assert gap(jnp.concatenate([y1, y2], axis=1), whole_y) < TOLERANCE
    assert gap(s2, whole_s) < TOLERANCE
    # no state given is a state of zeros
    zero_y, zero_s = run(u, dt, A, B, C, D, jnp.zeros_like(state))
    none_y, none_s = ssm.selective_scan(u, dt, A, B, C, D)
    assert gap(none_y, zero_y) < TOLERANCE and gap(none_s, zero_s) < TOLERANCE


def test_the_kernels_gradient_is_the_chunked_forms():
    args = inputs(32, seed=5)

    def loss(run):
        def f(u, dt, A, B, C, D, state):
            y, s = run(u, dt, A, B, C, D, state)
            return jnp.sum(y * y) + jnp.sum(s)
        return f

    kernel = jax.grad(loss(lambda *a: ssm.selective_scan(
        *a, block_t=16, block_c=128, interpret=True)),
        argnums=tuple(range(7)))(*args)
    chunked = jax.grad(loss(ssm._scan_chunked),
                       argnums=tuple(range(7)))(*args)
    for got, want in zip(kernel, chunked):
        assert got.shape == want.shape
        # the forward values differ by rounding, so the cotangents do
        assert gap(got, want) <= 1e-4 * max(1.0, float(jnp.max(
            jnp.abs(want))))


def test_which_form_runs_is_read_from_the_platform_and_the_shape(
        monkeypatch):
    args = inputs(256, C=512)

    def has_kernel(*a, **kw):
        return "pallas_call" in str(jax.make_jaxpr(
            lambda *x: ssm.selective_scan(*x, **kw))(*a))

    assert not has_kernel(*args)                # the CPU: XLA
    monkeypatch.setattr(ssm, "_on_tpu", lambda: True)
    assert has_kernel(*args)                    # 256 = 2 x 128 positions
    short = inputs(192, C=512)
    assert not has_kernel(*short)               # not a multiple of 128
    narrow = inputs(256, C=384)
    assert not has_kernel(*narrow)              # nor of the channel tile


# ------------------------------------------ a decode step, state carried

def step_inputs(rows, C, N=8, layers=3, dtype=jnp.float32, seed=0):
    """One position a row, and a run's carried state [layers, rows, N,
    C]."""
    u, dt, A, B, Cm, D, _ = inputs(1, C=C, N=N, rows=rows, dtype=dtype,
                                   seed=seed)
    states = jax.random.normal(jax.random.key(seed + 100),
                               (layers, rows, N, C))
    return u[:, 0], dt[:, 0], A, B[:, 0], Cm[:, 0], D, states


STEP_FORMS = {
    # rows, channels, then whether the kernel runs (interpreted)
    "the layer's slice, off the TPU": (6, 192, False),
    "kernel, one block of rows": (4, 128, True),
    "kernel, blocks of 16 rows, two passes a block": (32, 512, True),
    "kernel, channels short of a lane tile": (16, 64, True),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("form", sorted(STEP_FORMS))
def test_every_form_of_the_step_is_the_recurrence(form, dtype):
    rows, C, kernel = STEP_FORMS[form]
    u, dt, A, B, Cm, D, states = step_inputs(rows, C, dtype=dtype)
    assert (ssm.step_blocks(rows, A.shape[0], C, interpret=kernel)
            is not None) == kernel
    layer = 1
    want_y, want_s = ssm.selective_step(u, dt, A, B, Cm, D, states[layer])
    y, new = jax.jit(lambda *a: ssm.carried_step(*a, interpret=kernel))(
        u, dt, A, B, Cm, D, states, jnp.int32(layer), jnp.ones(rows, bool))
    assert y.dtype == dtype and new.dtype == jnp.float32
    assert new.shape == states.shape
    assert gap(new[layer], want_s) < TOLERANCE
    if dtype == jnp.float32:
        assert gap(y, want_y) < TOLERANCE
    else:   # rounded once: neighbouring values at most
        assert gap(y, want_y) <= 2 ** -8 * float(jnp.max(jnp.abs(
            want_y.astype(jnp.float32))))
    assert float(jnp.max(jnp.abs(want_y.astype(jnp.float32)))) > 1.0


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("kernel", [False, True],
                         ids=["the layer's slice", "kernel"])
def test_a_step_advances_live_rows_of_one_layer_and_nothing_else(
        kernel, layer):
    """Over a run's state of three layers and a traced layer index:
    live rows of that layer advance; rows left out keep their state bit
    for bit, whether a whole block of rows is left out or one among
    live ones; the other layers are what they were."""
    rows = 48
    u, dt, A, B, Cm, D, states = step_inputs(rows, 256, seed=2)
    # the first block of 16 rows mixed, the second all left out, the
    # third all live
    active = jnp.asarray([r % 3 != 1 for r in range(16)]
                         + [False] * 16 + [True] * 16)
    _, want = ssm.selective_step(u, dt, A, B, Cm, D, states[layer])
    run = jax.jit(lambda *a: ssm.carried_step(*a, interpret=kernel))
    _, new = run(u, dt, A, B, Cm, D, states, jnp.int32(layer), active)
    live = np.asarray(active)
    assert gap(new[layer][live], want[live]) < TOLERANCE
    assert not np.array_equal(np.asarray(new[layer][live]),
                              np.asarray(states[layer][live]))
    np.testing.assert_array_equal(np.asarray(new[layer][~live]),
                                  np.asarray(states[layer][~live]))
    for other in set(range(3)) - {layer}:
        np.testing.assert_array_equal(np.asarray(new[other]),
                                      np.asarray(states[other]))
    # no row live at all: the state is what it was
    _, still = run(u, dt, A, B, Cm, D, states, jnp.int32(layer),
                   jnp.zeros(rows, bool))
    np.testing.assert_array_equal(np.asarray(still), np.asarray(states))


@pytest.mark.parametrize("shape,on_tpu,kernel", [
    ((256, 16, 5120), True, True),      # the serving cell's own
    ((256, 16, 5120), False, False),    # the CPU: XLA
    ((32, 8, 512), True, True),
    ((250, 16, 5120), True, False),     # rows that are no whole blocks
    ((256, 12, 5120), True, False),     # a state of no whole sublane tiles
    ((256, 16, 5000), True, False),     # channels that are no whole passes
    ((256, 16, 16384), True, False),    # a block of rows too large
], ids=["cell 5", "off the TPU", "small", "ragged rows", "ragged state",
        "ragged channels", "wide"])
def test_which_form_of_the_step_runs_is_read_from_the_platform_and_the_shape(
        monkeypatch, shape, on_tpu, kernel):
    rows, N, C = shape
    monkeypatch.setattr(ssm, "_on_tpu", lambda: on_tpu)

    def array(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype)

    # (a function of its own: a trace is remembered by its function)
    jaxpr = str(jax.make_jaxpr(lambda *a: ssm.carried_step(*a))(
        array(rows, C, dtype=jnp.bfloat16), array(rows, C), array(N, C),
        array(rows, N, dtype=jnp.bfloat16),
        array(rows, N, dtype=jnp.bfloat16), array(C),
        array(2, rows, N, C), array(dtype=jnp.int32),
        array(rows, dtype=jnp.bool_)))
    assert ("pallas_call" in jaxpr) == kernel
    assert (ssm.STEP_KERNEL in jaxpr) == kernel
    # the XLA form is the layer's slice, written back in place
    assert ("dynamic_update_slice" in jaxpr) == (not kernel)


# ------------------------------------------------------- the convolution

def conv_by_hand(u, w, b):
    K = w.shape[0]
    rows = np.concatenate([np.zeros((u.shape[0], K - 1, u.shape[2])),
                           np.asarray(u, np.float64)], axis=1)
    x = np.asarray(b, np.float64) + sum(
        rows[:, k:k + u.shape[1]] * np.asarray(w[k], np.float64)
        for k in range(K))
    return x / (1.0 + np.exp(-x))


@pytest.mark.parametrize("cuts", [(), (5,), (1, 2, 3), (2, 17, 18)],
                         ids=["whole", "one edge", "shorter than the tail",
                              "edges a position apart"])
def test_the_convolutions_tail_carries_it_over_an_edge(cuts):
    """A sequence convolved in parts, each part from the tail the part
    before left (a prompt, then a token at a time), is the sequence
    convolved whole; a part shorter than the K - 1 rows of the tail
    keeps what is left of the older ones."""
    k = jax.random.split(jax.random.key(1), 3)
    u = jax.random.normal(k[0], (2, 24, 16))
    w, b = jax.random.normal(k[1], (4, 16)), jax.random.normal(k[2], (16,))
    want = conv_by_hand(u, w, b)
    edges = (0,) + cuts + (24,)
    parts, tail = [], None
    for a, z in zip(edges, edges[1:]):
        x, tail = ssm.causal_conv(u[:, a:z], w, b, tail)
        assert tail.shape == (2, 3, 16)
        parts.append(x)
    np.testing.assert_allclose(np.concatenate(parts, axis=1), want,
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(tail), np.asarray(u[:, -3:]))
