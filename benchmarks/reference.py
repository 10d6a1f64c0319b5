"""What every family's plain reference shares, and how a cell's
``correct`` is read off it.

A family (``families/<model_type>/reference.py``, found by
``loader.family_module``) brings its block: ``sizes_of``,
``seeded_params``, ``forward`` and ``by_leaf``. This module holds what
no family owns: the key a seed gives, the matrix product with its
lower-precision controls, the serving and training readings made
through a family's ``forward``, AdamW, and the comparisons. Plain
``jax.numpy`` in float32 at ``highest`` matmul precision: no kernels,
no cache, no batching of requests. It imports nothing of ``ray_tpu``
and takes nothing the program made: the weights come from the family's
``seeded_params`` (the program is handed the same function's output),
the training batches from ``traffic.train_batch``. In the functions
below ``ref`` is a family's reference module.

The control is the same code with ``quant="int8"``: every linear layer
(and the unembedding) multiplies int8-rounded activations (one scale
per token) by int8-rounded weights (one scale per output channel), the
usual W8A8 scheme and the step below bfloat16 that would tempt a later
PR on a v5e, whose matrix unit takes int8. In training its backward is
the straight-through one. ``quant="fp8"`` (e4m3, the same scales) is
the other step below bfloat16, read beside it when limits are set.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def seed_key(seed: int):
    """A PRNG key for any whole-number seed (the driver's pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


# ------------------------------------------- the controls' matrix product

def _fake_int8(a, axis):
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0.0, 1.0, scale)
    rounded = jnp.round(a / scale) * scale
    return a + lax.stop_gradient(rounded - a)   # straight-through


def _fake_fp8(a, axis):
    """float8 e4m3 with one scale per token or output channel."""
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale == 0.0, 1.0, scale)
    rounded = (a / scale).astype(jnp.float8_e4m3fn).astype(a.dtype) * scale
    return a + lax.stop_gradient(rounded - a)   # straight-through


def mm(x, w, quant):
    """``x @ w`` at ``highest``, or in the control precision ``quant``."""
    if quant == "int8":
        x, w = _fake_int8(x, -1), _fake_int8(w, 0)
    elif quant == "fp8":
        x, w = _fake_fp8(x, -1), _fake_fp8(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


# --------------------------------------------------------------- serving

@functools.partial(jax.jit, static_argnames=("sz", "quant", "forward"))
def _next_token_gaps(params, tokens, sz, quant, forward):
    """For one sequence [1, T]: at each position t, how far below the
    reference's best next-token logit lies (a) the token the sequence
    really has at t+1, (b) the token the ``quant`` control puts first."""
    ref = forward(params, tokens, sz)[0]                       # [T, V]
    best = jnp.max(ref, axis=-1)
    served = jnp.take_along_axis(
        ref[:-1], tokens[0, 1:, None], axis=-1)[:, 0]
    out = {"served": best[:-1] - served}
    if quant is not None:
        pick = jnp.argmax(forward(params, tokens, sz, quant)[0], axis=-1)
        out["control"] = best - jnp.take_along_axis(
            ref, pick[:, None], axis=-1)[:, 0]
    return out


def served_logit_gaps(ref, params, prompt, served, sz, quant=None,
                      pad_to: int = 128):
    """The reference over ``prompt + served`` (token id lists), once.

    Returns the gaps, in logits, at the positions that produced each
    served token: ``{"served": [...], "control": [...]}`` (the control
    only when ``quant`` is given). The sequence is padded at its end to
    a multiple of ``pad_to`` so few programs compile; under a causal
    mask the padding cannot reach the positions read."""
    seq = list(prompt) + list(served)
    first, last = len(prompt) - 1, len(seq) - 1     # positions t read
    padded = seq + [0] * (-len(seq) % pad_to)
    gaps = _next_token_gaps(params, jnp.asarray([padded], jnp.int32), sz,
                            quant, ref.forward)
    return {k: [float(x) for x in v[first:last]] for k, v in gaps.items()}


# -------------------------------------------------------------- training

ADAMW = {"lr": 3e-4, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
         "weight_decay": 1e-4}      # optax.adamw(3e-4)'s defaults


def _row_loss(params, tokens, targets, sz, quant, forward):
    logits = forward(params, tokens, sz, quant, remat=True)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


@functools.partial(jax.jit, static_argnames=("sz", "quant", "forward"),
                   donate_argnames=("acc",))
def _accumulate_row(params, acc, tokens, targets, weight, sz, quant,
                    forward):
    """acc += weight * d(row loss)/d(params). The arithmetic is float32;
    a row's gradient leaves autodiff in the parameters' stored type and
    is summed in float32."""
    loss, grads = jax.value_and_grad(_row_loss)(params, tokens, targets,
                                                sz, quant, forward)
    acc = jax.tree.map(lambda a, g: a + weight * g.astype(jnp.float32),
                       acc, grads)
    return loss, acc


def loss_and_grads(ref, params, batch, sz, quant=None, rows=None):
    """Mean next-token cross-entropy over the batch's rows and its
    gradient (float32), one row at a time so that it fits beside
    nothing else on a chip. ``rows`` keeps only those rows (the
    half-batch fault)."""
    rows = list(range(batch["tokens"].shape[0])) if rows is None else rows
    acc = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    total = 0.0
    for r in rows:
        loss, acc = _accumulate_row(
            params, acc, batch["tokens"][r:r + 1], batch["targets"][r:r + 1],
            1.0 / len(rows), sz, quant, ref.forward)
        total += float(loss) / len(rows)
    return total, acc


@functools.partial(jax.jit, donate_argnames=("params", "mu", "nu", "grads"))
def adamw_update(params, mu, nu, grads, count):
    """One AdamW step in float32 arithmetic on state stored in the
    parameters' own type (the configuration trains with bfloat16
    parameters and moments, as ``optax.adamw`` keeps them for bfloat16
    parameters). ``count`` is this step's number, from 1."""
    h = ADAMW

    def leaf(p, m, v, g):
        m32 = h["b1"] * m.astype(jnp.float32) + (1 - h["b1"]) * g
        v32 = h["b2"] * v.astype(jnp.float32) + (1 - h["b2"]) * g * g
        m, v = m32.astype(m.dtype), v32.astype(v.dtype)
        m_hat = m.astype(jnp.float32) / (1 - h["b1"] ** count)
        v_hat = v.astype(jnp.float32) / (1 - h["b2"] ** count)
        p32 = p.astype(jnp.float32)
        step = m_hat / (jnp.sqrt(v_hat) + h["eps"]) + h["weight_decay"] * p32
        return (p32 - h["lr"] * step).astype(p.dtype), m, v

    out = jax.tree.map(leaf, params, mu, nu, grads)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,         # noqa: E731
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2)


@functools.partial(jax.jit, static_argnames=("by_leaf",))
def leaf_norms(tree, by_leaf):
    """Norm, as float32, of every leaf of a parameter-shaped tree as the
    family's ``by_leaf`` names them (stacked layer leaves split by
    layer)."""
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in by_leaf(tree).items()}


@functools.partial(jax.jit, static_argnames=("by_leaf",))
def leaf_diff_norms(a, b, scale_b, by_leaf):
    """Norm of every leaf of ``a - scale_b * b`` (float32 arithmetic,
    nothing of the trees' size kept, so that the program's own peak of
    memory stays the one that is read)."""
    a, b = by_leaf(a), by_leaf(b)
    return {k: jnp.sqrt(jnp.sum(jnp.square(
        a[k].astype(jnp.float32) - scale_b * b[k].astype(jnp.float32))))
        for k in a}


def train_reference(ref, seed: int, sz, batch_of, steps: int = 3,
                    quant=None, rows=None, frozen=False,
                    other_first_gradient=None, other_scale=1.0,
                    keep_first_gradient=False) -> dict:
    """Follow the first ``steps`` optimizer steps from the seed.

    ``batch_of(i)`` gives step i's batch. Returns each step's loss, the
    per-leaf norms of the first gradient and of the parameters' change
    after the last step. ``quant``, ``rows`` and ``frozen`` (the step
    that returns its state unchanged) make the control and the faults.

    ``other_first_gradient`` is another side's first gradient (times
    ``1 / other_scale``): the per-leaf norms of this side's less that
    one come back as ``grad_diff_norms``. ``keep_first_gradient`` hands
    this side's back too, rounded to bfloat16, for a control to be read
    against.
    """
    params = ref.seeded_params(seed, sz)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    out, losses = {}, []
    for i in range(steps):
        loss, grads = loss_and_grads(ref, params, batch_of(i), sz, quant,
                                     rows)
        losses.append(loss)
        if i == 0:
            out["grad_norms"] = leaf_norms(grads, ref.by_leaf)
            if other_first_gradient is not None:
                out["grad_diff_norms"] = leaf_diff_norms(
                    grads, other_first_gradient, jnp.float32(other_scale),
                    ref.by_leaf)
            if keep_first_gradient:
                first = jax.tree.map(lambda g: g.astype(jnp.bfloat16), grads)
        if not frozen:
            params, mu, nu = adamw_update(params, mu, nu, grads,
                                          jnp.float32(i + 1))
        del grads
    out["change_norms"] = leaf_diff_norms(
        params, ref.seeded_params(seed, sz), jnp.float32(1.0), ref.by_leaf)
    out = {name: {k: float(v) for k, v in norms.items()}
           for name, norms in out.items()}
    out["losses"] = losses
    if keep_first_gradient:
        out["first_gradient"] = first
    return out


# ----------------------------------------------------------- comparisons

def worst_leaf_gap(got: dict, want: dict, skip=()) -> float:
    """The largest gap between a leaf's norm in ``got`` and in ``want``
    (the reference), against the reference's norm of that leaf or of
    its median leaf, whichever is larger."""
    ordered = sorted(want.values())
    median = ordered[len(ordered) // 2]
    return max((abs(got[k] - want[k]) / max(want[k], median, 1e-30)
                for k in want if k not in skip), default=0.0)


def compare_training(got: dict, want: dict, diff_norms=None) -> dict:
    """The numbers a training cell is held to. Leaves whose reference
    gradient is under a thousandth of the median leaf's move by
    round-off alone and are left out of the change. ``diff_norms`` are
    the per-leaf norms of ``got``'s first gradient less ``want``'s."""
    ordered = sorted(want["grad_norms"].values())
    median = ordered[len(ordered) // 2]
    idle = [k for k, v in want["grad_norms"].items() if v < 1e-3 * median]
    out = {
        "loss_gap": max(abs(a - b) / abs(b) for a, b in
                        zip(got["losses"], want["losses"])),
        "grad_norm_gap": worst_leaf_gap(got["grad_norms"],
                                        want["grad_norms"]),
        "change_norm_gap": worst_leaf_gap(got["change_norms"],
                                          want["change_norms"], skip=idle),
        "leaves_left_out": len(idle),
    }
    if diff_norms is not None:
        # the norm of the difference, which a norm's gap is blind to:
        # noise that leaves every norm where it was turns the gradient
        shares = sorted(diff_norms[k] / max(v, median, 1e-30)
                        for k, v in want["grad_norms"].items())
        out["grad_diff_gap"] = shares[-1]
        out["grad_diff_median"] = shares[len(shares) // 2]
    return out
