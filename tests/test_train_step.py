"""The train step's two promises, on the CPU at tiny widths in float32.

``remat=True`` changes what the step holds, never what it computes: for
every kind of layer that has a gradient (dense, window, Mamba,
retention, a model whose layers lend to later ones, and the dense model
through the flash kernels, interpreted, whose forward names the two
results the rule keeps), loss, gradients and updated parameters are
those of ``remat=False``. (An expert layer has none: the loop over its
tiles ends where the routing says, and ``lax.fori_loop`` with such an
end has no reverse mode.) And the step consumes its state: ``params``
and ``opt_state`` are donated, so the arrays it was given are gone when
it returns and a caller goes on with the ones it got back.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models import (ParallelConfig, TransformerConfig, init_params,
                            loss_fn, make_train_step)
from ray_tpu.models import transformer
from ray_tpu.ops.attention import flash_attention

F, W, M, R, G, X = "full", "window", "mamba", "retention", "gmu", "cross"
D = "dense"
TINY = dict(vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_seq=128,
            dtype=jnp.float32)
SSM = dict(ssm_inner=64, ssm_state=4, ssm_dt_rank=2, ssm_conv=4)


def kinds(*mixers, ffn=D):
    return dict(n_layers=len(mixers),
                layer_kinds=tuple((mixer, ffn) for mixer in mixers))


CASES = {
    "dense": {},
    # the same model through the three flash kernels, interpreted
    "dense-kernels": {},
    "window": dict(kinds(W, F, W), window=8, n_kv_heads=2),
    "mamba": dict(kinds(M, M, F), rope=False, n_kv_heads=1, **SSM),
    "retention": dict(kinds(R, R, F), n_kv_heads=2, qk_norm=True),
    # Mamba and window layers, one full layer whose K/V two cross layers
    # read, gated memory units, differential attention
    "lending": dict(kinds(M, W, M, F, G, X, G, X), rope=False, window=8,
                    n_kv_heads=2, differential=True, attn_bias=True,
                    layer_norm=True, ssm_inner_norms=False, **SSM),
}


# the same mathematics, fused by XLA in another order where the backward
# computes a value again: rounding (the retention case reads 5e-6 of the
# largest gradient, the others less); a value kept where it must be
# computed anew, or the other way round, reads in whole percents
TOLERANCE = 2e-5


def gap(a, b):
    return max(jax.tree.leaves(jax.tree.map(
        lambda x, y: float(jnp.max(jnp.abs(x - y))), a, b)))


@pytest.mark.parametrize("case", sorted(CASES))
def test_remat_changes_what_the_step_holds_and_not_what_it_computes(
        case, monkeypatch, capsys):
    cfg = TransformerConfig(**dict(TINY, **CASES[case]))
    T = cfg.max_seq if case == "dense-kernels" else 24
    if case == "dense-kernels":
        monkeypatch.setattr(transformer, "flash_attention", functools.partial(
            flash_attention, interpret=True))
    # the 0.02 initializer leaves a 32-wide model's logits to its
    # embedding alone: the matrices are scaled up until the layers
    # decide them
    seeded = jax.tree.map(lambda a: a * 6 if a.ndim >= 3 else a,
                          init_params(jax.random.key(3), cfg))
    tokens = jax.random.randint(jax.random.key(4), (2, T + 1), 0, cfg.vocab)
    batch = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}

    got = {}
    for remat in (False, True):
        pcfg = ParallelConfig(remat=remat)
        grads = jax.grad(loss_fn)(seeded, batch, cfg, pcfg)
        # (momentum, so that opt_state holds arrays; not Adam, whose
        # first step is the gradient's sign and turns rounding in an
        # entry near zero into a whole step)
        step, optimizer = make_train_step(
            cfg, pcfg, optimizer=optax.sgd(0.1, momentum=0.9))
        params = jax.tree.map(jnp.copy, seeded)
        opt_state = optimizer.init(params)
        given = jax.tree.leaves((params, opt_state))
        params, opt_state, loss = step(params, opt_state, batch)
        # the step consumed what it was given
        assert all(leaf.is_deleted() for leaf in given)
        got[remat] = (float(loss), grads, params)

    (loss, grads, params), (loss_r, grads_r, params_r) = got[False], got[True]
    assert np.isfinite(loss) and min(jax.tree.leaves(jax.tree.map(
        lambda g: float(jnp.max(jnp.abs(g))), grads["layers"]))) > 0
    assert abs(loss - loss_r) <= TOLERANCE * abs(loss)
    assert gap(grads, grads_r) <= TOLERANCE * gap(grads, jax.tree.map(
        jnp.zeros_like, grads))
    assert gap(params, params_r) <= TOLERANCE * 0.1    # the step's rate
    assert gap(params, seeded) > 1e-3      # and the step moved them

    if case == "dense-kernels":
        # what the rule keeps of the kernels: q, k, v and out as the
        # backward kernels read them, heads first; lse as [L, B, H, T],
        # which HBM does not pad, and no column [.., T, 1] of it
        jax.ad_checkpoint.print_saved_residuals(
            lambda p: loss_fn(p, batch, cfg, ParallelConfig(remat=True)),
            seeded)
        kept = [line.split()[0] for line in capsys.readouterr().out.split(
            "\n") if line]
        L, H = cfg.n_layers, cfg.n_heads
        assert kept.count(f"f32[{L},2,{H},{T},{cfg.head_dim}]") == 4
        assert f"f32[{L},2,{H},{T}]" in kept
        assert not [k for k in kept if k.endswith(f"{cfg.n_heads},{T},1]")]
