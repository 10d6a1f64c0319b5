"""Differential tests: SPMD schedules vs single-device oracles.

Strategy follows the reference's scheduler-oracle pattern (SURVEY.md
§7 step 4): every parallel schedule must reproduce the plain
single-device math bit-for-bit-ish (fp32 tolerances) on a virtual
8-device CPU mesh.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.ops.attention import attention
from ray_tpu.parallel import (
    build_mesh,
    default_mesh_shape,
    pipeline_spmd,
    ring_attention,
    shard_map,
    ulysses_attention,
)
from ray_tpu.parallel.mesh import MeshConfig


def cpus(n):
    devs = jax.devices("cpu")
    if len(devs) < n:
        pytest.skip(f"need {n} cpu devices, have {len(devs)}")
    return devs[:n]


def test_default_mesh_shape():
    for n in (1, 2, 4, 8, 16, 64):
        cfg = default_mesh_shape(n)
        assert np.prod(cfg.sizes()) == n
    cfg = default_mesh_shape(16)
    assert all(s >= 2 for s in cfg.sizes())


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_oracle(causal):
    mesh = Mesh(np.array(cpus(4)), ("sp",))
    B, T, H, D = 2, 32, 2, 8
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, H, D), jnp.float32)

    want = attention(q, k, v, causal=causal)

    fn = shard_map(
        functools.partial(ring_attention, axis="sp", causal=causal),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3,
        out_specs=P(None, "sp"), check_vma=False)
    got = jax.jit(fn)(q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_ulysses_matches_oracle():
    mesh = Mesh(np.array(cpus(2)), ("sp",))
    B, T, H, D = 2, 16, 4, 8
    ks = jax.random.split(jax.random.key(1), 3)
    q, k, v = (jax.random.normal(kk, (B, T, H, D), jnp.float32)
               for kk in ks)
    want = attention(q, k, v, causal=True)
    fn = shard_map(
        functools.partial(ulysses_attention, axis="sp", causal=True),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3,
        out_specs=P(None, "sp"), check_vma=False)
    got = jax.jit(fn)(q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_pipeline_matches_sequential():
    n_stage = 4
    mesh = Mesh(np.array(cpus(n_stage)), ("pp",))
    B, Din = 8, 16
    ks = jax.random.split(jax.random.key(2), 2)
    w = jax.random.normal(ks[0], (n_stage, Din, Din), jnp.float32) * 0.3
    x = jax.random.normal(ks[1], (B, Din), jnp.float32)

    def stage_fn(wl, h):
        # wl arrives [1, Din, Din] per rank (pp-sharded leading dim)
        return jnp.tanh(h @ wl[0])

    want = x
    for i in range(n_stage):
        want = jnp.tanh(want @ w[i])

    fn = shard_map(
        functools.partial(pipeline_spmd, stage_fn, axis="pp",
                          num_microbatches=4),
        mesh=mesh, in_specs=(P("pp"), P(None)), out_specs=P(None),
        check_vma=False)
    got = jax.jit(lambda w_, x_: fn(w_, x_))(w, x)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_pipeline_grads_match_sequential():
    n_stage = 2
    mesh = Mesh(np.array(cpus(2)), ("pp",))
    B, Din = 4, 8
    ks = jax.random.split(jax.random.key(3), 2)
    w = jax.random.normal(ks[0], (n_stage, Din, Din), jnp.float32) * 0.3
    x = jax.random.normal(ks[1], (B, Din), jnp.float32)

    def stage_fn(wl, h):
        return jnp.tanh(h @ wl[0])

    def seq_loss(w_):
        h = x
        for i in range(n_stage):
            h = jnp.tanh(h @ w_[i])
        return jnp.sum(h * h)

    def pipe_loss_local(w_, x_):
        out = pipeline_spmd(stage_fn, w_, x_, axis="pp",
                            num_microbatches=2)
        # every pp rank computes this same loss; shard_map AD sums the
        # redundant copies' cotangents, so divide by the pp size
        return jnp.sum(out * out) / n_stage

    fn = shard_map(
        jax.grad(pipe_loss_local), mesh=mesh,
        in_specs=(P("pp"), P(None)), out_specs=P("pp"),
        check_vma=False)
    got = jax.jit(fn)(w, x)
    want = jax.grad(seq_loss)(w)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("mcfg", [
    MeshConfig(dp=1, pp=2, sp=2, tp=2),
    MeshConfig(dp=2, pp=2, sp=1, tp=2),
])
def test_spmd_train_step_matches_oracle(mcfg):
    import optax

    from ray_tpu.models import (ParallelConfig, TransformerConfig,
                                init_params, loss_fn, make_train_step,
                                param_specs)
    from ray_tpu.models.transformer import _opt_state_specs

    mesh = build_mesh(mcfg, cpus(8))
    cfg = TransformerConfig(vocab=32, d_model=32, n_heads=4,
                            n_layers=4, d_ff=32, max_seq=16,
                            dtype=jnp.float32)
    pcfg = ParallelConfig(dp="dp" if mcfg.dp > 1 else None,
                          pp="pp" if mcfg.pp > 1 else None,
                          sp="sp" if mcfg.sp > 1 else None,
                          tp="tp" if mcfg.tp > 1 else None,
                          attn="ring" if mcfg.sp > 1 else "local",
                          num_microbatches=2)
    opt = optax.sgd(0.1)
    step, _ = make_train_step(cfg, pcfg, mesh=mesh, optimizer=opt)
    oracle_step, _ = make_train_step(cfg, ParallelConfig(),
                                     optimizer=opt)

    params = init_params(jax.random.key(5), cfg)
    opt_state = opt.init(params)
    B, T = 4, 16
    kt = jax.random.split(jax.random.key(6), 2)
    batch = {
        "tokens": jax.random.randint(kt[0], (B, T), 0, cfg.vocab),
        "targets": jax.random.randint(kt[1], (B, T), 0, cfg.vocab),
    }

    pspecs = param_specs(pcfg)
    sh = lambda s: NamedSharding(mesh, s)  # noqa: E731
    # each step consumes its state, and device_put hands a replicated
    # leaf's own buffer to the device the two share: the mesh gets a copy
    params_d = jax.device_put(
        jax.tree.map(jnp.copy, params),
        jax.tree.map(sh, pspecs, is_leaf=lambda x: isinstance(x, P)))
    opt_d = jax.device_put(
        opt_state, jax.tree.map(
            sh, _opt_state_specs(opt, cfg, pspecs),
            is_leaf=lambda x: isinstance(x, P)))
    batch_d = jax.device_put(batch, sh(P(pcfg.dp, pcfg.sp)))

    # two steps: the second's loss only matches if step-1 grads did
    p1, o1, l1 = step(params_d, opt_d, batch_d)
    q1, oo1, m1 = oracle_step(params, opt_state, batch)
    np.testing.assert_allclose(float(l1), float(m1), rtol=1e-4)
    # updated params must match the oracle's (catches grad scaling
    # bugs on every axis — wq is pp+tp sharded, embed replicated)
    np.testing.assert_allclose(
        np.array(p1["layers"]["wq"]), np.array(q1["layers"]["wq"]),
        rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(
        np.array(p1["embed"]), np.array(q1["embed"]),
        rtol=1e-3, atol=1e-5)
    _, _, l2 = step(p1, o1, batch_d)
    _, _, m2 = oracle_step(q1, oo1, batch)
    np.testing.assert_allclose(float(l2), float(m2), rtol=1e-4)
