"""The flash kernels at the shapes the benchmark's cells run, compiled
for a TPU v5e that is described and not attached (libtpu's compiler runs
on this CPU box; nothing executes): Mosaic accepts them at the blocks
``flash_blocks`` chooses (a table that overflows VMEM fails here), every
shape takes the kernel (a prompt of 128 too), and the compiled program
names its three custom calls ``flash_fwd``, ``flash_bwd_dkv`` and
``flash_bwd_dq``, which is what the benchmark's kernel readers look up
in a device trace.

The topology is described inside a fixture, by the one xdist worker
that is given this file: libtpu loads in one process at a time, so no
other test file may do the same and nothing here runs at import.
"""

import importlib
import re

import pytest

KERNELS = ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")
# B, T, H, Dh, the dtype, and whether the gradient is compiled too
SHAPES = {
    # ouro-2.6b-d12.train-2k
    "train-2k": ((4, 2048, 16, 128), "bfloat16", True),
    # ouro-2.6b.decode-closed
    "prefill-128": ((1, 128, 16, 128), "bfloat16", False),
    "prefill-256": ((1, 256, 16, 128), "bfloat16", False),
    # chip_smoke.py: its train leg, and the longer of its kernel checks
    "dense-168m": ((16, 1024, 16, 64), "bfloat16", True),
    "smoke-4k": ((4, 4096, 8, 128), "bfloat16", True),
    # rows of 512 bytes, two ways, which keep the caps swept at 256, and
    # of 1024, which halve them: each has to fit VMEM as well
    "train-2k-f32": ((4, 2048, 16, 128), "float32", True),
    "head-256": ((2, 2048, 8, 256), "bfloat16", True),
    "head-256-f32": ((2, 2048, 8, 256), "float32", True),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip can be written to the persistent
    cache but not read back without one: keep it out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def mosaic_calls(compiled_text):
    """Which kernel each Mosaic custom call of the program is, by the
    instruction's name as the benchmark's kernel readers match it."""
    from benchmarks.inside import kernel_of

    names = re.findall(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
        compiled_text)
    return sorted(kernel_of(n, KERNELS) or n for n in names)


@pytest.mark.parametrize("cell", sorted(SHAPES))
def test_flash_kernels_compile_for_v5e_under_their_own_names(
        cell, one_chip, no_compile_cache, monkeypatch):
    import jax
    import jax.numpy as jnp
    from jax import lax

    # (ray_tpu.ops exports a function under the module's name)
    attention = importlib.import_module("ray_tpu.ops.attention")

    # this process's default backend is the CPU; the program is for the
    # described chip, so take the branch a TPU process takes
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    shape, dtype, with_gradient = SHAPES[cell]
    arg = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)

    def loss(q, k, v):
        # as models/transformer.py runs its layers under remat: a scan
        # over checkpointed bodies, so that the kernels get the names
        # the training program gives them (under a bare jax.grad the
        # compiler wraps them: jvp_flash_fwd_)
        layer = jax.checkpoint(
            lambda h: attention.flash_attention(h, k, v, causal=True))
        out, _ = lax.scan(lambda h, _: (layer(h), None), q, None, length=2)
        return jnp.sum(out.astype(jnp.float32))

    forward = jax.jit(attention.flash_attention).lower(
        arg, arg, arg).compile().as_text()
    assert "%flash_fwd.1 = " in forward
    assert mosaic_calls(forward) == ["flash_fwd"]
    if not with_gradient:
        return
    both = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        arg, arg, arg).compile().as_text()
    # the forward, remat's second forward, and the backward's two
    assert mosaic_calls(both) == sorted(KERNELS + ("flash_fwd",))
