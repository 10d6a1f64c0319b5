"""Test fixtures.

Mirrors the reference's fixture strategy (reference:
python/ray/tests/conftest.py): ``ray_start_regular`` boots a small
single-node cluster per test; ``ray_start_shared`` is module-scoped for
cheap read-only tests. JAX-based tests force an 8-device virtual CPU mesh
so multi-chip sharding logic runs without TPU hardware.
"""

import os

# Must be set before any jax import anywhere in the test process: the
# differential oracles need the 8-device virtual CPU mesh (TPU fp32
# matmuls round through bf16 and would break them), and a test process
# must never become a chip's holder.
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

import pytest  # noqa: E402

import ray_tpu  # noqa: E402
from ray_tpu._private import faultpoints  # noqa: E402


@pytest.fixture(autouse=True)
def _disarm_faultpoints():
    """No fault armed by one test may leak into the next (the registry
    is process-wide by design)."""
    yield
    faultpoints.reset()


@pytest.fixture
def ray_start_regular():
    info = ray_tpu.init(num_cpus=2)
    yield info
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_4cpu():
    info = ray_tpu.init(num_cpus=4)
    yield info
    ray_tpu.shutdown()


@pytest.fixture(scope="module")
def ray_start_shared():
    info = ray_tpu.init(num_cpus=2)
    yield info
    ray_tpu.shutdown()


@pytest.fixture
def decode_kernel_interpreted(monkeypatch):
    """``models.decode`` takes ``ops.attention``'s decode kernel under
    ``interpret`` for its full-attention runs (off the TPU it would
    take the XLA form): ``slot_decode_step`` traces anew inside the
    test, and again after it."""
    import functools
    import importlib

    from ray_tpu.models import decode

    attention = importlib.import_module("ray_tpu.ops.attention")
    for name in ("decode_attention", "decode_rows_fetched"):
        monkeypatch.setattr(decode, name, functools.partial(
            getattr(attention, name), interpret=True))
    decode.slot_decode_step.clear_cache()
    decode._decode_loop.clear_cache()
    yield
    decode.slot_decode_step.clear_cache()
    decode._decode_loop.clear_cache()
