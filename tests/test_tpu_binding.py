"""The `TPU` resource owns the chip: who gets which environment.

No jax here and no chip: the actors only read ``os.environ``, which is
where the raylet's binding lives (platform selection, chip indices,
libtpu's process bounds). That the selected platform really starts —
and raises when it cannot — is chip_smoke.py's business.
"""

import os
import signal

import pytest

import ray_tpu


@ray_tpu.remote
class Env:
    def read(self):
        keys = ("JAX_PLATFORMS", "RAY_TPU_CHIPS", "TPU_VISIBLE_CHIPS",
                "TPU_CHIPS_PER_PROCESS_BOUNDS", "TPU_PROCESS_BOUNDS",
                "JAX_COMPILATION_CACHE_DIR")
        return {"pid": os.getpid(), **{k: os.environ.get(k) for k in keys}}


@pytest.fixture
def two_chips():
    ray_tpu.init(num_cpus=2, num_tpus=2)
    yield ray_tpu.worker.global_worker.node.raylet
    ray_tpu.shutdown()


def _handle(raylet, pid):
    return next(w for w in raylet.workers.values() if w.pid == pid)


def test_tpu_actor_is_bound_and_plain_actor_is_pinned_to_cpu(two_chips):
    raylet = two_chips
    tpu = ray_tpu.get(Env.options(num_tpus=1).remote().read.remote())
    plain = ray_tpu.get(Env.remote().read.remote())

    assert tpu["JAX_PLATFORMS"] == "tpu,cpu"
    assert tpu["RAY_TPU_CHIPS"] in ("0", "1")
    # one chip of a two-chip host: carved out for this process
    assert tpu["TPU_VISIBLE_CHIPS"] == tpu["RAY_TPU_CHIPS"]
    assert tpu["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert tpu["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert tpu["JAX_COMPILATION_CACHE_DIR"]

    assert plain["JAX_PLATFORMS"] == "cpu"
    assert plain["RAY_TPU_CHIPS"] is None
    assert plain["TPU_VISIBLE_CHIPS"] is None

    # a dedicated cold process, never a fork of the CPU-pinned template
    assert _handle(raylet, tpu["pid"]).spawned_via == "popen"
    assert _handle(raylet, tpu["pid"]).tpu_chips == \
        (int(tpu["RAY_TPU_CHIPS"]),)
    assert _handle(raylet, plain["pid"]).tpu_chips == ()


def test_chips_are_exclusive_and_granted_again_after_kill(two_chips):
    a, b, c = (Env.options(num_tpus=1).remote() for _ in range(3))
    env_a = ray_tpu.get(a.read.remote())
    env_b = ray_tpu.get(b.read.remote())
    assert {env_a["RAY_TPU_CHIPS"], env_b["RAY_TPU_CHIPS"]} == {"0", "1"}

    # no third chip: the third actor waits, and the first two live on
    pending = c.read.remote()
    ready, _ = ray_tpu.wait([pending], timeout=2.0)
    assert not ready
    assert ray_tpu.get(a.read.remote())["pid"] == env_a["pid"]

    ray_tpu.kill(a)
    env_c = ray_tpu.get(pending, timeout=30)
    assert env_c["RAY_TPU_CHIPS"] == env_a["RAY_TPU_CHIPS"]
    assert env_c["pid"] != env_a["pid"]


def test_whole_host_lease_is_not_carved(two_chips):
    env = ray_tpu.get(Env.options(
        resources={"TPU": 2}).remote().read.remote())
    assert env["JAX_PLATFORMS"] == "tpu,cpu"
    assert env["RAY_TPU_CHIPS"] == "0,1"
    assert env["TPU_VISIBLE_CHIPS"] is None
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] is None


def test_restarted_actor_is_bound_again(two_chips):
    actor = Env.options(num_tpus=1, max_restarts=1,
                        max_task_retries=2).remote()
    first = ray_tpu.get(actor.read.remote())
    os.kill(first["pid"], signal.SIGKILL)
    again = ray_tpu.get(actor.read.remote(), timeout=60)
    assert again["pid"] != first["pid"]
    assert again["JAX_PLATFORMS"] == "tpu,cpu"
    assert again["RAY_TPU_CHIPS"] in ("0", "1")


def test_task_cannot_hold_tpu(two_chips):
    @ray_tpu.remote(num_tpus=1)
    def on_chip():
        return 1

    with pytest.raises(ValueError, match="task cannot hold TPU"):
        on_chip.remote()

    @ray_tpu.remote
    def plain():
        return 1

    with pytest.raises(ValueError, match="task cannot hold TPU"):
        plain.options(resources={"TPU": 1}).remote()


def test_fractional_chip_is_rejected(two_chips):
    with pytest.raises(ValueError, match="whole chips"):
        Env.options(num_tpus=0.5).remote()
