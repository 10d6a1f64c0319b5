"""What a replica states of itself to the control plane: how many
requests the routers may keep in flight at it (the deployment's
``max_concurrent_queries``, or its decode loop's own capacity where that
is larger), how long its constructor may take before the deployment
fails (longer for a replica that leases a TPU), and when it counts as
drained (no request for a moment, so that a straggler is served).
"""

import asyncio
import time
import types

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu._private.config import RayTpuConfig
from ray_tpu.serve.controller import _startup_timeout_s
from ray_tpu.serve.decode_scheduler import DecodeScheduler
from ray_tpu.serve.replica import DRAIN_QUIET_S, Replica


class _Engine:
    max_len = 16

    def __init__(self, slots):
        self.slots = slots


@pytest.mark.parametrize("slots,depth,capacity", [
    (8, None, 8 + 64),          # the floor of the queue's bound
    (128, None, 128 + 256),     # a batch of waiters and as many again
    (128, 10, 138),             # the caller's own bound
])
def test_a_decode_loops_capacity_is_its_slots_and_its_queue(
        slots, depth, capacity):
    async def made():
        kwargs = {} if depth is None else {"max_queue_depth": depth}
        return DecodeScheduler(_Engine(slots), **kwargs).capacity

    assert asyncio.run(made()) == capacity


class _PlainCallable:
    def __call__(self, x):
        return x


class _HostsADecodeLoop:
    def __init__(self, capacity):
        self.decode_scheduler = types.SimpleNamespace(capacity=capacity)


@pytest.mark.parametrize("callable_def,args,cap,want", [
    (_PlainCallable, (), 100, 100),
    (_HostsADecodeLoop, (72,), 100, 100),   # 8 slots + 64: the cap stays
    (_HostsADecodeLoop, (384,), 100, 384),  # 128 slots + 256 waiting
    (_HostsADecodeLoop, (384,), 500, 500),  # a larger cap is the user's
])
def test_a_replica_states_its_decode_loops_capacity_where_larger(
        callable_def, args, cap, want):
    replica = Replica(callable_def, args, {}, max_concurrent_queries=cap)
    assert asyncio.run(replica.concurrency()) == want
    # the replica's own shed sits that far above it as before
    assert replica._max_inflight - want == RayTpuConfig(
        ).serve_max_queue_depth


def test_a_draining_replica_serves_a_straggler_before_it_is_drained():
    """The controller kills a replica as soon as ``drain`` returns, and
    a router that has not seen the new snapshot yet may still send to
    it: drained is no request for a moment, not none at this instant."""
    async def scenario():
        replica = Replica(_PlainCallable, (), {})
        await asyncio.sleep(DRAIN_QUIET_S)      # idle before the drain
        drain = asyncio.ensure_future(replica.drain())
        await asyncio.sleep(DRAIN_QUIET_S / 4)
        assert not drain.done()
        straggler = time.monotonic()
        assert await replica.handle_request("__call__", (7,), {}) == 7
        assert await drain == 0
        return time.monotonic() - straggler

    assert asyncio.run(scenario()) >= DRAIN_QUIET_S


@pytest.mark.parametrize("options,want", [
    ({}, 60.0),
    ({"num_cpus": 2}, 60.0),
    ({"num_tpus": 1}, 600.0),
    ({"resources": {"TPU": 4.0}}, 600.0),
    ({"num_tpus": 0}, 60.0),
])
def test_the_start_up_limit_is_the_configs_and_longer_with_a_tpu(
        options, want):
    assert _startup_timeout_s(options) == want


@pytest.fixture
def short_start_up():
    ray_tpu.init(num_cpus=4, _system_config={
        "serve_replica_startup_timeout_s": 1.0})
    serve.start()
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def test_a_constructor_that_hangs_fails_the_deployment_at_the_limit(
        short_start_up):
    @serve.deployment
    class Hangs:
        def __init__(self):
            time.sleep(30)

        def __call__(self):
            return "late"

    @serve.deployment
    def sound():
        return "ok"

    t0 = time.perf_counter()
    with pytest.raises(Exception, match="(?i)timeout|timed out"):
        Hangs.deploy()
    assert time.perf_counter() - t0 < 15
    # the controller goes on serving
    sound.deploy()
    assert ray_tpu.get(sound.get_handle().remote()) == "ok"


@pytest.fixture
def serve_cluster():
    ray_tpu.init(num_cpus=4)
    serve.start()
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def test_the_routers_are_told_the_replicas_own_cap(serve_cluster):
    """A deployment left at the default cap whose replica hosts a decode
    loop of 128 slots: the snapshot the routers get names the loop's
    capacity, and a plain deployment's stays the default."""
    @serve.deployment
    class Batches:
        def __init__(self):
            self.decode_scheduler = types.SimpleNamespace(
                capacity=384, stats=lambda: {})

        def __call__(self):
            return "ok"

    @serve.deployment
    def plain():
        return "ok"

    Batches.deploy()
    plain.deploy()
    from ray_tpu.serve import _get_controller

    controller = _get_controller()
    for name, want in (("Batches", 384), ("plain", 100)):
        snapshot = ray_tpu.get(
            controller.get_replica_snapshot.remote(name))
        assert snapshot["max_concurrent_queries"] == want
        assert len(snapshot["replicas"]) == 1
    assert ray_tpu.get(Batches.get_handle().remote()) == "ok"
