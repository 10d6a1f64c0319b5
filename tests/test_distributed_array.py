"""DistributedArray + SPMD gang tests (ISSUE 16).

Covers the tentpole surfaces: shard/plan math, put_sharded/get_shard/
assemble/reshard/all_gather/all_reduce correctness, the owner-side
shard GROUP release (refs free as one unit, no leak-detector flags),
gang placement in ONE lease round (asserted via rpc telemetry), the
gang epoch fence, and the observability satellites (shard placement on
``state.list_objects()`` records, ``gangs`` block in GetNodeStats).
"""

import asyncio
import time

import numpy as np
import pytest

import ray_tpu
import ray_tpu.state as state
from ray_tpu import exceptions as exc
from ray_tpu._private import distributed_array as da
from ray_tpu._private import rpc

# ------------------------------------------------------------ plan math


def test_mesh_and_shard_slices_cover_disjoint():
    mesh = da.Mesh((2, 3), ("x", "y"))
    assert mesh.nranks == 6
    assert mesh.coords(0) == (0, 0)
    assert mesh.coords(5) == (1, 2)
    spec = da.PartitionSpec("x", "y")
    shape = (10, 7)
    slices = da.shard_slices(shape, mesh, spec)
    seen = np.zeros(shape, dtype=np.int64)
    for s in slices:
        seen[s] += 1
    # exact cover: every element in exactly one shard
    assert (seen == 1).all()


def test_balanced_split_remainder():
    # 10 over 3 -> 4,3,3 (front-loaded remainder)
    parts = da.balanced_split(10, 3)
    assert [b - a for a, b in parts] == [4, 3, 3]
    assert parts[0][0] == 0 and parts[-1][1] == 10


def test_gather_plan_moves_every_destination_byte():
    shape = (12, 9)
    itemsize = 8
    m_src = da.Mesh((3,), ("x",))
    s_src = da.PartitionSpec("x")
    m_dst = da.Mesh((3,), ("y",))
    s_dst = da.PartitionSpec(None, "y")
    plan = da.gather_plan(shape, itemsize, m_src, s_src, m_dst, s_dst)
    for dst_rank in range(3):
        nbytes = int(np.prod(
            da.shard_shape(shape, m_dst, s_dst, dst_rank))) * itemsize
        total = sum(r[2] for _sr, runs in plan[dst_rank] for r in runs)
        assert total == nbytes
        # dst offsets are disjoint and in-range
        covered = np.zeros(nbytes, dtype=np.int8)
        for _sr, runs in plan[dst_rank]:
            for s, d, ln in runs:
                covered[d:d + ln] += 1
        assert (covered == 1).all()


def test_gather_plan_replicated_source_dedups():
    # a replicated dim must contribute each byte ONCE, not per replica
    shape = (8, 8)
    m_src = da.Mesh((2,), ("x",))
    s_src = da.PartitionSpec()  # fully replicated: every rank holds all
    m_dst = da.Mesh((1,), ("g",))
    s_dst = da.PartitionSpec()
    plan = da.gather_plan(shape, 8, m_src, s_src, m_dst, s_dst)
    total = sum(r[2] for _sr, runs in plan[0] for r in runs)
    assert total == 8 * 8 * 8


# --------------------------------------------------- data-path correctness


def test_put_sharded_get_shard_assemble(ray_start_4cpu):
    mesh = ray_tpu.Mesh((2,), ("x",))
    spec = ray_tpu.PartitionSpec("x")
    arr = np.arange(64, dtype=np.float64).reshape(8, 8)
    darr = ray_tpu.put_sharded(arr, mesh, spec)
    assert darr.shape == (8, 8) and len(darr.shards) == 2
    s0 = ray_tpu.get_shard(darr, 0)
    assert np.array_equal(s0, arr[:4])
    full = ray_tpu.assemble(darr)
    assert np.array_equal(full, arr)


def test_reshard_row_to_col_correctness(ray_start_4cpu):
    mesh = ray_tpu.Mesh((2,), ("x",))
    arr = np.arange(16 * 12, dtype=np.float32).reshape(16, 12)
    darr = ray_tpu.put_sharded(arr, mesh, ray_tpu.PartitionSpec("x"))
    darr2 = ray_tpu.reshard(darr, ray_tpu.Mesh((2,), ("y",)),
                            ray_tpu.PartitionSpec(None, "y"))
    assert np.array_equal(ray_tpu.assemble(darr2), arr)
    # shard contents landed exactly, not merely the assembled view
    assert np.array_equal(ray_tpu.get_shard(darr2, 1), arr[:, 6:])


def test_all_gather_and_all_reduce(ray_start_4cpu):
    mesh = ray_tpu.Mesh((2,), ("x",))
    arr = np.arange(32, dtype=np.float64).reshape(4, 8)
    darr = ray_tpu.put_sharded(arr, mesh, ray_tpu.PartitionSpec("x"))
    ref = ray_tpu.all_gather(darr)
    assert np.array_equal(ray_tpu.get(ref), arr)
    # all_reduce: full-shape partials (replicated spec), summed
    partial = np.full((4, 4), 1.5)
    dar = ray_tpu.put_sharded(partial, ray_tpu.Mesh((3,), ("r",)),
                              ray_tpu.PartitionSpec())
    out = ray_tpu.get(ray_tpu.all_reduce(dar))
    assert np.allclose(out, 3 * 1.5)


def test_put_sharded_rejects_object_dtype(ray_start_regular):
    arr = np.array([{"a": 1}, {"b": 2}], dtype=object)
    with pytest.raises(TypeError):
        ray_tpu.put_sharded(arr, ray_tpu.Mesh((2,), ("x",)),
                            ray_tpu.PartitionSpec("x"))


# -------------------------------------------------- shard group lifetime


@pytest.fixture
def shard_cluster():
    info = ray_tpu.init(num_cpus=2, _system_config={
        "metrics_report_period_ms": 200,
        "raylet_heartbeat_period_ms": 100,
        "leak_sweep_interval_s": 0.3})
    yield info
    ray_tpu.shutdown()


def _shard_states(oid_hexes, timeout=15.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        recs = {o["object_id"]: o for o in state.list_objects()}
        if all(h in recs for h in oid_hexes):
            return {h: recs[h] for h in oid_hexes}
        time.sleep(0.2)
    raise AssertionError("shard records never reached the object table")


def test_shard_group_frees_as_one_unit(shard_cluster):
    """Holding ONE shard ref pins the WHOLE group; dropping the last
    ref releases every shard in one wave — and the leak detector never
    flags the group."""
    core = ray_tpu.worker.global_worker.core
    mesh = ray_tpu.Mesh((2,), ("x",))
    arr = np.ones(400_000, dtype=np.float64)  # 3.2 MB -> plasma shards
    darr = ray_tpu.put_sharded(arr, mesh, ray_tpu.PartitionSpec("x"))
    oids = [s.ref.object_id for s in darr.shards]
    held = darr.shards[0].ref  # extra ref on shard 0 only
    del darr
    time.sleep(1.0)
    # shard 1's handle ref is gone, but the GROUP defers its release
    # while shard 0 is still reachable
    for oid in oids:
        assert core.reference_counter.has_reference(oid), oid.hex()
    del held
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        if not any(core.reference_counter.has_reference(o) for o in oids):
            break
        time.sleep(0.1)
    for oid in oids:
        assert not core.reference_counter.has_reference(oid), oid.hex()
    time.sleep(1.0)  # leak sweep window
    assert state.summary_objects()["leaked"] == 0


def test_shard_placement_on_object_records(shard_cluster):
    """state.list_objects() shows shard rank + mesh coords (satellite
    5: placement introspection rides the existing object plane)."""
    mesh = ray_tpu.Mesh((2,), ("x",))
    arr = np.ones(400_000, dtype=np.float64)
    darr = ray_tpu.put_sharded(arr, mesh, ray_tpu.PartitionSpec("x"))
    hexes = [s.ref.object_id.hex() for s in darr.shards]
    recs = _shard_states(hexes)
    for rank, h in enumerate(hexes):
        shard = recs[h].get("shard")
        assert shard, recs[h]
        assert shard["rank"] == rank
        assert tuple(shard["coords"]) == (rank,)
        assert shard["mesh"] is not None


# ------------------------------------------------------------- SPMD gangs


def _tel_count(side: str, method: str) -> int:
    entry = getattr(rpc.telemetry, side).get(method)
    return entry.count if entry is not None else 0


def test_gang_books_in_one_lease_round(ray_start_4cpu):
    """Gang placement is ONE RequestGangLease call — not N
    RequestWorkerLease round-trips (the acceptance telemetry assert)."""

    # warm the pool so the booking round finds forked idle workers —
    # a cold pool grants short and the driver retries, which would
    # obscure the one-round assertion below
    # (two tasks at once, until two processes have answered: on a loaded
    # box one quick worker would else take both while the rest still fork)
    @ray_tpu.remote
    def warm():
        import os
        time.sleep(0.2)
        return os.getpid()

    pids = set()
    for _ in range(20):
        pids.update(ray_tpu.get([warm.remote() for _ in range(2)]))
        if len(pids) >= 2:
            break
    assert len(pids) >= 2

    before_gang = _tel_count("client", "RequestGangLease")
    before_lease = _tel_count("client", "RequestWorkerLease")
    gang = ray_tpu.create_gang(2)
    try:
        assert _tel_count("client", "RequestGangLease") == before_gang + 1
        assert _tel_count("client",
                          "RequestWorkerLease") == before_lease
        assert gang.world_size == 2 and len(gang.members) == 2
        assert [m for m in gang.members]  # rank-ordered adopted members

        def rankfn(r):
            import os
            return (r, os.getpid())

        vals = ray_tpu.get(gang.run(rankfn))
        assert sorted(v[0] for v in vals) == [0, 1]
        assert len({v[1] for v in vals}) == 2  # distinct processes
    finally:
        gang.release()


def test_gang_epoch_fence_rejects_stale_push(ray_start_4cpu):
    """After re-formation the old incarnation's epoch is fenced: a
    stale member/owner push (Request or Release at the old epoch) is
    rejected, never applied to the new incarnation."""
    core = ray_tpu.worker.global_worker.core
    gang = ray_tpu.create_gang(2)
    old_epoch = gang.epoch
    gang.reform()
    assert gang.epoch == old_epoch + 1
    try:
        from ray_tpu._private import protocol

        # stale release from the OLD incarnation: fenced
        reply, _ = core._run(core.raylet_conn.call(
            "ReleaseGangLease",
            protocol.ReleaseGangLeaseRequest(
                gang_id=gang.gang_id, epoch=old_epoch).to_header()))
        assert reply.get("stale_epoch") and not reply.get("ok")
        # stale gang-lease request (same epoch as live): fenced too
        reply, _ = core._run(core.raylet_conn.call(
            "RequestGangLease",
            protocol.RequestGangLeaseRequest(
                gang_id=gang.gang_id, epoch=gang.epoch,
                count=2).to_header()))
        assert reply.get("stale_epoch") and not reply.get("granted")
        # the live incarnation still works
        vals = ray_tpu.get(gang.run(lambda r: r + 10))
        assert sorted(vals) == [10, 11]
    finally:
        gang.release()


def test_gang_release_returns_workers_to_pool(ray_start_4cpu):
    """Released members go back to the idle pool: a plain task runs
    fine afterwards and a fresh gang books again."""
    gang = ray_tpu.create_gang(2)
    ray_tpu.get(gang.run(lambda r: r))
    gang.release()

    @ray_tpu.remote
    def f(x):
        return x + 1

    assert ray_tpu.get(f.remote(1)) == 2
    gang2 = ray_tpu.create_gang(2)
    try:
        assert sorted(ray_tpu.get(gang2.run(lambda r: r))) == [0, 1]
    finally:
        gang2.release()


@pytest.fixture
def gang_failfast_cluster():
    info = ray_tpu.init(num_cpus=2, _system_config={
        "gang_lease_retry_attempts": 0})
    yield info
    ray_tpu.shutdown()


def test_gang_placement_error_when_infeasible(gang_failfast_cluster):
    """More ranks than the cluster's CPUs can host: typed
    all-or-nothing failure with nothing leased behind it."""
    with pytest.raises(exc.GangPlacementError):
        ray_tpu.create_gang(3, resources={"CPU": 1.0})

    # nothing leaked behind the rollback: a plain task still schedules
    @ray_tpu.remote
    def f():
        return 42

    assert ray_tpu.get(f.remote()) == 42


def test_gangs_block_in_node_stats(ray_start_4cpu):
    core = ray_tpu.worker.global_worker.core
    gang = ray_tpu.create_gang(2)
    try:
        async def _q():
            conn = await rpc.connect(core.raylet_address,
                                     peer_name="test-gang-stats")
            try:
                reply, _ = await conn.call("GetNodeStats", {})
                return reply
            finally:
                await conn.close()

        stats = asyncio.run(_q())
        gangs = stats.get("gangs")
        assert gangs and gangs["num_gang_leases"] >= 1
        homed = gangs["homed"]
        assert any(g["gang_id"] == gang.gang_id.hex() and
                   g["size"] == 2 and not g["broken"] for g in homed)
    finally:
        gang.release()


# ------------------------------------------------------- ring plan math


def test_ring_segments_partition_exactly():
    """Segments tile [0, nbytes) contiguously, element-aligned, with
    balanced lengths — including uneven splits and P > element count."""
    for nel, nranks, itemsize in [(10, 3, 8), (7, 7, 4), (5, 8, 4),
                                  (1, 3, 8), (1000, 3, 2), (12, 4, 8)]:
        nbytes = nel * itemsize
        segs = da.ring_segments(nbytes, itemsize, nranks)
        assert len(segs) == nranks
        off = 0
        for s_off, s_len in segs:
            assert s_off == off and s_len >= 0
            assert s_len % itemsize == 0
            off += s_len
        assert off == nbytes
        lens = [ln for _o, ln in segs]
        # balanced: lengths differ by at most one element
        assert max(lens) - min(lens) <= itemsize
    with pytest.raises(ValueError):
        da.ring_segments(10, 8, 3)  # nbytes not element-aligned


@pytest.mark.parametrize("nranks", [2, 3, 4, 7])
def test_ring_reduce_schedule_correct_by_simulation(nranks):
    """Simulate the schedule under barrier semantics (exactly what the
    driver's round loop provides): after 2(P-1) steps every rank's
    every segment has folded in every rank's contribution exactly
    once, and each step is a single ring cycle."""
    scheds = [da.ring_reduce_schedule(r, nranks) for r in range(nranks)]
    assert all(len(s) == 2 * (nranks - 1) for s in scheds)
    # contributions[rank][seg] = set of ranks folded in so far
    cur = [[{r} for _ in range(nranks)] for r in range(nranks)]
    for step in range(2 * (nranks - 1)):
        nxt = [[set(segs) for segs in rank_segs] for rank_segs in cur]
        for r in range(nranks):
            st = scheds[r][step]
            assert st["step"] == step
            assert st["recv_peer"] == (r - 1) % nranks
            assert st["send_peer"] == (r + 1) % nranks
            src = cur[st["recv_peer"]][st["seg"]]
            if st["reduce"]:
                assert st["phase"] == "rs"
                nxt[r][st["seg"]] = cur[r][st["seg"]] | src
            else:
                assert st["phase"] == "ag"
                nxt[r][st["seg"]] = set(src)
        cur = nxt
    full = set(range(nranks))
    for r in range(nranks):
        for seg in range(nranks):
            assert cur[r][seg] == full, (r, seg, cur[r][seg])
    with pytest.raises(ValueError):
        da.ring_reduce_schedule(0, 1)


@pytest.mark.parametrize("nranks", [2, 3, 5])
def test_ring_gather_schedule_correct_by_simulation(nranks):
    """All-gather ring: rank r starts owning segment r; after P-1 copy
    steps every rank holds every segment."""
    scheds = [da.ring_gather_schedule(r, nranks) for r in range(nranks)]
    assert all(len(s) == nranks - 1 for s in scheds)
    cur = [{r} for r in range(nranks)]  # segments held per rank
    for step in range(nranks - 1):
        nxt = [set(h) for h in cur]
        for r in range(nranks):
            st = scheds[r][step]
            assert not st["reduce"]
            assert st["recv_peer"] == (r - 1) % nranks
            # the puller's upstream peer must already hold the segment
            # (barrier between rounds is what guarantees this)
            assert st["seg"] in cur[st["recv_peer"]], (r, step, st)
            nxt[r].add(st["seg"])
        cur = nxt
    assert all(h == set(range(nranks)) for h in cur)


# --------------------------------------------- ring collectives (e2e)


def _query_raylet_stats(address: str) -> dict:
    async def _q():
        conn = await rpc.connect(address, peer_name="test-ring-stats")
        try:
            reply, _ = await conn.call("GetNodeStats", {})
            return reply
        finally:
            await conn.close()

    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(_q())
    finally:
        loop.close()


def test_all_reduce_rides_the_ring_with_bandwidth_bound(ray_start_4cpu):
    """P=3 replicated partials: all_reduce must take the ring (records
    in the collectives telemetry block), every rank moving exactly
    2*(P-1)/P * N wire bytes, and the result must equal the numpy
    fold."""
    core = ray_tpu.worker.global_worker.core
    partial = np.arange(3000, dtype=np.float64).reshape(50, 60)
    dar = ray_tpu.put_sharded(partial, ray_tpu.Mesh((3,), ("r",)),
                              ray_tpu.PartitionSpec())
    out = ray_tpu.get(ray_tpu.all_reduce(dar))
    assert np.array_equal(out, partial * 3)
    stats = _query_raylet_stats(core.raylet_address)
    coll = stats.get("collectives")
    assert coll and coll["finished"] >= 3 and coll["active_members"] == 0
    ring = [r for r in coll["recent"]
            if r["algo"] == "ring" and r["op"] == "sum" and r["ok"]]
    assert len(ring) >= 3
    nbytes = partial.nbytes
    expect = 2 * (3 - 1) * nbytes // 3
    for rec in ring[-3:]:
        assert rec["steps"] == 4 and rec["folds"] >= 2
        # exact bound, not just <=: every byte of the 2(P-1)/P schedule
        # moved and nothing more (segments are element-balanced so the
        # per-rank total can differ from the ideal by < 2 elements/step)
        assert abs(rec["wire_bytes"] - expect) <= 4 * partial.itemsize


def test_all_reduce_min_max_end_to_end(ray_start_4cpu):
    """min/max ride the same ring as sum (distinct-operand coverage is
    in the 3-raylet test; put_sharded replicates ONE partial, so here
    min/max are idempotent and sum multiplies by P)."""
    rng = np.random.default_rng(3)
    part = rng.integers(-1000, 1000, size=(40, 30)).astype(np.int64)
    mesh = ray_tpu.Mesh((3,), ("r",))
    spec = ray_tpu.PartitionSpec()
    for op, want in [("min", part), ("max", part), ("sum", part * 3)]:
        dar = ray_tpu.put_sharded(part, mesh, spec)
        out = ray_tpu.get(ray_tpu.all_reduce(dar, op=op))
        assert np.array_equal(out, want), op


def test_all_reduce_rejects_bad_op_and_dtype(ray_start_4cpu):
    partial = np.ones((4, 4), dtype=np.float64)
    dar = ray_tpu.put_sharded(partial, ray_tpu.Mesh((3,), ("r",)),
                              ray_tpu.PartitionSpec())
    with pytest.raises(ValueError):
        ray_tpu.all_reduce(dar, op="mean")
    cpx = np.ones((4, 4), dtype=np.complex128)
    dcx = ray_tpu.put_sharded(cpx, ray_tpu.Mesh((3,), ("r",)),
                              ray_tpu.PartitionSpec())
    with pytest.raises(TypeError):
        ray_tpu.all_reduce(dcx)


@pytest.fixture
def three_extra_raylets(ray_start_4cpu):
    """THREE extra in-process raylets joined to the running head's GCS
    on a dedicated loop thread: a real multi-raylet topology for ring
    e2e tests (members on distinct nodes, steps over real TCP)."""
    import threading

    from ray_tpu._private.config import RayTpuConfig
    from ray_tpu._private.raylet import Raylet

    core = ray_tpu.worker.global_worker.core
    loop = asyncio.new_event_loop()
    thr = threading.Thread(target=loop.run_forever, daemon=True,
                           name="ring-extra-raylets")
    thr.start()
    cfg = RayTpuConfig.create({
        "num_prestart_workers": 0, "event_log_enabled": False})

    async def _boot():
        out = []
        for i in range(3):
            r = Raylet(cfg, 0, session_dir=core.session_dir,
                       node_name=f"ring-extra-{i}")
            await r.start(core.gcs_address)
            out.append(r)
        return out

    raylets = asyncio.run_coroutine_threadsafe(_boot(), loop).result(30)
    yield raylets, loop

    async def _stop():
        for r in raylets:
            try:
                await r.stop()
            except Exception:
                pass

    asyncio.run_coroutine_threadsafe(_stop(), loop).result(30)
    loop.call_soon_threadsafe(loop.stop)
    thr.join(5)


def _seed_darr(core, raylets, loop, parts, mesh, spec):
    """Hand-build a DistributedArray whose rank-r shard lives on
    raylets[r]'s store (put_sharded always lands shards on the
    driver's node; ring e2e needs them spread out)."""
    from ray_tpu._private.core_worker import IN_PLASMA
    from ray_tpu._private.object_ref import ObjectRef
    from ray_tpu._private.shm_store import plan_segment, write_segment

    shards = []
    for rank, part in enumerate(parts):
        ser = core.serialization_context.serialize(np.ascontiguousarray(part))
        _h, raw, offsets, total = plan_segment(ser)

        def _seed(_ser=ser, _raylet=raylets[rank], _plan=(_h, raw, offsets, total)):
            name, size = write_segment(_ser, plan=_plan)
            oid = core._next_put_id()
            assert _raylet.store.seal(oid, name, size)
            return oid, size

        oid, size = asyncio.run_coroutine_threadsafe(
            asyncio.to_thread(_seed), loop).result(30)
        core.reference_counter.add_owned_object(oid)
        core.reference_counter.add_location(
            oid, raylets[rank].node_id.binary(), size)
        core.memory_store.put(oid, IN_PLASMA)
        ref = ObjectRef(oid, owner_address=core.address, worker=core,
                        call_site="test-seed")
        shards.append(da.ShardInfo(
            ref=ref, rank=rank,
            node_id=raylets[rank].node_id.binary(),
            data_offset=offsets[1], nbytes=raw[1].nbytes,
            shape=part.shape))
    shape = parts[0].shape if spec == ray_tpu.PartitionSpec() else None
    assert shape is not None, "helper only builds replicated arrays"
    return da.DistributedArray(mesh, spec, shape, str(parts[0].dtype),
                               shards)


def test_ring_all_reduce_three_raylets_matches_fold(three_extra_raylets):
    """The e2e acceptance test: an all_reduce whose members live on
    three DISTINCT raylets rides the ring over real RPC + data-plane
    connections, and its result is numerically identical to the
    in-tree fold path's on the same operands (int partials: both
    orders are exact)."""
    raylets, loop = three_extra_raylets
    core = ray_tpu.worker.global_worker.core
    rng = np.random.default_rng(17)
    parts = [rng.integers(-10_000, 10_000, size=(64, 48))
             .astype(np.int64) for _ in range(3)]
    mesh = ray_tpu.Mesh((3,), ("r",))
    spec = ray_tpu.PartitionSpec()

    darr = _seed_darr(core, raylets, loop, parts, mesh, spec)
    ring_out = ray_tpu.get(ray_tpu.all_reduce(darr))
    want = parts[0] + parts[1] + parts[2]
    assert np.array_equal(ring_out, want)

    # ring engaged on the extra raylets, not the head: every member
    # raylet shows one finished ring collective with the exact
    # 2*(P-1)/P wire bound
    nbytes = parts[0].nbytes
    expect = 2 * (3 - 1) * nbytes // 3
    for r in raylets:
        coll = _query_raylet_stats(r.address).get("collectives")
        assert coll and coll["finished"] >= 1
        assert coll["active_members"] == 0
        rec = [c for c in coll["recent"] if c["algo"] == "ring"][-1]
        assert rec["ok"] and rec["steps"] == 4
        assert abs(rec["wire_bytes"] - expect) <= 4 * parts[0].itemsize

    # force the fold path on the SAME operands and compare exactly
    darr2 = _seed_darr(core, raylets, loop, parts, mesh, spec)
    saved = core.config.collective_algorithm
    core.config.collective_algorithm = "fold"
    try:
        fold_out = ray_tpu.get(ray_tpu.all_reduce(darr2))
    finally:
        core.config.collective_algorithm = saved
    assert np.array_equal(fold_out, ring_out)

    # min/max across DISTINCT per-rank operands, same topology
    darr3 = _seed_darr(core, raylets, loop, parts, mesh, spec)
    assert np.array_equal(
        ray_tpu.get(ray_tpu.all_reduce(darr3, op="min")),
        np.minimum(np.minimum(parts[0], parts[1]), parts[2]))
    darr4 = _seed_darr(core, raylets, loop, parts, mesh, spec)
    assert np.array_equal(
        ray_tpu.get(ray_tpu.all_reduce(darr4, op="max")),
        np.maximum(np.maximum(parts[0], parts[1]), parts[2]))
