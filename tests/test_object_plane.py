"""Object-plane behaviors: spill/restore under pressure, cancel, lineage
reconstruction after node loss.

Reference coverage model: python/ray/tests/test_object_spilling.py,
test_cancel.py, test_reconstruction.py.
"""

import asyncio
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster
from ray_tpu import exceptions as exc


def _stats(raylet_address: str) -> dict:
    from ray_tpu._private import rpc

    async def _q():
        conn = await rpc.connect(raylet_address, peer_name="test-stats")
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


def test_spill_and_restore_under_pressure(tmp_path):
    """Pinned primaries spill to disk when the store overfills, and a
    later get restores them (reference: LocalObjectManager spill/restore,
    local_object_manager.h:90,:109)."""
    ray_tpu.init(num_cpus=1, object_store_memory=4 * 1024 * 1024)
    try:
        mb = 1024 * 1024
        refs = [ray_tpu.put(np.full(mb // 8, i, dtype=np.float64))
                for i in range(6)]  # 6 MB into a 4 MB store
        # every value still readable — early ones restored from spill
        for i, r in enumerate(refs):
            val = ray_tpu.get(r)
            assert val[0] == float(i) and len(val) == mb // 8
        node = ray_tpu.worker.global_worker.node
        stats = node.raylet.store.stats()
        assert stats["num_spills"] >= 1, stats
        assert stats["num_restores"] >= 1, stats
    finally:
        ray_tpu.shutdown()


def test_spill_to_external_storage(tmp_path):
    """Spilling targets a workflow-storage URL instead of the local
    session dir (reference: external_storage.py:71 — S3 via smart_open;
    here the same seam with the file:// backend standing in for the
    cloud bucket): spilled blobs land under the URL, restores read them
    back, and frees delete them."""
    import os

    store_dir = tmp_path / "ext_spill"
    ray_tpu.init(num_cpus=1, object_store_memory=4 * 1024 * 1024,
                 _system_config={
                     "spill_external_storage_url": f"file://{store_dir}"})
    try:
        mb = 1024 * 1024
        refs = [ray_tpu.put(np.full(mb // 8, i, dtype=np.float64))
                for i in range(6)]  # 6 MB into a 4 MB store
        node = ray_tpu.worker.global_worker.node
        stats = node.raylet.store.stats()
        assert stats["num_spills"] >= 1, stats
        # the spilled blobs are IN the external store, not the session
        spill_keys = os.listdir(store_dir / "spill")
        assert len(spill_keys) >= 1
        # every value still readable — restored from external storage
        for i, r in enumerate(refs):
            val = ray_tpu.get(r)
            assert val[0] == float(i) and len(val) == mb // 8
        assert node.raylet.store.stats()["num_restores"] >= 1
    finally:
        ray_tpu.shutdown()


def test_cancel_queued_task():
    """Cancelling a not-yet-running task makes get() raise
    TaskCancelledError (reference: test_cancel.py)."""
    ray_tpu.init(num_cpus=1)
    try:
        @ray_tpu.remote
        def slow(t):
            time.sleep(t)
            return t

        blocker = slow.remote(3.0)
        queued = [slow.remote(0.0) for _ in range(20)]
        victim = queued[-1]
        ray_tpu.cancel(victim)
        with pytest.raises((exc.TaskCancelledError, exc.RayTaskError)):
            ray_tpu.get(victim, timeout=20)
        assert ray_tpu.get(blocker) == 3.0
    finally:
        ray_tpu.shutdown()


def test_lineage_reconstruction_after_node_loss():
    """Losing every copy of a task return triggers resubmission of the
    creating task on a surviving node (reference: ObjectRecoveryManager,
    object_recovery_manager.h:92 + test_reconstruction.py)."""
    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 1})
    a = c.add_node(num_cpus=1, resources={"spot": 1})
    b = c.add_node(num_cpus=1, resources={"spot": 1})
    c.connect()
    try:
        @ray_tpu.remote(resources={"spot": 1}, max_retries=2)
        def produce():
            import numpy as np
            return np.arange(200_000)  # 1.6 MB -> plasma on the spot node

        ref = produce.remote()
        assert ray_tpu.get(ref)[-1] == 199_999
        # find which node executed it and kill that node
        sa, sb = _stats(a.raylet_address), _stats(b.raylet_address)
        holder, other = (a, b) if sa["store"]["num_objects"] else (b, a)
        c.remove_node(holder)  # SIGKILL: the only data copy dies with it
        c.wait_for_nodes(2, timeout=30)
        # the driver's pulled copy? The driver attached via head raylet -
        # drop the cached attachment to force a fresh pull
        core = ray_tpu.worker.global_worker.core
        with core._attached_lock:
            for att in core._attached.values():
                att.close()
            core._attached.clear()
        head_stats = _stats(c.head.raylet_address)
        if head_stats["store"]["num_objects"]:
            # head holds a replica; free it so the get must reconstruct
            from ray_tpu._private import rpc as _rpc

            async def _free():
                conn = await _rpc.connect(c.head.raylet_address,
                                          peer_name="t")
                try:
                    await conn.call("FreeObject",
                                    {"object_id": ref.object_id.binary()})
                finally:
                    await conn.close()
            loop = asyncio.new_event_loop()
            loop.run_until_complete(_free())
            loop.close()
        out = ray_tpu.get(ref, timeout=60)
        assert out[-1] == 199_999
        assert core.stats["tasks_retried"] >= 1
    finally:
        ray_tpu.shutdown()
        c.shutdown()


def test_attachment_deferred_release():
    """A detached mapping with live zero-copy consumers must not raise
    BufferError (from SharedMemory.__del__) and must be unmapped the
    moment the consumer dies — deterministically, via the consumers'
    buffer exports holding the mmap, with NO fallback parking
    (reference: plasma client Release discipline,
    src/ray/object_manager/plasma/client.cc)."""
    import gc

    from ray_tpu._private import shm_store
    from ray_tpu._private.serialization import SerializationContext

    ctx = SerializationContext()
    arr = np.arange(4096, dtype=np.float64)
    name, size = shm_store.write_segment(ctx.serialize(arr))
    try:
        gc.collect()  # an earlier test's deferred mapping must not
        # be released by THIS test's collect and skew the count
        base = shm_store.deferred_count()
        att = shm_store.AttachedObject(name)
        # Zero-copy view into the mapping, as ray_tpu.get() produces.
        view = ctx.deserialize(att.metadata, att.frames)
        assert isinstance(view, np.ndarray) and view[17] == 17.0
        att.close()  # consumer still alive: unmap deferred, no BufferError
        assert shm_store.deferred_count() == base + 1
        assert shm_store.zombie_count() == 0  # fallback path not taken
        assert view[4095] == 4095.0  # still readable while deferred
        del view
        gc.collect()
        # consumer gone: the mmap was deallocated (munmapped) with it
        assert shm_store.deferred_count() == base
        assert shm_store.zombie_count() == 0
    finally:
        shm_store.ShmStoreServer._unlink(name)


@pytest.fixture(autouse=True)
def _no_fallback_parking():
    """Across the whole object-plane suite, the deferred-release path
    must fully absorb consumer-pinned detaches: the fallback park list
    stays empty (r4 verdict ask #8)."""
    from ray_tpu._private import shm_store

    yield
    assert shm_store.zombie_count() == 0


# ---------------------------------------------------------------------------
# Zero-copy put pipeline (single-memcpy write path)
# ---------------------------------------------------------------------------


def test_alloc_lease_abort_returns_segment_to_pool():
    """Seal-or-abort lease protocol (raylint shm-lifecycle): a writer
    whose fill fails hands the segment back via abort_lease and the
    warm pages go straight back to the recycle pool — not parked in
    _lent until the 600 s stale sweep."""
    from ray_tpu._private.ids import ObjectID
    from ray_tpu._private.serialization import SerializedObject
    from ray_tpu._private.shm_store import ShmStoreServer, write_segment

    store = ShmStoreServer(capacity_bytes=64 << 20, spilling_enabled=False)
    payload = np.ones(1 << 20, dtype=np.uint8)
    obj = SerializedObject(b"raw", [payload.tobytes()])
    name, size = write_segment(obj)
    oid = ObjectID.from_random()
    assert store.seal(oid, name, size)
    store.free(oid)  # unexposed -> parked in the recycle pool
    assert name in store._recycle

    got = store.take_recycled(size)
    assert got is not None and got[0] == name
    assert name in store._lent and name not in store._recycle

    store.abort_lease(name)  # the failed-fill path (AbortSegment RPC)
    assert name not in store._lent
    assert name in store._recycle, "aborted lease must be re-parked"
    # the very next lease of a similar size reuses the warm segment
    again = store.take_recycled(size)
    assert again is not None and again[0] == name
    store.release_lease(name)
    store._unlink(name)


def test_write_segment_exact_sizing_and_roundtrip():
    """The two-pass writer sizes the segment exactly (plan == file
    size) and the attached readback deserializes bit-identical."""
    import os

    from ray_tpu._private import shm_store
    from ray_tpu._private.serialization import SerializationContext

    ctx = SerializationContext()
    value = {"a": np.arange(10000, dtype=np.float32),
             "b": [1, "two", 3.0],
             "c": np.ones((13, 7), dtype=np.int64)}
    serialized = ctx.serialize(value)
    planned = shm_store.segment_nbytes(serialized)
    name, total = shm_store.write_segment(serialized)
    try:
        assert total == planned
        assert os.path.getsize(f"/dev/shm/{name}") == total
        att = shm_store.AttachedObject(name)
        got = ctx.deserialize(att.metadata, att.frames)
        assert np.array_equal(got["a"], value["a"])
        assert got["b"] == value["b"]
        assert np.array_equal(got["c"], value["c"])
        got = None
        att.close()
    finally:
        shm_store._map_cache.clear()
        shm_store.ShmStoreServer._unlink(name)


def test_put_hot_path_never_flattens(ray_start_regular):
    """A large put must never call the copying SerializedObject.to_wire
    (pickle-5 buffers ride as raw views end to end) — counted via a
    shim on the copying API."""
    from unittest import mock

    from ray_tpu._private.serialization import SerializedObject

    calls = []
    orig = SerializedObject.to_wire

    def counting(self):
        calls.append(self)
        return orig(self)

    arr = np.ones(1024 * 1024, dtype=np.float64)  # 8 MB -> plasma
    with mock.patch.object(SerializedObject, "to_wire", counting):
        ref = ray_tpu.put(arr)
        got = ray_tpu.get(ref)
    assert np.array_equal(got, arr)
    assert not calls, "put/get flattened frames via to_wire()"


def test_put_noncontiguous_and_readonly_arrays(ray_start_regular):
    """Non-contiguous arrays (pickled in-band by numpy) and readonly
    arrays (readonly buffer views) both roundtrip exactly."""
    base = np.arange(200000, dtype=np.float64)
    strided = base[::3]
    assert not strided.flags["C_CONTIGUOUS"]
    ro = np.arange(150000, dtype=np.int32)
    ro.setflags(write=False)
    f_order = np.asfortranarray(
        np.arange(120000, dtype=np.float32).reshape(300, 400))
    got_s, got_r, got_f = ray_tpu.get(
        [ray_tpu.put(strided), ray_tpu.put(ro), ray_tpu.put(f_order)])
    assert np.array_equal(got_s, strided)
    assert np.array_equal(got_r, ro)
    assert np.array_equal(got_f, f_order) and got_f.flags["F_CONTIGUOUS"]


def test_write_segment_pwrite_chunking(monkeypatch):
    """The huge-frame path (tier-3 pwrite) split across many
    sub-2GiB-cap chunks is bit-exact — the cap is shrunk so a modest
    frame exercises the same loop a >2GiB frame would."""
    from ray_tpu._private import shm_store
    from ray_tpu._private.serialization import SerializationContext

    ctx = SerializationContext()
    arr = np.random.default_rng(3).integers(
        0, 255, 1_000_003, dtype=np.uint8)  # odd size
    serialized = ctx.serialize(arr)
    monkeypatch.setattr(shm_store, "PWRITE_CHUNK_BYTES", 4096 + 1)
    # force tier 3 (pwrite): disable the writer map cache
    monkeypatch.setattr(shm_store._map_cache, "cap_bytes", 0)
    name, total = shm_store.write_segment(serialized)
    try:
        att = shm_store.AttachedObject(name)
        got = ctx.deserialize(att.metadata, att.frames)
        assert np.array_equal(got, arr)
        got = None
        att.close()
    finally:
        shm_store.ShmStoreServer._unlink(name)


def test_writer_parity_native_vs_pure_python():
    """All writer tiers (cached mapping, fresh mapping, pwrite, and the
    pure-Python fallback copy) produce byte-identical segments."""
    import os

    from ray_tpu._private import native, shm_store
    from ray_tpu._private.serialization import SerializationContext

    ctx = SerializationContext()
    value = {"x": np.arange(300000, dtype=np.float64),
             "y": b"tail" * 1000}

    def read_bytes(name):
        with open(f"/dev/shm/{name}", "rb") as f:
            return f.read()

    images = {}
    names = []
    try:
        # tier 2: fresh mapped write (native copy engine)
        n, _ = shm_store.write_segment(ctx.serialize(value))
        names.append(n)
        images["mapped_native"] = read_bytes(n)
        # tier 3: pwrite
        try:
            shm_store._map_cache.cap_bytes = 0
            n, _ = shm_store.write_segment(ctx.serialize(value))
            names.append(n)
            images["pwrite"] = read_bytes(n)
        finally:
            shm_store._map_cache.cap_bytes = 1 << 30
        # tier 2 again with native masked: pure-Python fallback copies
        saved = native._mod, native._tried
        native._mod, native._tried = None, True
        try:
            n, _ = shm_store.write_segment(ctx.serialize(value))
            names.append(n)
            images["mapped_python"] = read_bytes(n)
        finally:
            native._mod, native._tried = saved
        ref = images["mapped_native"]
        for label, img in images.items():
            assert img == ref, f"writer tier {label} diverged"
        # and the image deserializes to the original value
        att = shm_store.AttachedObject(names[0])
        got = ctx.deserialize(att.metadata, att.frames)
        assert np.array_equal(got["x"], value["x"])
        assert got["y"] == value["y"]
        got = None
        att.close()
    finally:
        shm_store._map_cache.clear()
        for n in names:
            shm_store.ShmStoreServer._unlink(n)


def test_recycled_segments_never_corrupt_live_views(ray_start_regular):
    """SAFETY: freeing an object whose segment a consumer still views
    zero-copy must NOT let the recycler overwrite those pages — exposed
    segments are unlinked (mapping stays valid), never parked."""
    arr = np.full(1024 * 1024, 7.0, dtype=np.float64)  # 8 MB
    ref = ray_tpu.put(arr)
    view = ray_tpu.get(ref)  # zero-copy mmap view of the segment
    assert view[0] == 7.0
    del ref  # frees the object; the segment has a live consumer
    # hammer the recycler with same-size puts: a corrupted pool would
    # overwrite the consumer's pages
    for _ in range(8):
        junk = [ray_tpu.put(np.zeros(1024 * 1024, dtype=np.float64))
                for _ in range(3)]
        del junk
    assert float(view[0]) == 7.0 and float(view[-1]) == 7.0, \
        "recycler overwrote a segment with live zero-copy consumers"
    view = None


def test_wire_frames_matches_to_wire():
    """Differential: the no-copy wire form and the copying snapshot
    form carry identical bytes for every frame."""
    from ray_tpu._private.serialization import SerializationContext

    ctx = SerializationContext()
    for value in [np.arange(5000, dtype=np.float32),
                  {"k": np.ones(17), "s": "text", "n": 42},
                  [b"raw", bytearray(b"ba"), memoryview(b"mv")],
                  ValueError("boom")]:
        serialized = ctx.serialize(value)
        meta_a, snap = serialized.to_wire()
        meta_b, live = serialized.wire_frames()
        assert meta_a == meta_b
        assert len(snap) == len(live)
        for s, l in zip(snap, live):
            assert bytes(l) == s


def test_serializer_differential_old_vs_new(ray_start_regular):
    """Acceptance differential: values routed through the OLD copying
    wire form (to_wire snapshot) and the NEW zero-copy pipeline
    deserialize bit-identical — numpy arrays, jax arrays, nested
    containers with embedded ObjectRefs, and error payloads."""
    import jax.numpy as jnp

    from ray_tpu._private import shm_store
    from ray_tpu._private.serialization import META_ERROR

    core = ray_tpu.worker.global_worker.core
    ctx = core.serialization_context
    inner = ray_tpu.put(np.arange(32))
    values = [
        np.random.default_rng(0).standard_normal((257, 33)),
        jnp.linspace(0.0, 1.0, 10_001),
        {"refs": [inner, inner], "arr": np.ones(1000, dtype=np.int16),
         "nest": ({"deep": np.zeros(3)}, "s", 7)},
    ]
    for value in values:
        serialized = ctx.serialize(value)
        # OLD path: flattened bytes snapshot
        meta, flat = serialized.to_wire()
        old = ctx.deserialize(meta, flat)
        # NEW path: raw views through a real segment write + attach
        name, _ = shm_store.write_segment(serialized)
        try:
            att = shm_store.AttachedObject(name)
            new = ctx.deserialize(att.metadata, att.frames)
            if hasattr(value, "shape"):
                assert np.asarray(old).tobytes() == \
                    np.asarray(new).tobytes()
                assert np.asarray(old).dtype == np.asarray(new).dtype
            else:
                assert np.asarray(old["arr"]).tobytes() == \
                    np.asarray(new["arr"]).tobytes()
                assert [r.object_id for r in old["refs"]] == \
                    [r.object_id for r in new["refs"]]
                assert np.asarray(old["nest"][0]["deep"]).tobytes() == \
                    np.asarray(new["nest"][0]["deep"]).tobytes()
                assert old["nest"][1:] == new["nest"][1:]
            new = None
            att.close()
        finally:
            shm_store._map_cache.clear()
            shm_store.ShmStoreServer._unlink(name)
    # error payloads: both forms raise the same error
    err = ctx.serialize_error(ValueError("differential boom"))
    meta, flat = err.to_wire()
    assert meta == META_ERROR
    with pytest.raises(ValueError, match="differential boom"):
        ctx.deserialize(meta, flat)
    meta2, live = err.wire_frames()
    with pytest.raises(ValueError, match="differential boom"):
        ctx.deserialize(meta2, [bytes(f) for f in live])
