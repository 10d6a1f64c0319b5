"""GCS fault tolerance: journal persistence + restart recovery.

Reference coverage model: python/ray/tests/test_gcs_fault_tolerance.py —
kill the GCS process, restart it on the same address, and assert that
metadata (named actors, KV) survives and raylets re-register.
"""

import os
import signal
import socket
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu.cluster_utils import NodeHandle


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn_gcs(port: int, journal: str, tmpdir: str, tag: str,
               faultpoints_spec=None) -> NodeHandle:
    addr_file = os.path.join(tmpdir, f"gcs_{tag}.addr")
    env = dict(os.environ)
    env["RAY_TPU_GCS_JOURNAL_PATH"] = journal
    if faultpoints_spec is not None:
        # deterministic fault schedule armed at GCS boot
        # (faultpoints.arm_from_env in node.main)
        import json

        env["RAY_TPU_FAULTPOINTS"] = json.dumps(faultpoints_spec)
    else:
        env.pop("RAY_TPU_FAULTPOINTS", None)
    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.node", "--gcs-only",
         "--gcs-listen", f"tcp://127.0.0.1:{port}",
         "--address-file", addr_file],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    node = NodeHandle(proc, addr_file, head=True)
    node.wait_ready()
    return node


def _spawn_raylet(gcs_address: str, tmpdir: str) -> NodeHandle:
    addr_file = os.path.join(tmpdir, "raylet.addr")
    env = dict(os.environ)
    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.node",
         "--gcs-address", gcs_address, "--num-cpus", "2",
         "--address-file", addr_file],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    node = NodeHandle(proc, addr_file, head=False)
    node.wait_ready()
    return node


def test_gcs_restart_preserves_metadata(tmp_path):
    port = _free_port()
    journal = str(tmp_path / "gcs.journal")
    gcs = _spawn_gcs(port, journal, str(tmp_path), "a")
    raylet = _spawn_raylet(gcs.gcs_address, str(tmp_path))
    try:
        ray_tpu.init(address=gcs.gcs_address)

        @ray_tpu.remote
        class KVHolder:
            def __init__(self):
                self.state = {}

            def put(self, k, v):
                self.state[k] = v
                return True

            def get(self, k):
                return self.state.get(k)

        holder = KVHolder.options(name="survivor",
                                  lifetime="detached").remote()
        assert ray_tpu.get(holder.put.remote("k", 41))
        ray_tpu.experimental_internal_kv_put(b"mykey", b"myvalue")

        # SIGKILL the GCS; the raylet and the actor worker stay alive.
        gcs.proc.send_signal(signal.SIGKILL)
        gcs.proc.wait(timeout=10)
        gcs2 = _spawn_gcs(port, journal, str(tmp_path), "b")
        # raylet reconnects + re-registers within its retry budget
        deadline = time.time() + 30
        ok = False
        while time.time() < deadline:
            try:
                nodes = ray_tpu.nodes()
                if any(n["Alive"] for n in nodes):
                    ok = True
                    break
            except Exception:
                pass
            time.sleep(0.25)
        assert ok, "raylet did not re-register with the restarted GCS"

        # KV survived the restart via journal replay
        assert ray_tpu.experimental_internal_kv_get(b"mykey") == b"myvalue"
        # the named actor survived: lookup works and its state is intact
        # (the worker process never died)
        h2 = ray_tpu.get_actor("survivor")
        assert ray_tpu.get(h2.get.remote("k"), timeout=30) == 41
        gcs2.terminate()
    finally:
        ray_tpu.shutdown()
        raylet.terminate()
        gcs.terminate()


def test_gcs_killed_between_journal_append_and_reply(tmp_path):
    """The canonical "did my mutation land?" crash: the GCS dies AFTER
    the journal append but BEFORE the reply (faultpoint
    ``gcs.journal.append`` armed kill via the environment). The
    client's _gcs_call redial must carry the KVPut through the restart
    — idempotently: the value is present exactly once, and the raylet
    re-registers."""
    import threading

    port = _free_port()
    journal = str(tmp_path / "gcs_kill.journal")
    gcs = _spawn_gcs(port, journal, str(tmp_path), "a", faultpoints_spec=[
        {"name": "gcs.journal.append", "action": "kill", "nth": 1,
         "match": {"op": "kv_put"}}])
    raylet = _spawn_raylet(gcs.gcs_address, str(tmp_path))
    try:
        ray_tpu.init(address=gcs.gcs_address)
        err: list = []

        def put():
            try:
                # 1st attempt: journaled, then the GCS dies pre-reply.
                # The client's transparent redial retries once the
                # restarted GCS answers.
                ray_tpu.experimental_internal_kv_put(b"crashkey",
                                                     b"crashval")
            except Exception as e:  # noqa: BLE001 — reported below
                err.append(e)

        t = threading.Thread(target=put)
        t.start()
        gcs.proc.wait(timeout=30)  # the armed kill fired
        gcs2 = _spawn_gcs(port, journal, str(tmp_path), "b")
        t.join(timeout=60)
        assert not t.is_alive(), "kv_put hung across the GCS crash"
        assert not err, f"kv_put failed across the GCS crash: {err[0]!r}"
        assert ray_tpu.experimental_internal_kv_get(b"crashkey") == \
            b"crashval"
        # raylet re-registration after the restart
        deadline = time.time() + 30
        while time.time() < deadline:
            if any(n["Alive"] for n in ray_tpu.nodes()):
                break
            time.sleep(0.25)
        else:
            raise AssertionError("raylet never re-registered")
        gcs2.terminate()
    finally:
        ray_tpu.shutdown()
        raylet.terminate()
        gcs.terminate()


def test_register_actor_retry_after_severed_reply(tmp_path):
    """The GCS connection dies mid-reply to RegisterActor (faultpoint
    ``rpc.reply.send`` sever): the handler RAN, the client retries over
    a fresh connection, and the registration must dedupe — one actor,
    no name collision, creation completes."""
    port = _free_port()
    journal = str(tmp_path / "gcs_sever.journal")
    gcs = _spawn_gcs(port, journal, str(tmp_path), "a", faultpoints_spec=[
        {"name": "rpc.reply.send", "action": "sever", "nth": 1,
         "match": {"method": "RegisterActor"}}])
    raylet = _spawn_raylet(gcs.gcs_address, str(tmp_path))
    try:
        ray_tpu.init(address=gcs.gcs_address)

        @ray_tpu.remote
        class A:
            def ping(self):
                return "pong"

        a = A.options(name="sever-survivor").remote()
        assert ray_tpu.get(a.ping.remote(), timeout=60) == "pong"
        named = ray_tpu.worker.global_worker.core.gcs_call_sync(
            "ListNamedActors", {"namespace": None})
        names = [e["name"] for e in named["actors"]]
        assert names.count("sever-survivor") == 1, names
    finally:
        ray_tpu.shutdown()
        raylet.terminate()
        gcs.terminate()


def test_task_events_usable_after_gcs_restart(tmp_path):
    """GCS restart mid-job: the in-memory task-event table dies with
    the process (bounded loss by design) but the REBUILT table must
    ingest post-restart events consistently — list_tasks() and the
    summary work, new task histories are complete."""
    port = _free_port()
    journal = str(tmp_path / "gcs_events.journal")
    gcs = _spawn_gcs(port, journal, str(tmp_path), "a")
    raylet = _spawn_raylet(gcs.gcs_address, str(tmp_path))
    try:
        ray_tpu.init(address=gcs.gcs_address)

        @ray_tpu.remote
        def t(x):
            return x + 1

        assert ray_tpu.get([t.remote(i) for i in range(4)],
                           timeout=60) == [1, 2, 3, 4]
        gcs.proc.send_signal(signal.SIGKILL)
        gcs.proc.wait(timeout=10)
        gcs2 = _spawn_gcs(port, journal, str(tmp_path), "b")
        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                if any(n["Alive"] for n in ray_tpu.nodes()):
                    break
            except Exception:  # noqa: BLE001 — GCS still rebooting
                pass
            time.sleep(0.25)
        # post-restart tasks land in the rebuilt table with full
        # histories (flushed on the 2 s metrics cadence — poll)
        assert ray_tpu.get([t.remote(i) for i in range(4, 8)],
                           timeout=60) == [5, 6, 7, 8]
        import ray_tpu.state as state_mod
        deadline = time.time() + 20
        finished = []
        while time.time() < deadline and not finished:
            finished = [r for r in state_mod.list_tasks(limit=1000)
                        if r["state"] == "FINISHED"]
            if not finished:
                time.sleep(0.5)
        assert finished, "rebuilt task-event table never saw the " \
                         "post-restart tasks"
        summary = state_mod.summary_tasks()
        assert summary, "summary_tasks unusable after restart"
        gcs2.terminate()
    finally:
        ray_tpu.shutdown()
        raylet.terminate()
        gcs.terminate()


def test_journal_replay_tolerates_torn_tail(tmp_path):
    from ray_tpu._private.gcs_storage import GcsJournal, replay

    path = str(tmp_path / "j.bin")
    j = GcsJournal(path)
    j.append("kv_put", {"key": b"a", "value": b"1"})
    j.append("kv_put", {"key": b"b", "value": b"2"})
    j.close()
    # simulate a crash mid-append: garbage half-record at the tail
    with open(path, "ab") as f:
        f.write(b"\xff\xff\xff\x7f partial")
    records = list(replay(path))
    assert [p["key"] for _, p in records] == [b"a", b"b"]
