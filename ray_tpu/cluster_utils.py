"""Multi-node test harness: N real node processes on one host.

Role parity: reference ray.cluster_utils.Cluster
(reference: python/ray/cluster_utils.py:11, add_node :62, remove_node
:125) — the fixture every multi-node CI test uses. Each node is a real
``python -m ray_tpu._private.node`` subprocess (its own GCS connection,
raylet, shm store, worker pool), so failure injection = killing the
process, exactly like the reference's component-failure tests.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import time
from typing import Dict, List, Optional

import ray_tpu


class NodeHandle:
    def __init__(self, proc: subprocess.Popen, address_file: str,
                 head: bool):
        self.proc = proc
        self.address_file = address_file
        self.head = head
        self.gcs_address = ""
        self.raylet_address = ""
        self.session_dir = ""
        self.node_id: bytes = b""

    def wait_ready(self, timeout: float = 30.0):
        deadline = time.time() + timeout
        while time.time() < deadline:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"node process exited rc={self.proc.returncode}")
            if os.path.exists(self.address_file):
                with open(self.address_file) as f:
                    lines = f.read().splitlines()
                if len(lines) >= 3:
                    self.gcs_address = lines[0]
                    self.raylet_address = lines[1]
                    self.session_dir = lines[2]
                    return self
            # raylint: disable=async-blocking — test-harness boot wait on the user thread; no loop exists yet
            time.sleep(0.05)
        raise TimeoutError("node did not come up")

    def kill(self):
        """Hard-kill (failure injection — reference: Cluster.remove_node
        with allow_graceful=False kills the raylet process)."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGKILL)
            self.proc.wait(timeout=10)

    def terminate(self):
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=5)


class Cluster:
    """Boot a head node + N worker nodes as subprocesses; drivers attach
    with ``ray_tpu.init(address=cluster.address)``."""

    def __init__(self, initialize_head: bool = True,
                 head_node_args: Optional[Dict] = None,
                 connect: bool = False,
                 env: Optional[Dict[str, str]] = None):
        self.nodes: List[NodeHandle] = []
        self.head: Optional[NodeHandle] = None
        self._tmpdir = os.path.join(
            os.environ.get("RAY_TPU_TMPDIR", "/tmp/ray_tpu"),
            f"cluster_{os.getpid()}_{int(time.time() * 1000)}")
        os.makedirs(self._tmpdir, exist_ok=True)
        self._env = dict(os.environ)
        if env:
            self._env.update(env)
        self._counter = 0
        if initialize_head:
            self.head = self.add_node(head=True, **(head_node_args or {}))
        if connect:
            self.connect()

    @property
    def address(self) -> str:
        return self.head.gcs_address if self.head else ""

    def add_node(self, num_cpus: int = 1, head: bool = False,
                 resources: Optional[Dict[str, float]] = None,
                 node_name: str = "", wait: bool = True) -> NodeHandle:
        self._counter += 1
        address_file = os.path.join(self._tmpdir,
                                    f"node_{self._counter}.addr")
        cmd = [sys.executable, "-m", "ray_tpu._private.node",
               "--num-cpus", str(num_cpus),
               "--address-file", address_file]
        if node_name:
            cmd += ["--node-name", node_name]
        if resources:
            cmd += ["--resources",
                    ",".join(f"{k}={v}" for k, v in resources.items())]
        if head:
            cmd += ["--head"]
        else:
            assert self.head is not None, "head node required first"
            cmd += ["--gcs-address", self.head.gcs_address]
        proc = subprocess.Popen(
            cmd, env=self._env,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        node = NodeHandle(proc, address_file, head)
        if wait:
            node.wait_ready()
            if not head:
                self._wait_node_count()
        self.nodes.append(node)
        return node

    def _alive_nodes(self) -> list:
        """Node info list from the GCS (drivers need not be connected)."""
        import asyncio

        from ray_tpu._private import rpc

        async def _q():
            conn = await rpc.connect(self.address, peer_name="cluster-util")
            try:
                reply, _ = await conn.call("GetAllNodeInfo", {})
                return [n for n in reply["nodes"] if n["alive"]]
            finally:
                await conn.close()

        loop = asyncio.new_event_loop()
        try:
            return loop.run_until_complete(_q())
        finally:
            loop.close()

    def _wait_node_count(self, timeout: float = 30.0):
        want = 1 + sum(1 for n in self.nodes if not n.head
                       and n.proc.poll() is None) + 1  # + the one joining
        deadline = time.time() + timeout
        while time.time() < deadline:
            if len(self._alive_nodes()) >= want:
                return
            # raylint: disable=async-blocking — test-harness membership wait; subprocess polling has no event to wait on
            time.sleep(0.05)
        raise TimeoutError(f"cluster did not reach {want} nodes")

    def wait_for_nodes(self, count: int, timeout: float = 30.0):
        deadline = time.time() + timeout
        while time.time() < deadline:
            if len(self._alive_nodes()) == count:
                return
            # raylint: disable=async-blocking — test-harness membership wait; subprocess polling has no event to wait on
            time.sleep(0.05)
        raise TimeoutError(
            f"expected {count} alive nodes, have {len(self._alive_nodes())}")

    def remove_node(self, node: NodeHandle, allow_graceful: bool = False):
        if allow_graceful:
            node.terminate()
        else:
            node.kill()
        if node in self.nodes:
            self.nodes.remove(node)

    def connect(self, **kwargs):
        return ray_tpu.init(address=self.address, **kwargs)

    def shutdown(self):
        for node in reversed(self.nodes):
            node.terminate()
        self.nodes.clear()
        self.head = None
