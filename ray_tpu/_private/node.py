"""Node orchestration: boot GCS + raylet (+ workers) for a head or worker
node.

Role parity: reference python/ray/node.py + _private/services.py — the
``Node`` object starts and supervises the per-node daemons. Here the GCS
and raylet are asyncio services hosted on a dedicated IO thread inside the
node process (head) or inside a standalone ``python -m
ray_tpu._private.node`` process (worker nodes / multi-node tests); worker
processes are always real subprocesses.
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import os
import shutil
import tempfile
import time
from typing import Dict, Optional

from ray_tpu._private import rpc
from ray_tpu._private.config import RayTpuConfig
from ray_tpu._private.gcs import GcsServer
from ray_tpu._private.raylet import Raylet

logger = logging.getLogger(__name__)


def new_session_dir() -> str:
    base = os.environ.get("RAY_TPU_TMPDIR", "/tmp/ray_tpu")
    path = os.path.join(base, f"session_{int(time.time()*1000)}_{os.getpid()}")
    os.makedirs(os.path.join(path, "sockets"), exist_ok=True)
    os.makedirs(os.path.join(path, "logs"), exist_ok=True)
    return path


class Node:
    """A head node (GCS + raylet) or worker node (raylet only)."""

    def __init__(self, config: Optional[RayTpuConfig] = None,
                 num_cpus: int = 1, num_tpus: Optional[int] = None,
                 custom_resources: Optional[Dict[str, float]] = None,
                 session_dir: str = "", node_name: str = ""):
        self.config = config or RayTpuConfig.create()
        self.num_cpus = num_cpus
        resources = dict(custom_resources or {})
        # No chip autodetection: counting them through jax would make
        # THIS process the chip's holder. The caller states num_tpus.
        if num_tpus:
            resources["TPU"] = float(num_tpus)
        self.custom_resources = resources
        self.session_dir = session_dir or new_session_dir()
        self.node_name = node_name
        self.gcs: Optional[GcsServer] = None
        self.raylet: Optional[Raylet] = None
        self.gcs_address = ""
        self.raylet_address = ""
        self._loop_thread: Optional[rpc.EventLoopThread] = None
        self._owns_session_dir = not session_dir

    async def _boot_gcs(self, gcs_listen: str = ""):
        self.gcs = GcsServer(self.config)
        self.gcs_address = await self.gcs.start(
            gcs_listen or
            (f"tcp://127.0.0.1:{self.config.gcs_port}"
             if self.config.gcs_port else "tcp://127.0.0.1:0"))

    def start_head(self, gcs_listen: str = ""):
        self._loop_thread = rpc.EventLoopThread("rtpu-node-io")

        async def _boot():
            await self._boot_gcs(gcs_listen)
            self.raylet = Raylet(self.config, self.num_cpus,
                                 self.custom_resources, self.session_dir,
                                 self.node_name)
            self.raylet_address = await self.raylet.start(self.gcs_address)

        self._loop_thread.run(_boot(), timeout=30)
        return self

    def start_gcs_only(self, gcs_listen: str = ""):
        """GCS process without a raylet (for GCS fault-tolerance setups
        where raylets live in other processes and survive a GCS restart)."""
        self._loop_thread = rpc.EventLoopThread("rtpu-gcs-io")
        self._loop_thread.run(self._boot_gcs(gcs_listen), timeout=30)
        return self

    def start_worker_node(self, gcs_address: str):
        self._loop_thread = rpc.EventLoopThread("rtpu-node-io")
        self.gcs_address = gcs_address

        async def _boot():
            self.raylet = Raylet(self.config, self.num_cpus,
                                 self.custom_resources, self.session_dir,
                                 self.node_name)
            self.raylet_address = await self.raylet.start(gcs_address)

        self._loop_thread.run(_boot(), timeout=30)
        return self

    def stop(self):
        if self._loop_thread is None:
            return

        async def _stop():
            if self.raylet:
                await self.raylet.stop()
            if self.gcs:
                await self.gcs.stop()

        try:
            self._loop_thread.run(_stop(), timeout=10)
        except Exception:
            logger.debug("node stop incomplete", exc_info=True)
        self._loop_thread.stop()
        self._loop_thread = None
        if self._owns_session_dir and not os.environ.get("RAY_TPU_KEEP_SESSION_DIR"):
            shutil.rmtree(self.session_dir, ignore_errors=True)


def main(argv=None):
    """Standalone node process: ``python -m ray_tpu._private.node``."""
    parser = argparse.ArgumentParser()
    parser.add_argument("--head", action="store_true")
    parser.add_argument("--gcs-only", action="store_true",
                        help="run only the GCS (no raylet) in this process")
    parser.add_argument("--gcs-address", default="")
    parser.add_argument("--gcs-listen", default="",
                        help="head only: address for the GCS to listen on")
    parser.add_argument("--num-cpus", type=int, default=1)
    parser.add_argument("--resources", default="",
                        help="comma list k=v of custom resources")
    parser.add_argument("--session-dir", default="")
    parser.add_argument("--node-name", default="")
    parser.add_argument("--address-file", default="",
                        help="write 'gcs_address raylet_address' here when up")
    args = parser.parse_args(argv)

    logging.basicConfig(level="INFO")
    # Deterministic fault schedules reach standalone GCS/raylet
    # processes through the environment (chaos + fault-tolerance tests).
    from ray_tpu._private import faultpoints
    faultpoints.arm_from_env()
    resources = {}
    if args.resources:
        for kv in args.resources.split(","):
            k, _, v = kv.partition("=")
            resources[k] = float(v)

    node = Node(num_cpus=args.num_cpus, custom_resources=resources,
                session_dir=args.session_dir, node_name=args.node_name)
    if args.gcs_only:
        node.start_gcs_only(gcs_listen=args.gcs_listen)
    elif args.head:
        node.start_head(gcs_listen=args.gcs_listen)
    else:
        if not args.gcs_address:
            parser.error("--gcs-address required for worker nodes")
        node.start_worker_node(args.gcs_address)

    if args.address_file:
        tmp = args.address_file + ".tmp"
        with open(tmp, "w") as f:
            f.write(f"{node.gcs_address}\n{node.raylet_address}\n"
                    f"{node.session_dir}\n")
        os.replace(tmp, args.address_file)

    try:
        while True:
            # raylint: disable=async-blocking — head daemon main thread parks forever; all work is on the IO loop thread
            time.sleep(3600)
    except KeyboardInterrupt:
        node.stop()


if __name__ == "__main__":
    main()
