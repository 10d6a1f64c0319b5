"""The ``ray_tpu`` command line: cluster lifecycle + introspection.

Parity target: reference python/ray/scripts/scripts.py — ``ray start``
(:485), ``stop`` (:800), ``status`` (:1521), ``memory`` (:1497),
``timeline`` (:1433), ``microbenchmark`` (:1421).

Usage::

    python -m ray_tpu start --head [--num-cpus N]
    python -m ray_tpu start --address tcp://HOST:PORT
    python -m ray_tpu status | memory | timeline | microbenchmark
    python -m ray_tpu stop
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

_BASE = os.environ.get("RAY_TPU_TMPDIR", "/tmp/ray_tpu")
_CURRENT = os.path.join(_BASE, "ray_current_cluster")
_PIDS = os.path.join(_BASE, "cli_node_pids")


def _node_log(pid: int) -> str:
    """Where a CLI-started node's stderr is kept until ``stop``."""
    return os.path.join(_BASE, f"cli_node_{pid}.err")


def _read_current_address() -> str:
    try:
        with open(_CURRENT) as f:
            return f.read().strip()
    except FileNotFoundError:
        return ""


def _resolve_address(args) -> str:
    addr = getattr(args, "address", "") or _read_current_address()
    if not addr:
        sys.exit("no running cluster found: pass --address or run "
                 "`python -m ray_tpu start --head` first")
    return addr


def _connect(args):
    import ray_tpu

    ray_tpu.init(address=_resolve_address(args), log_to_driver=False)
    return ray_tpu


def cmd_start(args) -> None:
    os.makedirs(_BASE, exist_ok=True)
    addr_file = os.path.join(
        _BASE, f"cli_addr_{os.getpid()}_{int(time.time())}")
    cmd = [sys.executable, "-m", "ray_tpu._private.node",
           "--num-cpus", str(args.num_cpus),
           "--address-file", addr_file]
    if args.head:
        cmd += ["--head"]
        if args.port:
            cmd += ["--gcs-listen", f"tcp://127.0.0.1:{args.port}"]
    else:
        if not args.address:
            sys.exit("worker nodes need --address of the head GCS")
        cmd += ["--gcs-address", args.address]
    if args.resources:
        cmd += ["--resources", args.resources]

    # the node's stderr (its log, and the reason when it cannot start)
    # goes to a file: a pipe would block or break once this CLI exits
    err_path = addr_file + ".err"
    with open(err_path, "wb") as err:
        proc = subprocess.Popen(cmd, start_new_session=True,
                                stdout=subprocess.DEVNULL, stderr=err)

    def give_up(why: str):
        with open(err_path, errors="replace") as f:
            tail = f.read()[-4000:].strip()
        os.unlink(err_path)
        sys.exit(f"{why}; the node's last output:\n{tail}" if tail
                 else why)

    deadline = time.monotonic() + 60
    while time.monotonic() < deadline and not os.path.exists(addr_file):
        if proc.poll() is not None:
            give_up(f"node process exited early (rc={proc.returncode})")
        # raylint: disable=async-blocking — CLI process waiting on a child daemon's address file; no loop here
        time.sleep(0.1)
    if not os.path.exists(addr_file):
        proc.terminate()
        give_up("timed out waiting for the node to come up")
    os.replace(err_path, _node_log(proc.pid))
    with open(addr_file) as f:
        gcs_address, raylet_address, session_dir = \
            f.read().strip().splitlines()
    os.unlink(addr_file)

    with open(_PIDS, "a") as f:
        f.write(f"{proc.pid}\n")
    if args.head:
        with open(_CURRENT, "w") as f:
            f.write(gcs_address)
        print(f"started head node (pid {proc.pid})")
        print(f"  GCS address: {gcs_address}")
        print("connect with:")
        print(f"  ray_tpu.init(address={gcs_address!r})")
        print("or from this shell:")
        print(f"  python -m ray_tpu status")
    else:
        print(f"started worker node (pid {proc.pid}) -> {args.address}")
    print(f"  session dir: {session_dir}")
    if args.block:
        try:
            proc.wait()
        except KeyboardInterrupt:
            proc.terminate()


def cmd_stop(args) -> None:
    try:
        with open(_PIDS) as f:
            pids = [int(line) for line in f.read().split()]
    except FileNotFoundError:
        print("no CLI-started nodes found")
        return
    stopped = 0
    for pid in pids:
        try:
            os.killpg(os.getpgid(pid), signal.SIGTERM)
            stopped += 1
        except (ProcessLookupError, PermissionError):
            pass
    for path in (_PIDS, _CURRENT, *map(_node_log, pids)):
        try:
            os.unlink(path)
        except FileNotFoundError:
            pass
    print(f"stopped {stopped} node process(es)")


def cmd_status(args) -> None:
    ray_tpu = _connect(args)
    from ray_tpu import state

    print(state.status())
    addr = state.metrics_address()
    if addr:
        print(f"Prometheus metrics: http://{addr}/metrics")
    ray_tpu.shutdown()


def cmd_memory(args) -> None:
    ray_tpu = _connect(args)
    from ray_tpu import state

    print(state.memory_summary())
    ray_tpu.shutdown()


def cmd_stack(args) -> None:
    """All-worker stack dump per node (reference: `ray stack`,
    scripts.py:1393 — py-spy over local worker pids; here every worker
    self-reports all its threads over RPC, so it works cluster-wide)."""
    ray_tpu = _connect(args)
    from ray_tpu._private import rpc as rpc_mod

    nodes = [n for n in ray_tpu.nodes() if n.get("Alive")]

    async def _dump(address):
        conn = await rpc_mod.connect(address, peer_name="stack-cli")
        try:
            reply, _ = await conn.call("DumpWorkerStacks", {}, timeout=15.0)
            return reply
        finally:
            await conn.close()

    core = ray_tpu.worker.global_worker.core
    for n in nodes:
        print(f"===== node {n['NodeID'][:12]} {n['Address']} =====")
        try:
            reply = core._run(_dump(n["Address"]))
        except Exception as e:  # noqa: BLE001
            print(f"  unreachable: {e}")
            continue
        for w in reply.get("workers", []):
            print(f"--- worker pid {w.get('pid')} "
                  f"{w.get('worker_id', '')[:12]} ---")
            print(w.get("stacks") or w.get("error", ""))
    ray_tpu.shutdown()


def cmd_logs(args) -> None:
    """List or tail a node's session log files over the raylet RPC."""
    ray_tpu = _connect(args)
    from ray_tpu._private import rpc as rpc_mod

    nodes = [n for n in ray_tpu.nodes() if n.get("Alive")]
    node = nodes[0] if nodes else None
    for n in nodes:
        if args.node and n["NodeID"].startswith(args.node):
            node = n
            break
    if node is None:
        print("no alive nodes")
        ray_tpu.shutdown()
        return

    async def _logs(address):
        conn = await rpc_mod.connect(address, peer_name="logs-cli")
        try:
            reply, _ = await conn.call(
                "GetLogs", {"name": args.name, "tail": args.tail},
                timeout=10.0)
            return reply
        finally:
            await conn.close()

    core = ray_tpu.worker.global_worker.core
    reply = core._run(_logs(node["Address"]))
    if "files" in reply and not args.name:
        for f in reply["files"]:
            print(f"{f.get('size', 0):>10}  {f['name']}")
    elif "lines" in reply:
        print(f"==> {reply['name']} <==")
        for line in reply["lines"]:
            print(line)
    else:
        print(reply.get("error", reply))
    ray_tpu.shutdown()


def cmd_timeline(args) -> None:
    ray_tpu = _connect(args)
    events = ray_tpu.timeline()
    out = args.output or os.path.join(
        _BASE, f"timeline_{int(time.time())}.json")
    with open(out, "w") as f:
        json.dump(events, f)
    print(f"wrote {len(events)} events to {out} "
          f"(open in chrome://tracing or Perfetto)")
    ray_tpu.shutdown()


def _microbenchmark_main() -> None:
    # In-process cluster, same harness shape as the reference's
    # `ray microbenchmark` (reference: _private/ray_perf.py).
    import ray_tpu

    ray_tpu.init(num_cpus=max(1, os.cpu_count() or 1))

    @ray_tpu.remote
    def small():
        return b"ok"

    @ray_tpu.remote
    class A:
        def ping(self):
            return b"ok"

    def timeit(name, fn, n):
        fn(min(n, 100))  # warmup
        best = 0.0
        for _ in range(3):
            t0 = time.perf_counter()
            fn(n)
            best = max(best, n / (time.perf_counter() - t0))
        print(f"{name}: {best:,.1f}/s")

    timeit("single client tasks async",
           lambda n: ray_tpu.get([small.remote() for _ in range(n)]),
           5000)
    a = A.remote()
    timeit("1:1 actor calls async",
           lambda n: ray_tpu.get([a.ping.remote() for _ in range(n)]),
           5000)
    timeit("single client put",
           lambda n: [ray_tpu.put(b"x") for _ in range(n)] and None,
           5000)
    ray_tpu.shutdown()


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(
        prog="ray_tpu", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = parser.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("start", help="start a head or worker node")
    p.add_argument("--head", action="store_true")
    p.add_argument("--address", default="",
                   help="head GCS address (worker nodes)")
    p.add_argument("--port", type=int, default=0,
                   help="head: fixed GCS port")
    p.add_argument("--num-cpus", type=int,
                   default=max(1, os.cpu_count() or 1))
    p.add_argument("--resources", default="",
                   help="comma list k=v of custom resources")
    p.add_argument("--block", action="store_true",
                   help="stay attached to the node process")
    p.set_defaults(fn=cmd_start)

    p = sub.add_parser("stop", help="stop CLI-started nodes")
    p.set_defaults(fn=cmd_stop)

    for name, fn in [("status", cmd_status), ("memory", cmd_memory),
                     ("timeline", cmd_timeline), ("stack", cmd_stack),
                     ("logs", cmd_logs)]:
        p = sub.add_parser(name)
        p.add_argument("--address", default="")
        if name == "timeline":
            p.add_argument("--output", default="")
        if name == "logs":
            p.add_argument("--node", default="",
                           help="node id hex prefix (default: first node)")
            p.add_argument("--name", default="",
                           help="log file substring; empty lists files")
            p.add_argument("--tail", type=int, default=200)
        p.set_defaults(fn=fn)

    p = sub.add_parser("microbenchmark",
                       help="task/actor/put throughput on this machine")
    p.set_defaults(fn=lambda a: _microbenchmark_main())

    args = parser.parse_args(argv)
    args.fn(args)


if __name__ == "__main__":
    main()
