"""Drain + leak check for ci/sanitize.sh (r4 verdict ask #6).

Runs a 100k-task drain with the ASAN/UBSAN-instrumented fastpath on the
whole hot chain (C submit, C complete, compact wire rows, batched
pushes), then a steady-state CPython-allocator check over repeated
submit/complete bursts: after a warm-up burst, further identical bursts
must not grow ``sys.getallocatedblocks()`` beyond noise — the
release-build stand-in for a ``Py_DEBUG`` ``sys.gettotalrefcount``
sweep (which needs a debug interpreter this image does not ship).
"""
import gc
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import ray_tpu  # noqa: E402
from ray_tpu._private import native  # noqa: E402


def main() -> int:
    if native.load_fastpath() is None:
        print("SKIP: native fastpath did not load (no compiler?)")
        return 0
    assert os.environ.get("RAY_TPU_NATIVE_SANITIZE"), \
        "run via ci/sanitize.sh (instrumented build + LD_PRELOAD)"
    ray_tpu.init(num_cpus=max(1, os.cpu_count() or 1))

    @ray_tpu.remote
    def t():
        return b"ok"

    # -- 100k drain under the instrumented tier --------------------------
    n = int(os.environ.get("ASAN_DRAIN_TASKS", "100000"))
    t0 = time.perf_counter()
    refs = [t.remote() for _ in range(n)]
    for start in range(0, n, 20_000):
        ray_tpu.get(refs[start:start + 20_000], timeout=600)
    refs = None
    print(f"drain: {n} tasks in {time.perf_counter() - t0:.1f}s (ASAN)")

    # -- allocator steady-state over submit/complete bursts --------------
    def burst(k=2000):
        ray_tpu.get([t.remote() for _ in range(k)], timeout=300)

    core = ray_tpu.worker.global_worker.core

    def settle(deadline_s=30.0):
        """Wait for the batched decref drain: released refs reach the
        IO loop asynchronously, and under ASAN everything is slower —
        sampling before the tables empty would read backlog as leak."""
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < deadline_s:
            if not core.pending_tasks and \
                    not core.reference_counter._refs:
                break
            time.sleep(0.05)
        gc.collect()

    burst()  # warm caches (interned scheduling classes, wire buffers...)
    settle()
    base = sys.getallocatedblocks()
    for _ in range(5):
        burst()
    settle()
    grown = sys.getallocatedblocks() - base
    # 5 bursts x 2000 tasks; a per-task leak of even one block would
    # show as >=10k. Allow generous noise for interpreter internals.
    print(f"leak check: allocated-block growth after 10k tasks = {grown}")
    ray_tpu.shutdown()
    if grown > 2000:
        print("FAIL: native submit/complete loop leaks allocator blocks")
        return 1
    print("leak check: steady state OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
