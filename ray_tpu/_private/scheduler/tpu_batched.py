"""tpu_batched scheduling backend: the decision path as one JAX kernel
over STATE-RESIDENT arrays.

The north-star design (BASELINE.json): instead of per-task callback chains
(reference: ClusterTaskManager::DispatchScheduledTasksToWorkers,
src/ray/raylet/scheduling/cluster_task_manager.cc), the whole tick is a
single jit-compiled program over arrays:

  * demands  [T, R]  — resource demand per pending lease request
  * totals   [N, R]  / avail [N, R] — cluster resource table
  * locality [T, N]  — bytes of each task's args already on each node
  * is_local [N]

The request-side arrays are **resident**: they live in jax's CPU backend
across ticks, keyed by slot. A tick uploads only the DELTA — rows for
newly arrived / changed requests, cleared validity bits for departed
ones — so tick cost is O(changes) + one kernel launch, not O(T × N)
Python work (the round-2 shape). Requests keep their slot for life; a
per-tick permutation restores arrival order inside the kernel (grants
must see earlier grants' resource consumption, so the scan is ordered).

One ``lax.scan`` over tasks with fully vectorized per-node feasibility +
fixed-point scoring inside each step; XLA fuses gather + scan into one
program, so a tick over thousands of pending tasks is one device launch
instead of thousands of callback invocations. Capacities are bucketed
(powers of two) to keep retraces rare; growth copies into a bigger
bucket. Ticks are submit-triggered and coalesced by the raylet
(_schedule_tick schedules at most one tick per loop turn).

Placements are bit-identical to the host backend (shared fixed-point
score, scheduler/scoring.py); tests/test_scheduler_diff.py enforces it.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional

from ray_tpu._private.scheduler import (
    GRANT, INFEASIBLE, SPILL, WAIT, Decision, NodeView, PendingRequest,
    SchedulingBackend,
)
from ray_tpu._private.scheduler.scoring import (
    HI_LOC_SHIFT, LO_LOC_MASK, LOC_MAX, UTIL_MAX, UTIL_SCALE,
    spread_threshold_fp,
)

ACTION_WAIT = -1
ACTION_INFEASIBLE = -2


def _bucket(n: int) -> int:
    """Pad to power-of-two-ish buckets so jit retraces stay rare."""
    b = 8
    while b < n:
        b *= 2
    return b


def _require_cpu_jax() -> None:
    """The kernel shares a process with the raylet, and a chip belongs
    to the one worker that leases ``TPU``, so the kernel is CPU-only by
    construction: the hosting process must have pinned jax to the CPU
    (``JAX_PLATFORMS=cpu``). Without the pin, resolving any backend
    initialises every platform jax knows, the TPU included, inside the
    raylet — so refuse."""
    import jax

    if jax.config.jax_platforms != "cpu":
        raise RuntimeError(
            "scheduler_backend='tpu_batched' runs its kernel on CPU jax "
            "inside the raylet's process; start that process with "
            "JAX_PLATFORMS=cpu (the chip belongs to the worker that "
            f"leases TPU). jax_platforms is {jax.config.jax_platforms!r}")


@functools.lru_cache(maxsize=None)
def _compiled_kernel(t_bucket: int, n_bucket: int, r_bucket: int):
    """Gather (slot → arrival order) + feasibility/scoring scan, fused
    into one jitted program."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def kernel(demands_s, locality_s, dep_ready_s, perm, totals, avail0,
               is_local, valid_task, valid_node, spread_fp):
        # *_s are SLOT-ordered resident arrays; perm maps scan position
        # (arrival order) → slot. valid_task is per scan POSITION.
        demands = demands_s[perm]
        locality = locality_s[perm]
        dep_ready = dep_ready_s[perm]
        inv_totals = jnp.where(totals > 0, 1.0 / jnp.maximum(totals, 1e-9), 0.0)
        local_idx = jnp.argmax(is_local)

        def step(avail, inp):
            d, loc, tvalid, t_ready = inp
            feasible = jnp.all(totals + 1e-9 >= d[None, :], axis=1) & valid_node
            ready = jnp.all(avail + 1e-9 >= d[None, :], axis=1) & feasible
            used = (totals - avail) + d[None, :]
            # Fixed-point critical-resource utilization, ceil semantics.
            frac = used * inv_totals
            fp = jnp.ceil(frac * UTIL_SCALE).astype(jnp.int32)
            fp = jnp.clip(jnp.where(totals > 0, fp, 0), 0, UTIL_MAX)
            util_fp = jnp.max(fp, axis=1)                       # [N] i32
            anti_loc = (1 << 20) - jnp.minimum(
                loc.astype(jnp.int32) >> 10, LOC_MAX)
            node_idx = jnp.arange(n_bucket, dtype=jnp.int32)
            remote = jnp.where(is_local, 0, 1).astype(jnp.int32)
            # 58-bit key carried as (hi, lo) int32 pair (see scoring.py).
            hi = (util_fp << 10) | (anti_loc >> HI_LOC_SHIFT)
            lo = ((anti_loc & LO_LOC_MASK) << 16) | (remote << 15) | node_idx
            imax = jnp.int32(2**31 - 1)
            hi = jnp.where(ready, hi, imax)
            min_hi = jnp.min(hi)
            lo = jnp.where(ready & (hi == min_hi), lo, imax)
            best = jnp.argmin(lo)
            # Hybrid rule: local node wins while under the spread threshold.
            local_ready = ready[local_idx] & (util_fp[local_idx] <= spread_fp)
            chosen = jnp.where(local_ready, local_idx, best)
            any_ready = jnp.any(ready)
            any_feasible = jnp.any(feasible)
            # Frontier gate: a local grant waits for dep prefetch; a spill
            # to a node already holding the data proceeds (scoring.py).
            blocked = (chosen == local_idx) & ~t_ready
            action = jnp.where(
                ~tvalid, ACTION_WAIT,
                jnp.where(~any_feasible, ACTION_INFEASIBLE,
                          jnp.where(any_ready & ~blocked, chosen,
                                    ACTION_WAIT)))
            take = (action >= 0)
            delta = jnp.where(
                (jnp.arange(n_bucket) == action)[:, None] & take, d[None, :], 0.0)
            return avail - delta, action.astype(jnp.int32)

        _, actions = lax.scan(
            step, avail0, (demands, locality, valid_task, dep_ready))
        return actions

    return jax.jit(kernel)


@functools.lru_cache(maxsize=1)
def _row_scatter():
    """Jitted row scatter (jit caches per shape/dtype on its own)."""
    import jax

    return jax.jit(lambda arr, idx, rows: arr.at[idx].set(rows))


class _ResidentState:
    """Slot-addressed request arrays living on the kernel device."""

    def __init__(self, cap_t: int, cap_n: int, cap_r: int):
        import jax.numpy as jnp

        self.cap_t, self.cap_n, self.cap_r = cap_t, cap_n, cap_r
        self.demands = jnp.zeros((cap_t, cap_r), jnp.float32)
        self.locality = jnp.zeros((cap_t, cap_n), jnp.int32)
        self.dep_ready = jnp.ones((cap_t,), bool)
        self.slots: Dict[int, int] = {}       # req_id -> slot
        self.free: List[int] = list(range(cap_t - 1, -1, -1))
        # per-request fingerprint of the mutable inputs (deps_ready +
        # locality dict) so changed rows re-upload
        self.finger: Dict[int, tuple] = {}


class TpuBatchedBackend(SchedulingBackend):
    """Drop-in for HostBackend behind the scheduler seam. Construction
    raises unless the hosting process is pinned to CPU jax."""

    def __init__(self):
        _require_cpu_jax()
        self._resource_names: List[str] = []
        self._state: Optional[_ResidentState] = None
        self._node_order: List[bytes] = []
        self.num_row_uploads = 0   # introspection: delta-upload counter
        self.num_rebuilds = 0

    # ---------------------------------------------------------- resident

    def _intern_kinds(self, pending, nodes) -> List[str]:
        kinds = list(self._resource_names)
        known = set(kinds)
        for req in pending:
            for k in req.resources:
                if k not in known:
                    kinds.append(k)
                    known.add(k)
        for n in nodes:
            for k in n.total:
                if k not in known:
                    kinds.append(k)
                    known.add(k)
        self._resource_names = kinds
        return kinds

    @staticmethod
    def _fingerprint(req: PendingRequest) -> tuple:
        # exact: the host oracle reads locality dicts directly, so a
        # missed change would diverge the differential tests
        return (req.deps_ready, tuple(sorted(req.locality.items())))

    def _ensure_state(self, n_pending: int, nodes: List[NodeView],
                      kinds: List[str]) -> _ResidentState:
        """(Re)build the resident arrays when capacities or the node
        column order change; otherwise return the live state."""
        node_order = [n.node_id for n in nodes]
        st = self._state
        # Sized from n_pending alone: each tick reconciles slots to
        # exactly the pending set before allocating, so n_pending live
        # requests always fit an n_pending-bucket capacity.
        need_t = _bucket(n_pending)
        need_n = _bucket(len(nodes))
        need_r = _bucket(max(len(kinds), 1))
        if (st is None or need_t > st.cap_t or need_n != st.cap_n
                or need_r != st.cap_r or node_order != self._node_order):
            self._state = _ResidentState(
                max(need_t, st.cap_t if st else 0), need_n, need_r)
            self._node_order = node_order
            self.num_rebuilds += 1
            # existing requests re-upload on this tick (their
            # fingerprints are dropped)
        return self._state

    def schedule(self, pending: List[PendingRequest],
                 nodes: List[NodeView],
                 spread_threshold: float) -> List[Decision]:
        import numpy as np

        if not pending:
            return []
        # Stable resource-kind interning across ticks (reference:
        # scheduling_ids.h string->int interning).
        kinds = self._intern_kinds(pending, nodes)
        kidx = {k: i for i, k in enumerate(kinds)}
        nidx = {n.node_id: i for i, n in enumerate(nodes)}
        st = self._ensure_state(len(pending), nodes, kinds)
        T, N = len(pending), len(nodes)
        tb, nb, rb = st.cap_t, st.cap_n, st.cap_r

        # ---- delta detection: new / changed / departed requests ----
        current = set()
        dirty: List[PendingRequest] = []
        for req in pending:
            current.add(req.req_id)
            fp = self._fingerprint(req)
            if st.finger.get(req.req_id) != fp:
                st.finger[req.req_id] = fp
                dirty.append(req)
        for req_id in [r for r in st.slots if r not in current]:
            st.free.append(st.slots.pop(req_id))
            st.finger.pop(req_id, None)

        if dirty:
            idx = np.empty((len(dirty),), np.int32)
            drows = np.zeros((len(dirty), rb), np.float32)
            lrows = np.zeros((len(dirty), nb), np.int32)
            deps = np.ones((len(dirty),), bool)
            for i, req in enumerate(dirty):
                slot = st.slots.get(req.req_id)
                if slot is None:
                    slot = st.free.pop()
                    st.slots[req.req_id] = slot
                idx[i] = slot
                for k, v in req.resources.items():
                    if v > 0:
                        drows[i, kidx[k]] = v
                for node_id, nbytes in req.locality.items():
                    ni = nidx.get(node_id)
                    if ni is not None:
                        lrows[i, ni] = min(nbytes, 2**31 - 1)
                deps[i] = req.deps_ready
            scatter = _row_scatter()
            st.demands = scatter(st.demands, idx, drows)
            st.locality = scatter(st.locality, idx, lrows)
            st.dep_ready = scatter(st.dep_ready, idx, deps)
            self.num_row_uploads += len(dirty)

        # ---- per-tick small inputs (arrival order + node table) ----
        perm = np.zeros((tb,), np.int32)
        valid_task = np.zeros((tb,), bool)
        for pos, req in enumerate(pending):
            perm[pos] = st.slots[req.req_id]
            valid_task[pos] = True
        totals = np.zeros((nb, rb), np.float32)
        avail = np.zeros((nb, rb), np.float32)
        is_local = np.zeros((nb,), bool)
        valid_node = np.zeros((nb,), bool)
        for ni, n in enumerate(nodes):
            valid_node[ni] = True
            is_local[ni] = n.is_local
            for k, v in n.total.items():
                totals[ni, kidx[k]] = v
            for k, v in n.available.items():
                avail[ni, kidx[k]] = v

        kernel = _compiled_kernel(tb, nb, rb)
        actions = np.asarray(kernel(
            st.demands, st.locality, st.dep_ready, perm, totals, avail,
            is_local, valid_task, valid_node,
            np.int32(min(spread_threshold_fp(spread_threshold), 2**31 - 1))))

        decisions: List[Decision] = []
        local = next((n for n in nodes if n.is_local), None)
        for ti, req in enumerate(pending):
            a = int(actions[ti])
            if a == ACTION_INFEASIBLE:
                decisions.append(Decision(req.req_id, INFEASIBLE))
            elif a == ACTION_WAIT or a >= N:
                decisions.append(Decision(req.req_id, WAIT))
            elif local is not None and nodes[a].node_id == local.node_id:
                decisions.append(Decision(req.req_id, GRANT))
            else:
                decisions.append(Decision(req.req_id, SPILL,
                                          spill_address=nodes[a].address))
        return decisions
