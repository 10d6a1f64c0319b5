#!/usr/bin/env bash
# Chaos soak gate: every fixed-seed fault schedule (tests/chaos.py
# driven by tests/test_chaos.py) over the in-process data plane AND the
# real subprocess cluster — stripe sever, corrupt chunk, short read,
# delay storm, raylet crash, heartbeat partition, GCS restart, mixed,
# worker kill, OOM storm (seeded simulated-RSS ramps through the node
# memory watchdog: kills, OOM retries, lease backpressure — asserting
# the raylet/GCS survive every event), the mixed_version rolling-
# upgrade smoke (an old-schema raylet speaking v1 stubs compiled from
# tests/fixtures/rpc_schemas_v1.json against the current GCS through a
# seeded gcs_restart — version negotiation recorded in node info), and
# the gang_kill soak (SIGKILL an SPMD gang member mid-step: typed
# failure, epoch-fenced reform, pool reclaim, zero leaked objects),
# and the ring_kill soak (abruptly kill a ring-collective peer
# mid-all_reduce: exact fallback value or typed error, RingAbort
# drains every survivor, gang fence intact, zero leaked segments/fds),
# and the replica_kill soak (SIGKILL a serve replica mid-request:
# idempotent requests retry onto a peer, non-idempotent fail typed,
# the controller's health loop restores the replica count, and the
# in-flight zero-copy ingress segments leak nothing).
# Runs the slow-marked schedules too (tier-1 carries only
# the 2-schedule smoke); any invariant violation (pull hang, admission
# budget leak, segment-lease leak, a leak-detector-flagged object
# [summary_objects()["leaked"] != 0], fd leak, unresurrected
# partitioned node, dishonest task-event history) fails CI.
#
# Determinism contract: a schedule is fully determined by its (kind,
# seed) pair — a failure here replays locally with exactly
#   python -m pytest "tests/test_chaos.py::test_chaos_soak[<kind>]" -m ''
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

# -m '' = no marker filter: the slow soak schedules run here (the
# tier-1 command excludes them with its own -m 'not slow').
python -m pytest tests/test_chaos.py tests/test_faultpoints.py \
    -q -p no:cacheprovider -m '' "$@"

# The full run above already soaks worker_kill with the zygote ENABLED
# (worker_zygote_enabled defaults on): die-at-Nth-task schedules,
# killpg teardown, the no-zombie and fd brackets all hold when every
# worker is a fork of the template. This second run pins the
# cold-Popen path the same way (it is the fallback and the TPU-worker
# default), including the per-spawn log-fd regression bracket.
env RAY_TPU_WORKER_ZYGOTE_ENABLED=0 python -m pytest \
    tests/test_chaos.py::test_chaos_soak_worker_kill \
    -q -p no:cacheprovider -m ''

# Streaming leases are ON by default, so the full run above soaked
# every schedule (worker_kill, raylet kills, oom_storm, and the new
# credit_revoke revocation paths) over the credit plane. This final
# run pins the schedules that exercise the lease protocol with credits
# OFF — the legacy request/grant path must keep passing the identical
# recovery bar (the fallback is a first-class mode, not dead code).
exec env RAY_TPU_LEASE_CREDITS_ENABLED=0 python -m pytest \
    tests/test_chaos.py::test_chaos_soak_worker_kill \
    tests/test_chaos.py::test_chaos_soak_oom_storm \
    tests/test_chaos.py::test_chaos_soak_credit_raylet_kill \
    tests/test_chaos.py::test_chaos_soak_gang_kill \
    tests/test_chaos.py::test_chaos_soak_ring_kill \
    "tests/test_chaos.py::test_chaos_soak[raylet_kill]" \
    -q -p no:cacheprovider -m ''
