#!/bin/bash
# Chaos-storm smoke gate (<2min): run the deterministic-seed storms —
# including the disk-fault seeds (bitflip/EIO/ENOSPC injection, with the
# no-corrupt-bytes-observed and quarantine-evacuation invariants), the
# abusive-tenant QoS storm (victim p99 contained, abuser mostly
# THROTTLED, shed-before-queue held), and the raft membership-churn
# seeds (add-learner/remove/transfer/leader-kill under writes: ≤1
# leader per term, zero acked-write loss, removed node never leads) and
# the write-pipeline seeds (workers killed / WRITE_BLOCK faults injected
# under concurrent multi-block writers: zero acked-write loss, bounded
# per-file budgets, flagged replicas healed, plus the replicas=1 replay
# variant) and the cache_scan seeds (a 2x-capacity one-touch backfill
# scan against a hot read loop: S3-FIFO admission must hold the
# post-quiesce hot hit rate above the floor, docs/caching.md) — plus
# the deadline/breaker acceptance tests from
# tests/test_storm.py and fail on any invariant violation.
#
# Usage: scripts/storm_smoke.sh [project_root]
#   STORM_RAFT_REPEAT=N   additionally run the raft election/storm tests
#                         N times each (--repeat; flaky-election hunter)
# Exit: 0 = all invariants held, 1 = violation/failure, 2 = harness error.

set -u

ROOT="${1:-$(cd "$(dirname "$0")/.." && pwd)}"
cd "$ROOT" || exit 2

run_pytest() {
    timeout -k 10 115 env JAX_PLATFORMS=cpu python -m pytest -q \
        -p no:cacheprovider -p no:xdist -p no:randomly "$@"
}

echo "storm_smoke: deterministic-seed storms + deadline/breaker gates"
run_pytest tests/test_storm.py -m 'not slow'
rc=$?
if [ $rc -eq 124 ] || [ $rc -eq 137 ]; then
    echo "storm_smoke: TIMEOUT — storm gate exceeded 115s" >&2
    exit 2
elif [ $rc -ne 0 ]; then
    echo "storm_smoke: FAIL — storm invariants violated (rc=$rc)" >&2
    exit 1
fi

if [ "${STORM_RAFT_REPEAT:-0}" -gt 1 ]; then
    echo "storm_smoke: raft storm x${STORM_RAFT_REPEAT} (flaky-election hunt)"
    timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest -q \
        -p no:cacheprovider -p no:xdist -p no:randomly \
        --repeat "$STORM_RAFT_REPEAT" \
        tests/test_raft.py -k "storm or prevote or failover or membership"
    rc=$?
    if [ $rc -ne 0 ]; then
        echo "storm_smoke: FAIL — raft storm repeat found a flake (rc=$rc)" >&2
        exit 1
    fi
fi

echo "storm_smoke: PASS"
