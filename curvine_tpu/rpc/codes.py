"""RPC operation codes.

Parity: curvine-common/src/fs/rpc_code.rs:20 (same catalogue, same grouping;
TPU-specific codes appended at 100+)."""

from __future__ import annotations

import enum


class RpcCode(enum.IntEnum):
    UNDEFINED = 0
    HEARTBEAT = 1

    # filesystem API (master)
    MKDIR = 2
    DELETE = 3
    CREATE_FILE = 4
    OPEN_FILE = 5
    APPEND_FILE = 6
    FILE_STATUS = 7
    LIST_STATUS = 8
    EXISTS = 9
    RENAME = 10
    ADD_BLOCK = 11
    COMPLETE_FILE = 12
    GET_BLOCK_LOCATIONS = 13
    GET_MASTER_INFO = 14
    SET_ATTR = 15
    SYMLINK = 16
    LINK = 17
    RESIZE_FILE = 18
    ASSIGN_WORKER = 19
    GET_LOCK = 20
    SET_LOCK = 21
    LIST_LOCK = 22
    CREATE_FILES_BATCH = 23
    ADD_BLOCKS_BATCH = 24
    COMPLETE_FILES_BATCH = 25
    FREE = 26
    LIST_OPTIONS = 27
    CONTENT_SUMMARY = 28
    META_BATCH = 29           # heterogeneous mkdir/create/delete list

    # manager interface
    MOUNT = 30
    UNMOUNT = 31
    UPDATE_MOUNT = 32
    GET_MOUNT_TABLE = 33
    GET_MOUNT_INFO = 34

    SUBMIT_JOB = 35
    GET_JOB_STATUS = 36
    CANCEL_JOB = 37
    REPORT_TASK = 38
    SUBMIT_TASK = 39
    WORKER_HEARTBEAT = 40
    WORKER_BLOCK_REPORT = 41

    SUBMIT_BLOCK_REPLICATION_JOB = 42
    REPORT_BLOCK_REPLICATION_RESULT = 43
    REQUEST_REPLACEMENT_WORKER = 44
    REPORT_UNDER_REPLICATED_BLOCKS = 45
    DECOMMISSION_WORKER = 46
    # worker -> master: all k+m cells of an erasure-coded stripe are
    # written + committed; master journals the stripe map and retires
    # the replicated copies copy-first-delete-last
    EC_COMMIT_STRIPE = 47

    METRICS_REPORT = 60
    # cluster-health rollup (master monitor + dir watchdog snapshot)
    # Parity: curvine-server/src/master/master_monitor.rs +
    # fs_dir_watchdog.rs — state, capacity, liveness, stuck-op sentinel
    CLUSTER_HEALTH = 61
    # span collection (curvine_tpu/obs): fetch one trace's spans from a
    # process's ring buffer; the master additionally fans the request
    # out to workers when asked to collect (web /api/trace, `cv trace`)
    GET_SPANS = 62
    # metadata lease invalidation push (master → client, req_id=0, no
    # response expected): `{"paths": [...], "epoch": e}` over the
    # already-open client connection on rename/delete/resize/TTL-expiry.
    # The future FUSE inval_entry/inval_inode notify plane consumes the
    # SAME message — docs/read-plane.md.
    META_INVALIDATE = 63

    # sharded namespace plane (master/sharding.py). SHARD_TX drives the
    # cross-shard two-phase protocol on a participant shard
    # (prepare/commit/abort/forget); SHARD_TX_LIST feeds the crash-
    # recovery sweep; SHARD_STATS/SHARD_TABLE feed /metrics, the web UI
    # and `cv report`.
    SHARD_TX = 70
    SHARD_TX_LIST = 71
    SHARD_STATS = 72
    SHARD_TABLE = 73

    # multi-tenant admission plane (common/qos.py): per-tenant
    # qps/throttled/inflight snapshot feeding /api/tenants, /metrics
    # and the `cv report` tenants table
    TENANT_STATS = 74

    # epoch-aware prefetch (docs/caching.md): the SDK advises the
    # master of the deterministic shard order for the epoch it is about
    # to read; the master keeps a rolling window of upcoming shards
    # warming ahead of the read cursor (master/jobs.py kind="prefetch")
    PREFETCH_WINDOW = 75

    # the read's list-taking form (CurvineClient.prime): `{"paths": [...]}`
    # → `{"responses": [...]}`, one entry a path, positional — what
    # GET_BLOCK_LOCATIONS answers for it (`file_blocks`) or the error it
    # would have raised (`error`, `error_code`), so one path's
    # FileNotFound or denial fails that path alone. The worker's half is
    # GET_BLOCK_INFO with `block_ids` for `block_id`.
    GET_BLOCK_LOCATIONS_BATCH = 76

    # block interface (worker)
    WRITE_BLOCK = 80
    READ_BLOCK = 81
    WRITE_BLOCKS_BATCH = 82
    WRITE_COMMITS_BATCH = 83
    DELETE_BLOCK = 84
    GET_BLOCK_INFO = 85
    # short-circuit local writes: co-located client writes the block file
    # directly (one hash pass, no socket), then registers it
    SC_WRITE_OPEN = 86
    SC_WRITE_COMMIT = 87
    SC_WRITE_ABORT = 88
    # short-circuit read accounting: clients report per-block read
    # counters so worker heat/atime reflect fd-path traffic
    SC_READ_REPORT = 89

    # raft-lite (master HA journal replication)
    RAFT_VOTE = 90
    RAFT_APPEND = 91
    RAFT_SNAPSHOT = 92
    # pre-vote (raft §9.6 / role_monitor.rs parity): a would-be candidate
    # probes for electability WITHOUT bumping its term, so a partitioned
    # node rejoining cannot depose a healthy leader with inflated terms
    RAFT_PREVOTE = 93
    # membership lifecycle (docs/raft.md). SNAPSHOT_CHUNK streams the
    # state in bounded, resumable pieces with a final CRC (RAFT_SNAPSHOT
    # remains the legacy monolithic path for states under one chunk);
    # TIMEOUT_NOW is the leader-transfer trigger (§3.10: target skips
    # pre-vote and elects immediately); STATUS answers on any node;
    # MEMBER_CHANGE/TRANSFER are the leader-side admin entry points.
    RAFT_SNAPSHOT_CHUNK = 94
    RAFT_TIMEOUT_NOW = 95
    RAFT_STATUS = 96
    RAFT_MEMBER_CHANGE = 97
    RAFT_TRANSFER = 98

    # TPU extensions
    HBM_PIN = 100        # pin a cached block into the HBM tier
    HBM_UNPIN = 101
    BROADCAST_MODEL = 102  # checkpoint broadcast over the pod
    ICI_TRANSFER = 103   # device-path block pull from a peer's HBM tier
