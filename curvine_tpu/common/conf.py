"""Cluster configuration.

Parity: curvine-common/src/conf/ (master/worker/client/fuse/job sections,
loaded from a TOML file with programmatic overrides)."""

from __future__ import annotations

import dataclasses
import logging
import os

import tomllib
from dataclasses import dataclass, field

MB = 1024 * 1024
GB = 1024 * MB


@dataclass
class MasterConf:
    hostname: str = "127.0.0.1"
    rpc_port: int = 8995
    web_port: int = 9000
    # metadata store dir; empty → "<journal_dir>-meta" so every master
    # node gets its own store without extra conf
    meta_dir: str = ""
    # metadata store: "kv" (log-structured KV; namespace can exceed RAM,
    # O(journal-tail) restarts) or "mem" (dicts + snapshot replay)
    meta_store: str = "kv"
    # kv engine: "auto" (native C++ LSM when built — csrc/kv_engine.cc,
    # the RocksDB role), "native" (require it) or "python"; identical
    # on-disk format, switchable per restart
    meta_engine: str = "auto"
    meta_cache_inodes: int = 65_536
    # journal
    journal_dir: str = "data/journal"
    journal_fsync: bool = False   # fsync every WAL append (crash durability)
    # group commit: coalesce concurrent mutations into one journal flush
    # + one KV batch. Idle ops commit immediately; under load the window
    # lingers up to journal_group_commit_ms (0 = no linger, still batches
    # whatever is runnable) capped at journal_group_max entries per group.
    journal_group_commit_ms: float = 1.0
    journal_group_max: int = 1024
    snapshot_interval_entries: int = 100_000
    # heartbeats
    worker_heartbeat_ms: int = 3_000
    worker_lost_timeout_ms: int = 30_000
    heartbeat_check_ms: int = 1_000
    # block allocation
    block_placement_policy: str = "local"   # local|random|robin|weighted|load|ici
    # ICI torus shape for the hop-count distance function (e.g. [4, 2]
    # or [2, 2, 2]); empty → distances fall back to host labels
    ici_mesh_shape: list[int] = field(default_factory=list)
    # retry cache
    retry_cache_size: int = 100_000
    retry_cache_ttl_ms: int = 600_000
    # ttl scanner
    ttl_check_ms: int = 1_000
    # permissions (parity: acl_feature.rs)
    acl_enabled: bool = True
    superuser: str = "root"
    supergroup: str = "supergroup"
    # native metadata read plane (csrc/meta_mirror.cc): FILE_STATUS and
    # EXISTS served by C++ threads on a separate fast port; 0 = ephemeral
    fast_meta: bool = True
    fast_port: int = 0
    # client metadata read leases (master/read_leases.py): stat/list
    # answers carry a lease {ttl_ms, epoch}; the master remembers which
    # client conns hold leases per PARENT DIRECTORY (coarse, capped at
    # meta_lease_dirs dirs LRU) and pushes META_INVALIDATE over the open
    # conn on rename/delete/resize/TTL-expiry. Leases are soft state: a
    # restart mints a new epoch, which clients treat as revoke-all.
    meta_lease_ms: int = 3_000
    meta_lease_dirs: int = 4_096
    # audit/metrics
    audit_log: bool = False
    # dir watchdog (parity: fs_dir_watchdog.rs): namespace ops / path
    # locks stuck longer than this are logged + metric-flagged
    watchdog_stall_ms: int = 10_000
    # off-box disaster recovery (parity: journal/ufs_loader.rs): upload
    # the namespace snapshot to this UFS URI periodically; an EMPTY
    # master dir restores from it on start. "" disables.
    ufs_backup_uri: str = ""
    ufs_backup_interval_s: int = 300
    # sharded namespace (master/sharding.py): >1 partitions the inode
    # tree across meta_shards single-writer shard actors, the RPC
    # endpoint becoming a thin router. 1 = today's in-process path,
    # byte-for-byte. Sharding is mutually exclusive with raft HA for
    # now — see docs/metadata-scale.md for the matrix.
    meta_shards: int = 1
    # "process": each shard is a multiprocessing (spawn) child with its
    # own event loop — the multi-core deployment shape. "inproc": shard
    # servers share the router's loop (tests / single-core boxes; same
    # wire protocol, no core scaling).
    shard_backend: str = "process"
    # router-side LRU of directories already broadcast-created on every
    # shard (the every-dir-everywhere invariant)
    shard_dir_cache: int = 65_536
    # raft (HA); empty peers → single-node journal mode
    raft_peers: list[str] = field(default_factory=list)
    raft_node_id: int = 1
    # membership lifecycle (master/ha.py, docs/raft.md): a learner is
    # auto-promoted to voter once its replication lag (leader last_seq -
    # learner match) drops below raft_promote_lag entries
    raft_promote_lag: int = 64
    # snapshot catch-up streams in chunks of this size (the monolithic
    # blob could not fit under MAX_FRAME at 10M-file namespace scale)
    raft_snapshot_chunk_mb: int = 4
    # `cv raft transfer`: max time the leader pauses writes while
    # draining the target before giving up and resuming
    raft_transfer_timeout_ms: int = 5_000
    # start this node as a non-voting learner (it joins quorum only
    # after a PROMOTE config entry commits)
    raft_learner: bool = False
    # time budget for one master-dispatched replication pull (submit RPC
    # + the destination's pull from the source), propagated in the RPC
    # header so the worker's peer stream is bounded by the same budget
    replication_pull_budget_ms: int = 20_000


@dataclass
class TierConf:
    storage_type: str = "mem"   # hbm|mem|ssd|hdd
    dir: str = "data/mem"       # dir (file layout) | backing file (bdev)
    capacity: int = 1 * GB
    # "file": one file per block in hashed subdirs; "bdev": blocks as
    # extents inside ONE preallocated backing file / raw device
    layout: str = "file"
    # direct-IO submission depth for THIS tier (0 → the worker-wide
    # direct_io_queue_depth); advertised to clients via GET_BLOCK_INFO
    # so parallel readers size their slice count to it
    queue_depth: int = 0


@dataclass
class WorkerConf:
    hostname: str = "127.0.0.1"
    rpc_port: int = 8996
    web_port: int = 9001
    tiers: list[TierConf] = field(default_factory=lambda: [TierConf()])
    heartbeat_ms: int = 3_000
    block_report_interval_ms: int = 60_000
    io_chunk_size: int = 4 * MB
    # eviction watermarks (fraction of tier capacity)
    eviction_high_water: float = 0.95
    eviction_low_water: float = 0.80
    # hot-data promotion: blocks read >= min_reads since the last scan
    # move up to the fastest tier (0 disables the scan)
    promote_interval_ms: int = 30_000
    promote_min_reads: int = 3
    # TPU/ICI placement
    ici_coords: list[int] = field(default_factory=list)
    # hbm tier (bytes reserved on device for cache; 0 disables)
    hbm_capacity: int = 0
    # ICI data plane (docs/ici-plane.md): advertise HBM-resident blocks
    # to peers and serve replication pulls device-to-device; any failure
    # falls back to the TCP rail (counter, never an error)
    ici_transfer: bool = True
    # peer-addressable export table entries (LRU, advisory metadata)
    hbm_export_cap: int = 128
    # max exported blocks advertised per heartbeat
    hbm_advertise_max: int = 64
    task_parallelism: int = 4
    # direct-IO data plane for SSD/HDD tiers (worker/io_engine.py —
    # the SPDK-role page-cache bypass): cold block reads and tier-move
    # copies go through an O_DIRECT submission/completion ring.
    # Filesystems rejecting O_DIRECT fall back per-request.
    direct_io: bool = True
    direct_io_engine: str = "auto"     # auto|uring|threads|off
    direct_io_queue_depth: int = 32
    direct_io_alignment: int = 4096
    direct_io_threads: int = 2
    direct_io_segment: int = 1 * MB    # split size for batched reads
    # background checksum scrub: every scrub_interval_s verify the
    # scrub_batch least-recently-verified committed blocks (full-store
    # progress within ceil(N/batch) cycles)
    scrub_interval_s: float = 60.0
    scrub_batch: int = 16
    # per-tier-dir DiskHealth state machine (worker/storage.py):
    # >= disk_error_threshold IO errors within disk_error_decay_s mark a
    # dir SUSPECT; a write/read/unlink probe every disk_probe_interval_s
    # then either rehabilitates it (disk_probe_successes consecutive
    # passes) or quarantines it (disk_probe_failures consecutive fails).
    # Quarantined dirs stop allocating, advertise zero capacity, and
    # their committed blocks are evacuated by the master — at most
    # disk_evac_batch block ids advertised per heartbeat so a disk-fault
    # storm can't flood the replication queue.
    disk_error_threshold: int = 3
    disk_error_decay_s: float = 60.0
    disk_probe_interval_s: float = 5.0
    disk_probe_failures: int = 2
    disk_probe_successes: int = 3
    disk_evac_batch: int = 256
    # shared-memory short-circuit reads (docs/data-plane.md): MEM-tier
    # blocks are exported as sealed memfds and handed to co-located
    # clients over an SCM_RIGHTS unix side channel; read_range becomes a
    # zero-RPC, zero-copy mmap slice. Needs os.memfd_create (Linux);
    # auto-disabled elsewhere and clients fall back to the socket path.
    shm_reads: bool = True
    # (the export table has no option: it is bounded by the bytes it
    # holds — 8 GiB and never more than the MEM tiers' capacity — and
    # by an eighth of `ulimit -n` in entries, worker/shm.py. The key
    # `shm_export_cap`, its old bound in entries, still loads and is
    # ignored, as any unknown key is)
    # warm-cache shm exports for the tiers BELOW mem (docs/data-plane.md):
    # a read-hot SSD/HDD block's bytes are copied ONCE into a sealed
    # memfd and served over the same SCM_RIGHTS channel as a MEM export —
    # zero RPCs and zero syscalls per read from then on. Byte-bounded;
    # 0 disables the warm cache (MEM-tier exports are unaffected).
    shm_warm_cap_mb: int = 64
    # block heat (reads, via the SC_READ_REPORT rail) required before a
    # below-MEM block qualifies for a warm export — one-touch scans never
    # earn a copy (and the S3-FIFO warm admission evicts them first if
    # they somehow do)
    shm_warm_min_reads: int = 3
    # cache admission on the MEM + HBM tiers (docs/caching.md):
    # "s3fifo" = ghost-cache admission (small probationary FIFO + main
    # FIFO + ghost queue of recently-evicted ids) so a one-touch backfill
    # scan cannot flush the multi-touch working set; "lru" = the
    # byte-compatible historical policy (victims by atime)
    cache_admission: str = "s3fifo"
    cache_ghost_entries: int = 8192
    cache_small_ratio: float = 0.1


@dataclass
class ClientConf:
    master_addrs: list[str] = field(default_factory=lambda: ["127.0.0.1:8995"])
    # identity sent with every request (empty → the OS user / its group)
    user: str = ""
    groups: list[str] = field(default_factory=list)
    # tenant id for admission control (common/qos.py): stamped into the
    # RPC header beside deadline_ms/trace_ctx on every outbound request.
    # Empty → "default". The S3 gateway derives it from the access key
    # instead; this field is the explicit path for native clients.
    tenant: str = ""
    # epoch-aware prefetch (docs/caching.md): shards ahead of the read
    # cursor kept warming via PREFETCH_WINDOW advise calls (0 disables)
    prefetch_window: int = 8
    block_size: int = 64 * MB
    replicas: int = 1
    write_chunk_size: int = 4 * MB
    read_chunk_size: int = 4 * MB
    read_ahead_chunks: int = 4
    # adaptive read path (parity: curvine-client read_detector.rs):
    # positional reads prefetch ahead while the pattern is sequential,
    # stop when it turns random
    enable_smart_prefetch: bool = True
    sequential_read_threshold: int = 3
    # sharded parallel reads of one large file (fs_reader_parallel.rs):
    # files >= large_file_size split into read_parallel concurrent slices
    read_parallel: int = 4
    large_file_size: int = 64 * MB
    short_circuit: bool = True
    storage_type: str = "mem"
    write_type: str = "cache"      # cache|fs
    # write-pipeline fault tolerance (docs/resilience.md): keep the open
    # block's bytes in a bounded replay buffer (capped at one block) so
    # a mid-stream replica loss can abandon the block, re-place it on a
    # fresh worker, and replay — the caller's write never sees the
    # fault. Disable for memory-tight callers; the stream then fails on
    # losing its last replica (survivor fan-out continuation still works).
    write_replay_buffer: bool = True
    # fan-out floor: keep streaming on surviving replicas while at least
    # this many remain; below it the whole block is re-placed + replayed.
    # Lost replicas are reported so the healing plane restores the count.
    write_min_replicas: int = 1
    rpc_timeout_ms: int = 30_000
    conn_retry_max: int = 3
    conn_retry_base_ms: int = 100
    conn_pool_size: int = 4
    # end-to-end deadline budget per read operation (rpc/deadline.py):
    # propagated in RPC headers and decremented across hops; per-hop
    # timeouts become min(rpc_timeout, remaining/replicas_left) so a
    # wedged worker costs a fraction of the budget, not a full RPC
    # timeout, before replica failover. 0 disables (legacy behavior).
    op_deadline_ms: int = 0
    # per-worker circuit breakers (client/health.py): after
    # breaker_fail_threshold consecutive failures/timeouts against one
    # worker address the breaker opens for breaker_open_ms (replica
    # choice deprioritizes it; placement retries exclude it), then
    # half-opens for a single probe. Counts decay after breaker_decay_ms
    # without failures.
    breaker_enabled: bool = True
    breaker_fail_threshold: int = 3
    breaker_open_ms: int = 5_000
    breaker_decay_ms: int = 30_000
    # end-to-end read integrity: verify full-block reads against the
    # commit-time crc carried by GET_BLOCK_INFO / READ_BLOCK EOF frames
    # before returning bytes; mismatches count read.checksum_mismatch,
    # report the corrupt replica, and fail over to the next replica
    read_verify: bool = True
    # route stat/exists to the master's native fast port when advertised
    fast_meta: bool = True
    # client metadata lease cache (client/meta_cache.py): bounded LRU of
    # positive AND negative stat/list entries, valid for the master-
    # granted lease TTL or until a META_INVALIDATE push / local write
    # drops them. Read-your-writes holds on the writing client; cross-
    # client staleness is bounded by master.meta_lease_ms.
    meta_cache: bool = True
    meta_cache_entries: int = 4_096


@dataclass
class FuseConf:
    mount_point: str = "/tmp/curvine-fuse"
    fs_path: str = "/"
    attr_ttl_ms: int = 1_000
    entry_ttl_ms: int = 1_000
    max_write: int = 1024 * 1024
    workers: int = 2
    # in-place/random writes: files up to this size are staged in RAM and
    # rewritten to the cache at release (0 disables → EOPNOTSUPP)
    inplace_max_mb: int = 256
    # bdi readahead window (KiB): sequential reads arrive as max_write-
    # sized requests instead of the kernel's 128 KiB default (8x fewer
    # ops). Best-effort — needs writable /sys. 0 keeps kernel default.
    read_ahead_kb: int = 1024
    # per-mount metrics HTTP endpoint (/metrics prometheus + /ops JSON
    # with per-op latency quantiles); 0 disables.
    # Parity: curvine-fuse/src/web_server.rs + fuse_metrics.rs
    metrics_port: int = 0
    # loopback by default: op names leak path activity
    metrics_host: str = "127.0.0.1"


@dataclass
class ObsConf:
    """Observability plane (curvine_tpu/obs): tracing + profiler knobs."""
    # master switch: False skips span creation entirely (no-op spans)
    enabled: bool = True
    # head-based sampling rate for NEW traces; error and slow spans are
    # always recorded regardless
    trace_sample_rate: float = 0.01
    # ops slower than this emit a structured slow-op log line and keep
    # their span even when unsampled
    slow_op_ms: int = 1_000
    # per-process span ring-buffer capacity
    span_store_size: int = 8192
    # budget for the master's GET_SPANS fan-out to workers when
    # assembling /api/trace/<id> / `cv trace`
    trace_collect_timeout_ms: int = 2_000


@dataclass
class RpcConf:
    """Wire transport knobs (curvine_tpu/rpc/transport.py), shared by
    every peer in the process: clients, the master and worker servers."""
    # coalesced writer: all frames queued within one event-loop tick
    # leave in a single vectored send, bounded per batch by bytes/frames
    send_coalesce_bytes: int = 256 * 1024
    send_coalesce_frames: int = 128
    # frames whose data payload is at most this long are flattened into
    # the batch buffer; larger payloads ride the iovec uncopied
    send_inline_max: int = 8 * 1024
    # bulk-recv buffer: one sock_recv_into typically lands many small
    # frames, decoded back-to-back with no further syscalls
    recv_buffer_bytes: int = 256 * 1024
    # registered receive buffers (transport.RegisteredBuffers): remote
    # block reads land in page-aligned mmap-backed destinations acquired
    # from a bounded reuse pool — the client-side mirror of the worker's
    # io_uring registered buffers (numpy/HBM-view friendly; readinto
    # scatters the payload straight into them). 0 disables pooling;
    # aligned allocation still applies above recv_aligned_min.
    recv_registered_bytes: int = 32 * MB
    # reads at least this large get an aligned mmap-backed destination
    # instead of a heap numpy buffer
    recv_aligned_min: int = 256 * 1024


@dataclass
class QosConf:
    """Multi-tenant admission control (common/qos.py): token-bucket
    quotas, inflight caps, overload shedding. All rates default to 0 =
    unlimited, so the admission plane is wired in everywhere but admits
    everything until quotas are set — byte-compatible with a pre-QoS
    cluster."""
    enabled: bool = True
    # process-wide request rate across all tenants (0 = unlimited)
    global_qps: float = 0.0
    global_burst: float = 0.0
    # per-tenant defaults; burst 0 → one second's worth of tokens
    tenant_default_qps: float = 0.0
    tenant_default_burst: float = 0.0
    # DAGOR-style priority: under overload, tenants with priority below
    # the current shed level are rejected first (higher = keep longer)
    tenant_default_priority: int = 5
    # concurrent admitted requests per tenant (0 = unlimited)
    tenant_inflight_cap: int = 0
    # op-class sub-buckets as a fraction of the tenant rate: each class
    # (meta/read/write) may use share × qps; the tenant bucket still
    # caps the sum, so 1.0 shares mean "any mix up to the tenant rate"
    meta_share: float = 1.0
    read_share: float = 1.0
    write_share: float = 1.0
    # per-tenant overrides, "name:qps[:priority[:inflight_cap]]"
    tenants: list[str] = field(default_factory=list)
    # overload shedding: raise the shed level while the admitted-
    # inflight depth exceeds the high-water mark or >= slow_frac of a
    # window's completions ran slower than obs.slow_op_ms
    shed_enabled: bool = True
    shed_inflight_hi: int = 512
    shed_slow_frac: float = 0.5
    shed_adjust_interval_s: float = 0.25
    shed_retry_after_ms: int = 250
    # dead-on-arrival fast-fail: drop requests whose remaining deadline
    # budget < doa_margin × the op class's EWMA service time
    doa_enabled: bool = True
    doa_margin: float = 1.0


@dataclass
class GatewayConf:
    # S3 gateway SigV4 verification: static credential pair. Empty access
    # key = anonymous mode (explicit opt-in for cluster-internal use);
    # set both to require signed requests (403 otherwise).
    s3_access_key: str = ""
    s3_secret_key: str = ""
    # background sweep of abandoned multipart uploads (an idle gateway
    # must still reclaim; the inline sweep only fires on initiates).
    # 0 disables the background task.
    stale_gc_interval_s: float = 3600.0

    def s3_credentials(self) -> dict | None:
        if self.s3_access_key:
            return {self.s3_access_key: self.s3_secret_key}
        return None


@dataclass
class ECConf:
    """Erasure-coded capacity tier (docs/erasure-coding.md).

    EC is a per-file/directory storage class (`cv ec set-policy`); this
    section sets the cluster defaults the convert job and the stripe
    audit use."""

    # master-side enable switch for the background convert job; the
    # codec, degraded reads, and reconstruction work regardless (stripes
    # that already exist must stay readable when conversion is off)
    enabled: bool = True
    # default profile for files marked `ec` without an explicit one and
    # for `cv ec convert` without --profile
    profile: str = "rs-6-3"
    # a block is "cold" (eligible for conversion) when its file's mtime
    # is at least this old; 0 = every complete file qualifies
    convert_cold_s: int = 0


@dataclass
class ClusterConf:
    cluster_name: str = "curvine-tpu"
    master: MasterConf = field(default_factory=MasterConf)
    worker: WorkerConf = field(default_factory=WorkerConf)
    client: ClientConf = field(default_factory=ClientConf)
    fuse: FuseConf = field(default_factory=FuseConf)
    gateway: GatewayConf = field(default_factory=GatewayConf)
    obs: ObsConf = field(default_factory=ObsConf)
    rpc: RpcConf = field(default_factory=RpcConf)
    qos: QosConf = field(default_factory=QosConf)
    ec: ECConf = field(default_factory=ECConf)
    data_dir: str = "data"

    @staticmethod
    def load(path: str | None = None,
             env: dict | None = None) -> "ClusterConf":
        """Load from TOML; CURVINE_CONF env var is the fallback location.
        ``CURVINE_<SECTION>_<FIELD>`` env vars override file values
        (container/k8s deployments configure through these):
        ``CURVINE_CLIENT_MASTER_ADDRS=m1:8995,m2:8995``,
        ``CURVINE_WORKER_RPC_PORT=9996``, ``CURVINE_DATA_DIR=/data``.
        Values are coerced to the field's type (int/float/bool/list)."""
        env = os.environ if env is None else env
        path = path or env.get("CURVINE_CONF", "")
        conf = ClusterConf()
        if path and os.path.exists(path):
            with open(path, "rb") as f:
                data = tomllib.load(f)
            _apply(conf, data)
        _apply_env(conf, env)
        return conf

    def master_addr(self) -> str:
        return f"{self.master.hostname}:{self.master.rpc_port}"


def _apply(obj, data: dict) -> None:
    for k, v in data.items():
        if not hasattr(obj, k):
            continue
        cur = getattr(obj, k)
        if dataclasses.is_dataclass(cur) and isinstance(v, dict):
            _apply(cur, v)
        elif k == "tiers" and isinstance(v, list):
            obj.tiers = [TierConf(**t) for t in v]
        else:
            setattr(obj, k, v)


def _coerce(cur, raw: str, annotation: str = ""):
    if isinstance(cur, bool):
        return raw.strip().lower() in ("1", "true", "yes", "on")
    if isinstance(cur, int):
        return int(raw)
    if isinstance(cur, float):
        return float(raw)
    if isinstance(cur, list):
        items = [s.strip() for s in raw.split(",") if s.strip()]
        # element type from the field annotation (defaults are often
        # empty lists, so the current value can't tell us)
        if "int" in annotation:
            return [int(s) for s in items]
        if "float" in annotation:
            return [float(s) for s in items]
        return items
    return raw


def _apply_env(conf: "ClusterConf", env: dict) -> None:
    sections = {"master": conf.master, "worker": conf.worker,
                "client": conf.client, "fuse": conf.fuse,
                "obs": conf.obs, "rpc": conf.rpc, "qos": conf.qos,
                "ec": conf.ec}
    for key, raw in env.items():
        if not key.startswith("CURVINE_") or key == "CURVINE_CONF":
            continue
        rest = key[len("CURVINE_"):].lower()
        section, _, field_name = rest.partition("_")
        target = sections.get(section)
        if target is None:          # top-level field: CURVINE_DATA_DIR
            target, field_name = conf, rest
        if not field_name or not hasattr(target, field_name):
            continue
        cur = getattr(target, field_name)
        if dataclasses.is_dataclass(cur) or field_name == "tiers":
            continue                # structured fields stay TOML-only
        ann = ""
        for f in dataclasses.fields(target):
            if f.name == field_name:
                ann = str(f.type)
                break
        try:
            setattr(target, field_name, _coerce(cur, raw, ann))
        except (TypeError, ValueError) as e:
            # a typo'd env override (CURVINE_WORKER_RPC_PORT=abc) must
            # surface, not silently fall back to the default
            logging.getLogger(__name__).warning(
                "ignoring env override %s=%r: %s", key, raw, e)
