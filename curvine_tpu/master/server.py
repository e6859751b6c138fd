"""Master RPC server: binds RpcCode → MasterFilesystem + managers.

Parity: curvine-server/src/master/master_handler.rs + master_server.rs.
The namespace is a single-writer actor: all handlers run on one asyncio
loop, so mutations are serialized without locks (the reference uses an
actor + RwLock split; asyncio gives us the same property for free)."""

from __future__ import annotations

import asyncio
import logging

from curvine_tpu.common.conf import ClusterConf
from curvine_tpu.common.journal import Journal
from curvine_tpu.common.types import CommitBlock, SetAttrOpts, now_ms
from curvine_tpu.common.metrics import MetricsRegistry
from curvine_tpu.common.path import norm_path
from curvine_tpu.master.acl import AclEnforcer, R, UserCtx, W, X
from curvine_tpu.master.filesystem import MasterFilesystem
from curvine_tpu.master.jobs import JobManager
from curvine_tpu.master.mount import MountManager
from curvine_tpu.master.replication import ReplicationManager
from curvine_tpu.master.retry_cache import RetryCache
from curvine_tpu.master.ttl import TtlManager
from curvine_tpu.obs.trace import Tracer
from curvine_tpu.rpc import Message, RpcCode, RpcServer, ServerConn
from curvine_tpu.rpc.frame import pack, unpack

log = logging.getLogger(__name__)


class MasterServer:
    def __init__(self, conf: ClusterConf | None = None,
                 journal: bool = True, shard_id: int | None = None,
                 shard_count: int = 1):
        self.conf = conf or ClusterConf()
        mc = self.conf.master
        # sharded namespace (master/sharding.py): shard_id is set when
        # THIS server is one shard actor of a router's fleet (striped id
        # allocation); meta_shards>1 with shard_id=None makes this
        # server the ROUTER. shards=1 never constructs any of it.
        self.shard_id = shard_id
        self.shard_count = shard_count
        self.sharded = mc.meta_shards > 1 and shard_id is None
        if self.sharded and mc.raft_peers:
            from curvine_tpu.common import errors as _err
            raise _err.InvalidArgument(
                "meta_shards>1 is mutually exclusive with raft HA "
                "(set meta_shards=1 under raft; see docs/metadata-scale.md)")
        j = Journal(mc.journal_dir, fsync=mc.journal_fsync) if journal else None
        store = None
        if mc.meta_store == "kv":
            from curvine_tpu.master.store import KvMetaStore
            meta_dir = mc.meta_dir or mc.journal_dir.rstrip("/") + "-meta"
            store = KvMetaStore(meta_dir, fsync=mc.journal_fsync,
                                cache_inodes=mc.meta_cache_inodes,
                                engine=mc.meta_engine)
        # native metadata read plane: mirror every committed namespace
        # mutation into C++ and serve stat/exists from native threads.
        # Three shapes (docs/read-plane.md):
        #   * single master — mirror its own store, serve the fast port;
        #   * shard ACTOR — mirror its partition, never bind a port (the
        #     router fronts the fleet via mm_fleet_attach);
        #   * inproc ROUTER — a front mirror holding only the mount
        #     table; reads route to the attached shard mirrors by
        #     crc32(parent) % n. The process backend keeps the front
        #     disabled: member mirrors live in child address spaces.
        self.fastmeta = None
        if mc.fast_meta and (not self.sharded
                             or mc.shard_backend == "inproc"):
            from curvine_tpu.master import fastmeta
            if fastmeta.available():
                if store is None:
                    from curvine_tpu.master.store import MemMetaStore
                    store = MemMetaStore()
                self.fastmeta = fastmeta.FastMeta(
                    acl_enabled=mc.acl_enabled, superuser=mc.superuser,
                    supergroup=mc.supergroup)
                store = fastmeta.MirroredStore(store, self.fastmeta)
        self.fs = MasterFilesystem(
            journal=j, placement=mc.block_placement_policy,
            lost_timeout_ms=mc.worker_lost_timeout_ms,
            snapshot_interval=mc.snapshot_interval_entries, store=store,
            id_stride=shard_count if shard_id is not None else 1,
            id_offset=shard_id or 0,
            ici_mesh_shape=mc.ici_mesh_shape or None)
        self.fs.audit_log = mc.audit_log
        self.mounts = MountManager(self.fs)
        self.fs.mounts = self.mounts
        self.metrics = MetricsRegistry("master")
        # group commit: installed even with journal=None (perf clusters) —
        # then only the KV write batches are grouped. RPC replies release
        # at _group_barrier, after the group's flush.
        from curvine_tpu.common.journal import GroupCommitter
        self.fs.committer = GroupCommitter(
            j, self.fs.store, window_ms=mc.journal_group_commit_ms,
            max_entries=mc.journal_group_max, metrics=self.metrics)
        self.jobs = JobManager(self.fs, self.mounts)
        self.jobs.ec_conf = self.conf.ec
        self.replication = ReplicationManager(
            self.fs, pull_budget_ms=mc.replication_pull_budget_ms,
            metrics=self.metrics)
        self.fs.on_worker_lost = self.replication.on_worker_lost
        self.ttl = TtlManager(self.fs, check_ms=mc.ttl_check_ms)
        # client read leases (master/read_leases.py): only on endpoints
        # that hold CLIENT connections — the router when sharded, the
        # master otherwise. Shard actors see only router conns; their
        # TTL expiries are relayed to the router's manager instead.
        self.leases = None
        if shard_id is None:
            from curvine_tpu.master.read_leases import ReadLeaseManager
            self.leases = ReadLeaseManager(ttl_ms=mc.meta_lease_ms,
                                           max_dirs=mc.meta_lease_dirs)
            self.ttl.on_expire = \
                lambda path: self.leases.invalidate([path])
        from curvine_tpu.master.quota import QuotaManager
        self.quota = QuotaManager(self.fs)
        if self.leases is not None:
            self.quota.on_free = \
                lambda path: self.leases.invalidate([path])
        from curvine_tpu.master.locks import LockManager
        self.locks = LockManager()
        self.acl = AclEnforcer(self.fs, enabled=mc.acl_enabled,
                               superuser=mc.superuser,
                               supergroup=mc.supergroup)
        self.retry_cache = RetryCache(mc.retry_cache_size, mc.retry_cache_ttl_ms)
        from curvine_tpu.master.monitor import DirWatchdog, MasterMonitor
        self.watchdog = DirWatchdog(self.metrics, self.locks,
                                    stall_s=mc.watchdog_stall_ms / 1000)
        self.monitor = MasterMonitor(self)
        self.rpc = RpcServer(mc.hostname, mc.rpc_port, "master",
                             rpc_conf=self.conf.rpc)
        # in-flight requests register at the DISPATCH level so a wedge
        # anywhere (fault hook, handler, commit barrier) is visible
        self.rpc.watchdog = self.watchdog
        # observability plane: server spans per dispatch (trace context
        # picked off the header) + per-code rpc.<name> histograms; the
        # store additionally holds spans the CLIENTS push via
        # METRICS_REPORT, so one GET_SPANS collect sees both
        self.tracer = Tracer.from_conf("master", self.conf.obs,
                                       metrics=self.metrics)
        self.rpc.obs = self.tracer
        self.rpc.metrics = self.metrics
        # multi-tenant admission control (common/qos.py): checked in the
        # conn loop before a request queues; unlimited by default
        from curvine_tpu.common.qos import AdmissionController
        self.qos = AdmissionController.from_conf(
            self.conf.qos, slow_op_ms=self.conf.obs.slow_op_ms,
            metrics=self.metrics)
        self.rpc.qos = self.qos
        self.replication.tracer = self.tracer
        # pool for the GET_SPANS fan-out to workers (trace assembly)
        from curvine_tpu.rpc.client import ConnectionPool
        self._obs_pool = ConnectionPool(size=1)
        self.raft = None
        if mc.raft_peers:
            from curvine_tpu.master.ha import RaftLite
            peers = {i + 1: addr for i, addr in enumerate(mc.raft_peers)
                     if i + 1 != mc.raft_node_id}
            if 0 < mc.raft_node_id <= len(mc.raft_peers):
                self_addr = mc.raft_peers[mc.raft_node_id - 1]
            else:
                self_addr = f"{mc.hostname}:{mc.rpc_port}"
            self.raft = RaftLite(
                mc.raft_node_id, peers, self.fs, self.rpc,
                self_addr=self_addr, learner=mc.raft_learner,
                promote_lag=mc.raft_promote_lag,
                snapshot_chunk_bytes=mc.raft_snapshot_chunk_mb * 1024 * 1024,
                transfer_timeout_s=mc.raft_transfer_timeout_ms / 1000,
                metrics=self.metrics)
            self.fs.on_mutation = self.raft.on_mutation
        self.shards = None
        if self.sharded:
            from curvine_tpu.master.sharding import ShardRouter
            self.shards = ShardRouter(self, journal=journal)
        self._register_handlers()
        if self.shards is not None:
            self._register_shard_routes()
        self._worker_counters: dict[int, dict] = {}
        # worker_id -> count of non-healthy tier dirs (from heartbeats);
        # feeds the cluster-wide dirs.unhealthy gauge
        self._dirs_unhealthy: dict[int, int] = {}
        self._bg: list[asyncio.Task] = []
        from curvine_tpu.common.executor import ScheduledExecutor
        self.executor = ScheduledExecutor("master")
        self.ufs_backup = None
        if mc.ufs_backup_uri:
            from curvine_tpu.master.ufs_backup import UfsBackup
            self.ufs_backup = UfsBackup(self.fs, mc.ufs_backup_uri)

    @property
    def addr(self) -> str:
        return self.rpc.addr

    async def start(self) -> None:
        self.fs.recover()
        if self.ufs_backup is not None:
            # disaster bootstrap: a wiped/virgin master dir restores the
            # namespace from the newest UFS snapshot (local truth wins
            # when any history exists). Parity: ufs_loader.rs.
            try:
                await self.ufs_backup.bootstrap_if_empty()
            except Exception as e:  # noqa: BLE001 — degrade, don't die
                log.warning("ufs backup bootstrap failed: %s", e)
        self.mounts.load_from_store()
        # durable decommission intents (KV cold starts skip replay, so
        # runtime-only state would otherwise vanish on restart)
        self.fs.workers.deco_ids |= set(self.fs.store.iter_deco())
        if self.shards is not None:
            # shards (and the crash-recovery sweep) come up before the
            # endpoint accepts traffic
            await self.shards.start()
            self.executor.submit_periodic("shard-stats",
                                          self.shards.poll_stats, 2.0)
        await self.rpc.start()
        if self.raft is not None:
            await self.raft.start()
        # periodic duties ride the scheduled executor
        # (parity: curvine-common/src/executor/ ScheduledExecutor)
        interval = self.conf.master.heartbeat_check_ms / 1000
        # HA followers must not ACT on replicated state (ttl deletes,
        # evictions, lease recovery, repair dispatch): acting appends
        # divergent local journal entries. Every mutating periodic duty
        # is gated on leadership; single-node mode gates to True.
        gate = self._is_leader
        self.executor.submit_periodic("heartbeat-check",
                                      self._heartbeat_tick, interval)
        if self.fastmeta is not None and self.shard_id is not None:
            # shard actor: keep the mirror warm for the router's front
            # plane, but never bind a fast port of its own
            self.fastmeta.load_from_store(self.fs.store)
        elif self.fastmeta is not None:
            # bulk load AFTER recover (KV cold starts never replay old
            # inodes through the store wrapper), then keep serving in
            # lockstep with leadership. The plane is best-effort: a bind
            # failure degrades to Python-only, never a dead master.
            try:
                self.fastmeta.serve(self.conf.master.hostname,
                                    self.conf.master.fast_port)
                self.fastmeta.load_from_store(self.fs.store)
            except Exception as e:  # noqa: BLE001 — degrade, don't die
                log.warning("fast metadata plane disabled: %s", e)
                self.fastmeta.close()
                self.fastmeta = None
            else:
                self._fast_serving = False
                self._fast_gate_tick()
                self.executor.submit_periodic("fastmeta-gate",
                                              self._fast_gate_tick, 1.0)
        self.executor.submit_periodic("lease-recovery",
                                      self._lease_recovery_tick, 30.0)
        self.executor.submit_periodic("watchdog", self.watchdog.tick, 1.0)
        if self.ufs_backup is not None:
            async def backup_tick():
                if self._is_leader():
                    await self.ufs_backup.upload_if_advanced()
            self.executor.submit_periodic(
                "ufs-backup", backup_tick,
                self.conf.master.ufs_backup_interval_s)
        self.executor.submit("ttl", self.ttl.run(leader_gate=gate))
        self.executor.submit("replication",
                             self.replication.run(leader_gate=gate))
        self.executor.submit("jobs", self.jobs.run(leader_gate=gate))
        self.executor.submit("quota", self.quota.run(leader_gate=gate))
        log.info("master started at %s", self.addr)

    def _is_leader(self) -> bool:
        return self.raft is None or self.raft.is_leader

    def _fast_gate_tick(self) -> None:
        """Fast-path serving tracks leadership: followers mirror the
        namespace (replicated applies flow through the same store
        wrapper) but must not serve reads that bypass the leader."""
        want = self._is_leader()
        if want != self._fast_serving:
            self.fastmeta.set_serving(want)
            self._fast_serving = want
            log.info("fast metadata plane %s (port %s)",
                     "serving" if want else "gated off",
                     self.fastmeta.port)

    def _lease_recovery_tick(self) -> None:
        if self._is_leader():
            self.fs.recover_stale_leases()

    def _heartbeat_tick(self) -> None:
        # LOST bookkeeping runs everywhere (follower-served reads must
        # not return dead-worker locations); repair-dispatch side effects
        # stay leader-gated. Counter pruning is local metrics state and
        # runs everywhere too.
        self.fs.check_lost_workers(act=self._is_leader())
        # dead workers' last snapshots must not pin the gauges forever
        self._prune_worker_counters()
        # KV compaction debt: segment count waiting for merge (creation
        # bursts at namespace scale show up here before read latency does)
        kv = getattr(self.fs.store, "kv", None)
        if kv is not None:
            segs = getattr(kv, "segment_count", None)
            if segs is None:
                segs = len(getattr(kv, "segments", ()))
            self.metrics.gauge("meta.kv_segments", segs)

    def _prune_worker_counters(self) -> None:
        # draining workers still serve and still report: keep their
        # counters or the aggregate gauges flap for the whole drain
        live = {w.address.worker_id
                for w in self.fs.workers.serving_workers()}
        if any(k not in live for k in self._worker_counters):
            self._worker_counters = {k: v for k, v
                                     in self._worker_counters.items()
                                     if k in live}
        for name in ("bytes.read", "bytes.written"):
            self.metrics.gauge(name, sum(
                c.get(name, 0) for c in self._worker_counters.values()))

    async def stop(self) -> None:
        if self.raft is not None:
            await self.raft.stop()
        await self.executor.stop()
        for t in self._bg:
            t.cancel()
        self._bg.clear()
        await self.rpc.stop()
        if self.shards is not None:
            if self.fastmeta is not None:
                # join the front's native serve threads BEFORE freeing
                # the member mirrors they read from
                self.fastmeta.stop_serving()
            await self.shards.stop()
        await self._obs_pool.close()
        try:
            self.fs.flush_group()   # drain any open journal group
        except Exception as e:  # noqa: BLE001 — already-broken committer
            log.warning("final group flush failed: %s", e)
        if self.fs.journal:
            self.fs.journal.close()
        if self.fastmeta is not None:
            self.fastmeta.close()
        self.fs.store.close()

    # ---------------- handlers ----------------

    def _register_handlers(self) -> None:
        r = self.rpc.register
        C = RpcCode
        r(C.MKDIR, self._h(self._mkdir, mutate=True))
        r(C.DELETE, self._h(self._delete, mutate=True))
        r(C.CREATE_FILE, self._h(self._create_file, mutate=True))
        r(C.OPEN_FILE, self._h(self._open_file))
        r(C.APPEND_FILE, self._h(self._append_file, mutate=True))
        r(C.FILE_STATUS, self._h(self._file_status))
        r(C.LIST_STATUS, self._h(self._list_status))
        r(C.EXISTS, self._h(self._exists))
        r(C.RENAME, self._h(self._rename, mutate=True))
        r(C.ADD_BLOCK, self._h(self._add_block, mutate=True))
        r(C.COMPLETE_FILE, self._h(self._complete_file, mutate=True))
        r(C.GET_BLOCK_LOCATIONS, self._h(self._get_block_locations))
        r(C.GET_BLOCK_LOCATIONS_BATCH, self._get_block_locations_batch)
        r(C.GET_MASTER_INFO, self._h(self._master_info))
        r(C.SET_ATTR, self._h(self._set_attr, mutate=True))
        r(C.SYMLINK, self._h(self._symlink, mutate=True))
        r(C.LINK, self._h(self._link, mutate=True))
        r(C.RESIZE_FILE, self._h(self._resize, mutate=True))
        r(C.FREE, self._h(self._free, mutate=True))
        r(C.CREATE_FILES_BATCH, self._h(self._create_files_batch, mutate=True))
        r(C.ADD_BLOCKS_BATCH, self._h(self._add_blocks_batch, mutate=True))
        r(C.COMPLETE_FILES_BATCH, self._h(self._complete_files_batch, mutate=True))
        r(C.LIST_OPTIONS, self._h(self._list_options))
        r(C.CONTENT_SUMMARY, self._h(self._content_summary))
        r(C.META_BATCH, self._h(self._meta_batch, mutate=True))
        r(C.GET_LOCK, self._h(self._get_lock))
        r(C.SET_LOCK, self._h(self._set_lock))
        r(C.LIST_LOCK, self._h(self._list_lock))
        r(C.ASSIGN_WORKER, self._h(self._assign_worker))
        r(C.METRICS_REPORT, self._h(self._metrics_report))
        r(C.CLUSTER_HEALTH, self._h(self._cluster_health))
        r(C.GET_SPANS, self._h(self._get_spans))
        # worker plane
        r(C.WORKER_HEARTBEAT, self._h(self._worker_heartbeat))
        r(C.WORKER_BLOCK_REPORT, self._h(self._worker_block_report))
        r(C.REQUEST_REPLACEMENT_WORKER, self._h(self._replacement_worker))
        r(C.REPORT_UNDER_REPLICATED_BLOCKS, self._h(self._report_under_replicated))
        r(C.REPORT_BLOCK_REPLICATION_RESULT, self._h(self._replication_result))
        r(C.EC_COMMIT_STRIPE, self._h(self._ec_commit_stripe, mutate=True))
        r(C.DECOMMISSION_WORKER, self._h(self._decommission_worker,
                                         mutate=True))
        # mounts
        r(C.MOUNT, self._h(self._mount, mutate=True))
        r(C.UNMOUNT, self._h(self._umount, mutate=True))
        r(C.UPDATE_MOUNT, self._h(self._update_mount, mutate=True))
        r(C.GET_MOUNT_TABLE, self._h(self._mount_table))
        r(C.GET_MOUNT_INFO, self._h(self._mount_info))
        # jobs
        r(C.SUBMIT_JOB, self._h(self._submit_job, mutate=True))
        r(C.GET_JOB_STATUS, self._h(self._job_status))
        r(C.CANCEL_JOB, self._h(self._cancel_job, mutate=True))
        r(C.PREFETCH_WINDOW, self._h(self._prefetch_window, mutate=True))
        r(C.REPORT_TASK, self._h(self._report_task))
        # sharded namespace plane: every master answers the 2PC
        # participant protocol and stats (a shard IS a MasterServer);
        # SHARD_TABLE is only meaningful on a router
        r(C.SHARD_TX, self._h(self._shard_tx, mutate=True))
        r(C.SHARD_TX_LIST, self._h(self._shard_tx_list))
        r(C.SHARD_STATS, self._h(self._shard_stats))
        r(C.SHARD_TABLE, self._h(self._shard_table))
        r(C.TENANT_STATS, self._h(self._tenant_stats))
        # raft membership admin plane (docs/raft.md). MEMBER_CHANGE rides
        # the mutate path: leader gate + journaled config entry + commit
        # barrier (the RPC acks once the change is committed). TRANSFER
        # does its own leader gate and journals nothing. RAFT_STATUS is
        # registered by RaftLite itself so ANY node answers it.
        r(C.RAFT_MEMBER_CHANGE, self._h(self._raft_member_change,
                                        mutate=True))
        r(C.RAFT_TRANSFER, self._h(self._raft_transfer))

    def _register_shard_routes(self) -> None:
        """meta_shards>1: this endpoint is a thin router. Namespace
        codes RE-register to forwarding handlers (master/sharding.py);
        mounts, jobs, locks, health, spans and worker assignment stay
        router-local. Routed handlers skip _h's barriers — durability
        is the owning shard's group commit, and retries dedup in the
        owning shard's retry cache (routing is deterministic), except
        the multi-step 2PC ops which cache at the router."""
        sh = self.shards
        r = self.rpc.register
        C = RpcCode

        def wrap(fn, cache: bool = False, inval=None):
            # inval: the mutation code whose touched paths must be
            # lease-invalidated after the owning shard acks (the router
            # holds the client conns, so pushes originate here)
            async def handler(msg: Message, conn: ServerConn):
                req = self._norm_req(unpack(msg.data) or {})
                if cache:
                    key = (req.get("client_id"), req.get("call_id"))
                    if key[0] is not None and key[1] is not None:
                        hit = self.retry_cache.get(key)
                        if hit is not None:
                            return {}, hit
                        data = pack(await fn(req, msg))
                        if inval is not None:
                            self._lease_invalidate(inval, req)
                        self.retry_cache.put(key, data)
                        return {}, data
                leased = inval is None and self._lease_grant(msg, req, conn)
                out = await fn(req, msg)
                if inval is not None:
                    self._lease_invalidate(inval, req)
                elif leased and isinstance(out, dict):
                    out["lease"] = self.leases.token()
                return {}, pack(out)
            return handler

        def fwd(code):
            mutates = code in (C.CREATE_FILE, C.APPEND_FILE,
                               C.COMPLETE_FILE, C.RESIZE_FILE,
                               C.SYMLINK, C.MKDIR)
            return wrap(lambda q, m, c=code: sh.r_forward(c, q, m),
                        inval=code if mutates else None)

        for code in (C.CREATE_FILE, C.OPEN_FILE, C.APPEND_FILE,
                     C.ADD_BLOCK, C.COMPLETE_FILE, C.GET_BLOCK_LOCATIONS,
                     C.RESIZE_FILE, C.SYMLINK, C.MKDIR):
            r(code, fwd(code))
        r(C.FILE_STATUS, wrap(sh.r_file_status))
        r(C.EXISTS, wrap(sh.r_exists))
        r(C.LIST_STATUS, wrap(sh.r_list_status))
        r(C.LIST_OPTIONS, wrap(sh.r_list_options))
        r(C.CONTENT_SUMMARY, wrap(sh.r_content_summary))
        r(C.SET_ATTR, wrap(sh.r_set_attr, inval=C.SET_ATTR))
        r(C.FREE, wrap(sh.r_free, inval=C.FREE))
        r(C.DELETE, wrap(sh.r_delete, inval=C.DELETE))
        r(C.RENAME, wrap(sh.r_rename, cache=True, inval=C.RENAME))
        r(C.LINK, wrap(sh.r_link, cache=True, inval=C.LINK))
        for code in (C.CREATE_FILES_BATCH, C.ADD_BLOCKS_BATCH,
                     C.COMPLETE_FILES_BATCH, C.META_BATCH):
            r(code, wrap(lambda q, m, c=code: sh.r_batch(c, q, m),
                         inval=code if code != C.ADD_BLOCKS_BATCH
                         else None))
        r(C.WORKER_HEARTBEAT, wrap(
            lambda q, m: sh.r_worker_heartbeat(q, m,
                                               self._worker_heartbeat)))
        r(C.WORKER_BLOCK_REPORT, wrap(sh.r_worker_block_report))

        async def not_routed(q, msg):
            # the router's own tree holds none of the files; the client
            # sees the refusal and opens each file for itself
            from curvine_tpu.common import errors as cerr
            raise cerr.Unsupported("GET_BLOCK_LOCATIONS_BATCH is not "
                                   "routed over a sharded namespace")
        r(C.GET_BLOCK_LOCATIONS_BATCH, wrap(not_routed))

    # Path-valued request fields, normalized ('.'/'..' resolved, root
    # escapes rejected) before ANY handler sees them — an S3-gateway key
    # like '..%2Fx' must never become a literal inode name.
    _PATH_KEYS = ("path", "src", "dst", "link", "cv_path")

    @classmethod
    def _norm_req(cls, req: dict) -> dict:
        for k in cls._PATH_KEYS:
            v = req.get(k)
            if isinstance(v, str):
                req[k] = norm_path(v)
        for sub in req.get("requests") or []:
            if isinstance(sub, dict):
                cls._norm_req(sub)
        return req

    def _h(self, fn, mutate: bool = False):
        import inspect

        async def call(req):
            rep = fn(req)
            if inspect.isawaitable(rep):
                rep = await rep
            return rep

        async def handler(msg: Message, conn: ServerConn):
            # per-code latency histograms moved to the dispatch level
            # (RpcServer.metrics → rpc.<code_name>), uniform with the
            # worker; this wrapper only keeps the mutation discipline
            req = self._norm_req(unpack(msg.data) or {})
            if mutate and self.raft is not None:
                self.raft.check_leader()
            if mutate:
                key = (req.get("client_id"), req.get("call_id"))
                if key[0] is not None and key[1] is not None:
                    cached = self.retry_cache.get(key)
                    if cached is not None:
                        return {}, cached
                    rep = await call(req)
                    await self._group_barrier()
                    await self._commit_barrier(msg.deadline)
                    self._lease_invalidate(msg.code, req)
                    data = pack(rep)
                    self.retry_cache.put(key, data)
                    return {}, data
            leased = not mutate and self._lease_grant(msg, req, conn)
            rep = await call(req)
            if mutate:
                await self._group_barrier()
                await self._commit_barrier(msg.deadline)
                self._lease_invalidate(msg.code, req)
            elif leased and isinstance(rep, dict):
                rep["lease"] = self.leases.token()
            return {}, pack(rep)
        return handler

    # reads that may carry `"lease": True` → register the conn as a
    # cache holder on the entry's parent directory (the listed dir
    # itself for LIST_STATUS) and stamp the token into the reply
    _LEASED_READS = frozenset({int(RpcCode.FILE_STATUS),
                               int(RpcCode.EXISTS),
                               int(RpcCode.LIST_STATUS)})
    # mutation code → request keys naming the namespace paths it touched
    _INVAL_KEYS = {
        int(RpcCode.MKDIR): ("path",),
        int(RpcCode.CREATE_FILE): ("path",),
        int(RpcCode.DELETE): ("path",),
        int(RpcCode.APPEND_FILE): ("path",),
        int(RpcCode.COMPLETE_FILE): ("path",),
        int(RpcCode.RENAME): ("src", "dst"),
        int(RpcCode.SET_ATTR): ("path",),
        int(RpcCode.SYMLINK): ("link",),
        int(RpcCode.LINK): ("src", "dst"),
        int(RpcCode.RESIZE_FILE): ("path",),
        int(RpcCode.FREE): ("path",),
        int(RpcCode.MOUNT): ("cv_path",),
        int(RpcCode.UNMOUNT): ("cv_path",),
        int(RpcCode.UPDATE_MOUNT): ("cv_path",),
    }
    _INVAL_BATCHES = frozenset({int(RpcCode.META_BATCH),
                                int(RpcCode.CREATE_FILES_BATCH),
                                int(RpcCode.COMPLETE_FILES_BATCH)})

    def _lease_grant(self, msg: Message, req: dict, conn) -> bool:
        """Register `conn` as a lease holder for a `"lease": True` read.
        Granted BEFORE the handler runs so ENOENT answers are leased too
        (the client caches negatives; a later create must push)."""
        if (self.leases is None or not req.get("lease")
                or int(msg.code) not in self._LEASED_READS
                or not isinstance(req.get("path"), str)):
            return False
        from curvine_tpu.master.read_leases import parent_dir
        p = req["path"]
        self.leases.grant(conn, p if int(msg.code) ==
                          int(RpcCode.LIST_STATUS) else parent_dir(p))
        return True

    def _lease_invalidate(self, code: int, req: dict) -> None:
        """A mutation landed: push META_INVALIDATE for the paths it
        touched to every conn holding a lease on an affected dir."""
        if self.leases is None:
            return
        code = int(code)
        if code in self._INVAL_BATCHES:
            paths = [r.get("path") for r in req.get("requests") or ()
                     if isinstance(r, dict)]
        else:
            keys = self._INVAL_KEYS.get(code)
            if not keys:
                return
            paths = [req.get(k) for k in keys]
        self.leases.invalidate([p for p in paths if isinstance(p, str)])

    async def _group_barrier(self) -> None:
        """Group-commit rule: a mutation is acked only after the journal
        group containing it has flushed (and its KV batch landed). This
        await is where concurrent mutations pile into one group."""
        if self.fs.committer is not None:
            await self.fs.committer.sync()

    async def _commit_barrier(self, deadline=None) -> None:
        """Raft commit rule: a mutation is acked to the client only after
        its journal entry is replicated on a quorum (closes the acked-
        write-loss window of the round-1 design). A caller deadline caps
        the wait: past it the client is gone, so holding the dispatch
        slot longer is dead work (the entry still commits in the
        background — only the ack is abandoned)."""
        if self.raft is not None:
            await self.raft.wait_committed(self.fs.journal.seq,
                                           deadline=deadline)

    # --- fs ---
    def _mkdir(self, q):
        ctx = UserCtx.from_req(q)
        if self.fs.exists(q["path"]):
            self.acl.check(ctx, q["path"], 0)     # idempotent: traverse only
        else:
            self.acl.check(ctx, q["path"], W | X, on_parent=True)
        st = self.fs.mkdir(q["path"], create_parent=q.get("create_parent", True),
                           mode=q.get("mode", 0o755),
                           owner=q.get("owner") or ctx.user,
                           group=q.get("group") or (ctx.groups[0] if ctx.groups
                                                    else ctx.user),
                           x_attr=q.get("x_attr"))
        return {"status": st.to_wire()}

    def _delete(self, q):
        self.acl.check(UserCtx.from_req(q), q["path"], W | X, on_parent=True)
        self.fs.delete(q["path"], recursive=q.get("recursive", False))
        self.quota.invalidate(q["path"])
        return {}

    def _create_file(self, q, ctx=None):
        if ctx is None:
            ctx = UserCtx.from_req(q)
        # one shared walk feeds the acl branch, the quota check, AND the
        # filesystem's own validation (no awaits in between)
        walked = self.fs.tree.walk_parent(q["path"])
        parent, _name, existing = walked
        if existing is not None:
            self.acl.check(ctx, q["path"], W)     # overwrite needs w on file
        else:
            self.acl.check(ctx, q["path"], W | X, on_parent=True)
        self.quota.check_create(q["path"], parent=parent)
        st = self.fs.create_file(
            q["path"], overwrite=q.get("overwrite", False),
            create_parent=q.get("create_parent", True),
            replicas=q.get("replicas", 1),
            block_size=q.get("block_size", self.conf.client.block_size),
            mode=q.get("mode", 0o644), owner=q.get("owner") or ctx.user,
            group=q.get("group") or (ctx.groups[0] if ctx.groups
                                     else ctx.user),
            client_name=q.get("client_name", ""),
            x_attr=q.get("x_attr"), storage_policy=q.get("storage_policy"),
            file_type=q.get("file_type", 1), walked=walked)
        if st.storage_policy.ttl_ms > 0:
            # index at create so the TTL engages without waiting for the
            # periodic O(namespace) rescan
            self.ttl.index(st.id, st.mtime, st.storage_policy.ttl_ms)
        return {"status": st.to_wire()}

    def _open_file(self, q):
        self.acl.check(UserCtx.from_req(q), q["path"], R)
        fb = self.fs.get_block_locations(q["path"])
        return {"file_blocks": fb.to_wire()}

    def _append_file(self, q):
        self.acl.check(UserCtx.from_req(q), q["path"], W)
        fb = self.fs.append_file(q["path"], client_name=q.get("client_name", ""))
        return {"file_blocks": fb.to_wire()}

    async def _file_status(self, q):
        from curvine_tpu.common import errors as cerr
        self.acl.check(UserCtx.from_req(q), q["path"], 0)   # traverse only
        try:
            return {"status": self.fs.file_status(q["path"]).to_wire()}
        except cerr.FileNotFound:
            st = await self.mounts.ufs_status(q["path"])
            if st is None:
                raise
            return {"status": st.to_wire()}

    async def _content_summary(self, q):
        """Recursive length/file/dir counts in ONE RPC, computed on the
        master's inode tree (the reference's ContentSummary aggregates
        client-side over N ListStatus calls — content_summary.rs). The
        walk yields to the event loop periodically (a big subtree must
        not stall heartbeats), requires R|X on every directory like HDFS
        getContentSummary, and refuses subtrees intersecting mounts —
        their totals live (partly) in the UFS, so clients aggregate the
        unified listing instead (CurvineClient.content_summary does)."""
        import asyncio as _aio
        from curvine_tpu.common import errors as cerr
        path = q["path"]
        ctx = UserCtx.from_req(q)
        # traverse check FIRST: FileNotFound vs PermissionDenied must not
        # become an existence oracle inside unreadable directories
        self.acl.check(ctx, path, 0)
        node = self.fs.tree.resolve(path)
        if node is None:
            raise cerr.FileNotFound(path)
        if self.mounts is not None:
            prefix = (path.rstrip("/") or "") + "/"
            if self.mounts.get_mount(path) is not None or any(
                    m.cv_path.startswith(prefix)
                    for m in self.mounts.table()):
                raise cerr.Unsupported(
                    f"{path} intersects mounts: aggregate the unified "
                    "listing client-side")
        if node.is_dir:
            self.acl.check(ctx, path, R)
        # Weakly consistent (HDFS-style): the walk yields to the event
        # loop every 2048 nodes, so concurrent delete/rename can detach
        # subtrees mid-traversal — counts reflect no single namespace
        # snapshot (path_of tolerates detached nodes: it stops at the
        # first missing parent).
        length = file_count = dir_count = visited = 0
        stack = [node]
        while stack:
            n = stack.pop()
            if n.is_dir:
                if not self.acl.allows(n, ctx, R | X):
                    raise cerr.PermissionDenied(
                        f"user={ctx.user} needs r-x on "
                        f"{self.fs.tree.path_of(n)}")
                dir_count += 1
                stack.extend(ch for _nm, ch in self.fs.tree.children(n))
            else:
                file_count += 1
                length += n.len
            visited += 1
            if visited % 2048 == 0:
                await _aio.sleep(0)
        return {"length": length, "file_count": file_count,
                "directory_count": dir_count}

    async def _list_status(self, q):
        """Cached entries merged with the mounted UFS listing (unified
        metadata view — UFS objects appear before they are ever cached).
        Parity: reference sync_ufs_meta / unified listing."""
        from curvine_tpu.common import errors as cerr
        path = q["path"]
        node = self.fs.tree.resolve(path)
        self.acl.check(UserCtx.from_req(q), path,
                       R if node is not None and node.is_dir else 0)
        try:
            cached = self.fs.list_status(path)
        except cerr.FileNotFound:
            if await self.mounts.ufs_status(path) is None:
                raise
            cached = []
        merged = {s.name: s for s in await self.mounts.ufs_list(path)}
        merged.update({s.name: s for s in cached})
        return {"statuses": [merged[k].to_wire() for k in sorted(merged)]}

    async def _exists(self, q):
        self.acl.check(UserCtx.from_req(q), q["path"], 0)   # traverse
        if self.fs.exists(q["path"]):
            return {"exists": True}
        st = await self.mounts.ufs_status(q["path"])
        return {"exists": st is not None}

    def _rename(self, q):
        ctx = UserCtx.from_req(q)
        self.acl.check(ctx, q["src"], W | X, on_parent=True)
        self.acl.check(ctx, q["dst"], W | X, on_parent=True)
        out = {"result": self.fs.rename(q["src"], q["dst"])}
        self.quota.invalidate(q["src"])
        self.quota.invalidate(q["dst"])
        return out

    def _check_write_lease(self, q) -> None:
        """Writes to an OPEN file are restricted to the lease holder (the
        client that created/appended it, which was ACL-authorized then);
        everyone else needs W — and traverse is always enforced so open
        files can't be probed through unreadable dirs."""
        ctx = UserCtx.from_req(q)
        self.acl.check(ctx, q["path"], 0)             # traverse, always
        node = self.fs.tree.resolve(q["path"])
        if node is not None and not node.is_complete and node.client_name:
            caller = q.get("client_name") or q.get("client_id")
            if caller == node.client_name or self.acl._is_super(ctx):
                return                                # lease holder
            from curvine_tpu.common import errors as cerr
            raise cerr.LeaseConflict(
                f"{q['path']} is open by another client")
        self.acl.check(ctx, q["path"], W)

    def _add_block(self, q):
        self._check_write_lease(q)
        node = self.fs.tree.resolve(q["path"])
        if node is not None:
            self.quota.check_create(q["path"], new_bytes=node.block_size,
                                    new_files=0)
        lb = self.fs.add_block(
            q["path"], client_host=q.get("client_host", ""),
            exclude_workers=q.get("exclude_workers"),
            commit_blocks=[CommitBlock.from_wire(c)
                           for c in q.get("commit_blocks", [])],
            ici_coords=q.get("ici_coords"),
            abandon_block=q.get("abandon_block"))
        return {"block": lb.to_wire()}

    def _complete_file(self, q):
        self._check_write_lease(q)
        ok = self.fs.complete_file(
            q["path"], q.get("len", 0),
            commit_blocks=[CommitBlock.from_wire(c)
                           for c in q.get("commit_blocks", [])],
            client_name=q.get("client_name", ""),
            only_flush=q.get("only_flush", False))
        return {"result": ok}

    def _get_block_locations(self, q):
        self.acl.check(UserCtx.from_req(q), q["path"], R)
        return {"file_blocks": self.fs.get_block_locations(q["path"]).to_wire()}

    async def _get_block_locations_batch(self, msg: Message,
                                         conn: ServerConn):
        """GET_BLOCK_LOCATIONS for a list of paths in one round trip:
        each path is normalized, checked against the caller's ACL and
        answered for itself, its error beside the others' block lists.
        Admitted as one read, charged as one a path."""
        from curvine_tpu.common import errors as cerr
        from curvine_tpu.common.qos import READ, TENANT_KEY
        q = unpack(msg.data) or {}
        paths = q.get("paths") or []
        self.qos.charge(msg.header.get(TENANT_KEY), READ, len(paths) - 1)
        out = []
        for p in paths:
            try:
                out.append(self._get_block_locations(
                    self._with_identity(q, {"path": norm_path(p)})))
            except cerr.CurvineError as e:
                out.append({"error": str(e), "error_code": int(e.code)})
        return {}, pack({"responses": out})

    def _master_info(self, q):
        info = self.fs.master_info(self.addr)
        # advertise only a SERVING plane: a follower's fast port answers
        # fast-gated for everything, and a client attached to a follower
        # for reads would otherwise keep rediscovering the useless addr
        if (self.fastmeta is not None and self.fastmeta.port
                and self._is_leader()):
            host = self.addr.rsplit(":", 1)[0]
            info.fast_addr = f"{host}:{self.fastmeta.port}"
        wire = info.to_wire()
        if self.shards is not None:
            # the router's own tree is (near) empty: report the fleet
            rows = [s for s in self.shards.stats if s.get("state") == "up"]
            if rows:
                wire["inode_num"] = sum(s.get("inodes", 0) for s in rows)
                wire["block_num"] = sum(s.get("blocks", 0) for s in rows)
            wire["meta_shards"] = self.conf.master.meta_shards
        return {"info": wire}

    # --- sharded namespace plane (master/sharding.py) ---

    def _shard_tx(self, q):
        """2PC participant protocol, executed on this shard's actor
        loop; mutate=True dispatch means every phase's journal entry is
        group-committed before the coordinator sees the reply."""
        from curvine_tpu.common import errors as cerr
        phase = q["phase"]
        if phase == "prepare_src":
            return {"rec": self.fs.tx_prepare(
                q["txid"], q["op"], q["src"], q["dst"], role="src")}
        if phase == "prepare_dst":
            self.fs.tx_prepare(q["txid"], q["op"], q["src"], q["dst"],
                               role="dst", rec=q["rec"])
        elif phase == "commit":
            self.fs.tx_commit(q["txid"])
        elif phase == "abort":
            self.fs.tx_abort(q["txid"])
        elif phase == "forget":
            self.fs.tx_forget(q["txid"])
        else:
            raise cerr.InvalidArgument(f"unknown shard tx phase {phase!r}")
        return {}

    def _shard_tx_list(self, q):
        return {"txs": self.fs.list_tx()}

    def _shard_stats(self, q):
        import os as _os
        fs = self.fs
        com = fs.committer
        handled = sum(h.count for name, h in self.metrics.histograms.items()
                      if name.startswith("rpc."))
        if fs.journal is not None:
            seq = fs.journal.seq
        elif fs.store.kind == "kv":
            seq = fs.store.get_counter("applied_seq", 0)
        else:
            seq = 0
        return {"shard_id": -1 if self.shard_id is None else self.shard_id,
                "inodes": fs.tree.count(), "blocks": fs.blocks.count(),
                "journal_seq": seq,
                "queue_depth": max(0, com._dirty - com._synced) if com else 0,
                "groups": com.groups if com else 0,
                "entries": com.entries if com else 0,
                "handled": handled, "pid": _os.getpid(),
                "uptime_ms": now_ms() - fs.start_ms}

    async def _shard_table(self, q):
        """Shard rows plus the read fan-out plane's rollup: lease-
        manager state, aggregated client.meta_cache.* counters pushed
        via METRICS_REPORT, and native fast-meta counters. One RPC
        feeds both the shard table and the read-plane rows of
        `cv report` (docs/read-plane.md)."""
        out: dict = {"shards": []}
        if self.shards is not None:
            out["shards"] = await self.shards.poll_stats()
        if self.leases is not None:
            out["leases"] = self.leases.stats()
        pre = "client.meta_cache."
        cache = {k[len(pre):]: v for k, v in self.metrics.counters.items()
                 if k.startswith(pre)}
        if cache:
            out["meta_cache"] = cache
        if self.fastmeta is not None:
            out["fastmeta"] = self.fastmeta.counters()
        # write-pipeline fault-tolerance rollup (client.write.* counters
        # pushed via METRICS_REPORT): failovers absorbed, bytes replayed
        # after total replica loss, degraded commits awaiting healing
        pre_w = "client.write."
        wp = {k[len(pre_w):]: v for k, v in self.metrics.counters.items()
              if k.startswith(pre_w)}
        if wp:
            out["write_plane"] = wp
        # data-plane read rollup (client.read.* counters pushed via
        # METRICS_REPORT): shm short-circuit hits/fallbacks and bytes
        # delivered zero-copy (docs/data-plane.md)
        pre_r = "client.read."
        rp = {k[len(pre_r):]: v for k, v in self.metrics.counters.items()
              if k.startswith(pre_r)}
        if rp:
            out["read_plane"] = rp
        # healing-rail rollup: replicate/evacuate/reconstruct outcomes +
        # scrub verdicts (master-side counters), and the EC stripe plane
        for prefix, key in (("replication.", "replication"),
                            ("ec.", "ec_plane")):
            vals = {k[len(prefix):]: v
                    for k, v in self.metrics.counters.items()
                    if k.startswith(prefix)}
            if vals:
                out[key] = vals
        # cache-intelligence rollup (docs/caching.md): workers heartbeat
        # flattened "cache.<tier>.<stat>" admission counters (hits,
        # misses, ghost_hits, scan_evicted, admits) and per-tenant
        # tier-0 occupancy as "cache.tier0.<tenant>" — summed across
        # workers into per-tier dicts for `cv report`'s Cache plane line
        cp: dict = {}
        for counters in self._worker_counters.values():
            for k, v in counters.items():
                if not k.startswith("cache."):
                    continue
                tier, _, stat = k[len("cache."):].partition(".")
                if stat:
                    grp = cp.setdefault(tier, {})
                    grp[stat] = grp.get(stat, 0) + v
        if cp:
            out["cache_plane"] = cp
        # ICI-plane rollup (docs/ici-plane.md): worker "ici.*" heartbeat
        # counters (peer pulls, tcp fallbacks, hbm exports) + client
        # "client.ici.*" broadcast counters pushed via METRICS_REPORT +
        # the master's own replication.ici_* dispatch counters
        ici: dict = {}
        for counters in self._worker_counters.values():
            for k, v in counters.items():
                if k.startswith("ici."):
                    stat = k[len("ici."):]
                    ici[stat] = ici.get(stat, 0) + v
        pre_i = "client.ici."
        for k, v in self.metrics.counters.items():
            if k.startswith(pre_i):
                stat = k[len(pre_i):]
                ici[stat] = ici.get(stat, 0) + v
        for name, stat in (("replication.ici_hinted", "hinted"),
                           ("replication.ici_transfers", "transfers")):
            v = self.metrics.counters.get(name, 0)
            if v:
                ici[stat] = ici.get(stat, 0) + v
        if ici:
            out["ici_plane"] = ici
        return out

    def _tenant_stats(self, q):
        return self.qos.snapshot()

    def _raft_member_change(self, q):
        """cv raft add/remove (+ the auto-promote path when driven by
        hand): journal a single-server membership change. The mutate
        wrapper's commit barrier makes the ack mean 'config committed'."""
        if self.raft is None:
            from curvine_tpu.common import errors as cerr
            raise cerr.Unsupported("raft is not enabled on this master")
        return self.raft.propose_member_change(
            q.get("action", ""), q.get("node_id", 0), q.get("addr", ""))

    async def _raft_transfer(self, q):
        """cv raft transfer: drain to the target voter + TIMEOUT_NOW."""
        if self.raft is None:
            from curvine_tpu.common import errors as cerr
            raise cerr.Unsupported("raft is not enabled on this master")
        target = await self.raft.transfer_leadership(q.get("target"))
        return {"target": target}

    def _set_attr(self, q):
        opts = SetAttrOpts.from_wire(q.get("opts", {}))
        self.acl.check_set_attr(UserCtx.from_req(q), q["path"], opts)
        self.fs.set_attr(q["path"], opts)
        node = self.fs.tree.resolve(q["path"])
        if node is not None:
            self.ttl.index(node.id, node.mtime, node.storage_policy.ttl_ms)
        return {}

    def _symlink(self, q):
        self.acl.check(UserCtx.from_req(q), q["link"], W | X, on_parent=True)
        return {"status": self.fs.symlink(q["target"], q["link"]).to_wire()}

    def _link(self, q):
        ctx = UserCtx.from_req(q)
        self.acl.check(ctx, q["src"], 0)
        self.acl.check(ctx, q["dst"], W | X, on_parent=True)
        return {"status": self.fs.link(q["src"], q["dst"]).to_wire()}

    def _resize(self, q):
        self.acl.check(UserCtx.from_req(q), q["path"], W)
        self.fs.resize_file(q["path"], q["len"])
        return {}

    def _free(self, q):
        self.acl.check(UserCtx.from_req(q), q["path"], W)
        freed = self.fs.free(q["path"], q.get("recursive", False))
        self.quota.invalidate(q["path"])
        return {"freed": freed}

    def _list_options(self, q):
        """Filtered/paged listing. Parity: list_options in filesystem.rs —
        supports glob filtering, dirs-only/files-only, offset+limit."""
        import fnmatch
        statuses = self.fs.list_status(q["path"])
        pattern = q.get("pattern")
        if pattern:
            statuses = [s for s in statuses
                        if fnmatch.fnmatch(s.name, pattern)]
        if q.get("dirs_only"):
            statuses = [s for s in statuses if s.is_dir]
        if q.get("files_only"):
            statuses = [s for s in statuses if not s.is_dir]
        offset = q.get("offset", 0)
        limit = q.get("limit", 0)
        total = len(statuses)
        if limit:
            statuses = statuses[offset:offset + limit]
        elif offset:
            statuses = statuses[offset:]
        return {"statuses": [s.to_wire() for s in statuses], "total": total}

    def _get_lock(self, q):
        return {"locks": [l.to_wire()
                          for l in self.locks.get_lock(q["path"])]}

    def _set_lock(self, q):
        if q.get("release"):
            return {"released": self.locks.release(q["path"], q["owner"])}
        info = self.locks.set_lock(q["path"], q["owner"],
                                   kind=q.get("kind", "exclusive"),
                                   ttl_ms=q.get("ttl_ms", 60_000))
        return {"lock": info.to_wire()}

    def _list_lock(self, q):
        return {"locks": [l.to_wire() for l in self.locks.list_locks()]}

    def _assign_worker(self, q):
        """Pick a worker for a client (short-circuit target / load work).
        Parity: RpcCode::AssignWorker."""
        chosen = self.fs.policy.choose(
            self.fs.workers.live_workers(), 1,
            client_host=q.get("client_host", ""),
            exclude=set(q.get("exclude_workers", [])),
            ici_coords=q.get("ici_coords"))
        return {"worker": chosen[0].address.to_wire()}

    def _metrics_report(self, q):
        """Clients push counters (aggregated into master metrics) and
        their finished trace spans (ingested into the master's span
        store so trace assembly sees the client side of every request).
        Parity: RpcCode::MetricsReport."""
        for name, value in (q.get("counters") or {}).items():
            self.metrics.inc(f"client.{name}", value)
        spans = q.get("spans")
        if spans:
            self.tracer.ingest(spans)
        return {}

    def _get_spans(self, q):
        """One trace's spans from this master's store; with
        ``collect=True`` the request fans out to the workers too and
        returns the merged set (web /api/trace and `cv trace` use
        this)."""
        tid = str(q.get("trace_id", ""))
        if q.get("collect"):
            return self.collect_trace(tid)        # awaited by _h
        return {"spans": self.tracer.spans_for(tid)}

    async def collect_trace(self, trace_id: str) -> dict:
        """Merge this master's spans (incl. client-pushed ones) with
        every serving worker's over GET_SPANS; a slow/dead worker costs
        the collect timeout, never the assembly."""
        spans = list(self.tracer.spans_for(trace_id))
        timeout = self.conf.obs.trace_collect_timeout_ms / 1000.0
        payload = pack({"trace_id": trace_id})

        async def fetch(w):
            a = w.address
            conn = await self._obs_pool.get(
                f"{a.ip_addr or a.hostname}:{a.rpc_port}")
            rep = await conn.call(RpcCode.GET_SPANS, data=payload,
                                  timeout=timeout)
            return (unpack(rep.data) or {}).get("spans", [])

        workers = self.fs.workers.serving_workers()
        if workers:
            results = await asyncio.wait_for(
                asyncio.gather(*(fetch(w) for w in workers),
                               return_exceptions=True),
                timeout + 1.0)
            for r in results:
                if isinstance(r, list):
                    spans.extend(r)
                else:
                    log.debug("span collect from a worker failed: %s", r)
        return {"spans": spans}

    def _cluster_health(self, q):
        """Cluster-health rollup (monitor + watchdog snapshot).
        Parity: master_monitor.rs state + fs_dir_watchdog.rs sentinel."""
        return self.monitor.health()

    @staticmethod
    def _with_identity(q: dict, r: dict) -> dict:
        """Batch RPCs carry identity on the OUTER request; it must be
        stamped onto every inner one (and win over anything smuggled
        there) or ACL/lease checks would see the default superuser."""
        ident = {k: q[k] for k in ("user", "groups", "client_name",
                                   "client_id") if k in q}
        return {**r, **ident}

    def _create_files_batch(self, q):
        # identity fields and the caller ctx are batch-invariant: hoist
        # them out of the per-item loop (hot at namespace-bench rates)
        ident = {k: q[k] for k in ("user", "groups", "client_name",
                                   "client_id") if k in q}
        ctx = UserCtx.from_req(q)
        return {"responses": [self._create_file({**r, **ident}, ctx=ctx)
                              for r in q["requests"]]}

    _META_BATCH_OPS = None      # lazily bound: op name -> handler

    def _meta_batch(self, q):
        """Heterogeneous metadata batch (META_BATCH): mkdir/create/delete
        lists amortize per-op round trips into the same journal groups.
        Per-item domain errors come back as {"error", "error_code"} so one
        bad path doesn't fail its batch-mates."""
        from curvine_tpu.common import errors as err
        if self._META_BATCH_OPS is None:
            self._META_BATCH_OPS = {"mkdir": self._mkdir,
                                    "create": self._create_file,
                                    "delete": self._delete}
        out = []
        for r in q["requests"]:
            r = self._with_identity(q, r)
            fn = self._META_BATCH_OPS.get(r.get("op"))
            try:
                if fn is None:
                    raise err.InvalidArgument(
                        f"meta_batch: unknown op {r.get('op')!r}")
                out.append(fn(r))
            except err.CurvineError as e:
                out.append({"error": str(e), "error_code": int(e.code)})
        return {"responses": out}

    def _add_blocks_batch(self, q):
        return {"responses": [self._add_block(self._with_identity(q, r))
                              for r in q["requests"]]}

    def _complete_files_batch(self, q):
        return {"responses": [self._complete_file(self._with_identity(q, r))
                              for r in q["requests"]]}

    # --- worker plane ---
    def _worker_heartbeat(self, q):
        cmds = self.fs.worker_heartbeat(q["info"])
        self.metrics.gauge("workers.live", len(self.fs.workers.live_workers()))
        wid_hb = q["info"]["address"]["worker_id"]
        evac = q.get("evac_blocks")
        if evac:
            # blocks stranded on this worker's quarantined dirs: copy
            # them elsewhere, then retire the quarantined replica. The
            # worker repeats the (bounded) set every beat until it
            # drains, so nothing here needs to be persisted.
            self.replication.enqueue_evacuation(
                wid_hb, [int(b) for b in evac])
        unhealthy = sum(1 for s in (q["info"].get("storages") or [])
                        if s.get("health", "healthy") != "healthy")
        if unhealthy or wid_hb in self._dirs_unhealthy:
            self._dirs_unhealthy[wid_hb] = unhealthy
            self.metrics.gauge("dirs.unhealthy",
                               sum(self._dirs_unhealthy.values()))
        # ICI plane: bounded snapshot of the worker's HBM export table —
        # soft state for the replication manager's device-path hints,
        # refreshed (or cleared) every beat like evac_blocks
        self.replication.note_hbm_blocks(
            wid_hb, [int(b) for b in q.get("hbm_blocks") or []])
        wm = q.get("metrics")
        if wm:
            # aggregate worker-plane byte counters (dashboard throughput);
            # lost/decommissioned workers are pruned so their final
            # snapshots don't inflate the gauges forever
            wid = q["info"]["address"]["worker_id"]
            self._worker_counters[wid] = wm
            self._prune_worker_counters()
        return cmds

    def _worker_block_report(self, q):
        return self.fs.worker_block_report(
            q["worker_id"], q.get("blocks", {}), q.get("storage_types", {}),
            incremental=q.get("incremental", False),
            removed=q.get("removed"))

    def _replacement_worker(self, q):
        w = self.replication.replacement_worker(
            q["block_id"], set(q.get("exclude_workers", [])))
        return {"worker": w.address.to_wire()}

    def _decommission_worker(self, q):
        """cv node decommission/recommission: journaled intent, so it
        survives restarts and failovers. Admin (superuser) only."""
        ctx = UserCtx.from_req(q)
        if self.acl.enabled and not self.acl._is_super(ctx):
            from curvine_tpu.common import errors as cerr
            raise cerr.PermissionDenied(
                f"user={ctx.user}: decommission is superuser-only")
        self.fs.decommission_worker(q["worker_id"],
                                    on=q.get("on", True))
        w = self.fs.workers.workers.get(q["worker_id"])
        return {"state": int(w.state) if w is not None else -1}

    def _report_under_replicated(self, q):
        if not self._is_leader():
            # reject so the worker rotates to the leader instead of the
            # report being silently dropped by the gated repair queue
            from curvine_tpu.common import errors as cerr
            raise cerr.NotLeader("repair reports go to the leader")
        # a corrupt replica is FLAGGED, never summarily deleted: it
        # stops counting toward the live replica total (forcing
        # re-replication) but stays on disk as a verified last-resort
        # source until the block is back at desired strength — only then
        # does the replication manager retire the location and order the
        # physical delete. Dropping it any earlier turns possible
        # bit-rot into certain data loss if the remaining holder dies
        # mid-heal (or the mismatch was a transient read fault).
        wid = q.get("worker_id")
        bids = q.get("block_ids", [])
        # scrub verdicts (BlockStore.verify_detail): "mismatch" = bit-rot
        # (an EC cell is re-encoded from survivors), "truncated" = short
        # write (re-pull the full copy). Recorded before enqueue so the
        # dispatcher classifies with the verdict in hand.
        verdicts = q.get("verdicts")
        if verdicts:
            self.replication.note_verdicts(
                {int(k): v for k, v in verdicts.items()})
        if wid is not None:
            self.replication.enqueue_evacuation(wid, bids)
        else:
            # clients report the lost cell behind a degraded EC read
            # this way (no worker attribution — the holder is gone)
            ec_cells = getattr(self.fs, "ec_cells", {})
            lost = sum(1 for b in bids if b in ec_cells)
            if lost:
                self.metrics.inc("ec.degraded_reads", lost)
            self.replication.enqueue(bids)
        out = {"success": True}
        # degraded-commit liveness check: a writer about to commit on a
        # reduced replica set asks which survivors this master still
        # considers LIVE — a worker that died between its finish ack and
        # the commit must count as lost, not as the block's sole copy
        confirm = q.get("confirm_live")
        if confirm is not None:
            live = {w.address.worker_id for w in self.fs.workers.live_workers()}
            out["live"] = [w for w in confirm if w in live]
        return out

    def _replication_result(self, q):
        self.replication.on_result(q["block_id"], q["worker_id"],
                                   q.get("success", False),
                                   q.get("message", ""),
                                   via=q.get("via", ""))
        return {}

    def _ec_commit_stripe(self, q):
        """EC_COMMIT_STRIPE: a converting (or reconstructing) worker
        finished writing cells. Journals the stripe map (first commit)
        and registers the runtime cell locations; the replicated copies
        retire copy-first-delete-last via heartbeat pending_deletes."""
        cells = [[int(c["block_id"]), int(c["worker_id"]),
                  int(c.get("storage_type", 1))] for c in q.get("cells", [])]
        self.fs.ec_commit(q["block_id"], cells)
        self.metrics.inc("ec.stripes_committed")
        return {"success": True}

    # --- mounts ---
    def _mount(self, q):
        info = self.mounts.mount(q["cv_path"], q["ufs_path"],
                                 properties=q.get("properties"),
                                 auto_cache=q.get("auto_cache", False),
                                 write_type=q.get("write_type", 0),
                                 ttl_ms=q.get("ttl_ms", 0),
                                 ttl_action=q.get("ttl_action", 0),
                                 storage_type=q.get("storage_type", ""),
                                 block_size=q.get("block_size", 0),
                                 replicas=q.get("replicas", 0),
                                 access_mode=q.get("access_mode", "rw"))
        return {"mount": info.to_wire()}

    def _umount(self, q):
        self.mounts.umount(q["cv_path"])
        return {}

    def _update_mount(self, q):
        info = self.mounts.update(q["cv_path"], properties=q.get("properties"),
                                  auto_cache=q.get("auto_cache"),
                                  ttl_ms=q.get("ttl_ms"),
                                  ttl_action=q.get("ttl_action"),
                                  access_mode=q.get("access_mode"))
        return {"mount": info.to_wire()}

    def _mount_table(self, q):
        return {"mounts": [m.to_wire() for m in self.mounts.table()]}

    def _mount_info(self, q):
        m = self.mounts.get_mount(q["path"])
        return {"mount": m.to_wire() if m else None}

    # --- jobs ---
    def _submit_job(self, q):
        kind = q.get("kind", "load")
        if q.get("if_absent") and kind == "load":
            job_id, outcome = self.jobs.submit_load_if_absent(
                q["path"], replicas=q.get("replicas", 1))
        else:
            job_id, outcome = self.jobs.submit(
                kind, q["path"], recursive=q.get("recursive", True),
                replicas=q.get("replicas", 1)).job_id, "submitted"
        self.metrics.gauge("jobs.load.live", self.jobs.live_loads())
        return {"job_id": job_id, "outcome": outcome}

    def _prefetch_window(self, q):
        """Epoch-aware prefetch advise (docs/caching.md): the client
        names its read cursor in the deterministic epoch order; the
        job manager keeps a rolling window of upcoming shards warm."""
        job = self.jobs.advise_prefetch(
            q["path"], cursor=int(q.get("cursor", 0)),
            window=int(q.get("window", 8)), epoch=int(q.get("epoch", 0)),
            seed=int(q.get("seed", 0)))
        return {"job_id": job.job_id, "state": int(job.state),
                "cursor": job.cursor, "window": job.window,
                "planned": getattr(job, "_next", 0),
                "total": job.total_files}

    def _job_status(self, q):
        return {"job": self.jobs.status(q["job_id"]).to_wire()}

    def _cancel_job(self, q):
        self.jobs.cancel(q["job_id"])
        return {}

    def _report_task(self, q):
        self.jobs.report_task(q["task"])
        self.metrics.gauge("jobs.load.live", self.jobs.live_loads())
        return {}
