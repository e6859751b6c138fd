"""Worker server: block read/write handlers, heartbeat, tasks, replication.

Parity: curvine-server/src/worker/ (worker_server.rs, handler/read_handler,
handler/write_handler, block/heartbeat_task, task/load_task_runner,
replication/worker_replication_handler)."""

from __future__ import annotations

import asyncio
import logging
import os
import re
import time
import zlib

from curvine_tpu.common import checksum
from curvine_tpu.common import errors as err
from curvine_tpu.common.conf import ClusterConf
from curvine_tpu.common.metrics import MetricsRegistry
from curvine_tpu.common.types import (
    BlockState, JobState, StorageType, TaskInfo, WorkerAddress, WorkerInfo,
    now_ms,
)
from curvine_tpu.obs.trace import Tracer
from curvine_tpu.rpc import Message, RpcCode, RpcServer, ServerConn
from curvine_tpu.rpc.client import Connection, ConnectionPool
from curvine_tpu.rpc.frame import Flags, pack, response_for, unpack
from curvine_tpu.worker.storage import BdevTier, BlockStore, TierDir

log = logging.getLogger(__name__)

_TIER_NAMES = {"hbm": StorageType.HBM, "mem": StorageType.MEM,
               "ssd": StorageType.SSD, "hdd": StorageType.HDD}


def _tenant_of(msg) -> str:
    """Writer's tenant id off the RPC header (qos front-door rail) —
    stamped onto the block for the tier-0 cache partitions; "" for
    cluster-internal traffic that carries no tenant."""
    from curvine_tpu.common.qos import TENANT_KEY
    try:
        return str(msg.header.get(TENANT_KEY) or "")
    except AttributeError:
        return ""


def worker_id_for(hostname: str, port: int) -> int:
    return zlib.crc32(f"{hostname}:{port}".encode()) & 0x7FFFFFFF


def _open_block_writer(info):
    """File layout: fresh per-block file. Bdev layout: seek to the
    block's extent inside the shared backing file (NEVER truncate it)."""
    if getattr(info, "is_extent", False):
        f = open(info.path, "r+b")
        f.seek(info.offset)
        return f
    return open(info.path, "wb")


def _read_back(info, length: int) -> bytes:
    """Re-read a just-written block file (cross-algo checksum check on
    the replication pull path — rare: only when the source committed
    with an algo this worker doesn't stream)."""
    with open(info.path, "rb") as f:
        if getattr(info, "is_extent", False):
            f.seek(info.offset)
        return f.read(length)


def _write_block_bytes(info, data: bytes, hook=None) -> None:
    if hook is not None:
        hook.check_write(info.path)
        data = data[:hook.torn_write_len(info.path, len(data))]
    with _open_block_writer(info) as f:
        f.write(data)


_HEALTH_LEVEL = {"healthy": 0, "suspect": 1, "quarantined": 2}


def _metric_key(dir_id: str) -> str:
    """dir ids carry ':' and '/' — flatten to a metric-safe suffix."""
    return re.sub(r"[^0-9A-Za-z_.]+", "_", dir_id).strip("_")


def _integrity_header(info) -> dict:
    """Commit-time checksum riding every READ_BLOCK EOF frame (pure
    metadata — no extra IO): clients verify full-block reads against it
    end to end, catching media rot the wire checksums can't see."""
    if info.crc32c is None:
        return {}
    return {"block_crc32": info.crc32c, "block_crc_algo": info.crc_algo}


def _write_file_bytes(path: str, data: bytes) -> None:
    with open(path, "wb") as f:
        f.write(data)


class WorkerServer:
    def __init__(self, conf: ClusterConf | None = None,
                 worker_id: int | None = None):
        self.conf = conf or ClusterConf()
        wc = self.conf.worker
        self.rpc = RpcServer(wc.hostname, wc.rpc_port, "worker",
                             rpc_conf=self.conf.rpc)
        tiers = [
            (BdevTier if getattr(t, "layout", "file") == "bdev" else TierDir)(
                _TIER_NAMES.get(t.storage_type, StorageType.MEM),
                t.dir, t.capacity)
            for t in wc.tiers]
        # direct-IO data plane: SSD/HDD tiers read O_DIRECT through one
        # shared submission ring (worker/io_engine.py); MEM tiers stay
        # on the page cache by design — that IS their storage medium
        from curvine_tpu.worker.io_engine import create_engine
        self.io_engine = None
        if any(t.storage_type >= StorageType.SSD for t in tiers):
            self.io_engine = create_engine(wc)
        if self.io_engine is not None:
            for tier, tc in zip(tiers, wc.tiers):
                if tier.storage_type >= StorageType.SSD:
                    tier.io_engine = self.io_engine
                    tier.io_queue_depth = (getattr(tc, "queue_depth", 0)
                                           or self.io_engine.queue_depth)
        for tier in tiers:
            if isinstance(tier, BdevTier):
                # the extent-reuse safety window must cover the slowest
                # reply a client would still honor (lease clocks start
                # at reply arrival) — keep it tied to the configured
                # RPC deadline, never below the class default
                tier.lease_slack_s = max(
                    tier.lease_slack_s,
                    self.conf.client.rpc_timeout_ms / 1000.0)
        self.store = BlockStore(tiers, wc.eviction_high_water,
                                wc.eviction_low_water,
                                admission=wc.cache_admission,
                                ghost_entries=wc.cache_ghost_entries,
                                small_ratio=wc.cache_small_ratio)
        self.metrics = MetricsRegistry("worker")
        # shared-memory read plane (worker/shm.py): sealed-memfd export
        # cache + SCM_RIGHTS side channel for co-located clients. The
        # channel itself starts in start() (port must be final); deleted
        # blocks drop their export so a stale copy is never handed out.
        from curvine_tpu.worker.shm import (EXPORT_CAP_BYTES, ShmExporter,
                                            WarmShmCache, shm_supported)
        self.shm = None
        self.shm_warm = None
        self._shm_channel = None
        if wc.shm_reads and shm_supported():
            # bounded in bytes: a block is copied once and kept while
            # resident, so the bound is what the exports may cost in
            # host memory — never more than the MEM tiers could hold
            mem_cap = sum(t.capacity for t in tiers
                          if t.storage_type == StorageType.MEM
                          and not isinstance(t, BdevTier))
            self.shm = ShmExporter(
                cap_bytes=min(EXPORT_CAP_BYTES, mem_cap),
                metrics=self.metrics)
            if wc.shm_warm_cap_mb > 0:
                # warm-cache exports for the tiers below MEM: read-hot
                # SSD/HDD blocks earn a byte-bounded sealed-memfd copy,
                # admitted through the same policy family as the MEM
                # tier so scans can't flush the warm working set
                self.shm_warm = WarmShmCache(
                    cap_bytes=wc.shm_warm_cap_mb * 1024 * 1024,
                    admission=wc.cache_admission,
                    ghost_entries=wc.cache_ghost_entries)
            # deleted blocks drop both export flavors; a tier move
            # (promote/demote) does too — the copy's bytes would stay
            # correct (blocks are immutable) but the block no longer
            # belongs to the tier whose policy admitted it
            self.store.on_delete = self._shm_invalidate
            self.store.on_move = self._shm_invalidate
        # per-dir DiskHealth thresholds from conf (the state machine
        # itself lives on each TierDir — worker/storage.py)
        for tier in self.store.tiers:
            tier.health.error_threshold = max(1, wc.disk_error_threshold)
            tier.health.decay_s = wc.disk_error_decay_s
            tier.health.probe_failures = max(1, wc.disk_probe_failures)
            tier.health.probe_successes = max(1, wc.disk_probe_successes)
        # observability plane: server spans per dispatch + per-code
        # rpc.<name> histograms; the io engine reports submit→complete
        # latency into the same registry
        self.tracer = Tracer.from_conf("worker", self.conf.obs,
                                       metrics=self.metrics)
        self.rpc.obs = self.tracer
        self.rpc.metrics = self.metrics
        # multi-tenant admission control on the data plane too: the
        # tenant id stamped at the front door rides every hop, so a
        # quota set once throttles READ_BLOCK/WRITE_BLOCK here the same
        # way it throttles metadata ops on the master
        from curvine_tpu.common.qos import AdmissionController
        self.qos = AdmissionController.from_conf(
            self.conf.qos, slow_op_ms=self.conf.obs.slow_op_ms,
            metrics=self.metrics)
        self.rpc.qos = self.qos
        # per-job cache partitions (docs/caching.md): eviction prefers
        # blocks of tenants over their tier-0 byte quota (from the same
        # "name:qps[:prio[:inflight[:tier0_mb]]]" tenant specs)
        self.store.tier0_quota = self.qos.tier0_quota
        if self.io_engine is not None:
            self.io_engine.metrics = self.metrics
        self.master_pool = ConnectionPool(size=2, rpc_conf=self.conf.rpc)
        self.peer_pool = ConnectionPool(size=2, rpc_conf=self.conf.rpc)
        self.worker_id = worker_id if worker_id is not None else 0
        self.chunk_size = wc.io_chunk_size
        # HBM tier-0: device-resident block cache. Building it claims
        # every local chip for THIS process (one process per chip), so
        # hbm_capacity > 0 belongs to a worker embedded in the process
        # that runs the JAX consumer; a standalone `cv worker` keeps 0.
        # A worker told to hold a tier that cannot come up does not
        # start — serving without it would hide the missing device.
        self.hbm = None
        if wc.hbm_capacity > 0:
            from curvine_tpu.tpu.compile_cache import enable_compile_cache
            from curvine_tpu.tpu.hbm import MultiHbmTier
            enable_compile_cache()
            # one tier per local chip (a TPU host drives 4-8): per-chip
            # capacity accounting, least-used placement, replica spread
            self.hbm = MultiHbmTier(wc.hbm_capacity,
                                    admission=wc.cache_admission,
                                    ghost_entries=wc.cache_ghost_entries,
                                    export_cap=wc.hbm_export_cap)
        self._bg: list[asyncio.Task] = []
        from curvine_tpu.common.executor import ScheduledExecutor
        self.executor = ScheduledExecutor("worker")
        self._task_sem = asyncio.Semaphore(wc.task_parallelism)
        self._task_client = None          # _task_client_get
        self._load_tasks: set = set()     # _submit_task
        self._evict_reports: set = set()  # _evict_once
        self._leader_idx = 0
        # heartbeat failure dedup/backoff state
        self._hb_fails = 0
        self._hb_backoff_until = 0.0
        # rate limit for master-requested full block reports (report_now)
        self._forced_report_at = 0.0
        # decommission drain (heartbeat-driven): refuse NEW write streams
        # with a retryable error so clients re-place elsewhere; streams
        # already open keep flowing until they finish
        self.draining = False
        self._register_handlers()

    @property
    def address(self) -> WorkerAddress:
        return WorkerAddress(
            worker_id=self.worker_id, hostname=self.conf.worker.hostname,
            ip_addr=self.conf.worker.hostname, rpc_port=self.rpc.port,
            web_port=self.conf.worker.web_port)

    @property
    def addr(self) -> str:
        return self.rpc.addr

    async def start(self) -> None:
        await self.rpc.start()
        if not self.worker_id:
            self.worker_id = worker_id_for(self.conf.worker.hostname,
                                           self.rpc.port)
        # join the ICI device domain (docs/ici-plane.md): peers sharing
        # this process's device runtime can then pull our HBM-resident
        # blocks device-to-device instead of over the TCP rail
        if self.hbm is not None and self.conf.worker.ici_transfer:
            from curvine_tpu.tpu import ici_plane
            ici_plane.register_endpoint(self.worker_id, self.hbm,
                                        self.conf.worker.ici_coords)
        # periodic duties ride the scheduled executor
        # (parity: curvine-common/src/executor/ ScheduledExecutor)
        wc = self.conf.worker
        self.executor.submit_periodic("heartbeat", self.heartbeat_once,
                                      wc.heartbeat_ms / 1000,
                                      initial_delay_s=0.0)
        # first full report right after the first heartbeat registers us:
        # the master's drain/replication logic distrusts its view of this
        # worker's holdings until one arrives
        self.executor.submit_periodic("block-report", self.block_report_once,
                                      wc.block_report_interval_ms / 1000,
                                      initial_delay_s=1.0)
        self.executor.submit_periodic("eviction", self._evict_once, 1.0)
        self.executor.submit_periodic("scrub", self._scrub_once,
                                      max(0.1, wc.scrub_interval_s))
        self.executor.submit_periodic("disk-probe", self._disk_probe_once,
                                      max(0.05, wc.disk_probe_interval_s))
        # host tiers to promote between, OR an HBM tier-0 to auto-pin
        # into — either gives the promote cycle work to do
        if wc.promote_interval_ms > 0 and (len(self.store.tiers) > 1
                                           or self.hbm is not None):
            self.executor.submit_periodic("promote", self._promote_once,
                                          wc.promote_interval_ms / 1000)
        if self.shm is not None:
            from curvine_tpu.worker.shm import ShmChannel, channel_path
            ch = ShmChannel(channel_path(self.rpc.port), self._shm_grant)
            try:
                ch.start()
                self._shm_channel = ch
            except OSError as e:
                # no unix sockets here (exotic sandbox): clients simply
                # never see the shm capability flags — clean fallback
                log.warning("shm side channel disabled: %s", e)
                self.shm = None
        log.info("worker %d started at %s", self.worker_id, self.addr)

    async def stop(self) -> None:
        if self.hbm is not None:
            from curvine_tpu.tpu import ici_plane
            ici_plane.unregister_endpoint(self.worker_id)
        await self.executor.stop()
        for t in self._bg:
            t.cancel()
        self._bg.clear()
        for t in [*self._load_tasks, *self._evict_reports]:
            t.cancel()
        if self._task_client is not None:
            await self._task_client.close()
            self._task_client = None
        if self._shm_channel is not None:
            await asyncio.to_thread(self._shm_channel.stop)
            self._shm_channel = None
        if self.shm is not None:
            self.shm.close()
        if self.shm_warm is not None:
            self.shm_warm.close()
        await self.rpc.stop()
        await self.master_pool.close()
        await self.peer_pool.close()
        if self.io_engine is not None:
            await asyncio.to_thread(self.io_engine.shutdown)
            self.io_engine = None

    # ---------------- master plane ----------------

    async def _master_conn(self) -> Connection:
        """Connection to the current LEADER (rotates on failure —
        `_leader_call` handles NOT_LEADER rotation for actual calls)."""
        addrs = self.conf.client.master_addrs
        return await self.master_pool.get(addrs[self._leader_idx
                                                % len(addrs)])

    async def _leader_call(self, code, data):
        """Call the leader, rotating through master_addrs on NOT_LEADER
        or connect failure (workers were previously pinned to addrs[0],
        which breaks every worker→master report in an HA cluster whose
        leader isn't the first address)."""
        addrs = self.conf.client.master_addrs
        last: Exception | None = None
        for i in range(len(addrs)):
            idx = (self._leader_idx + i) % len(addrs)
            try:
                conn = await self.master_pool.get(addrs[idx])
                rep = await conn.call(code, data=data)
                self._leader_idx = idx
                return rep
            except err.CurvineError as e:
                if e.code not in (err.ErrorCode.NOT_LEADER,
                                  err.ErrorCode.CONNECT):
                    raise
                last = e
        raise last or err.NotLeader("no reachable master")

    async def _bounded_master_call(self, addr: str, code, payload: bytes,
                                   connect_s: float, call_s: float):
        """Deadline covers BOTH the dial and the RPC. A call that times
        out may have cancelled a send mid-frame, so that connection is
        poisoned — close it so the pool never reuses it."""
        conn = await asyncio.wait_for(self.master_pool.get(addr), connect_s)
        try:
            return await asyncio.wait_for(conn.call(code, data=payload),
                                          call_s)
        except (asyncio.TimeoutError, asyncio.CancelledError):
            await conn.close()
            raise

    def _info(self) -> WorkerInfo:
        storages = self.store.storages()
        if self.hbm is not None:
            from curvine_tpu.common.types import StorageInfo
            if hasattr(self.hbm, "per_device_stats"):
                # one HBM StorageInfo PER CHIP: the master sees per-device
                # capacity, not a single opaque pool
                for s in reversed(self.hbm.per_device_stats()):
                    storages.insert(0, StorageInfo(
                        storage_type=StorageType.HBM,
                        dir_id=f"hbm:{s['device_id']}",
                        capacity=s["capacity"],
                        available=s["capacity"] - s["used"],
                        block_num=s["blocks"]))
            else:                              # single-device tier
                storages.insert(0, StorageInfo(
                    storage_type=StorageType.HBM, dir_id="hbm:0",
                    capacity=self.hbm.capacity,
                    available=self.hbm.capacity - self.hbm.used,
                    block_num=len(self.hbm._blocks)))
        return WorkerInfo(address=self.address, storages=storages,
                          last_heartbeat_ms=now_ms(),
                          ici_coords=list(self.conf.worker.ici_coords))

    def _cache_metrics(self) -> dict[str, float]:
        """Flattened cache.<tier>.<stat> counters: per-storage-type
        admission policy stats (summed over dirs), the HBM tier, and
        per-tenant tier-0 occupancy as cache.tier0.<tenant>."""
        out: dict[str, float] = {}
        for t in self.store.tiers:
            pre = f"cache.{t.storage_type.name.lower()}."
            for k, v in t.policy.stats().items():
                if k in ("small", "main", "ghost"):
                    continue
                out[pre + k] = out.get(pre + k, 0) + v
        out["cache.store.misses"] = self.store.miss_total
        if self.hbm is not None:
            st = self.hbm.stats()
            for k in ("hits", "misses", "spills", "ghost_hits",
                      "scan_evicted"):
                out[f"cache.hbm.{k}"] = st.get(k, 0)
            # ICI-plane counters (docs/ici-plane.md): advertisement
            # volume + the device-path vs TCP-fallback split on pulls
            out["ici.hbm_exports"] = st.get("exports", 0)
            for k in ("ici.peer_pulls", "ici.tcp_fallbacks"):
                out[k] = self.metrics.counters.get(k, 0)
        for tenant, used in self.store.tenant_occupancy().items():
            out[f"cache.tier0.{tenant}"] = used
        if self.shm_warm is not None:
            # warm-cache shm plane (docs/data-plane.md): occupancy and
            # admission outcomes beside the tier caches they shadow
            for k, v in self.shm_warm.stats().items():
                if k in ("entries", "bytes", "exports", "hits",
                         "evictions"):
                    out[f"cache.shm_warm.{k}"] = v
                elif k in ("policy_admits", "policy_ghost_hits",
                           "policy_scan_evicted"):
                    out[f"cache.shm_warm.{k[len('policy_'):]}"] = v
        # registered receive pool: pool-resident bytes only (caller-
        # pinned views are NOT occupancy); gauges land on /metrics via
        # the heartbeat
        from curvine_tpu.rpc import transport
        for k, v in transport.recv_pool().stats().items():
            out[f"rpc.recv_{k}"] = v
        return out

    async def heartbeat_once(self) -> None:
        """Heartbeat EVERY master: followers serve reads and need live
        worker state + replica locations too (runtime locs never ride the
        journal). Delete commands from any master are idempotent.

        An unreachable cluster (shutdown ordering, master restart, net
        partition) must not traceback-spam every tick: one deduped
        warning, then exponential backoff — the tick returns immediately
        until the backoff lapses, and recovery logs once."""
        if time.monotonic() < self._hb_backoff_until:
            return
        if self.hbm is not None:
            from curvine_tpu.tpu.hbm import export_metrics
            export_metrics(self.hbm, self.metrics)
        wm = {
            "bytes.read": self.metrics.counters.get("bytes.read", 0),
            "bytes.written": self.metrics.counters.get("bytes.written", 0),
        }
        # cache-intelligence counters (docs/caching.md): flattened
        # per-tier admission stats + per-tenant tier-0 occupancy; the
        # master folds them into the `cv report` Cache plane rollup and
        # they double as local /metrics gauges
        cm = self._cache_metrics()
        wm.update(cm)
        for name, v in cm.items():
            self.metrics.gauge(name, v)
        body = {"info": self._info().to_wire(), "metrics": wm}
        # quarantined dirs: advertise (a bounded batch of) their resident
        # committed blocks so the master drives evacuation through the
        # replication manager — re-sent every beat until evacuated, so a
        # master restart mid-storm loses nothing; the cap keeps a fault
        # storm from flooding the replication queue
        evac = self.store.quarantined_blocks(
            limit=self.conf.worker.disk_evac_batch)
        if evac:
            body["evac_blocks"] = evac
            body["worker_id"] = self.worker_id
        # peer-addressable HBM advertisement (docs/ici-plane.md): a
        # bounded most-recent snapshot of the export table, re-sent (or
        # cleared) every beat — the master keeps it as soft state for
        # device-path pull hints, nothing journaled
        exports = getattr(self.hbm, "exports", None)
        if exports is not None and self.conf.worker.ici_transfer:
            body["hbm_blocks"] = [
                e["block_id"] for e in exports.snapshot(
                    limit=self.conf.worker.hbm_advertise_max)]
        payload = pack(body)
        deletes: set[int] = set()
        report_now = False
        draining = False

        async def beat(addr: str) -> bool:
            nonlocal report_now, draining
            try:
                rep = await self._bounded_master_call(
                    addr, RpcCode.WORKER_HEARTBEAT, payload,
                    connect_s=3.0, call_s=5.0)
                body = unpack(rep.data) or {}
                for bid in body.get("delete_blocks", []):
                    deletes.add(bid)
                if body.get("report_now"):
                    report_now = True
                if body.get("draining"):
                    draining = True
                return True
            except Exception as e:  # noqa: BLE001 — peer down is routine
                log.debug("heartbeat to %s failed: %s", addr, e)
                return False

        # CONCURRENT fan-out: one dead/unroutable master must not stall
        # the beat to the others
        oks = await asyncio.gather(*(beat(a)
                                     for a in self.conf.client.master_addrs))
        if not any(oks):
            self._hb_fails += 1
            base = self.conf.worker.heartbeat_ms / 1000.0
            delay = min(base * (2 ** min(self._hb_fails, 6)), 60.0)
            self._hb_backoff_until = time.monotonic() + delay
            if self._hb_fails == 1:
                log.warning(
                    "no master reachable for heartbeat (%s); backing off "
                    "exponentially up to 60s, further failures logged at "
                    "debug", ", ".join(self.conf.client.master_addrs))
            else:
                log.debug("heartbeat still failing (%d consecutive); "
                          "next attempt in %.1fs", self._hb_fails, delay)
            return
        if self._hb_fails:
            log.info("master reachable again after %d failed heartbeats",
                     self._hb_fails)
        self._hb_fails = 0
        self._hb_backoff_until = 0.0
        if draining != self.draining:
            # master state is authoritative either way: recommission
            # clears the refusal just like decommission sets it
            log.info("worker %d %s new write streams (decommission drain)",
                     self.worker_id, "refusing" if draining else "accepting")
            self.draining = draining
        for bid in deletes:
            self.store.delete(bid)
            if self.hbm is not None:
                self.hbm.drop(bid)
        if report_now and time.monotonic() - self._forced_report_at >= 1.0:
            # a master lost track of our holdings (it restarted, or we
            # returned from LOST): push a full report immediately instead
            # of leaving our blocks location-less until the periodic one.
            # In the BACKGROUND — a slow report awaited here would starve
            # the heartbeat tick and get us marked LOST all over again.
            self._forced_report_at = time.monotonic()
            self._bg = [t for t in self._bg if not t.done()]
            self._bg.append(asyncio.ensure_future(self.block_report_once()))

    async def block_report_once(self) -> None:
        held, types = self.store.report()
        payload = pack({"worker_id": self.worker_id, "blocks": held,
                        "storage_types": types})
        deletes: set[int] = set()

        async def report(addr: str) -> None:
            try:
                rep = await self._bounded_master_call(
                    addr, RpcCode.WORKER_BLOCK_REPORT, payload,
                    connect_s=5.0, call_s=30.0)
                for bid in (unpack(rep.data) or {}).get("delete_blocks", []):
                    deletes.add(bid)
            except Exception as e:  # noqa: BLE001
                log.debug("block report to %s failed: %s", addr, e)

        await asyncio.gather(*(report(a)
                               for a in self.conf.client.master_addrs))
        for bid in deletes:
            self.store.delete(bid)
            if self.hbm is not None:
                self.hbm.drop(bid)

    async def _evict_once(self) -> None:
        demoted0 = self.store.demoted_total
        await asyncio.to_thread(self.store.maybe_evict)
        # every block that LEFT the cache since the last tick, by this
        # trim or by a create that needed room; demotions moved tiers
        # without losing data and get their own counter
        dropped = self.store.take_dropped()
        if self.store.demoted_total > demoted0:
            self.metrics.inc("blocks.demoted",
                             self.store.demoted_total - demoted0)
        if not dropped:
            return
        self.metrics.inc("blocks.evicted", len(dropped))
        if self.hbm is not None:
            for bid in dropped:
                # capacity pressure, not deletion: ghost the device
                # copy so a re-broadcast of this (still-hot) block
                # re-admits straight to the policy's main queue
                self.hbm.drop(bid, evicted=True)
        # tell the masters now: until they know, they hand clients the
        # locations of blocks that are gone, and the full report that
        # would correct them is block_report_interval_ms away
        payload = pack({"worker_id": self.worker_id, "blocks": {},
                        "storage_types": {}, "incremental": True,
                        "removed": dropped})

        async def report(addr: str) -> None:
            try:
                await self._bounded_master_call(
                    addr, RpcCode.WORKER_BLOCK_REPORT, payload,
                    connect_s=3.0, call_s=5.0)
            except Exception as e:  # noqa: BLE001 — the full report heals
                log.debug("eviction report to %s failed: %s", addr, e)

        # not awaited: a master that is down must not stall the trim
        for addr in self.conf.client.master_addrs:
            t = asyncio.ensure_future(report(addr))
            self._evict_reports.add(t)
            t.add_done_callback(self._evict_reports.discard)

    async def _promote_once(self) -> None:
        """Hot-data promotion scan; tier changes reach the master on the
        next block report (storage types reconcile there). With an HBM
        tier enabled, the hottest blocks additionally auto-pin into
        device memory (tier-0 promotion — heat snapshot taken BEFORE the
        host scan halves it)."""
        wc = self.conf.worker
        hbm_hot: list[tuple[int, int, int]] = []
        if self.hbm is not None:
            # per-chip share bounds what can EVER pin; snapshot before
            # the host scan halves the heat counters
            per_chip = min(t.capacity for t in self.hbm.tiers.values()) \
                if hasattr(self.hbm, "tiers") else self.hbm.capacity
            hbm_hot = [t for t in self.store.hot_blocks(
                           wc.promote_min_reads, max_len=per_chip)
                       if t[0] not in self.hbm]
        promoted = await asyncio.to_thread(
            self.store.promote_scan, wc.promote_min_reads)
        if promoted:
            self.metrics.inc("blocks.promoted", len(promoted))
        pinned = 0
        budget = 256 << 20            # bound device transfers per cycle
        for bid, _heat, blen in hbm_hot:
            if budget <= 0:
                break
            try:
                n = await self._autopin_block(bid)
            except (err.CurvineError, OSError, ValueError) as e:
                # deleted/evicted since the snapshot, or the chip can't
                # take it: skip this block, keep pinning colder ones
                log.debug("hbm autopin of %d skipped: %s", bid, e)
                continue
            if n:
                budget -= n
                pinned += 1
        if pinned:
            self.metrics.inc("blocks.hbm_pinned", pinned)
            self.metrics.gauge("hbm.used", self.hbm.used)

    async def _autopin_block(self, block_id: int) -> int:
        """Read a committed block and pin it on the least-used local chip
        (the HBM tier's own LRU makes room). The read+put runs in a
        worker thread — up to 256MB of IO per cycle must not stall the
        event loop. Returns bytes pinned."""
        import numpy as np
        # pinned for the whole read+put: a bdev extent can't be freed
        # and reallocated under the preadv (would pin foreign bytes)
        info = self.store.pin_read(block_id, touch=False)
        try:
            if info.state != BlockState.COMMITTED:
                return 0

            def work() -> int:
                buf = np.empty(info.len, dtype=np.uint8)
                fd = os.open(info.path, os.O_RDONLY)
                try:
                    os.preadv(fd, [memoryview(buf)], info.offset)
                finally:
                    os.close(fd)
                if info.crc32c is not None \
                        and checksum.supported(info.crc_algo):
                    # verify the media copy BEFORE promotion — a bad
                    # replica must never become the hottest copy
                    if checksum.crc_update(info.crc_algo,
                                           buf.data) != info.crc32c:
                        raise err.AbnormalData(
                            f"block {block_id} failed promotion verify")
                from curvine_tpu.tpu import pallas_ops
                arr = self.hbm.put(block_id, buf)
                if (pallas_ops.block_checksum(arr)
                        != pallas_ops.block_checksum_host(buf)):
                    raise err.AbnormalData(
                        f"block {block_id} device copy diverges")
                return info.len

            try:
                n = await asyncio.to_thread(work)
            except err.AbnormalData:
                # on-disk copy (or the device transfer) is bad: drop the
                # pin, count it, and hand the replica to the heal path
                self.hbm.drop(block_id)
                self.metrics.inc("blocks.corrupt")
                try:
                    await self._leader_call(
                        RpcCode.REPORT_UNDER_REPLICATED_BLOCKS,
                        pack({"block_ids": [block_id],
                              "worker_id": self.worker_id}))
                except Exception as e:  # noqa: BLE001 — scrub retries
                    log.warning("promotion corrupt report failed: %s", e)
                return 0
        finally:
            self.store.unpin_read(block_id)
        if not self.store.contains(block_id):
            # deleted mid-pin: the delete path's hbm.drop may have run
            # BEFORE our put landed — drop again so nothing orphans
            self.hbm.drop(block_id)
            return 0
        return n

    async def _scrub_once(self) -> None:
        """Checksum scrub; corrupt blocks are reported to the master —
        WITH our worker id, so it can retire the location and order the
        physical delete once a clean replica exists. The block stays on
        disk until then: the worker never unilaterally destroys what
        might be the last copy."""
        corrupt = await asyncio.to_thread(self.store.scrub,
                                          self.conf.worker.scrub_batch)
        stats = self.store.scrub_last
        if stats.get("verified"):
            self.metrics.inc("blocks.scrub_verified", stats["verified"])
        if stats.get("truncated"):
            self.metrics.inc("blocks.corrupt_truncated", stats["truncated"])
        if stats.get("io_error"):
            self.metrics.inc("scrub.io_errors", stats["io_error"])
        self._export_dir_health()
        if corrupt:
            self.metrics.inc("blocks.corrupt", len(corrupt))
            if self.hbm is not None:
                for bid in corrupt:
                    self.hbm.drop(bid)     # never serve a corrupt pin
            try:
                await self._leader_call(
                    RpcCode.REPORT_UNDER_REPLICATED_BLOCKS,
                    pack({"block_ids": corrupt,
                          "worker_id": self.worker_id,
                          # verify_detail verdicts: the master repairs a
                          # "truncated" copy by re-pull and a "mismatch"
                          # (bit-rot) EC cell by re-encode from siblings
                          "verdicts": {bid: self.store.scrub_verdicts[bid]
                                       for bid in corrupt
                                       if bid in self.store.scrub_verdicts}}))
            except Exception as e:  # noqa: BLE001 — next scrub retries
                log.warning("corrupt-block report failed: %s", e)

    # ---------------- disk health plane ----------------

    def install_disk_faults(self, injector) -> None:
        """Attach a fault/disk.DiskFaultInjector to every storage IO
        path (block store + direct-IO engine). Test/storm control plane."""
        self.store.fault_hook = injector
        if self.io_engine is not None:
            self.io_engine.fault_hook = injector

    def _export_dir_health(self) -> None:
        """Per-dir health level and scrub staleness gauges (level 0 =
        healthy, 1 = suspect, 2 = quarantined)."""
        ages = self.store.scrub_ages()
        for t in self.store.tiers:
            key = _metric_key(t.dir_id)
            self.metrics.gauge(f"dir.health.{key}",
                               _HEALTH_LEVEL.get(t.health.state, 0))
            self.metrics.gauge(f"dir.scrub_age_s.{key}",
                               round(ages.get(t.dir_id, 0.0), 3))

    async def _disk_probe_once(self) -> None:
        """Background write/read/unlink probe of SUSPECT dirs:
        consecutive failures quarantine the dir (allocation stops, the
        master evacuates), consecutive successes rehabilitate it."""
        for tier in self.store.tiers:
            if not tier.health.suspect:
                continue
            ok = await asyncio.to_thread(self.store.probe_dir, tier)
            state = tier.health.probe_result(ok)
            if state == tier.health.QUARANTINED:
                log.error("dir %s QUARANTINED after failed probes; "
                          "blocks will be evacuated", tier.dir_id)
                self.metrics.inc("disk.quarantined")
            elif state == tier.health.HEALTHY:
                log.info("dir %s rehabilitated by probes", tier.dir_id)
        self._export_dir_health()

    # ---------------- handlers ----------------

    def _register_handlers(self) -> None:
        r = self.rpc.register
        r(RpcCode.WRITE_BLOCK, self._write_block)
        r(RpcCode.READ_BLOCK, self._read_block)
        r(RpcCode.DELETE_BLOCK, self._delete_block)
        r(RpcCode.GET_BLOCK_INFO, self._get_block_info)
        r(RpcCode.SC_WRITE_OPEN, self._sc_write_open)
        r(RpcCode.SC_WRITE_COMMIT, self._sc_write_commit)
        r(RpcCode.SC_WRITE_ABORT, self._sc_write_abort)
        r(RpcCode.SC_READ_REPORT, self._sc_read_report)
        r(RpcCode.WRITE_BLOCKS_BATCH, self._write_blocks_batch)
        r(RpcCode.HBM_PIN, self._hbm_pin)
        r(RpcCode.HBM_UNPIN, self._hbm_unpin)
        r(RpcCode.SUBMIT_BLOCK_REPLICATION_JOB, self._replicate_block)
        r(RpcCode.ICI_TRANSFER, self._ici_transfer)
        r(RpcCode.SUBMIT_TASK, self._submit_task)
        r(RpcCode.GET_SPANS, self._get_spans)

    async def _get_spans(self, msg: Message, conn: ServerConn):
        """This worker's recorded spans for one trace (master collect)."""
        q = unpack(msg.data) or {}
        return {}, pack({"spans":
                         self.tracer.spans_for(str(q.get("trace_id", "")))})

    async def _write_block(self, msg: Message, conn: ServerConn):
        """Chunked upload: request header {block_id, storage_type, len_hint},
        then CHUNK frames, then EOF {crc32}. Parity: write_handler.rs.
        Chunks are consumed zero-copy (stream sink runs inline in the
        connection's receive loop with a view into its reusable buffer)."""
        q = unpack(msg.data) or msg.header
        block_id = q["block_id"]
        if self.draining:
            # refusal happens at stream OPEN only — chunks of streams
            # admitted before the drain keep landing below
            raise err.WorkerDraining(
                f"worker {self.worker_id} is draining; "
                f"re-place block {block_id}")
        hint = StorageType(q.get("storage_type", int(StorageType.MEM)))
        # the dispatch span closes when this handler returns (chunks
        # arrive later, in the receive loop's task); a manually-finished
        # span covers the whole stream: request frame → EOF commit/error
        wspan = self.tracer.span("write_block_stream", parent=msg.trace,
                                 attrs={"block_id": block_id})
        info = self.store.create_temp(block_id, hint, q.get("len_hint", 0),
                                      tenant=_tenant_of(msg))
        hook = self.store.fault_hook
        if hook is not None:
            try:
                hook.check_write(info.path)
            except OSError:
                self.store.note_io_error(info.tier)
                self.store.delete(block_id)
                wspan.finish()
                raise
        inline_io = (info.tier.storage_type <= StorageType.MEM
                     and not info.is_extent)
        try:
            f = _open_block_writer(info) if inline_io else \
                await asyncio.to_thread(_open_block_writer, info)
        except OSError as e:
            # allocation-time media failure (mkdir/open of the temp
            # file) — must count against dir health like a mid-stream
            # write error, or a disk that dies at open never quarantines
            self.store.note_io_error(info.tier)
            self.store.delete(block_id)
            wspan.error(e).finish()
            raise
        # commit-checksum algo is the CLIENT's choice (it streams the
        # same hash for wire verification) — carried in the open header
        algo = q.get("algo", "crc32")
        if not checksum.supported(algo):
            algo = "crc32"
        state = {"crc": 0, "total": 0}
        max_len = info.alloc_len if info.is_extent else None
        # hash+write: on multi-core hosts each chunk is copied out of the
        # reusable receive buffer and processed in a worker thread chained
        # behind the previous one (CRC chain + file order need sequencing)
        # while the receive loop takes the next frame — zlib releases the
        # GIL, so hashing overlaps the socket. On a single core the thread
        # hops are pure overhead, so the original inline path is kept.
        offload = (os.cpu_count() or 1) > 1
        tail: dict = {"t": None}

        def _file_write(data) -> None:
            # fault hook: per-chunk EIO/ENOSPC, and torn writes (the crc
            # covers what the CLIENT sent — a silently truncated write is
            # exactly what verify_detail later flags as "truncated")
            if hook is not None:
                hook.check_write(info.path)
                data = data[:hook.torn_write_len(info.path, len(data))]
            f.write(data)

        def _hash_write(data) -> None:
            state["crc"] = checksum.crc_update(algo, data, state["crc"])
            _file_write(data)

        async def _chained(prev, data: bytes) -> None:
            if prev is not None:
                await prev
            if len(data) >= 256 * 1024:
                await asyncio.to_thread(_hash_write, data)
            else:
                _hash_write(data)

        async def sink(header: dict, view: memoryview, is_eof: bool) -> None:
            try:
                if len(view):
                    state["total"] += len(view)
                    if max_len is not None and state["total"] > max_len:
                        raise err.CapacityExceeded(
                            f"block {block_id} exceeds its "
                            f"{max_len}B extent")
                    if offload:
                        tail["t"] = asyncio.ensure_future(
                            _chained(tail["t"], bytes(view)))
                    elif inline_io:
                        _hash_write(view)
                    else:
                        state["crc"] = checksum.crc_update(
                            algo, view, state["crc"])
                        await asyncio.to_thread(_file_write, bytes(view))
                if not is_eof:
                    return
                if tail["t"] is not None:
                    await tail["t"]
                if header.get("abort"):
                    # the client superseded this upload attempt (mid-
                    # stream failover replaced the block elsewhere):
                    # discard the temp state now instead of leaking it
                    # until connection teardown. No ack — the client
                    # already stopped listening on this req_id.
                    conn.close_stream(msg.req_id)
                    f.close()
                    self.store.delete(block_id)
                    wspan.set_attr("aborted", True)
                    wspan.finish()
                    return
                conn.close_stream(msg.req_id)
                f.close()
                want = header.get("crc32")
                if want is not None \
                        and header.get("algo", algo) == algo \
                        and want != state["crc"]:
                    raise err.AbnormalData(
                        f"block {block_id} crc mismatch: "
                        f"{state['crc']:#x} != {want:#x}")
                await asyncio.to_thread(
                    self.store.commit, block_id, state["total"],
                    checksum=state["crc"], checksum_algo=algo)
                self.metrics.inc("bytes.written", state["total"])
                wspan.set_attr("bytes", state["total"])
                wspan.finish()
                await conn.send(response_for(msg, header={
                    "block_id": block_id, "len": state["total"],
                    "crc32": state["crc"], "worker_id": self.worker_id},
                    flags=Flags.RESPONSE | Flags.EOF))
            except Exception as e:  # noqa: BLE001 — surface to the client
                if isinstance(e, OSError):
                    # real (or injected) media write failure: feed the
                    # dir health machinery
                    self.store.note_io_error(info.tier)
                wspan.error(e).finish()
                conn.close_stream(msg.req_id)
                try:
                    f.close()
                except Exception:
                    pass
                self.store.delete(block_id)
                from curvine_tpu.rpc.frame import error_for
                await conn.send(error_for(msg, e))

        conn.set_stream_sink(msg.req_id, sink)
        return None                # reply is sent from the sink at EOF

    async def _sc_write_open(self, msg: Message, conn: ServerConn):
        """Short-circuit write grant: a co-located client writes the temp
        block file directly (no socket copy, one hash pass) and commits
        via SC_WRITE_COMMIT. The TPU-host counterpart of the reference's
        short-circuit read (orpc zero-copy parity, write direction)."""
        q = unpack(msg.data) or {}
        if self.draining:
            raise err.WorkerDraining(
                f"worker {self.worker_id} is draining; "
                f"re-place block {q['block_id']}")
        info = self.store.create_temp(
            q["block_id"], StorageType(q.get("storage_type",
                                             int(StorageType.MEM))),
            q.get("len_hint", 0), tenant=_tenant_of(msg))
        if info.is_extent:
            # the sc client opens the path with O_TRUNC — fatal on a
            # shared bdev file; stream over the socket instead
            self.store.delete(q["block_id"])
            raise err.Unsupported("short-circuit write unsupported on "
                                  "bdev tiers")
        return {}, pack({"path": info.path, "worker_id": self.worker_id})

    async def _sc_write_commit(self, msg: Message, conn: ServerConn):
        q = unpack(msg.data) or {}
        info = await asyncio.to_thread(
            self.store.commit, q["block_id"], q["len"],
            checksum=q.get("crc32"), checksum_algo=q.get("algo", "crc32"))
        self.metrics.inc("bytes.written", info.len)
        return {}, pack({"block_id": info.block_id, "len": info.len,
                         "worker_id": self.worker_id})

    async def _sc_write_abort(self, msg: Message, conn: ServerConn):
        q = unpack(msg.data) or {}
        self.store.delete(q["block_id"])
        return {}, pack({})

    async def _read_block(self, msg: Message, conn: ServerConn):
        """Streaming download. Request {block_id, offset, len, chunk_size}.
        Parity: read_handler.rs. Chunks are preadv'd into one reusable
        buffer and sent as views — no per-chunk allocations (first-touch
        page faults dominate large allocs on virtualized hosts). The
        transport is set to drain fully so buffer reuse is safe."""
        import numpy as np
        q = unpack(msg.data) or msg.header
        # read pin: while this stream runs, tier moves of bdev-resident
        # blocks are refused, so the extent can't be freed and reused
        # under us (file-layout moves stay safe via unlink semantics)
        info = self.store.pin_read(q["block_id"])
        try:
            offset = q.get("offset", 0)
            length = q.get("len", -1)
            chunk_size = q.get("chunk_size", self.chunk_size)
            end = info.len if length < 0 else min(info.len, offset + length)
            inline_io = info.tier.storage_type <= StorageType.MEM
            want_crc = bool(q.get("verify", False))
            hook = self.store.fault_hook
            # a bit-flip fault needs the bytes in userspace to mutate —
            # the kernel-sendfile path can't expose them, so fall through
            # to the copying path while such a spec is armed
            force_copy = hook is not None \
                and hook.wants_read_data(info.path)
            if hook is not None:
                hook.check_read(info.path)

            base = info.offset              # bdev extents start mid-file
            engine = info.tier.io_engine
            if engine is not None:
                # direct-IO tier: chunks come off the submission ring
                # O_DIRECT (batched at the engine's queue depth), so a
                # cold SSD/HDD read never evicts MEM-tier/FUSE pages.
                # One reusable buffer; send completes before reuse.
                buf = np.empty(min(chunk_size, max(1, end - offset)),
                               dtype=np.uint8)
                crc = 0
                pos = offset
                while pos < end:
                    if msg.deadline is not None:
                        # the client stopped listening at its budget:
                        # abandon the stream instead of shoveling chunks
                        # into a dead socket buffer
                        msg.deadline.check(f"read block {q['block_id']}")
                    n = min(chunk_size, end - pos)
                    view = memoryview(buf[:n])
                    got = await engine.read_into(info.path, base + pos, view)
                    if got <= 0:
                        break
                    view = view[:got]
                    if force_copy:
                        hook.mutate_read(info.path, view)
                    if want_crc:
                        crc = zlib.crc32(view, crc)
                    pos += got
                    await conn.send(response_for(
                        msg, data=view, flags=Flags.RESPONSE | Flags.CHUNK))
                header = {"len": pos - offset, "direct_io": True}
                header.update(_integrity_header(info))
                if want_crc:
                    header["crc32"] = crc
                await conn.send(response_for(
                    msg, header=header, flags=Flags.RESPONSE | Flags.EOF))
                self.metrics.inc("bytes.read", pos - offset)
                self.metrics.inc("bytes.read.direct", pos - offset)
                return None
            if not want_crc and not force_copy:
                # zero-copy: chunk payloads leave via kernel sendfile, data
                # never enters userspace (TCP checksums the wire; at-rest
                # integrity is the scrubber's job, end-to-end integrity
                # the client's — the commit-time crc rides the EOF frame)
                f = open(info.path, "rb")
                try:
                    pos = offset
                    while pos < end:
                        if msg.deadline is not None:
                            msg.deadline.check(
                                f"read block {q['block_id']}")
                        n = min(chunk_size, end - pos)
                        sent = await conn.send_chunk_from_file(
                            msg.code, msg.req_id, f, base + pos, n)
                        if sent <= 0:
                            break
                        pos += sent
                    header = {"len": pos - offset}
                    header.update(_integrity_header(info))
                    await conn.send(response_for(
                        msg, header=header,
                        flags=Flags.RESPONSE | Flags.EOF))
                    self.metrics.inc("bytes.read", pos - offset)
                finally:
                    f.close()
                return None

            # verified path: preadv into one reusable buffer + streaming
            # crc (sock_sendall completes only once the kernel took the
            # bytes, so reusing the buffer between sends is safe)
            fd = os.open(info.path, os.O_RDONLY)
            buf = np.empty(min(chunk_size, max(1, end - offset)),
                           dtype=np.uint8)
            try:
                crc = 0
                pos = offset
                while pos < end:
                    if msg.deadline is not None:
                        msg.deadline.check(f"read block {q['block_id']}")
                    n = min(chunk_size, end - pos)
                    view = memoryview(buf[:n])
                    if inline_io:
                        got = os.preadv(fd, [view], base + pos)
                    else:
                        got = await asyncio.to_thread(os.preadv, fd, [view],
                                                      base + pos)
                    if got <= 0:
                        break
                    view = view[:got]
                    if force_copy:
                        hook.mutate_read(info.path, view)
                    crc = zlib.crc32(view, crc)
                    pos += got
                    await conn.send(response_for(
                        msg, data=view, flags=Flags.RESPONSE | Flags.CHUNK))
                header = {"crc32": crc, "len": pos - offset}
                header.update(_integrity_header(info))
                await conn.send(response_for(
                    msg, header=header,
                    flags=Flags.RESPONSE | Flags.EOF))
                self.metrics.inc("bytes.read", pos - offset)
            finally:
                os.close(fd)
            return None
        except OSError:
            # media refused the read (real or injected): count it
            # against the dir health and surface the error to the
            # client, which fails over to another replica
            self.store.note_io_error(info.tier)
            raise
        finally:
            self.store.unpin_read(q["block_id"])

    async def _write_blocks_batch(self, msg: Message, conn: ServerConn):
        """Many small blocks in one request — the small-file fast path.
        Parity: worker/handler/batch_write_handler.rs. Body: msgpack
        {"blocks": [{block_id, storage_type, data}]}."""
        q = unpack(msg.data) or {}
        results = []
        for b in q.get("blocks", []):
            data = b["data"]
            info = self.store.create_temp(
                b["block_id"], StorageType(b.get("storage_type",
                                                 int(StorageType.MEM))),
                len(data), tenant=_tenant_of(msg))
            try:
                await asyncio.to_thread(_write_block_bytes, info, data)
                # sender-computed checksum (EC cell placement and other
                # trusted peers): the cell commits first-class verified,
                # so the scrubber covers it like any block
                await asyncio.to_thread(
                    self.store.commit, b["block_id"], len(data),
                    checksum=b.get("crc32"),
                    checksum_algo=b.get("algo", "crc32"))
                results.append({"block_id": b["block_id"], "len": len(data),
                                "worker_id": self.worker_id,
                                "storage_type": int(info.tier.storage_type)})
            except Exception as e:
                if isinstance(e, OSError):
                    self.store.note_io_error(info.tier)
                self.store.delete(b["block_id"])
                raise
        self.metrics.inc("bytes.written",
                         sum(r["len"] for r in results))
        # results ride the DATA frame: consumers (unified batch writer,
        # EC cell placement) parse unpack(rep.data)["results"]
        return {}, pack({"results": results})

    async def _delete_block(self, msg: Message, conn: ServerConn):
        q = unpack(msg.data) or {}
        self.store.delete(q["block_id"])
        if self.hbm is not None:
            self.hbm.drop(q["block_id"])     # no orphaned device copies
        return {}

    async def _get_block_info(self, msg: Message, conn: ServerConn):
        """Metadata + local path (enables client short-circuit reads):
        of `block_id`, or of every id of `block_ids` (CurvineClient.prime)
        as a list `blocks`, where a block this worker cannot serve is an
        error entry beside the others. Admitted as one read, charged as
        one a block."""
        q = unpack(msg.data) or {}
        if "block_ids" not in q:
            return self._block_info(q["block_id"])
        from curvine_tpu.common.qos import READ, TENANT_KEY
        self.qos.charge(msg.header.get(TENANT_KEY), READ,
                        len(q["block_ids"]) - 1)
        out = []
        for bid in q["block_ids"]:
            try:
                out.append(self._block_info(bid))
            except err.CurvineError as e:
                out.append({"block_id": bid, "error": str(e),
                            "error_code": int(e.code)})
        return {}, pack({"blocks": out})

    def _block_info(self, block_id: int) -> dict:
        # lookup + lease recording are one atomic store operation: a
        # free slipping in between would lease an already-freed extent
        info, lease_ms = self.store.grant_sc(block_id)
        rep = {"block_id": info.block_id, "len": info.len,
               "storage_type": int(info.tier.storage_type),
               "path": os.path.abspath(info.path),
               "offset": info.offset}
        if info.tier.io_engine is not None:
            # capability plumb-through: parallel readers size their
            # slice fan-out to the tier's submission depth instead of
            # guessing (client/reader.py read_range)
            rep["direct_io"] = True
            rep["queue_depth"] = (info.tier.io_queue_depth
                                  or info.tier.io_engine.queue_depth)
        if lease_ms:
            # extent grants expire: the client must re-probe before the
            # tier's quarantine can return the freed extent to reuse
            rep["lease_ms"] = lease_ms
        if info.crc32c is not None:
            # commit-time checksum: short-circuit readers verify the
            # mmap/pread bytes against it without a worker round-trip
            rep["crc32"] = info.crc32c
            rep["crc_algo"] = info.crc_algo
        if self._shm_servable(info):
            # capability negotiation: a client that understands the shm
            # plane fetches the sealed memfd over the side channel and
            # serves reads as zero-RPC mmap slices; everyone else just
            # ignores the flags and keeps the fd/socket paths
            rep["shm"] = True
            rep["shm_sock"] = self._shm_channel.path
        elif self._shm_warm_servable(info):
            # warm-cache export: a read-hot below-MEM block is servable
            # over the SAME channel/protocol; shm_warm lets the client
            # account the hit to the warm plane (read.shm_warm_hits)
            rep["shm"] = True
            rep["shm_warm"] = True
            rep["shm_sock"] = self._shm_channel.path
        exports = getattr(self.hbm, "exports", None)
        if exports is not None and self.conf.worker.ici_transfer:
            e = exports.get(block_id)
            if e is not None:
                # peer-addressable HBM advertisement (docs/ici-plane.md):
                # an ICI-capable consumer can fetch the device buffer
                # from this worker's tier instead of reading bytes —
                # device ordinal + mesh coords + buffer shape/dtype
                rep["hbm"] = {"worker_id": self.worker_id,
                              "ici_coords": list(
                                  self.conf.worker.ici_coords or []),
                              **e}
        return rep

    def _shm_servable(self, info) -> bool:
        """MEM-tier file-layout committed blocks only: extents live
        inside a shared backing file (a memfd copy would defeat the
        lease machinery) and disk tiers would double-buffer the page
        cache into anonymous memory for no latency win."""
        return (self.shm is not None and self._shm_channel is not None
                and info.state == BlockState.COMMITTED
                and not getattr(info, "is_extent", False)
                and info.tier.storage_type == StorageType.MEM)

    def _shm_warm_servable(self, info) -> bool:
        """Warm-cache eligibility for the tiers below MEM: committed
        file-layout blocks whose heat (the SC_READ_REPORT rail) crossed
        worker.shm_warm_min_reads and that fit the warm cache. Extents
        stay excluded for the same lease reasons as the MEM gate."""
        warm = self.shm_warm
        return (warm is not None and self._shm_channel is not None
                and info.state == BlockState.COMMITTED
                and not getattr(info, "is_extent", False)
                and int(info.tier.storage_type) > int(StorageType.MEM)
                and info.heat >= self.conf.worker.shm_warm_min_reads
                and info.len <= warm.cap_bytes)

    def _shm_invalidate(self, block_id: int) -> None:
        """BlockStore on_delete/on_move hook (fires under the store
        lock): drop both export flavors; must not re-enter the store."""
        if self.shm is not None:
            self.shm.invalidate(block_id)
        if self.shm_warm is not None:
            self.shm_warm.invalidate(block_id)

    def _shm_grant(self, block_id: int) -> tuple[int, int]:
        """Side-channel policy hook (runs on the channel thread): look
        the block up, gate on tier/layout, export a sealed memfd — from
        the MEM exporter or, for heat-qualified below-MEM blocks, the
        warm cache. LookupError → NOT_FOUND reply → the client falls
        back."""
        try:
            info = self.store.get(block_id, touch=False)
        except err.CurvineError:
            raise LookupError(f"block {block_id}") from None
        for table, servable, counter in (
                (self.shm, self._shm_servable, "shm.grants"),
                (self.shm_warm, self._shm_warm_servable,
                 "shm.warm_grants")):
            if not servable(info):
                continue

            def resident() -> bool:
                # asked after a new copy entered the table: a delete or
                # a tier move that ran meanwhile found nothing to drop
                try:
                    now = self.store.get(block_id, touch=False)
                except err.CurvineError:
                    return False
                return now.tier is info.tier and servable(now)

            fd, length = table.export(block_id, info.path, info.len,
                                      resident)
            self.metrics.inc(counter)
            return fd, length
        raise LookupError(f"block {block_id} not shm-servable")

    async def _sc_read_report(self, msg: Message, conn: ServerConn):
        """Short-circuit read accounting: clients read through cached fds
        (the store only sees the initial probe), so they periodically
        report per-block read counts — heat/atime then track actual
        traffic and the promotion/HBM-autopin scans target the truly hot
        blocks instead of the most-probed ones."""
        q = unpack(msg.data) or {}
        warm: dict[int, str] = {}
        for bid, reads in (q.get("block_reads") or {}).items():
            bid = int(bid)
            self.store.touch_reads(bid, int(reads))
            # the report is the moment heat crosses the warm threshold:
            # advertise newly warm-servable blocks on the REPLY so the
            # reporting client (which cached its GET_BLOCK_INFO probe
            # from before the block was hot) learns the capability
            # without a re-probe — its next read maps the warm copy
            if self.shm_warm is not None:
                try:
                    info = self.store.get(bid, touch=False)
                except err.CurvineError:
                    continue
                if self._shm_warm_servable(info):
                    warm[bid] = self._shm_channel.path
        return {"shm_warm": warm} if warm else {}

    async def _replicate_block(self, msg: Message, conn: ServerConn):
        """Pull a block replica from a peer worker and report to master.
        Parity: worker/replication/replication_job.rs (pull-based)."""
        q = unpack(msg.data) or {}
        block_id = q["block_id"]
        ok, message = True, ""
        ecq = q.get("ec")
        if ecq is not None:
            # stripe-cell rebuild: there may be NOTHING to copy — decode
            # the cell from k sibling cells instead of pulling a replica
            try:
                if not self.store.contains(block_id):
                    await self._reconstruct_cell(ecq, block_id)
                    await self._leader_call(
                        RpcCode.WORKER_BLOCK_REPORT, pack({
                            "worker_id": self.worker_id,
                            "blocks": {block_id: ecq["cell_size"]},
                            "storage_types": {block_id: int(
                                self.store.get(block_id,
                                               touch=False)
                                .tier.storage_type)},
                            "incremental": True}))
            except Exception as e:  # noqa: BLE001
                ok, message = False, str(e)
                self.store.delete(block_id)
            try:
                await self._leader_call(
                    RpcCode.REPORT_BLOCK_REPLICATION_RESULT,
                    pack({"block_id": block_id,
                          "worker_id": self.worker_id,
                          "success": ok, "message": message}))
            except Exception as e:
                log.warning("reconstruct result report failed: %s", e)
            return {"success": ok, "message": message}
        src = WorkerAddress.from_wire(q["source"])
        via = ""
        try:
            if not self.store.contains(block_id):
                # device path first when the master hinted the source
                # holds the block in HBM (docs/ici-plane.md): zero bytes
                # on the TCP rail when it lands. ANY failure — peer
                # outside the device domain, stale advertisement, device
                # error — falls through to the TCP pull below; the
                # fallback is a counter, never an error.
                ici = q.get("ici")
                if ici is not None and self.conf.worker.ici_transfer:
                    landed = False
                    try:
                        landed = await self._ici_land(
                            block_id, ici, q.get("block_len", 0))
                    except Exception as e:  # noqa: BLE001
                        log.debug("ici pull of block %d failed: %s",
                                  block_id, e)
                        self.store.delete(block_id)   # clear any temp
                    if landed:
                        via = "ici"
                        self.metrics.inc("ici.peer_pulls")
                    else:
                        self.metrics.inc("ici.tcp_fallbacks")
            if not self.store.contains(block_id):
                peer = await self.peer_pool.get(
                    f"{src.ip_addr or src.hostname}:{src.rpc_port}")
                info = self.store.create_temp(block_id,
                                              size_hint=q.get("block_len", 0))
                total = 0
                crc = 0
                crc_algo = checksum.preferred_algo()
                src_crc = None
                src_algo = None
                cap = info.alloc_len if info.is_extent else None
                hook = self.store.fault_hook
                f = await asyncio.to_thread(_open_block_writer, info)
                try:
                    # the master's pull budget rides the submit header:
                    # a dead/wedged source fails this stream inside the
                    # remaining budget instead of the full RPC timeout
                    async for m in peer.call_stream(
                            RpcCode.READ_BLOCK, header={"block_id": block_id},
                            deadline=msg.deadline):
                        if len(m.data):
                            total += len(m.data)
                            if cap is not None and total > cap:
                                # never write past the extent into a
                                # neighboring committed block
                                raise err.CapacityExceeded(
                                    f"replica {block_id} exceeds its "
                                    f"{cap}B extent")
                            crc = checksum.crc_update(crc_algo, m.data, crc)
                            if hook is not None:
                                hook.check_write(info.path)
                            await asyncio.to_thread(f.write, m.data)
                        if m.is_eof:
                            h = m.header or {}
                            src_crc = h.get("block_crc32")
                            src_algo = h.get("block_crc_algo")
                finally:
                    await asyncio.to_thread(f.close)
                if src_crc is not None:
                    got = crc if src_algo == crc_algo else (
                        checksum.crc_update(src_algo,
                                            _read_back(info, total))
                        if checksum.supported(src_algo) else None)
                    if got is not None and got != src_crc:
                        # the SOURCE replica (or the wire) is bad —
                        # healing must never multiply corruption; fail
                        # the job so the master retries another holder
                        raise err.AbnormalData(
                            f"replica pull of {block_id} checksum "
                            f"mismatch (got {got:#010x} want "
                            f"{src_crc:#010x})")
                self.store.commit(block_id, total, checksum=crc,
                                  checksum_algo=crc_algo)
                # tell master about the new replica via commit on next report;
                # also push an immediate incremental report
                await self._leader_call(RpcCode.WORKER_BLOCK_REPORT, pack({
                    "worker_id": self.worker_id,
                    "blocks": {block_id: total},
                    "storage_types": {block_id: int(info.tier.storage_type)},
                    "incremental": True}))
        except Exception as e:  # noqa: BLE001
            ok, message = False, str(e)
            if isinstance(e, OSError) and "info" in locals():
                # local media failure while landing the pull (open or
                # write) — connection errors ride CurvineError types, so
                # an OSError here is this disk's fault, not the source's
                self.store.note_io_error(info.tier)
            self.store.delete(block_id)
        try:
            await self._leader_call(
                RpcCode.REPORT_BLOCK_REPLICATION_RESULT,
                pack({"block_id": block_id, "worker_id": self.worker_id,
                      "success": ok, "message": message, "via": via}))
        except Exception as e:
            log.warning("replication result report failed: %s", e)
        return {"success": ok, "message": message, "via": via}

    async def _ici_land(self, block_id: int, hint: dict,
                        block_len: int) -> bool:
        """Land one replica over the ICI device path: fetch the peer's
        HBM-resident buffer through the in-process device domain
        (tpu/ici_plane.py), then commit it locally with the same crc
        discipline as a TCP pull. Returns False (peer not reachable this
        way, stale advertisement, length mismatch) to request the TCP
        fallback; only genuinely local landing failures raise."""
        import numpy as np
        from curvine_tpu.tpu import ici_plane
        arr = await asyncio.to_thread(
            ici_plane.fetch_device_block,
            int(hint.get("worker_id", -1)), block_id)
        if arr is None:
            return False
        buf = np.asarray(arr).reshape(-1).view(np.uint8)
        if block_len and buf.nbytes != block_len:
            return False        # advertisement outlived the block bytes
        info = self.store.create_temp(block_id, size_hint=buf.nbytes)
        if info.is_extent and buf.nbytes > info.alloc_len:
            self.store.delete(block_id)
            return False
        crc_algo = checksum.preferred_algo()
        crc = checksum.crc_update(crc_algo, buf)
        f = await asyncio.to_thread(_open_block_writer, info)
        try:
            await asyncio.to_thread(f.write, buf)
        finally:
            await asyncio.to_thread(f.close)
        self.store.commit(block_id, buf.nbytes, checksum=crc,
                          checksum_algo=crc_algo)
        await self._leader_call(RpcCode.WORKER_BLOCK_REPORT, pack({
            "worker_id": self.worker_id,
            "blocks": {block_id: buf.nbytes},
            "storage_types": {block_id: int(info.tier.storage_type)},
            "incremental": True}))
        return True

    async def _ici_transfer(self, msg: Message, conn: ServerConn):
        """Coordination RPC (RpcCode.ICI_TRANSFER): pair this worker
        with a named peer to move one block device-to-device. Succeeds
        only over the device path; a miss replies success=False WITHOUT
        raising so the caller keeps its TCP rail as the fallback —
        same contract as the hinted replication pull."""
        q = unpack(msg.data) or {}
        block_id = q["block_id"]
        if self.store.contains(block_id):
            return {"success": True, "via": "local"}
        if not self.conf.worker.ici_transfer:
            return {"success": False, "via": "",
                    "message": "ici transfer disabled"}
        landed = False
        try:
            landed = await self._ici_land(
                block_id, {"worker_id": q.get("source_worker_id", -1)},
                q.get("block_len", 0))
        except Exception as e:  # noqa: BLE001
            log.debug("ici transfer of block %d failed: %s", block_id, e)
            self.store.delete(block_id)
        if landed:
            self.metrics.inc("ici.peer_pulls")
            return {"success": True, "via": "ici"}
        self.metrics.inc("ici.tcp_fallbacks")
        return {"success": False, "via": ""}

    async def _hbm_pin(self, msg: Message, conn: ServerConn):
        """Pin a cached block into the HBM tier-0 (device-resident).
        In-process consumers (sdk/tpu loaders embedded on the TPU VM) then
        fetch it as an on-device array via `hbm.get`."""
        q = unpack(msg.data) or {}
        if self.hbm is None:
            raise err.Unsupported("hbm tier not enabled on this worker")
        block_id = q["block_id"]
        info = self.store.get(block_id)
        import numpy as np
        buf = np.empty(info.len, dtype=np.uint8)
        fd = os.open(info.path, os.O_RDONLY)
        try:
            os.preadv(fd, [memoryview(buf)], info.offset)
        finally:
            os.close(fd)
        multi = hasattr(self.hbm, "tiers")     # MultiHbmTier vs single
        if multi and q.get("replicas", 1) > 1:
            arrs = await asyncio.to_thread(self.hbm.put_replicated,
                                           block_id, buf, q["replicas"])
            arr = arrs[0]
        elif multi:
            arr = await asyncio.to_thread(self.hbm.put, block_id, buf,
                                          q.get("device_id"))
        else:
            arr = await asyncio.to_thread(self.hbm.put, block_id, buf)
        self.metrics.gauge("hbm.used", self.hbm.used)
        return {"block_id": block_id, "len": int(arr.nbytes),
                "holders": self.hbm.holders(block_id) if multi else [0],
                "hbm": self.hbm.stats()}

    async def _hbm_unpin(self, msg: Message, conn: ServerConn):
        q = unpack(msg.data) or {}
        if self.hbm is not None:
            self.hbm.drop(q["block_id"])
            self.metrics.gauge("hbm.used", self.hbm.used)
        return {}

    async def _submit_task(self, msg: Message, conn: ServerConn):
        q = unpack(msg.data) or {}
        task = TaskInfo.from_wire(q["task"])
        if task.kind == "ec_convert":
            asyncio.ensure_future(self._run_ec_convert_task(task))
        else:
            # held, so that stop() can cancel what still queues for a slot
            t = asyncio.ensure_future(self._run_load_task(task))
            self._load_tasks.add(t)
            t.add_done_callback(self._load_tasks.discard)
        return {"accepted": True}

    def _task_client_get(self):
        """The one client the load tasks share, made at the first task
        (a client per task dialled the master and this worker anew each
        time) and closed with the worker."""
        if self._task_client is None:
            from curvine_tpu.client import CurvineClient
            self._task_client = CurvineClient(self.conf)
        return self._task_client

    async def _run_load_task(self, task: TaskInfo) -> None:
        """UFS ↔ cache transfer. Parity: worker/task/load_task_runner.rs
        (load) + the export job flow (cache → UFS). Accounted as
        load.tasks / load.bytes / load.s / load.failed and the span
        `load` (docs/observability.md), queueing for a slot excluded."""
        async with self._task_sem:
            client = self._task_client_get()
            t0 = time.perf_counter()
            try:
                with self.tracer.span("load", attrs={
                        "path": task.path, "kind": task.kind or "load"}):
                    if task.kind == "export":
                        n = await client.export_to_ufs(task.path)
                    elif task.kind == "prefetch":
                        n = await client.prefetch(task.path)
                    else:
                        n = await client.load_from_ufs(task.path)
                task.state = JobState.COMPLETED
                task.loaded_len = n
                self.metrics.inc("load.bytes", n)
            except Exception as e:  # noqa: BLE001
                task.state = JobState.FAILED
                task.message = str(e)
                self.metrics.inc("load.failed")
                # a mount taken away while its loads queued is routine
                log.log(logging.INFO if isinstance(e, err.MountNotFound)
                        else logging.WARNING,
                        "load task %s failed: %s", task.task_id, e)
            finally:
                task.worker_id = self.worker_id
                try:
                    await self._leader_call(RpcCode.REPORT_TASK,
                                            pack({"task": task.to_wire()}))
                except Exception as e:
                    log.warning("task report failed: %s", e)
                self.metrics.inc("load.tasks")
                self.metrics.inc("load.s", time.perf_counter() - t0)

    # ---------------- erasure coding ----------------

    async def _pull_verified(self, src: WorkerAddress, block_id: int,
                             deadline=None) -> bytes:
        """Pull one whole block/cell from a peer into memory, verified
        against the commit-time checksum riding the EOF frame. In-memory
        on purpose: every EC caller needs the full bytes for the matrix
        pass anyway, and cells are bounded by block_size/k."""
        peer = await self.peer_pool.get(
            f"{src.ip_addr or src.hostname}:{src.rpc_port}")
        chunks: list[bytes] = []
        src_crc = src_algo = None
        async for m in peer.call_stream(
                RpcCode.READ_BLOCK, header={"block_id": block_id},
                deadline=deadline):
            if len(m.data):
                chunks.append(bytes(m.data))
            if m.is_eof:
                h = m.header or {}
                src_crc = h.get("block_crc32")
                src_algo = h.get("block_crc_algo")
        data = b"".join(chunks)
        if src_crc is not None and checksum.supported(src_algo):
            if checksum.crc_update(src_algo, data) != src_crc:
                raise err.AbnormalData(
                    f"pull of block {block_id} from worker "
                    f"{src.worker_id} failed checksum verify")
        return data

    async def _pull_any(self, sources: list[dict], block_id: int,
                        deadline=None) -> bytes:
        last: Exception | None = None
        for wire in sources:
            try:
                return await self._pull_verified(
                    WorkerAddress.from_wire(wire), block_id,
                    deadline=deadline)
            except Exception as e:  # noqa: BLE001 — try the next holder
                last = e
        raise last or err.BlockNotFound(
            f"no servable source for block {block_id}")

    def _write_local_cell(self, cell_id: int, data: bytes) -> int:
        """Commit one stripe cell into the local store with a fresh
        first-class checksum (cells scrub and verify like any block)."""
        info = self.store.create_temp(cell_id, size_hint=len(data))
        algo = checksum.preferred_algo()
        crc = checksum.crc_update(algo, data)
        try:
            _write_block_bytes(info, data, self.store.fault_hook)
            self.store.commit(cell_id, len(data), checksum=crc,
                              checksum_algo=algo)
        except Exception:
            self.store.delete(cell_id)
            raise
        return int(info.tier.storage_type)

    async def _place_cells(self, placed: dict) -> list[dict]:
        """Land encoded cells on their target workers. Local targets
        commit directly; remote targets ride WRITE_BLOCKS_BATCH (cells
        are small one-shot writes — the streaming protocol buys nothing)
        with the sender-computed checksum so every cell commits
        first-class verified. `placed`: addr_key -> (addr, [(cell_id,
        bytes), ...]). Returns EC_COMMIT_STRIPE cell entries."""
        out = []
        algo = checksum.preferred_algo()
        for addr, cells in placed.values():
            if addr.worker_id == self.worker_id:
                for cid, data in cells:
                    st = await asyncio.to_thread(
                        self._write_local_cell, cid, data)
                    out.append({"block_id": cid,
                                "worker_id": self.worker_id,
                                "storage_type": st})
                continue
            peer = await self.peer_pool.get(
                f"{addr.ip_addr or addr.hostname}:{addr.rpc_port}")
            rep = await peer.call(RpcCode.WRITE_BLOCKS_BATCH, data=pack({
                "blocks": [{"block_id": cid, "data": data,
                            "crc32": checksum.crc_update(algo, data),
                            "algo": algo}
                           for cid, data in cells]}))
            for r in (unpack(rep.data) or {}).get("results", []):
                out.append({"block_id": r["block_id"],
                            "worker_id": r["worker_id"],
                            "storage_type": r.get("storage_type", 1)})
        return out

    async def _convert_one_stripe(self, prof, plan: dict) -> None:
        from curvine_tpu.common import ec as eclib
        block_id = plan["block_id"]
        data = await self._pull_any(plan["sources"], block_id)
        if len(data) != plan["block_len"]:
            raise err.AbnormalData(
                f"block {block_id}: pulled {len(data)}B, "
                f"expected {plan['block_len']}B")
        cells, _ = await asyncio.to_thread(
            eclib.split, data, prof.k, plan["cell_size"])
        parity = await asyncio.to_thread(eclib.encode, prof, cells)
        coded = cells + parity
        placed: dict = {}
        for c in plan["cells"]:
            addr = WorkerAddress.from_wire(c["addr"])
            key = (addr.worker_id, addr.rpc_port)
            placed.setdefault(key, (addr, []))[1].append(
                (c["block_id"], bytes(coded[c["index"]])))
        entries = await self._place_cells(placed)
        # commit the stripe map on the master: this flips reads over to
        # the cells and starts retiring the replicated copies
        await self._leader_call(RpcCode.EC_COMMIT_STRIPE, pack({
            "block_id": block_id, "cells": entries}))

    async def _run_ec_convert_task(self, task: TaskInfo) -> None:
        """Stripe a batch of cold replicated blocks: pull each block
        (verified), RS-encode it into k+m cells, land the cells on their
        planned workers, and EC_COMMIT_STRIPE. One bad block fails the
        task (the job planner re-plans on resubmit) but blocks already
        committed stay converted — the conversion is per-stripe atomic."""
        from curvine_tpu.common import ec as eclib
        async with self._task_sem:
            payload = task.payload or {}
            done = 0
            try:
                prof = eclib.ECProfile.parse(payload.get("profile", ""))
                for plan in payload.get("blocks", []):
                    await self._convert_one_stripe(prof, plan)
                    done += 1
                task.state = JobState.COMPLETED
            except Exception as e:  # noqa: BLE001
                task.state = JobState.FAILED
                task.message = str(e)
                log.warning("ec convert task %s failed after %d stripes: "
                            "%s", task.task_id, done, e)
            task.loaded_len = done
            task.worker_id = self.worker_id
            try:
                await self._leader_call(RpcCode.REPORT_TASK,
                                        pack({"task": task.to_wire()}))
            except Exception as e:
                log.warning("task report failed: %s", e)

    async def _reconstruct_cell(self, ecq: dict, cell_id: int) -> None:
        """Rebuild one lost/rotten stripe cell from any k live sibling
        cells (decode, or re-encode for a parity target) and commit it
        locally under a fresh checksum."""
        from curvine_tpu.common import ec as eclib
        prof = eclib.ECProfile.parse(ecq["profile"])
        cell_size = ecq["cell_size"]
        slots: list[bytes | None] = [None] * (prof.k + prof.m)
        got = 0
        for s in ecq["sources"]:
            if got >= prof.k:
                break
            try:
                b = await self._pull_verified(
                    WorkerAddress.from_wire(s["addr"]), s["block_id"])
            except Exception as e:  # noqa: BLE001 — source died mid-heal
                log.debug("cell source %d unavailable: %s",
                          s["block_id"], e)
                continue
            if len(b) != cell_size:
                continue             # partial/stale copy: never decode it
            slots[s["index"]] = b
            got += 1
        if got < prof.k:
            raise err.BlockNotFound(
                f"cell {cell_id}: only {got}/{prof.k} sibling cells "
                f"readable")
        idx = ecq["cell_index"]
        rebuilt = await asyncio.to_thread(
            eclib.reconstruct, prof, slots, [idx])
        await asyncio.to_thread(self._write_local_cell, cell_id,
                                bytes(rebuilt[idx]))
