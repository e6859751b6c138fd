"""Unified client: cache + UFS fall-through.

Parity: curvine-client/src/unified/ (UnifiedFileSystem). Reads hit the
cache; a miss (file known to the mount but not cached / not complete)
falls back to reading straight from the UFS, optionally warming the cache
(auto_cache). Writes honor WriteType: CACHE (cache only) or FS
(write-through to UFS)."""

from __future__ import annotations

import asyncio
import logging
import time

from curvine_tpu.common import errors as err
from curvine_tpu.common.conf import ClusterConf
from curvine_tpu.common.types import FileBlocks, StorageState, StorageType
from curvine_tpu.client.fs_client import FsClient
from curvine_tpu.client.reader import (
    FsReader, Primed, probe_addr, report_sc_reads,
)
from curvine_tpu.client.writer import FsWriter
from curvine_tpu.obs import loop_meter
from curvine_tpu.obs.trace import Timed, Tracer
from curvine_tpu.rpc import RpcCode
from curvine_tpu.rpc.client import ConnectionPool
from curvine_tpu.rpc.frame import pack, unpack

log = logging.getLogger(__name__)

_TIERS = {"hbm": StorageType.HBM, "mem": StorageType.MEM,
          "ssd": StorageType.SSD, "hdd": StorageType.HDD}


class CurvineClient:
    """High-level facade: open/create/read/write + unified UFS fallback."""

    def __init__(self, conf: ClusterConf | None = None):
        self.conf = conf or ClusterConf()
        self.meta = FsClient(self.conf)
        self.pool = ConnectionPool(size=self.conf.client.conn_pool_size,
                                   timeout_ms=self.conf.client.rpc_timeout_ms,
                                   rpc_conf=self.conf.rpc)
        # per-worker circuit breakers, SHARED by every reader/writer this
        # client opens: a wedged worker is learned once, then skipped in
        # replica choice and excluded from placement until it heals
        cc = self.conf.client
        self.health = None
        if cc.breaker_enabled:
            from curvine_tpu.client.health import WorkerHealth
            self.health = WorkerHealth(
                fail_threshold=cc.breaker_fail_threshold,
                open_s=cc.breaker_open_ms / 1000.0,
                decay_s=cc.breaker_decay_ms / 1000.0)
        # tracing front end (docs/observability.md): ops stamp a trace
        # context at start; finished spans ship to the master alongside
        # the periodic metrics flush so /api/trace sees the client side
        self.tracer = Tracer.from_conf("client", self.conf.obs)
        self.meta.tracer = self.tracer
        # native-client tenant identity (common/qos.py): the process-
        # wide fallback covers the common single-tenant process; multi-
        # tenant processes (the gateway, the tenant storm) use
        # tenant_scope(), which always wins over this default
        if cc.tenant:
            from curvine_tpu.common.qos import set_process_tenant
            set_process_tenant(cc.tenant)
        # SUBMIT_JOB calls of auto-cache loads not answered yet, held so
        # that close() can cancel them; the master keeps one live load a
        # path, whoever asks and however often
        self._load_submits: set = set()
        # what prime() asked for a list of files at once, until the
        # opens and readers it was asked for have taken it
        self._primed = Primed()
        # client-side IO counters: short-circuit reads/writes bypass the
        # worker entirely, so their bytes are invisible to worker metrics
        # — pushed to the master (METRICS_REPORT) so dashboards see the
        # co-located fast path too
        self.counters: dict[str, float] = {}
        self._reported: dict[str, float] = {}
        self._metrics_task = None
        # the meter of the loop this client runs on (obs/loop_meter.py):
        # loop.* count into self.counters from the first async entry
        # point to close()
        self._loop_meter = None
        # the meta client accounts its calls (and the master's own time
        # from each reply) into the same dict: meta.*
        self.meta.counters = self.counters
        # and so do both pools, to the master and to the workers: rpc.*
        self.meta.pool.counters = self.pool.counters = self.counters
        # meta lease cache hit/miss/invalidation counters ride the same
        # METRICS_REPORT flush (master shows them as client.meta_cache.*)
        if self.meta.cache is not None:
            self.meta.cache.counters = self.counters

    async def close(self) -> None:
        if self._metrics_task is not None:
            self._metrics_task.cancel()
            self._metrics_task = None
        for t in list(self._load_submits):
            t.cancel()
        self._primed.close()
        try:
            await self.flush_reports()
            await self.flush_metrics()
        except Exception:      # noqa: BLE001 — best-effort on teardown
            pass
        await self.meta.close()
        await self.pool.close()
        if self._loop_meter is not None:
            self._loop_meter.detach(self.counters)
            self._loop_meter = None

    def _ensure_metrics_task(self) -> None:
        """Periodic flush so dashboards see long-running jobs' sc bytes
        as they happen, not as one spike at close(). Lazily started from
        async entry points (construction can be outside a loop)."""
        if self._metrics_task is not None:
            return
        if self._loop_meter is None:
            self._loop_meter = loop_meter.attach(self.counters)

        async def loop():
            while True:
                await asyncio.sleep(5.0)
                try:
                    await self.flush_metrics()
                except Exception:   # noqa: BLE001 — master away; retry
                    pass

        self._metrics_task = asyncio.ensure_future(loop())

    async def flush_metrics(self) -> None:
        """Push counter DELTAS since the last flush — and any finished
        trace spans — to the master."""
        # deltas come from a SNAPSHOT: increments landing during the RPC
        # await must stay unreported until the next flush
        snap = dict(self.counters)
        delta = {k: v - self._reported.get(k, 0)
                 for k, v in snap.items()
                 if v != self._reported.get(k, 0)}
        spans = self.tracer.drain()
        if delta or spans:
            try:
                await self.meta.report_metrics(delta, spans=spans)
            except BaseException:
                # master away: spans go back in the ring (order is
                # cosmetic) so the next flush retries them
                self.tracer.ingest(spans)
                raise
            self._reported = snap

    async def get_trace(self, trace_id: str) -> list[dict]:
        """All collected spans of one trace: flushes this client's
        finished spans to the master, then asks it to merge its own
        store with every worker's (GET_SPANS collect)."""
        try:
            await self.flush_metrics()
        except err.CurvineError:
            pass                       # collect may still answer
        rep = await self.meta.call(RpcCode.GET_SPANS,
                                   {"trace_id": trace_id, "collect": True})
        return rep.get("spans", [])

    # ---------------- plain cache paths ----------------

    async def create(self, path: str, overwrite: bool = False,
                     replicas: int | None = None,
                     block_size: int | None = None,
                     storage_type: str | None = None,
                     storage_policy: dict | None = None) -> FsWriter:
        cc = self.conf.client
        st = _TIERS.get(storage_type or cc.storage_type, StorageType.MEM)
        self._ensure_metrics_task()
        extra = {"storage_policy": storage_policy} if storage_policy else {}
        await self.meta.create_file(
            path, overwrite=overwrite,
            replicas=replicas if replicas is not None else cc.replicas,
            block_size=block_size or cc.block_size, **extra)
        return FsWriter(self.meta, path, self.pool,
                        block_size=block_size or cc.block_size,
                        chunk_size=cc.write_chunk_size, storage_type=st,
                        ici_coords=list(self.conf.worker.ici_coords) or None,
                        short_circuit=cc.short_circuit,
                        counters=self.counters, health=self.health,
                        tracer=self.tracer,
                        replay_buffer=cc.write_replay_buffer,
                        min_replicas=cc.write_min_replicas)

    async def append(self, path: str) -> FsWriter:
        fb = await self.meta.append_file(path)
        cc = self.conf.client
        w = FsWriter(self.meta, path, self.pool,
                     block_size=fb.status.block_size,
                     chunk_size=cc.write_chunk_size,
                     storage_type=_TIERS.get(cc.storage_type, StorageType.MEM),
                     short_circuit=cc.short_circuit,
                     counters=self.counters, health=self.health,
                     tracer=self.tracer,
                     replay_buffer=cc.write_replay_buffer,
                     min_replicas=cc.write_min_replicas)
        w.pos = fb.status.len
        return w

    async def prime(self, paths: list[str]) -> None:
        """Name the files the caller is about to open, all at once: the
        control exchanges of a read then cross once a peer for the list
        and not once a file. One GET_BLOCK_LOCATIONS_BATCH to the master
        (phase `locate`), then one list-taking GET_BLOCK_INFO to each
        co-located worker for the blocks the readers would probe (phase
        `probe`); each path's next `open` and its reader's probes find
        their answers here, and the readers' short-circuit read counts
        gather here at close until `flush_reports`. Advisory: where the
        master or a worker does not answer the list (an older one, a
        sharded router), nothing is held for it and every open and
        probe asks for itself, as for a path never primed."""
        self._ensure_metrics_task()
        c = self.counters
        with self.tracer.span("prime", attrs={"files": len(paths)}) as sp:
            c["read.prime.calls"] = c.get("read.prime.calls", 0) + 1
            try:
                with Timed(c, "read.phase.locate"):
                    answers = await self.meta.get_block_locations_batch(
                        paths)
            except err.CurvineError as e:
                log.debug("prime of %d files not answered: %s",
                          len(paths), e)
                return
            self._primed.files.update(zip(paths, answers))
            by_worker: dict[str, list[int]] = {}
            if self.conf.client.short_circuit:
                for lb in (lb for fb in answers if isinstance(fb, FileBlocks)
                           for lb in fb.block_locs if lb.locs):
                    addr = probe_addr(lb, self.meta.client_host)
                    if addr is not None:
                        by_worker.setdefault(addr, []).append(lb.block.id)

            async def probe(addr: str, block_ids: list[int]) -> None:
                try:
                    conn = await self.pool.get(addr)
                    # the lease clock of every block of the list: SEND
                    # time, earlier than any reader's own would be
                    sent_at = time.time()
                    rep = await conn.call(RpcCode.GET_BLOCK_INFO,
                                          data=pack({"block_ids": block_ids}))
                except err.CurvineError as e:
                    log.debug("prime probe of %s failed: %s", addr, e)
                    return
                srv = rep.srv_seconds()
                if srv is not None:
                    c["read.probe.srv_handle_s"] = \
                        c.get("read.probe.srv_handle_s", 0) + srv[1]
                for info in (unpack(rep.data) or {}).get("blocks") or ():
                    if "error" not in info:
                        self._primed.blocks[info["block_id"]] = (info,
                                                                 sent_at)

            if by_worker:
                with Timed(c, "read.phase.probe"):
                    await asyncio.gather(*(probe(a, b)
                                           for a, b in by_worker.items()))
            sp.set_attr("blocks", sum(map(len, by_worker.values())))
            sp.set_attr("workers", len(by_worker))

    async def flush_reports(self) -> None:
        """Send the short-circuit read counts that readers opened from a
        primed entry left at close: one SC_READ_REPORT a worker. The
        caller that primed flushes before it is done, so the worker's
        heat is complete by then."""
        reads, self._primed.reads = self._primed.reads, {}
        for addr, block_reads in reads.items():
            await report_sc_reads(self.pool, addr, block_reads)

    async def open(self, path: str) -> FsReader:
        self._ensure_metrics_task()
        # `locate`, the first phase of a read (docs/observability.md);
        # the reader accounts the others into the same counters
        with Timed(self.counters, "read.phase.locate",
                   self.tracer.span("open", attrs={"path": path})):
            # a primed answer serves one open
            fb = self._primed.files.pop(path, None)
            primed = fb is not None
            if isinstance(fb, err.CurvineError):
                raise fb
            if not primed:
                fb = await self.meta.get_block_locations(path)
        if _freed(fb.status) and fb.status.len:
            # an FsReader over the empty block list would read the
            # whole file as a hole, zeros
            raise err.BlockNotFound(
                f"{path}: freed from the cache, its bytes live in the "
                f"under-store alone (unified_open reads them there)")
        self.counters["read.files"] = self.counters.get("read.files", 0) + 1
        if primed:
            self.counters["read.primed.files"] = \
                self.counters.get("read.primed.files", 0) + 1
        cc = self.conf.client
        return FsReader(self.meta, path, fb, self.pool,
                        chunk_size=cc.read_chunk_size,
                        short_circuit=cc.short_circuit,
                        read_ahead=cc.read_ahead_chunks,
                        counters=self.counters,
                        smart_prefetch=cc.enable_smart_prefetch,
                        seq_threshold=cc.sequential_read_threshold,
                        health=self.health,
                        op_deadline_ms=cc.op_deadline_ms,
                        tracer=self.tracer,
                        verify=cc.read_verify,
                        primed=self._primed if primed else None)

    async def write_all(self, path: str, data: bytes, **kw) -> None:
        # one root span covers create + uploads + complete; every RPC
        # under it (meta calls, WRITE_BLOCK streams) inherits the trace
        # through the ambient context
        with self.tracer.span("write", attrs={"path": path,
                                              "bytes": len(data)}):
            async with await self.create(path, overwrite=True, **kw) as w:
                await w.write(data)

    async def read_all(self, path: str) -> bytes:
        return await self.unified_read(path)

    async def write_files_batch(self, files: dict[str, bytes],
                                storage_type: str | None = None) -> None:
        """Small-file fast path: one metadata round trip per phase and one
        batched block upload per worker (create/add/write/complete all
        batched). Parity: CreateFilesBatch/AddBlocksBatch/WriteBlocksBatch/
        CompleteFilesBatch codes."""
        if not files:
            return
        cc = self.conf.client
        st = _TIERS.get(storage_type or cc.storage_type, StorageType.MEM)
        paths = list(files)
        # create phase rides META_BATCH: the whole create list lands in
        # one journal group. Per-item errors fail the batch, matching the
        # old CREATE_FILES_BATCH all-or-error behavior.
        for r in await self.meta.meta_batch(
                [{"op": "create", "path": p, "overwrite": True,
                  "block_size": cc.block_size, "replicas": 1}
                 for p in paths]):
            if "error" in r:
                raise err.CurvineError.from_wire(r.get("error_code", 0),
                                                 r["error"])
        rep = await self.meta.call(RpcCode.ADD_BLOCKS_BATCH, {"requests": [
            {"path": p, "client_host": self.meta.client_host,
             "commit_blocks": [], "exclude_workers": []}
            for p in paths]}, mutate=True)
        from curvine_tpu.common.types import LocatedBlock
        located = [LocatedBlock.from_wire(r["block"])
                   for r in rep["responses"]]
        # group uploads per worker
        by_worker: dict[str, list[tuple[str, LocatedBlock]]] = {}
        for p, lb in zip(paths, located):
            loc = lb.locs[0]
            addr = f"{loc.ip_addr or loc.hostname}:{loc.rpc_port}"
            by_worker.setdefault(addr, []).append((p, lb))
        worker_of: dict[str, int] = {}
        for addr, items in by_worker.items():
            conn = await self.pool.get(addr)
            body = {"blocks": [
                {"block_id": lb.block.id, "storage_type": int(st),
                 "data": files[p]} for p, lb in items]}
            ack = await conn.call(RpcCode.WRITE_BLOCKS_BATCH, data=pack(body))
            for r in (unpack(ack.data) or {}).get("results", []):
                worker_of[r["block_id"]] = r["worker_id"]
        await self.meta.call(RpcCode.COMPLETE_FILES_BATCH, {"requests": [
            {"path": p, "len": len(files[p]),
             "client_name": self.meta.client_id,
             "commit_blocks": [{
                 "block_id": lb.block.id, "block_len": len(files[p]),
                 "worker_ids": [worker_of.get(lb.block.id,
                                              lb.locs[0].worker_id)],
                 "storage_type": int(st)}]}
            for p, lb in zip(paths, located)]}, mutate=True)

    # ---------------- unified (cache + UFS) ----------------

    async def _ufs_for(self, path: str):
        from curvine_tpu.ufs import create_ufs
        mount = await self.meta.get_mount_info(path)
        if mount is None:
            raise err.MountNotFound(f"no mount covers {path}")
        rel = path[len(mount.cv_path):] if mount.cv_path != "/" else path
        return mount, create_ufs(mount.ufs_path, properties=mount.properties), \
            mount.ufs_path + rel

    async def unified_read(self, path: str) -> bytes:
        """Cache first; fall back to UFS through the mount table."""
        with self.tracer.span("read", attrs={"path": path}) as sp:
            try:
                st = await self.meta.file_status(path)
                if st.is_complete and (st.len == 0 or
                                       await self._has_cached_blocks(path,
                                                                     st)):
                    r = await self.open(path)
                    return await r.read_all()
            except err.FileNotFound:
                pass
            # cache miss: the UFS leg gets its own child span so a trace
            # of a miss shows where the fallback time went
            with self.tracer.span("ufs_read", attrs={"path": path}):
                mount, ufs, uri = await self._ufs_for(path)
                data = await ufs.read_all(uri)
            sp.set_attr("ufs_fallback", True)
            if mount.auto_cache:
                try:
                    await self.write_all(path, data)
                except err.CurvineError as e:
                    log.debug("auto-cache of %s failed: %s", path, e)
            return data

    async def _has_cached_blocks(self, path: str, st) -> bool:
        """Every EXISTING block has a live location. Hole regions (a
        file resized past its written blocks) have no block at all and
        are served as zeros by the read path, so they don't count
        against cachedness — but a FREED file (TTL free / `cv free`:
        blocks dropped, storage state flipped to UFS) is not a hole
        file; its bytes live only in the under-store now."""
        if _freed(st):
            return False
        fb = await self.meta.get_block_locations(path)
        # `st` may be a leased copy from before the master freed the
        # file under cache pressure; the status that came with the
        # block list is the master's own
        if _freed(fb.status):
            return False
        # a committed stripe retires its replicas, so empty locs is the
        # NORMAL cached state for an erasure-coded block — it serves
        # through the cells (degraded decode included)
        return all(lb.locs or lb.ec is not None for lb in fb.block_locs)

    async def unified_open(self, path: str):
        """Open preferring cache; uncached files under a mount stream
        directly from the UFS (FsReader-compatible UfsReader). Cached
        reads are wrapped so a mid-stream replica loss falls back to
        the mounted object transparently (FallbackReader). Under an
        `auto_cache` mount a miss also asks the master for an
        asynchronous load of the file (docs/caching.md, "Auto-cache on
        open"); this read is served from the UFS either way."""
        self._ensure_metrics_task()
        with self.tracer.span("unified_open", attrs={"path": path}) as sp:
            st = await self.meta.file_status(path)
            try:
                cached = not _being_loaded(st) and (
                    st.len == 0 or await self._has_cached_blocks(path, st))
            except err.FileNotFound:
                cached = False      # UFS-only object: no inode yet
            if cached:
                try:
                    r = await self.open(path)
                except err.BlockNotFound:
                    pass        # the master freed it between the answers
                else:
                    if not _being_loaded(r.blocks.status):
                        sp.set_attr("served_by", "cache")
                        return FallbackReader(self, path, r, st)
                    # a load replaced the copy between the two answers:
                    # its bytes so far are not the object
                    await r.close()
            mount, ufs, uri = await self._ufs_for(path)
            if _being_loaded(st):
                # the inode is the load's own, still empty: the object's
                # length is the under-store's to say
                ust = await ufs.stat(uri)
                if ust is None:
                    raise err.FileNotFound(uri)
                length = ust.len
            else:
                length = st.len
            sp.set_attr("served_by", "ufs")
            return self._ufs_reader(path, mount, ufs, uri, length)

    def _ufs_reader(self, path: str, mount, ufs, uri: str, length: int):
        """A reader over the under-store's object for one read the
        cache did not serve — a miss, or a cached block dropped under
        its reader — accounted (read.ufs.files) and, under an
        auto_cache mount, followed by a load of the file."""
        from curvine_tpu.client.ufs_reader import UfsReader
        c = self.counters
        c["read.ufs.files"] = c.get("read.ufs.files", 0) + 1
        if mount.auto_cache:
            self._submit_load(path)
        return UfsReader(ufs, uri, length,
                         chunk_size=self.conf.client.read_chunk_size,
                         counters=c, tracer=self.tracer)

    def _submit_load(self, path: str) -> None:
        """Ask the master, in the background, to load one file into the
        cache unless a load of it is live there. Advisory: a refusal is
        logged and the read that asked is not held up or failed."""
        import asyncio
        c = self.counters

        async def submit():
            try:
                _, outcome = await self.meta.submit_load_if_absent(path)
                key = "cache.load." + outcome
                c[key] = c.get(key, 0) + 1
            except err.CurvineError as e:
                log.debug("auto-cache load of %s not submitted: %s",
                          path, e)

        t = asyncio.ensure_future(submit())
        self._load_submits.add(t)
        t.add_done_callback(self._load_submits.discard)

    async def content_summary(self, path: str) -> dict:
        """Recursive length/file/dir counts: ONE master RPC for pure
        cache subtrees; when the subtree intersects mounts (or the path
        exists only in a UFS), aggregates the unified listing instead —
        the master refuses to sum what partly lives in the UFS."""
        try:
            return await self.meta.content_summary(path)
        except (err.Unsupported, err.FileNotFound):
            pass
        st = await self.meta.file_status(path)   # unified: UFS-aware
        if not st.is_dir:
            return {"length": st.len, "file_count": 1,
                    "directory_count": 0}
        length = file_count = 0
        directory_count = 1                      # count the root itself
        stack = [path]
        while stack:
            p = stack.pop()
            for ch in await self.meta.list_status(p):
                if ch.is_dir:
                    directory_count += 1
                    stack.append(ch.path)
                else:
                    file_count += 1
                    length += ch.len
        return {"length": length, "file_count": file_count,
                "directory_count": directory_count}

    async def load_from_ufs(self, path: str, replicas: int | None = None) -> int:
        """Warm one file: UFS → cache (the worker-side of load tasks).
        Records the UFS object's mtime in the storage policy so fallback
        readers can detect a changed underlying object (ufs_mtime guard,
        reference state::StoragePolicy parity). Per-mount caching policy
        applies: the mount's ttl/storage/replica/block-size defaults
        govern the cached copy (reference state/mount.rs MountInfo)."""
        with self.tracer.span("ufs_load", attrs={"path": path}):
            return await self._load_from_ufs(path, replicas)

    async def _load_from_ufs(self, path: str,
                             replicas: int | None = None) -> int:
        from curvine_tpu.common.types import TtlAction
        mount, ufs, uri = await self._ufs_for(path)
        st = await ufs.stat(uri)
        if st is None:
            raise err.FileNotFound(uri)
        from curvine_tpu.common.types import StoragePolicy
        sp = StoragePolicy(
            # clamp: a UFS that reports mtime 0 must still mark this
            # create as a cache-warming load (read-only-mount exemption)
            ufs_mtime=max(int(st.mtime or 0), 1),
            ttl_ms=getattr(mount, "ttl_ms", 0) or 0,
            ttl_action=TtlAction(int(getattr(mount, "ttl_action", 0) or 0)))
        storage_type = getattr(mount, "storage_type", "") or None
        w = await self.create(
            path, overwrite=True,
            replicas=replicas if replicas is not None
            else (getattr(mount, "replicas", 0) or None),
            block_size=getattr(mount, "block_size", 0) or None,
            storage_type=storage_type, storage_policy=sp.to_wire())
        total = 0
        try:
            async for chunk in ufs.read(uri):
                await w.write(chunk)
                total += len(chunk)
            await w.close()
        except Exception:
            await w.abort()
            raise
        return total

    async def advise(self, path: str, cursor: int = 0, window: int = 8,
                     epoch: int = 0, seed: int = 0) -> dict:
        """Advise the master's rolling prefetch window (docs/caching.md):
        the caller is reading `path`'s shards in the deterministic
        (seed, epoch) order of common/epoch.py and its cursor is at
        shard index `cursor` — keep the next `window` shards warm."""
        return await self.meta.prefetch_window(path, cursor=cursor,
                                               window=window, epoch=epoch,
                                               seed=seed)

    async def prefetch(self, path: str) -> int:
        """Warm one file ahead of a read cursor (the worker side of
        prefetch tasks): already-cached files cost one metadata probe
        and a block touch; uncached mount-backed files load from the
        UFS. Advisory — a file that can't be warmed (freed, no mount)
        is skipped, never an error."""
        try:
            st = await self.meta.file_status(path)
            if st.is_complete and (st.len == 0 or
                                   await self._has_cached_blocks(path, st)):
                return 0               # already warm
        except err.FileNotFound:
            pass
        try:
            return await self.load_from_ufs(path)
        except err.MountNotFound:
            return 0                   # cache-native and gone: advisory

    async def export_to_ufs(self, path: str) -> int:
        """Persist one cached file out to its mounted UFS location."""
        mount, ufs, uri = await self._ufs_for(path)
        r = await self.open(path)
        try:
            total = await ufs.write(uri, r.chunks())
        finally:
            await r.close()
        return total

    async def write_through(self, path: str, data: bytes) -> None:
        """WriteType.FS: persist to UFS and cache."""
        mount, ufs, uri = await self._ufs_for(path)
        await ufs.write_all(uri, data)
        try:
            await self.write_all(path, data)
        except err.CurvineError as e:
            log.debug("cache copy of %s failed: %s", path, e)


def _freed(st) -> bool:
    """The master dropped the file's blocks and kept its inode (TTL
    free, `cv free`, cache pressure): its bytes live in the under-store
    alone, and its empty block list is not a hole."""
    return st.storage_policy.state == StorageState.UFS


def _being_loaded(st) -> bool:
    """An inode a UFS→cache load has created and not completed yet
    (load_from_ufs stamps ufs_mtime at create): its bytes so far are not
    the object, so readers go to the under-store until it completes."""
    return not st.is_complete and not st.is_dir \
        and bool(st.storage_policy.ufs_mtime)


# errors that mean "the cached copy is unreachable", not "the request is
# wrong" — only these divert a read to the UFS
_FALLBACK_CODES = frozenset({
    err.ErrorCode.BLOCK_NOT_FOUND, err.ErrorCode.WORKER_NOT_FOUND,
    err.ErrorCode.NO_AVAILABLE_WORKER, err.ErrorCode.CONNECT,
    err.ErrorCode.TIMEOUT, err.ErrorCode.IO, err.ErrorCode.ABNORMAL_DATA,
})


class FallbackReader:
    """Cached read stream that survives losing every replica mid-read.

    Parity: curvine-client/src/unified/ FallbackFsReader (and the Java
    SDK's CurvineFallbackInputStream): when a cached block becomes
    unreadable (workers died, block evicted under us), the stream
    reopens against the mounted UFS object and RESUMES at the position
    the caller's operation STARTED at — partial progress inside a
    failed read() is re-read, never silently skipped. Consistency
    follows the mount's write mode (reference fallback_read_test.rs
    TC-12..21): FS-mode mounts (write-through) require the recorded
    storage_policy.ufs_mtime to match the object or fail ABNORMAL_DATA;
    CACHE-mode mounts serve the CURRENT object (it may have grown or
    shrunk — a resume past its end fails instead of fabricating EOF).
    Files outside any mount simply re-raise the original cache error.
    """

    def __init__(self, client: CurvineClient, path: str, primary, st):
        self._client = client
        self._path = path
        self._r = primary            # FsReader until fallback, then UfsReader
        self._st = st
        self._fell_back = False

    # reader surface delegates (len/pos live on the active reader)
    @property
    def len(self):
        return self._r.len

    @property
    def pos(self):
        return self._r.pos

    def seek(self, pos: int) -> None:
        self._r.seek(pos)

    async def _fallback(self, cause: err.CurvineError, resume: int):
        if self._fell_back or cause.code not in _FALLBACK_CODES:
            raise cause
        try:
            mount, ufs, uri = await self._client._ufs_for(self._path)
        except err.CurvineError:
            raise cause              # not mounted: nothing to fall back to
        ust = await ufs.stat(uri)
        if ust is None:
            raise cause
        from curvine_tpu.common.types import WriteType
        recorded = self._st.storage_policy.ufs_mtime
        if mount.write_type == WriteType.FS:
            # write-through mount: the object must be the exact
            # generation that was cached — unknown mtimes refuse too
            if not recorded or not ust.mtime or ust.mtime != recorded:
                raise err.AbnormalData(
                    f"{self._path}: UFS object generation unknown or "
                    f"changed (mtime {ust.mtime} != recorded {recorded})"
                ) from cause
        elif ust.len < resume:
            # CACHE mode serves the current object, but it shrank past
            # the caller's offset (TC-18): resuming would fabricate EOF
            raise err.AbnormalData(
                f"{self._path}: UFS object shrank to {ust.len} below "
                f"read offset {resume}") from cause
        try:
            await self._r.close()
        except Exception:            # noqa: BLE001 — old stream is dead
            pass
        # the lost-replica event is an error span (always recorded, even
        # unsampled) so a trace of the degraded read names its cause
        self._client.tracer.span(
            "ufs_fallback", attrs={"path": self._path, "resume": resume}
        ).error(cause).finish()
        # routine where the cache is smaller than the set (a block can
        # be dropped between the master's answer and the read): counted
        # and traced, not a warning
        log.info("read fallback to UFS for %s at offset %d (%s)",
                 self._path, resume, cause)
        c = self._client.counters
        c["read.ufs_fallbacks"] = c.get("read.ufs_fallbacks", 0) + 1
        self._r = self._client._ufs_reader(self._path, mount, ufs, uri,
                                           ust.len)
        self._fell_back = True

    async def _do(self, op: str, *args):
        # resume point = the offset the caller's op STARTED at; a failed
        # read() may have advanced pos past bytes it then threw away,
        # and those must be re-read on the fallback stream. Positional
        # ops resume at their own offset (the shrink guard needs it:
        # a pread mid-file on a shrunken object must error, not EOF).
        if op in ("pread", "pread_view", "read_range"):
            resume = args[0]
        elif op == "read":
            resume = getattr(self._r, "pos", 0)
        else:
            resume = 0
        try:
            return await getattr(self._r, op)(*args)
        except err.CurvineError as e:
            await self._fallback(e, resume)
            if op == "read":
                self._r.seek(resume)
            return await getattr(self._r, op)(*args)

    async def read(self, n: int = -1) -> bytes:
        return await self._do("read", n)

    async def read_all(self) -> bytes:
        return await self._do("read_all")

    async def pread(self, offset: int, n: int) -> bytes:
        return await self._do("pread", offset, n)

    async def pread_view(self, offset: int, n: int):
        return await self._do("pread_view", offset, n)

    async def read_range(self, offset: int, n: int, parallel: int = 1):
        return await self._do("read_range", offset, n, parallel)

    async def mmap_view(self, offset: int, n: int):
        # mmap is a short-circuit-only optimization; a None return makes
        # callers take the pread path (which carries the fallback)
        try:
            return await self._r.mmap_view(offset, n)
        except err.CurvineError:
            return None

    async def chunks(self, chunk_size: int | None = None):
        # stream from the current position; a mid-iteration failure
        # restarts chunking on the fallback reader at the same offset
        while True:
            data = await self._do("read", chunk_size
                                  or self._client.conf.client
                                  .read_chunk_size)
            if not data:
                return
            yield data

    async def close(self) -> None:
        await self._r.close()
