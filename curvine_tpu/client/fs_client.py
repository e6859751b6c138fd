"""Metadata RPC client.

Parity: curvine-client/src/rpc/ (FsClient with master failover + retry) —
every mutation carries (client_id, call_id) for the master's retry cache."""

from __future__ import annotations

import itertools
import logging
import socket
import time
import uuid

from curvine_tpu.common import errors as err
from curvine_tpu.common.conf import ClusterConf
from curvine_tpu.common.types import (
    CommitBlock, FileBlocks, FileStatus, JobInfo, LocatedBlock, MasterInfo,
    MountInfo, SetAttrOpts,
)
from curvine_tpu.client.meta_cache import MISS, MetaCache, parent_dir
from curvine_tpu.obs.trace import NULL_SPAN
from curvine_tpu.rpc import RpcCode
from curvine_tpu.rpc.client import Connection, ConnectionPool, RetryPolicy
from curvine_tpu.rpc.frame import pack, unpack

log = logging.getLogger(__name__)


def _os_user() -> str:
    try:
        import getpass
        return getpass.getuser()
    except Exception:
        return "root"


def _os_groups(user: str) -> list[str]:
    """Primary AND supplementary groups (getgrouplist) — primary-only
    would deny group-permission access the OS actually grants."""
    try:
        import grp
        import os
        import pwd
        gid = pwd.getpwnam(user).pw_gid
        names = []
        # primary group FIRST — getgrouplist order is unspecified and the
        # master assigns groups[0] to newly created files
        for g in [gid] + [x for x in os.getgrouplist(user, gid) if x != gid]:
            try:
                names.append(grp.getgrgid(g).gr_name)
            except KeyError:
                continue
        return names
    except Exception:
        return []


# meta codes whose tracing would only be telemetry-about-telemetry
_UNTRACED = frozenset({RpcCode.METRICS_REPORT, RpcCode.GET_SPANS})


class FsClient:
    def __init__(self, conf: ClusterConf | None = None):
        self.conf = conf or ClusterConf()
        # optional Tracer (set by CurvineClient): each meta RPC becomes
        # a client span; the context is stamped into the RPC header by
        # the connection layer so the master's span links to it
        self.tracer = None
        # optional counter dict (set by CurvineClient, as `tracer` is):
        # per master call the client's wall time beside the server's own
        # queue and handle time from the reply (meta.*), so the three
        # parts of a call — the master, its queue, everything between —
        # can be told apart from this side
        self.counters: dict | None = None
        cc = self.conf.client
        self.masters = list(cc.master_addrs)
        self._active = 0
        self.pool = ConnectionPool(size=cc.conn_pool_size,
                                   timeout_ms=cc.rpc_timeout_ms,
                                   rpc_conf=self.conf.rpc)
        self.retry = RetryPolicy(max_retries=cc.conn_retry_max,
                                 base_ms=cc.conn_retry_base_ms)
        self.client_id = uuid.uuid4().hex
        self._call_ids = itertools.count(1)
        self.client_host = socket.gethostname()
        # identity for master-side ACL checks (acl_feature.rs parity)
        self.user = cc.user or _os_user()
        self.groups = list(cc.groups) or _os_groups(self.user)
        # native metadata fast path (master advertises it in MasterInfo)
        self._fast_enabled = cc.fast_meta
        self._fast_addr: str | None = None
        self._fast_probe_after = 0.0     # monotonic; throttles rediscovery
        # metadata lease cache (client/meta_cache.py): consulted before
        # either port; the master pushes META_INVALIDATE frames over
        # this pool's already-open conns, delivered via _on_push
        self.cache: MetaCache | None = None
        if cc.meta_cache:
            self.cache = MetaCache(entries=cc.meta_cache_entries)
            self.pool.set_push_handler(self._on_push)

    def _on_push(self, msg) -> None:
        """Unsolicited master frame on a pooled conn. Read-loop context:
        must not block. Epoch changes flush (master restarted — leases
        are soft state); paths sweep subtrees (rename/recursive delete
        push only the top path)."""
        if self.cache is None or msg.code != RpcCode.META_INVALIDATE:
            return
        body = unpack(msg.data) or {}
        self.cache.note_epoch(body.get("epoch"))
        self.cache.invalidate(body.get("paths") or (), subtree=True)

    def _inval(self, *paths: str, subtree: bool = False) -> None:
        """Local mutation succeeded: drop our own cached entries for the
        touched paths (read-your-writes on the writing client)."""
        if self.cache is not None:
            self.cache.invalidate([p for p in paths if p], subtree=subtree)

    def _cache_put(self, path: str, st) -> None:
        if self.cache is not None:
            self.cache.put("stat", path, st)

    async def close(self) -> None:
        await self.pool.close()

    async def _conn(self) -> Connection:
        return await self.pool.get(self.masters[self._active])

    async def call(self, code: RpcCode, req: dict, mutate: bool = False,
                   deadline=None) -> dict:
        req = dict(req)
        req.setdefault("user", self.user)
        req.setdefault("groups", self.groups)
        if mutate:
            req["client_id"] = self.client_id
            req["call_id"] = next(self._call_ids)

        # (the metrics push and the span collect neither trace nor
        # count themselves: an idle client would never fall silent)
        counted = code not in _UNTRACED
        span = NULL_SPAN
        if self.tracer is not None and counted:
            span = self.tracer.span(f"meta.{RpcCode(code).name.lower()}")

        async def once() -> dict:
            try:
                t0 = time.perf_counter()
                conn = await self._conn()
                rep = await conn.call(code, data=pack(req),
                                      deadline=deadline)
                if counted:
                    self._count_call(time.perf_counter() - t0,
                                     rep.srv_seconds(), span)
                return unpack(rep.data) or {}
            except err.CurvineError as e:
                if e.code in (err.ErrorCode.NOT_LEADER, err.ErrorCode.CONNECT):
                    self._note_leader_hint(e)
                    # the fast plane follows the leader: rediscover it
                    self._fast_addr = None
                    self._fast_probe_after = 0.0
                raise

        with span:
            # the retry policy never sleeps past the caller's budget
            return await self.retry.run(once, deadline=deadline)

    def _count_call(self, wall_s: float, srv, span) -> None:
        """One answered call of the Python port: meta.calls, meta.wall_s
        and — where the peer sent its own time — meta.srv_queue_s,
        meta.srv_handle_s, so that wall − queue − handle is the
        connection, the wire and this client's loop. (The native fast
        plane sends none and is not counted here: it would dilute the
        means.)"""
        queue_s, handle_s = srv or (0.0, 0.0)
        if srv is not None:
            span.set_attr("srv_queue_us", round(queue_s * 1e6))
            span.set_attr("srv_handle_us", round(handle_s * 1e6))
        c = self.counters
        if c is None:
            return
        for key, v in (("meta.calls", 1), ("meta.wall_s", wall_s),
                       ("meta.srv_queue_s", queue_s),
                       ("meta.srv_handle_s", handle_s)):
            c[key] = c.get(key, 0) + v

    def _note_leader_hint(self, e: err.CurvineError) -> None:
        """NOT_LEADER redirect handling: adopt the member list the error
        carries (the cluster may have grown/shrunk since our conf was
        written) and jump straight to the hinted leader; with no hint,
        fall back to round-robin rotation."""
        members = getattr(e, "members", None)
        if members:
            cur = self.masters[self._active] if self.masters else None
            self.masters = list(members)
            self._active = (self.masters.index(cur)
                            if cur in self.masters
                            else self._active % len(self.masters))
        hint = getattr(e, "leader_hint", None)
        if hint:
            if hint not in self.masters:
                self.masters.append(hint)
            self._active = self.masters.index(hint)
            return                      # don't rotate off a fresh hint
        self._active = (self._active + 1) % len(self.masters)

    # ---------------- native metadata fast path ----------------

    async def _fast_call(self, code: RpcCode, req: dict) -> dict | None:
        """Try the master's native read plane; None → use the Python
        port (not discovered, gated off, or the mirror can't answer).
        Authoritative errors (e.g. PermissionDenied) propagate."""
        if not self._fast_enabled:
            return None
        if self._fast_addr is None:
            now = time.monotonic()
            if now < self._fast_probe_after:
                return None
            self._fast_probe_after = now + 30.0
            try:
                info = await self.master_info()
                self._fast_addr = info.fast_addr or None
            except Exception:  # noqa: BLE001 — discovery is best-effort
                return None
            if self._fast_addr is None:
                return None
        req = dict(req)
        req.setdefault("user", self.user)
        req.setdefault("groups", self.groups)
        try:
            conn = await self.pool.get(self._fast_addr)
            rep = await conn.call(code, data=pack(req))
            return unpack(rep.data) or {}
        except err.CurvineError as e:
            if e.code == err.ErrorCode.FAST_MISS:
                return None
            if e.code == err.ErrorCode.PERMISSION_DENIED:
                raise                    # authoritative: ACL-exact denial
            # FAST_GATED (non-leader), CONNECT/TIMEOUT, and anything
            # unexpected: drop the address and use the Python port —
            # the fast plane is best-effort and must never turn an
            # answerable request into a hard failure
            self._fast_addr = None
            return None

    # ---------------- namespace API ----------------

    async def mkdir(self, path: str, create_parent: bool = True,
                    **kw) -> FileStatus:
        rep = await self.call(RpcCode.MKDIR,
                              {"path": path, "create_parent": create_parent,
                               **kw}, mutate=True)
        st = FileStatus.from_wire(rep["status"])
        self._inval(path)
        self._cache_put(path, st)
        return st

    async def create_file(self, path: str, overwrite: bool = False,
                          **kw) -> FileStatus:
        req = {"path": path, "overwrite": overwrite,
               "replicas": kw.pop("replicas", self.conf.client.replicas),
               "block_size": kw.pop("block_size", self.conf.client.block_size),
               "client_name": self.client_id, **kw}
        rep = await self.call(RpcCode.CREATE_FILE, req, mutate=True)
        st = FileStatus.from_wire(rep["status"])
        self._inval(path)
        self._cache_put(path, st)
        return st

    async def append_file(self, path: str) -> FileBlocks:
        rep = await self.call(RpcCode.APPEND_FILE,
                              {"path": path, "client_name": self.client_id},
                              mutate=True)
        self._inval(path)
        return FileBlocks.from_wire(rep["file_blocks"])

    async def exists(self, path: str) -> bool:
        if self.cache is not None:
            v = self.cache.get("stat", path)
            if v is not MISS:
                return v is not None
            try:
                # the stat flow populates the cache, negatives included
                await self.file_status(path)
                return True
            except err.FileNotFound:
                return False
        rep = await self._fast_call(RpcCode.EXISTS, {"path": path})
        if rep is not None:
            return rep["exists"]
        return (await self.call(RpcCode.EXISTS, {"path": path}))["exists"]

    async def file_status(self, path: str) -> FileStatus:
        mc = self.cache
        if mc is None:
            rep = await self._fast_call(RpcCode.FILE_STATUS, {"path": path})
            if rep is None:
                rep = await self.call(RpcCode.FILE_STATUS, {"path": path})
            return FileStatus.from_wire(rep["status"])
        v = mc.get("stat", path)
        if v is not MISS:
            if v is None:
                raise err.FileNotFound(path)
            return v
        d = parent_dir(path)
        if mc.lease_ok(d):
            # the directory lease is warm (the master knows to push us
            # invalidations): misses may ride the native fast plane
            rep = await self._fast_call(RpcCode.FILE_STATUS, {"path": path})
            if rep is not None:
                st = FileStatus.from_wire(rep["status"])
                mc.put("stat", path, st)
                return st
        try:
            rep = await self.call(RpcCode.FILE_STATUS,
                                  {"path": path, "lease": True})
        except err.FileNotFound:
            # the master registers leases on misses too: cache the
            # negative so repeat stats of absent paths stay local
            mc.note_dir(d)
            mc.put("stat", path, None)
            raise
        tok = rep.get("lease")
        if tok:
            mc.note_lease(tok, d)
        st = FileStatus.from_wire(rep["status"])
        mc.put("stat", path, st)
        return st

    async def list_status(self, path: str) -> list[FileStatus]:
        mc = self.cache
        if mc is None:
            rep = await self._fast_call(RpcCode.LIST_STATUS, {"path": path})
            if rep is None:
                rep = await self.call(RpcCode.LIST_STATUS, {"path": path})
            return [FileStatus.from_wire(s) for s in rep["statuses"]]
        v = mc.get("list", path)
        if v is not MISS:
            return list(v)
        rep = None
        if mc.lease_ok(path):
            rep = await self._fast_call(RpcCode.LIST_STATUS, {"path": path})
        if rep is None:
            rep = await self.call(RpcCode.LIST_STATUS,
                                  {"path": path, "lease": True})
            tok = rep.get("lease")
            if tok:
                mc.note_lease(tok, path)
        sts = [FileStatus.from_wire(s) for s in rep["statuses"]]
        mc.put("list", path, sts)
        return list(sts)

    async def delete(self, path: str, recursive: bool = False) -> None:
        await self.call(RpcCode.DELETE,
                        {"path": path, "recursive": recursive}, mutate=True)
        self._inval(path, subtree=recursive)

    async def meta_batch(self, requests: list[dict]) -> list[dict]:
        """Batched metadata mutations in ONE round trip. Each request is
        ``{"op": "mkdir"|"create"|"delete", "path": ..., ...}``; the reply
        list is positional, with per-item failures returned as
        ``{"error", "error_code"}`` instead of raising."""
        reqs = []
        for r in requests:
            r = dict(r)
            if r.get("op") == "create":
                r.setdefault("replicas", self.conf.client.replicas)
                r.setdefault("block_size", self.conf.client.block_size)
                r.setdefault("client_name", self.client_id)
            reqs.append(r)
        rep = await self.call(RpcCode.META_BATCH, {"requests": reqs},
                              mutate=True)
        self._inval(*[r.get("path", "") for r in reqs], subtree=True)
        return rep["responses"]

    async def rename(self, src: str, dst: str) -> bool:
        rep = await self.call(RpcCode.RENAME, {"src": src, "dst": dst},
                              mutate=True)
        self._inval(src, dst, subtree=True)
        return rep["result"]

    async def set_attr(self, path: str, opts: SetAttrOpts) -> None:
        await self.call(RpcCode.SET_ATTR,
                        {"path": path, "opts": opts.to_wire()}, mutate=True)
        self._inval(path, subtree=True)   # recursive mode/ttl sweeps

    async def symlink(self, target: str, link: str) -> FileStatus:
        rep = await self.call(RpcCode.SYMLINK,
                              {"target": target, "link": link}, mutate=True)
        st = FileStatus.from_wire(rep["status"])
        self._inval(link)
        self._cache_put(link, st)
        return st

    async def link(self, src: str, dst: str) -> FileStatus:
        rep = await self.call(RpcCode.LINK, {"src": src, "dst": dst},
                              mutate=True)
        st = FileStatus.from_wire(rep["status"])
        self._inval(src, dst)
        self._cache_put(dst, st)
        return st

    async def resize_file(self, path: str, new_len: int) -> None:
        await self.call(RpcCode.RESIZE_FILE,
                        {"path": path, "len": new_len}, mutate=True)
        self._inval(path)

    async def free(self, path: str, recursive: bool = False) -> int:
        rep = await self.call(RpcCode.FREE,
                              {"path": path, "recursive": recursive},
                              mutate=True)
        self._inval(path, subtree=recursive)
        return rep.get("freed", 0)

    # ---------------- block API ----------------

    async def add_block(self, path: str,
                        commit_blocks: list[CommitBlock] | None = None,
                        exclude_workers: list[int] | None = None,
                        ici_coords: list[int] | None = None,
                        abandon_block: int | None = None) -> LocatedBlock:
        rep = await self.call(RpcCode.ADD_BLOCK, {
            "path": path, "client_host": self.client_host,
            "commit_blocks": [c.to_wire() for c in commit_blocks or []],
            "exclude_workers": exclude_workers or [],
            "ici_coords": ici_coords or [],
            "abandon_block": abandon_block}, mutate=True)
        return LocatedBlock.from_wire(rep["block"])

    async def complete_file(self, path: str, length: int,
                            commit_blocks: list[CommitBlock] | None = None,
                            only_flush: bool = False) -> bool:
        rep = await self.call(RpcCode.COMPLETE_FILE, {
            "path": path, "len": length,
            "commit_blocks": [c.to_wire() for c in commit_blocks or []],
            "client_name": self.client_id, "only_flush": only_flush},
            mutate=True)
        self._inval(path)
        return rep["result"]

    async def get_block_locations(self, path: str,
                                  deadline=None) -> FileBlocks:
        rep = await self.call(RpcCode.GET_BLOCK_LOCATIONS, {"path": path},
                              deadline=deadline)
        return FileBlocks.from_wire(rep["file_blocks"])

    async def get_block_locations_batch(
            self, paths: list[str]) -> list[FileBlocks | err.CurvineError]:
        """get_block_locations for a list of paths in one round trip:
        positional, a path's error beside the others' block lists (an
        exception of the type its own call would raise, not raised)."""
        rep = await self.call(RpcCode.GET_BLOCK_LOCATIONS_BATCH,
                              {"paths": paths})
        return [err.CurvineError.from_wire(r.get("error_code", 0), r["error"])
                if "error" in r else FileBlocks.from_wire(r["file_blocks"])
                for r in rep["responses"]]

    async def master_info(self) -> MasterInfo:
        rep = await self.call(RpcCode.GET_MASTER_INFO, {})
        return MasterInfo.from_wire(rep["info"])

    async def cluster_health(self) -> dict:
        """Cluster-health rollup: master role, liveness, capacity,
        replication debt and the dir-watchdog's stuck-op snapshot.
        Parity: master_monitor.rs + fs_dir_watchdog.rs."""
        return await self.call(RpcCode.CLUSTER_HEALTH, {})

    async def shard_table(self) -> list[dict]:
        """Per-shard rows of the sharded namespace plane (empty on an
        unsharded master): inode/block counts, journal seq, queue
        depth, qps."""
        rep = await self.call(RpcCode.SHARD_TABLE, {})
        return rep.get("shards", [])

    async def read_plane_stats(self) -> dict:
        """The full SHARD_TABLE reply: {"shards", "leases"?,
        "meta_cache"?, "fastmeta"?} — shard rows plus the read
        fan-out plane's rollup (docs/read-plane.md). `cv report`
        uses this so one RPC feeds both tables."""
        return await self.call(RpcCode.SHARD_TABLE, {})

    async def tenant_stats(self) -> dict:
        """The master's admission-control snapshot (common/qos.py):
        shed level plus per-tenant qps/quota/inflight/throttled."""
        return await self.call(RpcCode.TENANT_STATS, {})

    # ---------------- raft membership plane ----------------

    async def raft_status(self) -> dict:
        """RAFT_STATUS from whichever master we're pointed at — answers
        on ANY node (role, term, leader, voters/learners, match lag)."""
        return await self.call(RpcCode.RAFT_STATUS, {})

    async def refresh_masters(self) -> list[str]:
        """Re-learn the master list from the cluster's active raft
        config (a node added with `cv raft add` is unknown to a conf
        written before it joined)."""
        st = await self.raft_status()
        members = [a for a in (st.get("voters") or {}).values() if a]
        if members:
            cur = (self.masters[self._active]
                   if self._active < len(self.masters) else None)
            self.masters = members
            self._active = (members.index(cur) if cur in members else 0)
        return list(self.masters)

    async def raft_member_change(self, action: str, node_id: int,
                                 addr: str = "") -> dict:
        """add/promote/remove a member (leader-routed; the ack means the
        config entry committed on a quorum)."""
        return await self.call(RpcCode.RAFT_MEMBER_CHANGE,
                               {"action": action, "node_id": node_id,
                                "addr": addr}, mutate=True)

    async def raft_transfer(self, target: int | None = None) -> int:
        """Graceful leader handoff; returns the new leader's node id."""
        rep = await self.call(RpcCode.RAFT_TRANSFER, {"target": target})
        return rep.get("target", 0)

    async def list_options(self, path: str, pattern: str | None = None,
                           dirs_only: bool = False, files_only: bool = False,
                           offset: int = 0, limit: int = 0
                           ) -> tuple[list[FileStatus], int]:
        rep = await self.call(RpcCode.LIST_OPTIONS, {
            "path": path, "pattern": pattern, "dirs_only": dirs_only,
            "files_only": files_only, "offset": offset, "limit": limit})
        return ([FileStatus.from_wire(s) for s in rep["statuses"]],
                rep["total"])

    async def set_lock(self, path: str, kind: str = "exclusive",
                       ttl_ms: int = 60_000) -> dict:
        rep = await self.call(RpcCode.SET_LOCK, {
            "path": path, "owner": self.client_id, "kind": kind,
            "ttl_ms": ttl_ms}, mutate=True)
        return rep["lock"]

    async def release_lock(self, path: str) -> bool:
        rep = await self.call(RpcCode.SET_LOCK, {
            "path": path, "owner": self.client_id, "release": True},
            mutate=True)
        return rep.get("released", False)

    async def get_lock(self, path: str) -> list[dict]:
        return (await self.call(RpcCode.GET_LOCK, {"path": path}))["locks"]

    async def list_locks(self) -> list[dict]:
        return (await self.call(RpcCode.LIST_LOCK, {}))["locks"]

    async def assign_worker(self, exclude: list[int] | None = None,
                            ici_coords: list[int] | None = None):
        from curvine_tpu.common.types import WorkerAddress
        rep = await self.call(RpcCode.ASSIGN_WORKER, {
            "client_host": self.client_host,
            "exclude_workers": exclude or [],
            "ici_coords": ici_coords or []})
        return WorkerAddress.from_wire(rep["worker"])

    async def report_metrics(self, counters: dict,
                             spans: list[dict] | None = None) -> None:
        req: dict = {"counters": counters}
        if spans:
            req["spans"] = spans
        await self.call(RpcCode.METRICS_REPORT, req)

    async def decommission_worker(self, worker_id: int,
                                  on: bool = True) -> int:
        """Mark a worker draining (no new blocks; replicas re-replicate
        elsewhere; DECOMMISSIONED once drained) or restore it."""
        rep = await self.call(RpcCode.DECOMMISSION_WORKER,
                              {"worker_id": worker_id, "on": on},
                              mutate=True)
        return rep["state"]

    # ---------------- mounts / jobs ----------------

    async def content_summary(self, path: str) -> dict:
        """length / file_count / directory_count of a subtree, computed
        master-side in one RPC."""
        return await self.call(RpcCode.CONTENT_SUMMARY, {"path": path})

    async def mount(self, cv_path: str, ufs_path: str,
                    properties: dict | None = None, auto_cache: bool = False,
                    write_type: int = 0, ttl_ms: int = 0, ttl_action: int = 0,
                    storage_type: str = "", block_size: int = 0,
                    replicas: int = 0, access_mode: str = "rw") -> MountInfo:
        rep = await self.call(RpcCode.MOUNT, {
            "cv_path": cv_path, "ufs_path": ufs_path,
            "properties": properties or {}, "auto_cache": auto_cache,
            "write_type": write_type, "ttl_ms": ttl_ms,
            "ttl_action": ttl_action, "storage_type": storage_type,
            "block_size": block_size, "replicas": replicas,
            "access_mode": access_mode}, mutate=True)
        self._inval(cv_path, subtree=True)
        return MountInfo.from_wire(rep["mount"])

    async def umount(self, cv_path: str) -> None:
        await self.call(RpcCode.UNMOUNT, {"cv_path": cv_path}, mutate=True)
        self._inval(cv_path, subtree=True)

    async def update_mount(self, cv_path: str,
                           properties: dict | None = None,
                           auto_cache: bool | None = None,
                           ttl_ms: int | None = None,
                           ttl_action: int | None = None,
                           access_mode: str | None = None) -> MountInfo:
        rep = await self.call(RpcCode.UPDATE_MOUNT, {
            "cv_path": cv_path, "properties": properties,
            "auto_cache": auto_cache, "ttl_ms": ttl_ms,
            "ttl_action": ttl_action, "access_mode": access_mode},
            mutate=True)
        return MountInfo.from_wire(rep["mount"])

    async def mount_table(self) -> list[MountInfo]:
        rep = await self.call(RpcCode.GET_MOUNT_TABLE, {})
        return [MountInfo.from_wire(m) for m in rep["mounts"]]

    async def get_mount_info(self, path: str) -> MountInfo | None:
        rep = await self.call(RpcCode.GET_MOUNT_INFO, {"path": path})
        return MountInfo.from_wire(rep["mount"]) if rep.get("mount") else None

    async def submit_job(self, kind: str, path: str, recursive: bool = True,
                         replicas: int = 1) -> str:
        rep = await self.call(RpcCode.SUBMIT_JOB, {
            "kind": kind, "path": path, "recursive": recursive,
            "replicas": replicas}, mutate=True)
        return rep["job_id"]

    async def submit_load(self, path: str, recursive: bool = True,
                          replicas: int = 1) -> str:
        return await self.submit_job("load", path, recursive, replicas)

    async def submit_load_if_absent(self, path: str) -> tuple[str, str]:
        """Load one file unless a load of that path is live at the
        master: (job id, outcome), the outcome "submitted" or "deduped"
        (that path's live job)."""
        rep = await self.call(RpcCode.SUBMIT_JOB, {
            "kind": "load", "path": path, "recursive": False,
            "replicas": 1, "if_absent": True}, mutate=True)
        return rep["job_id"], rep["outcome"]

    async def prefetch_window(self, path: str, cursor: int = 0,
                              window: int = 8, epoch: int = 0,
                              seed: int = 0) -> dict:
        """Epoch-aware prefetch advise (docs/caching.md): tell the
        master where the read cursor is in the deterministic
        (seed, epoch) shard order; it keeps `window` shards warm ahead."""
        return await self.call(RpcCode.PREFETCH_WINDOW, {
            "path": path, "cursor": int(cursor), "window": int(window),
            "epoch": int(epoch), "seed": int(seed)}, mutate=True)

    async def submit_export(self, path: str, recursive: bool = True) -> str:
        return await self.submit_job("export", path, recursive)

    async def job_status(self, job_id: str) -> JobInfo:
        rep = await self.call(RpcCode.GET_JOB_STATUS, {"job_id": job_id})
        return JobInfo.from_wire(rep["job"])

    async def cancel_job(self, job_id: str) -> None:
        await self.call(RpcCode.CANCEL_JOB, {"job_id": job_id}, mutate=True)
