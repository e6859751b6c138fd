"""Quota management + master-driven cache eviction.

Parity: curvine-server/src/master/quota/ (quota_manager.rs,
eviction/{evictor,lfu}.rs). Two responsibilities:

* per-directory quotas — byte/file limits stored on the inode
  (``quota.bytes`` / ``quota.files`` x-attrs), enforced against the
  subtree's usage on create/add_block;
* cluster cache pressure — when aggregate available capacity drops below
  the watermark, free the coldest complete files (LRU by atime, LFU tie
  break via access counter) until below the low watermark. Freed files
  keep their metadata (UFS-backed data stays reachable)."""

from __future__ import annotations

import asyncio
import logging

from curvine_tpu.common import errors as err
from curvine_tpu.common.types import StorageState

log = logging.getLogger(__name__)

QUOTA_BYTES = "quota.bytes"
QUOTA_FILES = "quota.files"


class QuotaManager:
    def __init__(self, fs, high_water: float = 0.92, low_water: float = 0.80,
                 check_interval_s: float = 5.0, usage_ttl_s: float = 2.0):
        self.fs = fs
        self.high_water = high_water
        self.low_water = low_water
        self.check_interval_s = check_interval_s
        self.usage_ttl_s = usage_ttl_s
        # called with the path of each file freed under cache pressure:
        # the master pushes the invalidation to clients that hold its
        # status under a read lease (their copy still says "cached")
        self.on_free = None
        # quota'd-dir usage cache: inode id -> [bytes, files, expiry].
        # The subtree walk is O(subtree) — unaffordable per create on big
        # namespaces — so enforcement reads a TTL'd snapshot and bumps it
        # optimistically for admissions inside the window (bursts between
        # walks still count against the quota).
        self._usage_cache: dict[int, list] = {}

    # ---------------- quotas ----------------

    def set_quota(self, path: str, max_bytes: int | None = None,
                  max_files: int | None = None) -> None:
        node = self.fs.tree.resolve(path)
        if node is None or not node.is_dir:
            raise err.NotADirectory(path)
        from curvine_tpu.common.types import SetAttrOpts
        add, remove = {}, []
        for key, v in ((QUOTA_BYTES, max_bytes), (QUOTA_FILES, max_files)):
            if v is None:
                remove.append(key)
            else:
                add[key] = str(v).encode()
        self.fs.set_attr(path, SetAttrOpts(add_x_attr=add,
                                           remove_x_attr=remove))

    def get_quota(self, path: str) -> dict:
        node = self.fs.tree.resolve(path)
        if node is None:
            raise err.FileNotFound(path)
        usage_bytes, usage_files = self._usage(node)
        return {
            "bytes": _int_attr(node, QUOTA_BYTES),
            "files": _int_attr(node, QUOTA_FILES),
            "used_bytes": usage_bytes,
            "used_files": usage_files,
        }

    def _usage(self, node) -> tuple[int, int]:
        if not node.is_dir:
            return node.len, 1
        b = f = 0
        for _name, child in self.fs.tree.children(node):
            cb, cf = self._usage(child)
            b += cb
            f += cf
        return b, f

    def _cached_usage(self, node) -> list:
        """[bytes, files, expiry, walked_clean] for a quota'd dir,
        rewalked past TTL. walked_clean: the snapshot came straight from
        a walk (no optimistic bumps since), so a denial may trust it."""
        import time
        ent = self._usage_cache.get(node.id)
        now = time.monotonic()
        if ent is None or ent[2] <= now:
            b, f = self._usage(node)
            ent = self._usage_cache[node.id] = [b, f,
                                                now + self.usage_ttl_s, True]
        return ent

    def invalidate(self, path: str) -> None:
        """Drop cached usage for every ancestor of `path` — called after
        deletes/frees/renames so freed quota is admissible immediately
        (the deny path trusts clean snapshots inside their TTL)."""
        parent, _ = self.fs.tree.resolve_parent(path)
        node = parent
        while node is not None:
            self._usage_cache.pop(node.id, None)
            node = self.fs.tree.get(node.parent_id) \
                if node.parent_id else None

    def check_create(self, path: str, new_bytes: int = 0,
                     new_files: int = 1, parent=None) -> None:
        """Walk ancestors of `path`; any quota'd dir must have room.
        Callers that already resolved the parent pass it to skip the
        path walk (create hot path)."""
        if parent is None:
            parent, _ = self.fs.tree.resolve_parent(path)
        node = parent
        while node is not None:
            xa = node.x_attr
            if not xa or (QUOTA_BYTES not in xa and QUOTA_FILES not in xa):
                node = self.fs.tree.get(node.parent_id) \
                    if node.parent_id else None
                continue
            qb = _int_attr(node, QUOTA_BYTES)
            qf = _int_attr(node, QUOTA_FILES)
            if qb is not None or qf is not None:
                import time
                ent = self._cached_usage(node)
                over = ((qb is not None and ent[0] + new_bytes > qb)
                        or (qf is not None and ent[1] + new_files > qf))
                if over and not ent[3]:
                    # a denial must be EXACT: optimistic bumps may have
                    # overshot and deletes may have freed quota inside the
                    # TTL window — rewalk ONCE before refusing. A clean
                    # walked snapshot inside its TTL is trusted, so a
                    # client hammering a full dir can't force a walk per
                    # attempt.
                    b, f = self._usage(node)
                    ent[:] = [b, f, time.monotonic() + self.usage_ttl_s,
                              True]
                ub, uf = ent[0], ent[1]
                if qb is not None and ub + new_bytes > qb:
                    raise err.QuotaExceeded(
                        f"{self.fs.tree.path_of(node)}: bytes quota {qb} "
                        f"(used {ub}, requested +{new_bytes})")
                if qf is not None and uf + new_files > qf:
                    raise err.QuotaExceeded(
                        f"{self.fs.tree.path_of(node)}: file quota {qf} "
                        f"(used {uf})")
                # count this admission against the window's snapshot
                ent[0] += new_bytes
                ent[1] += new_files
                ent[3] = False          # bumped: a denial must rewalk
            node = self.fs.tree.get(node.parent_id) \
                if node.parent_id else None

    # ---------------- cache pressure eviction ----------------

    def pressure(self) -> float:
        cap, avail = self.fs.workers.capacity()
        return (cap - avail) / cap if cap else 0.0

    def evict_once(self) -> int:
        """Free cold files until usage falls under low_water. Only files
        whose data also lives in UFS (storage state BOTH/UFS) or that are
        explicitly evictable are freed. Returns files freed."""
        cap, avail = self.fs.workers.capacity()
        if not cap or (cap - avail) / cap < self.high_water:
            return 0
        target_used = int(cap * self.low_water)
        used = cap - avail
        # coldest first: (atime, -len) — old and large go first
        candidates = sorted(
            (n for n in self.fs.tree.iter_files()
             if n.is_complete and n.blocks),
            key=lambda n: (n.atime, -n.len))
        freed = 0
        for node in candidates:
            if used <= target_used:
                break
            path = self.fs.tree.path_of(node)
            mount = self.fs.mounts.get_mount(path) if self.fs.mounts else None
            if mount is None and node.storage_policy.state == StorageState.CV:
                continue      # cache-only data: freeing would lose it
            try:
                self.fs.free(path)
                used -= node.len
                freed += 1
            except err.CurvineError as e:
                log.debug("evict %s failed: %s", path, e)
                continue
            if self.on_free is not None:
                try:
                    self.on_free(path)
                except Exception:   # noqa: BLE001 — push best-effort
                    log.exception("on_free hook for %s", path)
        if freed:
            log.info("cache pressure: freed %d cold files", freed)
        return freed

    async def run(self, leader_gate=None) -> None:
        while True:
            await asyncio.sleep(self.check_interval_s)
            try:
                if leader_gate is None or leader_gate():
                    self.evict_once()
            except Exception:
                log.exception("quota eviction loop")


def _int_attr(node, key: str) -> int | None:
    raw = node.x_attr.get(key)
    if raw is None:
        return None
    try:
        return int(raw.decode() if isinstance(raw, bytes) else raw)
    except (ValueError, AttributeError):
        return None
