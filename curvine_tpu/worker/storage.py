"""Tiered block storage.

Parity: curvine-server/src/worker/storage/ (vfs_dataset, vfs_dir, dir_state,
file_layout) + worker/block/block_store.rs. Tiers are ordered fastest-first
(MEM > SSD > HDD); a block is created on the fastest tier with room, spills
downward under pressure, and is evicted LRU when every tier is full.
Block files live in hashed subdirs (``<root>/<id % 256>/<id>.blk``), temp
files alongside (``.tmp``) renamed on commit — same layout discipline as
the reference's file_layout.rs."""

from __future__ import annotations

import logging
import os
import threading
import time
from dataclasses import dataclass, field

from curvine_tpu.common import errors as err
from curvine_tpu.common.types import BlockState, StorageInfo, StorageType

log = logging.getLogger(__name__)

_SUBDIRS = 256


@dataclass
class BlockInfo:
    block_id: int
    tier: "TierDir"
    len: int = 0
    state: BlockState = BlockState.TEMP
    atime: float = field(default_factory=time.time)
    crc32c: int | None = None     # content checksum recorded at commit
    crc_algo: str = "crc32c"      # crc32 (wire/zlib) or crc32c (native)
    # bdev layout: extent inside the tier's single backing file
    offset: int = 0
    alloc_len: int = 0
    heat: int = 0                 # reads since the last promotion scan
    verified_at: float = 0.0      # last successful scrub pass (0 = never)
    # writer's tenant id (qos TENANT_KEY off the RPC header): feeds the
    # per-tenant tier-0 occupancy gauges and the over-quota-first
    # eviction preference; "" for cluster-internal writes (replication,
    # EC cells, tier moves)
    tenant: str = ""

    @property
    def is_extent(self) -> bool:
        return isinstance(self.tier, BdevTier)

    @property
    def path(self) -> str:
        if self.is_extent:
            return self.tier.path
        suffix = ".tmp" if self.state == BlockState.TEMP else ".blk"
        return self.tier.block_path(self.block_id, suffix)


class DiskHealth:
    """Per-tier-directory health state machine (GFS/HDFS volume-failure
    discipline): decaying IO-error counts drive HEALTHY → SUSPECT; a
    background write/read/unlink probe (WorkerServer duty) either
    rehabilitates a SUSPECT dir or condemns it to QUARANTINED.
    Quarantined dirs advertise zero available capacity, are excluded
    from allocation / demotion / promotion, and the master evacuates
    their committed blocks. Quarantine is sticky for the process
    lifetime — a dir that failed its probes is not trusted again until
    an operator restarts the worker."""

    HEALTHY = "healthy"
    SUSPECT = "suspect"
    QUARANTINED = "quarantined"

    def __init__(self, error_threshold: int = 3, decay_s: float = 60.0,
                 probe_failures: int = 2, probe_successes: int = 3):
        self.state = self.HEALTHY
        self.error_threshold = max(1, error_threshold)
        self.decay_s = decay_s
        self.probe_failures = max(1, probe_failures)
        self.probe_successes = max(1, probe_successes)
        self.quarantined_at = 0.0
        self.errors_total = 0
        self._errors: list[float] = []    # recent error timestamps
        self._probe_fail = 0
        self._probe_ok = 0
        self._lock = threading.Lock()

    @property
    def healthy(self) -> bool:
        return self.state == self.HEALTHY

    @property
    def suspect(self) -> bool:
        return self.state == self.SUSPECT

    @property
    def quarantined(self) -> bool:
        return self.state == self.QUARANTINED

    def note_error(self, now: float | None = None) -> bool:
        """Record one IO error; True on the HEALTHY → SUSPECT edge."""
        now = time.time() if now is None else now
        with self._lock:
            self.errors_total += 1
            if self.state == self.QUARANTINED:
                return False
            cut = now - self.decay_s
            self._errors = [t for t in self._errors if t >= cut]
            self._errors.append(now)
            if self.state == self.HEALTHY \
                    and len(self._errors) >= self.error_threshold:
                self.state = self.SUSPECT
                self._probe_fail = self._probe_ok = 0
                return True
        return False

    def probe_result(self, ok: bool, now: float | None = None) -> str:
        """Fold one background-probe outcome in; returns the resulting
        state. Only SUSPECT dirs are probed — consecutive failures
        condemn, consecutive successes rehabilitate."""
        now = time.time() if now is None else now
        with self._lock:
            if self.state != self.SUSPECT:
                return self.state
            if ok:
                self._probe_ok += 1
                self._probe_fail = 0
                if self._probe_ok >= self.probe_successes:
                    self.state = self.HEALTHY
                    self._errors.clear()
            else:
                self._probe_fail += 1
                self._probe_ok = 0
                if self._probe_fail >= self.probe_failures:
                    self.state = self.QUARANTINED
                    self.quarantined_at = now
            return self.state


class TierDir:
    # direct-IO engine serving this tier's cold reads/copies (attached
    # by WorkerServer for SSD/HDD tiers; None → buffered path)
    io_engine = None
    # submission depth advertised to parallel readers (0 → engine default)
    io_queue_depth = 0

    def __init__(self, storage_type: StorageType, root: str, capacity: int,
                 dir_id: str = ""):
        self.storage_type = storage_type
        self.root = root
        self.capacity = capacity
        self.used = 0
        self.dir_id = dir_id or f"{storage_type.name.lower()}:{root}"
        self.health = DiskHealth()
        # admission policy (common/cache.py); BlockStore.__init__
        # replaces this per the configured worker.cache_admission
        from curvine_tpu.common.cache import LruPolicy
        self.policy = LruPolicy()
        os.makedirs(root, exist_ok=True)

    def block_path(self, block_id: int, suffix: str = ".blk") -> str:
        sub = os.path.join(self.root, f"{block_id % _SUBDIRS:02x}")
        os.makedirs(sub, exist_ok=True)
        return os.path.join(sub, f"{block_id}{suffix}")

    @property
    def probe_path(self) -> str:
        return os.path.join(self.root, ".cv_probe")

    @property
    def available(self) -> int:
        # a quarantined dir has no allocatable space: placement, spill
        # and promotion all key off this, and the heartbeat advertises
        # it so the master stops counting the capacity
        if self.health.quarantined:
            return 0
        return max(0, self.capacity - self.used)

    def info(self, block_num: int = 0) -> StorageInfo:
        return StorageInfo(storage_type=self.storage_type, dir_id=self.dir_id,
                           capacity=self.capacity, available=self.available,
                           block_num=block_num, health=self.health.state)


class BdevTier(TierDir):
    """Raw-device layout: blocks live as EXTENTS inside one preallocated
    backing file (or raw block device path) instead of one file per block
    — no per-block inode/dentry cost, sequential extents, O(1) allocation
    from a first-fit free list. Parity:
    curvine-server/src/worker/storage/layout/bdev_layout.rs.

    The allocation table persists in ``<path>.idx`` (msgpack, written
    atomically on commit/delete); uncommitted extents are reclaimed on
    restart like ``.tmp`` files in the file layout.

    LEASED extents are QUARANTINED on free: unlike the file layout,
    where POSIX unlink semantics keep an open fd valid after the block
    moves, a reused extent inside the shared backing file would hand a
    stale reader another block's bytes. Serving GET_BLOCK_INFO for an
    extent records a lease (quarantine_s / 2, after which the client
    must re-probe); freeing a still-live extent parks it in quarantine
    until the lease expires PLUS lease_slack_s (the client's lease
    clock starts at its request send; the slack absorbs any residual
    client/worker skew), while never-leased extents (fresh writes,
    aborted moves, never-probed victims) return to the free list
    immediately. The quarantine persists in the allocation index so a
    restart inside the window can't resurrect the space."""

    quarantine_s: float = 60.0
    # The client's lease clock starts when the GET_BLOCK_INFO reply
    # ARRIVES, not when the worker granted it — a reply delayed by load
    # or retries extends the window the client believes it may preadv
    # the extent. The slack must therefore cover the whole RPC deadline
    # (past it the client abandons the call and re-probes), not a fixed
    # local-clock fudge. Keep ≥ ClientConf.rpc_timeout_ms
    # (common/conf.py:118, 30s default).
    lease_slack_s: float = 30.0

    def __init__(self, storage_type: StorageType, path: str, capacity: int,
                 dir_id: str = ""):
        self.storage_type = storage_type
        self.path = path
        self.capacity = capacity
        self.used = 0
        self.dir_id = dir_id or f"bdev:{path}"
        self.health = DiskHealth()
        from curvine_tpu.common.cache import LruPolicy
        self.policy = LruPolicy()
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        if not os.path.exists(path):
            with open(path, "wb") as f:
                f.truncate(capacity)           # sparse preallocation
        # block_id -> (offset, alloc_len); free list of (offset, len)
        self.extents: dict[int, tuple[int, int]] = {}
        self._free: list[tuple[int, int]] = [(0, capacity)]
        # freed-but-not-yet-reusable extents:
        # (ready_time, off, len, block_id) — block_id lets reclaim skip
        # extents whose (deleted) block still has an active read pin
        self._quarantine: list[tuple[float, int, int, int]] = []
        self._quarantined = 0
        # block_id -> expiry of the latest short-circuit grant
        self._leases: dict[int, float] = {}

    def block_path(self, block_id: int, suffix: str = ".blk") -> str:
        raise err.Unsupported("bdev tier has no per-block files")

    @property
    def probe_path(self) -> str:
        # media-health probe rides a sidecar next to the backing file
        # (the backing file itself is the allocator's, extent-for-extent)
        return self.path + ".probe"

    @property
    def available(self) -> int:
        # pure read (heartbeat storages() reads it without the store
        # lock); BlockStore._reclaim_locked harvests expired quarantine
        # before every allocation/eviction decision
        if self.health.quarantined:
            return 0
        return max(0, self.capacity - self.used - self._quarantined)

    @property
    def lease_s(self) -> float:
        return self.quarantine_s / 2

    def note_lease(self, block_id: int, expiry: float) -> None:
        if expiry > self._leases.get(block_id, 0.0):
            self._leases[block_id] = expiry

    def free_would_quarantine(self, block_id: int,
                              now: float | None = None) -> bool:
        """True when freeing this block yields no allocatable space yet
        (an unexpired short-circuit lease forces quarantine) — eviction
        planning skips such victims: dropping them destroys data without
        helping the allocation that triggered the eviction."""
        if self.quarantine_s <= 0:
            return False
        now = time.time() if now is None else now
        # the client's lease clock starts at reply ARRIVAL: a lease
        # expired worker-side may still be live client-side for up to
        # the RPC deadline, so the liveness guard carries the same
        # slack as the quarantine duration
        return self._leases.get(block_id, 0.0) + self.lease_slack_s > now

    # ---- extent allocation (first-fit, merge on free) ----
    def reclaim(self, now: float | None = None,
                skip: frozenset | set = frozenset()) -> int:
        """Move expired quarantine entries back to the free list,
        leaving entries whose block id is in `skip` (active read pins)
        parked. Returns bytes reclaimed. Callers hold the store lock."""
        if not self._quarantine:
            return 0
        now = time.time() if now is None else now
        ready = [q for q in self._quarantine
                 if q[0] <= now and q[3] not in skip]
        if not ready:
            return 0
        taken = set(map(id, ready))
        self._quarantine = [q for q in self._quarantine
                            if id(q) not in taken]
        got = 0
        for _t, off, size, _bid in ready:
            self._free.append((off, size))
            self._quarantined -= size
            got += size
        self._merge_free()
        return got

    def _merge_free(self) -> None:
        # merge adjacent free extents (keeps the list from fragmenting)
        self._free.sort()
        merged: list[tuple[int, int]] = []
        for o, ln in self._free:
            if merged and merged[-1][0] + merged[-1][1] == o:
                merged[-1] = (merged[-1][0], merged[-1][1] + ln)
            else:
                merged.append((o, ln))
        self._free = merged

    def alloc(self, block_id: int, size: int) -> int:
        for i, (off, flen) in enumerate(self._free):
            if flen >= size:
                self.extents[block_id] = (off, size)
                if flen == size:
                    del self._free[i]
                else:
                    self._free[i] = (off + size, flen - size)
                self.used += size
                return off
        raise err.CapacityExceeded(
            f"{self.dir_id}: no extent of {size}B free")

    def free(self, block_id: int) -> None:
        ext = self.extents.pop(block_id, None)
        if ext is None:
            return
        off, size = ext
        self.used -= size
        lease = self._leases.pop(block_id, 0.0)
        now = time.time()
        if self.quarantine_s > 0 and lease + self.lease_slack_s > now:
            # an unexpired short-circuit grant may still read this
            # extent through a cached fd: unusable until the lease
            # passes PLUS the RPC deadline (the client's lease clock
            # starts at reply arrival, which can lag the grant by up to
            # the full RPC timeout)
            self._quarantine.append(
                (lease + self.lease_slack_s, off, size, block_id))
            self._quarantined += size
        else:
            self._free.append((off, size))
            self._merge_free()

    def quarantine_block(self, block_id: int) -> None:
        """Free a block's extent while an in-process reader still holds
        a pin on it (delete-mid-stream): the extent goes straight to
        quarantine — persisted via save_index, so a crash before the pin
        drops can't resurrect the space — and reclaim skips it while the
        pin lives."""
        ext = self.extents.pop(block_id, None)
        if ext is None:
            return
        off, size = ext
        self.used -= size
        lease = self._leases.pop(block_id, 0.0)
        ready = max(time.time() + max(self.quarantine_s, 1.0),
                    lease + self.lease_slack_s)
        self._quarantine.append((ready, off, size, block_id))
        self._quarantined += size

    # ---- persistent allocation table ----
    @property
    def index_path(self) -> str:
        return self.path + ".idx"

    def save_index(self, blocks: dict) -> None:
        """blocks: block_id -> BlockInfo (committed, this tier)."""
        import msgpack
        table = {b.block_id: [b.offset, b.alloc_len, b.len,
                              b.crc32c, b.crc_algo]
                 for b in blocks.values()
                 if b.tier is self and b.state == BlockState.COMMITTED}
        tmp = self.index_path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(msgpack.packb({"capacity": self.capacity,
                                   "blocks": table,
                                   # live quarantine rides the index: a
                                   # restart inside the window must not
                                   # resurrect leased space
                                   "quarantine": self._quarantine}))
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self.index_path)

    def load_index(self) -> dict[int, tuple[int, int, int, int | None, str]]:
        import msgpack
        try:
            with open(self.index_path, "rb") as f:
                d = msgpack.unpackb(f.read(), raw=False,
                                    strict_map_key=False)
        except (FileNotFoundError, ValueError, msgpack.UnpackException):
            return {}
        out = {}
        now = time.time()
        for bid, (off, alen, ln, crc, algo) in d.get("blocks", {}).items():
            bid = int(bid)
            self.extents[bid] = (off, alen)
            out[bid] = (off, alen, ln, crc, algo)
            # leases don't survive the restart, but the fds they cover
            # might: assume every surviving block was granted one just
            # before the crash, so an early free still quarantines
            if self.quarantine_s > 0:
                self._leases[bid] = now + self.lease_s
        # restore the unexpired quarantine (reclaim() harvests the rest;
        # pins don't survive a restart, so the ids only matter pre-crash)
        self._quarantine = [
            (t, off, ln, bid)
            for t, off, ln, bid in d.get("quarantine", []) if t > now]
        self._quarantined = sum(ln for _t, _o, ln, _b in self._quarantine)
        quarantined = {(off, ln) for _t, off, ln, _b in self._quarantine}
        # rebuild the free list from the allocated + quarantined extents
        occupied = sorted(list(self.extents.values()) + list(quarantined))
        self._free = []
        pos = 0
        for off, alen in occupied:
            if off > pos:
                self._free.append((pos, off - pos))
            pos = max(pos, off + alen)
        if pos < self.capacity:
            self._free.append((pos, self.capacity - pos))
        self.used = sum(alen for _, alen in self.extents.values())
        return out


class BlockStore:
    """Thread-safe tiered store (handlers run on the event loop; file IO in
    worker threads)."""

    def __init__(self, tiers: list[TierDir], high_water: float = 0.95,
                 low_water: float = 0.80, admission: str = "lru",
                 ghost_entries: int = 8192, small_ratio: float = 0.1):
        if not tiers:
            raise err.InvalidArgument("worker needs at least one tier")
        self.tiers = sorted(tiers, key=lambda t: int(t.storage_type))
        # per-tier-dir admission policy (common/cache.py): ghost-cache
        # scan resistance applies to the MEM-and-faster tiers (the ones
        # a backfill scan can flush); capacity tiers keep plain LRU —
        # their victims demote/drop by age and scans pass through anyway
        from curvine_tpu.common.cache import make_policy
        self.admission = admission
        for t in self.tiers:
            kind = admission if int(t.storage_type) <= int(StorageType.MEM) \
                else "lru"
            t.policy = make_policy(kind, ghost_entries=ghost_entries,
                                   small_ratio=small_ratio)
        # tier-0 byte quota per tenant (worker/server.py wires this to
        # the qos plane's tenant specs): callable tenant -> bytes|None.
        # None (no hook / no quota) keeps eviction order byte-identical.
        self.tier0_quota = None
        self.miss_total = 0           # lookups of blocks we don't hold
        self.blocks: dict[int, BlockInfo] = {}
        self.high_water = high_water
        self.low_water = low_water
        self.started_at = time.time()
        # disk-level fault injection (fault/disk.DiskFaultInjector);
        # None in production — storms and tests install one
        self.fault_hook = None
        # block-removal hook (worker/shm.py ShmExporter.invalidate): a
        # deleted/evicted block must drop its sealed-memfd export so a
        # stale copy is never handed to a new client. Fired under the
        # store lock; the callback must not call back into the store.
        self.on_delete = None
        # tier-move hook (same contract as on_delete — fired under the
        # store lock in _move_block's swap phase, must not re-enter the
        # store): a promoted/demoted block drops its shm exports, since
        # the copy was admitted under the OLD tier's policy and a
        # below-MEM warm copy must never outlive the block's tier
        # residency (docs/data-plane.md)
        self.on_move = None
        # last scrub cycle's outcome counts (metrics exporter reads it)
        self.scrub_last = {"verified": 0, "mismatch": 0, "truncated": 0,
                           "io_error": 0}
        # last cycle's per-block verdicts (block_id -> "mismatch" |
        # "truncated"): rides the corrupt-block report so the master
        # picks the repair path — a truncated copy is re-pulled whole,
        # a bit-rotten EC cell is re-encoded from its siblings
        self.scrub_verdicts: dict[int, str] = {}
        self._lock = threading.Lock()
        # block ids mid-tier-move (copy runs lock-free; see _move_block)
        self._moving: set[int] = set()
        # active in-process readers per block (worker streaming reads,
        # HBM autopin): a pinned bdev-resident block is never moved, so
        # its extent can't be freed and reused under the reader
        self._read_pins: dict[int, int] = {}
        # lifetime tier-movement stats (dropped = data actually left the
        # cache; demoted/promoted = moved between tiers, nothing lost)
        self.dropped_total = 0
        self.demoted_total = 0
        # ids dropped under cache pressure (by the trim or by a create
        # that needed room) that the worker has not yet told the master
        # about: take_dropped()
        self._dropped: list[int] = []
        self.promoted_total = 0
        self._load_existing()

    def _load_existing(self) -> None:
        """Rebuild the index from disk (worker restart)."""
        for tier in self.tiers:
            if isinstance(tier, BdevTier):
                for bid, (off, alen, ln, crc, algo) in \
                        tier.load_index().items():
                    self.blocks[bid] = BlockInfo(
                        block_id=bid, tier=tier, len=ln,
                        state=BlockState.COMMITTED, crc32c=crc,
                        crc_algo=algo or "crc32", offset=off,
                        alloc_len=alen)
                continue
            for sub in os.listdir(tier.root):
                subdir = os.path.join(tier.root, sub)
                if not os.path.isdir(subdir):
                    continue
                for name in os.listdir(subdir):
                    full = os.path.join(subdir, name)
                    if name.endswith((".tmp", ".mov")):
                        os.unlink(full)  # torn write/move from a prior run
                        continue
                    if not name.endswith(".blk"):
                        continue
                    bid = int(name[:-4])
                    size = os.path.getsize(full)
                    self.blocks[bid] = BlockInfo(block_id=bid, tier=tier,
                                                 len=size,
                                                 state=BlockState.COMMITTED)
                    tier.used += size
        if self.blocks:
            log.info("block store recovered %d blocks", len(self.blocks))

    # ---------- lifecycle ----------
    def pick_tier(self, hint: StorageType | None, size_hint: int) -> TierDir:
        # Preferred tier first, then any tier fastest-first with room.
        # Quarantined dirs never allocate — their blocks are being
        # evacuated, writing new data there would feed the failure.
        self._reclaim_locked()
        ordered = [t for t in self.tiers if not t.health.quarantined]
        if not ordered:
            raise err.CapacityExceeded("all tier dirs quarantined")
        if hint is not None:
            ordered = ([t for t in ordered if t.storage_type == hint]
                       + [t for t in ordered if t.storage_type != hint])
        for tier in ordered:
            if tier.available >= size_hint:
                return tier
        # Under pressure: evict on the preferred tier, then fall through
        # to the others — a bdev tier whose victims are all leased (e.g.
        # every surviving block right after a restart, load_index grants
        # synthetic leases) frees nothing until the leases lapse, and
        # writes must not bounce off the whole worker because one tier
        # is temporarily unevictable.
        for tier in ordered:
            self._evict_locked(tier, size_hint)
            if tier.available >= size_hint:
                return tier
        tried = ", ".join(f"{t.dir_id}={t.available}" for t in ordered)
        # Transient shortfall: a bdev tier whose room is merely parked in
        # unexpired quarantine or behind lease-encumbered victims (the
        # whole tier right after a restart — load_index grants synthetic
        # leases) WILL clear within lease_s + slack. Surface that as the
        # retryable CapacityPending so writers back off and re-place
        # instead of hard-failing for the window.
        now = time.time()
        for tier in ordered:
            if not isinstance(tier, BdevTier):
                continue
            pending = tier._quarantined + sum(
                b.alloc_len for b in self.blocks.values()
                if b.tier is tier and b.state == BlockState.COMMITTED
                and b.block_id not in self._moving
                and not self._read_pins.get(b.block_id)
                and tier.free_would_quarantine(b.block_id, now))
            if tier.available + pending >= size_hint:
                raise err.CapacityPending(
                    f"need {size_hint}B on {tier.dir_id}: {pending}B "
                    f"lease-encumbered/quarantined, clears within "
                    f"~{tier.lease_s + tier.lease_slack_s:.0f}s")
        raise err.CapacityExceeded(
            f"need {size_hint}B, all tiers tried after eviction: {tried}")

    def create_temp(self, block_id: int, hint: StorageType | None = None,
                    size_hint: int = 0, tenant: str = "") -> BlockInfo:
        with self._lock:
            if block_id in self._moving:
                # a tier move holds this id's paths/extents; a new
                # incarnation now would collide with the move's phase-3
                # cleanup (id-reuse data loss). Caller retries.
                raise err.FileAlreadyExists(
                    f"block {block_id} busy (tier move in flight)")
            if block_id in self.blocks:
                old = self.blocks[block_id]
                if old.state == BlockState.COMMITTED:
                    raise err.FileAlreadyExists(f"block {block_id} committed")
                self._remove_locked(old)
            tier = self.pick_tier(hint, size_hint)
            info = BlockInfo(block_id=block_id, tier=tier, tenant=tenant)
            if isinstance(tier, BdevTier):
                # extents are fixed at allocation: the client's len_hint
                # (block_size) bounds the block
                size = size_hint or 64 * 1024 * 1024
                info.offset = tier.alloc(block_id, size)
                info.alloc_len = size
            self.blocks[block_id] = info
            return info

    def commit(self, block_id: int, length: int,
               checksum: int | None = None,
               checksum_algo: str = "crc32") -> BlockInfo:
        """`checksum` is the streaming checksum already computed on the
        write path (no re-read); absent → computed natively from disk."""
        with self._lock:
            info = self._get_locked(block_id)
            if info.state == BlockState.COMMITTED:
                return info
            if info.is_extent:
                if length > info.alloc_len:
                    raise err.CapacityExceeded(
                        f"block {block_id}: {length}B > extent "
                        f"{info.alloc_len}B")
                info.state = BlockState.COMMITTED
                info.len = length
                # used was accounted at alloc; index persists below
            else:
                tmp = info.path
                info.state = BlockState.COMMITTED
                info.len = length
                os.replace(tmp, info.path)
                info.tier.used += length
        if checksum is None:
            # file IO outside the lock; fields published under it
            from curvine_tpu.common import native
            checksum = native.checksum_file(info.path, info.offset, length)
            checksum_algo = "crc32c"
        with self._lock:
            info.crc32c = checksum
            info.crc_algo = checksum_algo
            info.tier.policy.on_admit(block_id, length)
            if info.is_extent:
                # ONE index write per commit, under the lock (save_index
                # iterates self.blocks, which eviction mutates under it)
                info.tier.save_index(self.blocks)
        return info

    def verify(self, block_id: int) -> bool:
        """Re-checksum a committed block against its commit-time value."""
        ok, _reason = self.verify_detail(block_id)
        return ok

    def verify_detail(self, block_id: int) -> tuple[bool, str]:
        """Re-checksum a committed block; (ok, reason) where reason is
        "ok", "mismatch" (bit-rot: the full length read back but hashed
        wrong) or "truncated" (a torn write / shrunk file: fewer bytes
        than committed) — operators triage the two very differently.
        OSError from the media (including injected faults) propagates to
        the caller, which feeds the dir health machinery."""
        import zlib
        from curvine_tpu.common import native
        info = self.get(block_id, touch=False)
        if info.state != BlockState.COMMITTED or info.crc32c is None:
            return True, "ok"
        hook = self.fault_hook
        if hook is not None:
            hook.check_read(info.path)
        # file-layout blocks can cheaply pre-detect truncation; extent
        # blocks live inside the shared backing file, so the read loop's
        # short-read check is the only signal there
        if not info.is_extent:
            try:
                size = os.path.getsize(info.path)
            except FileNotFoundError:
                return False, "truncated"
            if size < info.len:
                return False, "truncated"
        use_native = info.crc_algo != "crc32" \
            and (hook is None or not hook.wants_read_data(info.path))
        if use_native:
            got = native.checksum_file(info.path, info.offset, info.len or 0)
            return got == info.crc32c, \
                ("ok" if got == info.crc32c else "mismatch")
        # chunked python read: streaming crc (zlib for crc32, the native
        # helper's incremental crc32c otherwise) with the fault hook
        # applied per chunk so injected bit-flips are observable
        crc = 0
        left = info.len
        with open(info.path, "rb") as f:
            f.seek(info.offset)
            while left > 0:
                chunk = f.read(min(1 << 20, left))
                if not chunk:
                    return False, "truncated"
                if hook is not None and hook.wants_read_data(info.path):
                    buf = bytearray(chunk)
                    hook.mutate_read(info.path, buf)
                    chunk = bytes(buf)
                crc = (zlib.crc32(chunk, crc)
                       if info.crc_algo == "crc32"
                       else native.crc32c(chunk, crc))
                left -= len(chunk)
        return crc == info.crc32c, \
            ("ok" if crc == info.crc32c else "mismatch")

    def scrub(self, limit: int = 16) -> list[int]:
        """Verify up to `limit` least-recently-verified blocks; corrupt
        blocks are REPORTED but kept — only the master may order the
        delete, and only once another live replica exists. Deleting
        locally would destroy the last copy when the mismatch is a
        transient read fault (or every other holder is down); a kept
        corrupt replica is harmless because readers verify and refuse
        it. Parity: the reference's abnormal-data detection on the
        worker data path. `scrub_last` holds the last cycle's verified /
        mismatch / truncated / io_error counts for the metrics
        exporter."""
        with self._lock:
            candidates = [b.block_id for b in sorted(
                (b for b in self.blocks.values()
                 if b.state == BlockState.COMMITTED
                 and b.crc32c is not None),
                key=lambda b: b.verified_at)[:limit]]
        stats = {"verified": 0, "mismatch": 0, "truncated": 0,
                 "io_error": 0}
        corrupt = []
        verdicts: dict[int, str] = {}
        for bid in candidates:
            try:
                ok, reason = self.verify_detail(bid)
            except err.CurvineError:
                continue
            except OSError as e:
                # the media refused the read: not evidence of bit-rot —
                # keep the block, count the error against the dir health
                stats["io_error"] += 1
                with self._lock:
                    b = self.blocks.get(bid)
                    tier = b.tier if b is not None else None
                if tier is not None:
                    tier.health.note_error()
                log.warning("scrub read of block %d failed: %s", bid, e)
                continue
            if ok:
                stats["verified"] += 1
                with self._lock:
                    b = self.blocks.get(bid)
                    if b is not None:
                        b.verified_at = time.time()
                continue
            log.error("block %d failed checksum scrub (%s); reporting "
                      "to master (kept until a clean replica exists)",
                      bid, reason)
            stats[reason] += 1
            # stamp it checked so the rotation moves on — re-reporting
            # is bounded to once per full scrub sweep
            with self._lock:
                b = self.blocks.get(bid)
                if b is not None:
                    b.verified_at = time.time()
            corrupt.append(bid)
            verdicts[bid] = reason
        self.scrub_last = stats
        self.scrub_verdicts = verdicts
        return corrupt

    def get(self, block_id: int, touch: bool = True) -> BlockInfo:
        with self._lock:
            info = self._get_locked(block_id)
            if touch:
                info.atime = time.time()
                info.heat += 1
                info.tier.policy.hits += 1
                info.tier.policy.on_access(block_id)
            return info

    def touch_reads(self, block_id: int, reads: int) -> None:
        """Account reads that bypassed get() — short-circuit clients hit
        the store once per open (the GET_BLOCK_INFO probe) and then read
        through a cached fd; they report per-block read counters on
        heartbeat so heat/atime reflect actual traffic and promotion
        targets the right blocks."""
        with self._lock:
            info = self.blocks.get(block_id)
            if info is not None and reads > 0:
                info.atime = time.time()
                info.heat += reads
                info.tier.policy.hits += reads
                info.tier.policy.on_access(block_id)

    def pin_read(self, block_id: int, touch: bool = True) -> BlockInfo:
        """Atomically look up a block and take a read pin on it; pair
        with unpin_read(). While pinned, tier moves of bdev-resident
        blocks are refused (_move_block), so the extent under an active
        reader can never be freed and reallocated mid-stream."""
        with self._lock:
            info = self._get_locked(block_id)
            if touch:
                info.atime = time.time()
                info.heat += 1
                info.tier.policy.hits += 1
                info.tier.policy.on_access(block_id)
            self._read_pins[block_id] = self._read_pins.get(block_id, 0) + 1
            return info

    def unpin_read(self, block_id: int) -> None:
        with self._lock:
            n = self._read_pins.get(block_id, 0) - 1
            if n <= 0:
                self._read_pins.pop(block_id, None)
            else:
                self._read_pins[block_id] = n

    def grant_sc(self, block_id: int) -> tuple[BlockInfo, int]:
        """Short-circuit grant: look up the block and, for bdev
        extents, record the lease ATOMICALLY with the lookup (a free
        slipping between get() and note_lease would lease an extent
        already on the free list). Returns (info, lease_ms) —
        lease_ms 0 for file-layout blocks (unlink semantics, no lease
        needed)."""
        with self._lock:
            info = self._get_locked(block_id)
            info.atime = time.time()
            info.heat += 1
            info.tier.policy.hits += 1
            info.tier.policy.on_access(block_id)
            lease_ms = 0
            if isinstance(info.tier, BdevTier) \
                    and info.tier.quarantine_s > 0:
                ls = info.tier.lease_s
                info.tier.note_lease(block_id, time.time() + ls)
                lease_ms = int(ls * 1000)
            return info, lease_ms

    def _reclaim_locked(self) -> None:
        """Harvest expired bdev quarantine before any allocation or
        eviction decision, skipping extents whose (deleted) block still
        has an active read pin."""
        pinned = set(self._read_pins)
        for t in self.tiers:
            if isinstance(t, BdevTier):
                t.reclaim(skip=pinned)

    def contains(self, block_id: int) -> bool:
        return block_id in self.blocks

    def delete(self, block_id: int) -> None:
        with self._lock:
            info = self.blocks.get(block_id)
            if info is not None:
                self._remove_locked(info)

    def _remove_locked(self, info: BlockInfo, evicted: bool = False) -> None:
        # `evicted` = removal under cache pressure (trim/evict): the id
        # enters the policy's ghost queue so a near-future re-admission
        # skips probation. Plain deletes/overwrites never ghost.
        info.tier.policy.on_remove(info.block_id, evicted=evicted)
        if self.on_delete is not None:
            try:
                self.on_delete(info.block_id)
            except Exception:  # noqa: BLE001 — removal must proceed
                pass
        if info.is_extent:
            if self._read_pins.get(info.block_id):
                # an active stream holds (fd, offset) into the backing
                # file: park the extent in quarantine (persisted below);
                # reclaim skips it while the pin lives
                info.tier.quarantine_block(info.block_id)
            else:
                info.tier.free(info.block_id)  # adjusts used by alloc_len
            self.blocks.pop(info.block_id, None)
            if info.state == BlockState.COMMITTED:
                info.tier.save_index(self.blocks)
            return
        try:
            os.unlink(info.path)
        except FileNotFoundError:
            pass
        except OSError as e:
            # a dying disk may refuse even the unlink: drop the index
            # entry anyway (GET_BLOCK_INFO must stop serving the block)
            # and let the health machinery see the error
            log.warning("unlink of %s failed: %s", info.path, e)
            info.tier.health.note_error()
        if info.tier.io_engine is not None:
            # drop the engine's cached fd: a recreated block at this
            # path must never be served from the unlinked file
            info.tier.io_engine.forget(info.path)
        if info.state == BlockState.COMMITTED:
            info.tier.used -= info.len
        self.blocks.pop(info.block_id, None)

    def _get_locked(self, block_id: int) -> BlockInfo:
        info = self.blocks.get(block_id)
        if info is None:
            self.miss_total += 1
            raise err.BlockNotFound(f"block {block_id}")
        return info

    # ---------- tier movement ----------
    @staticmethod
    def _copy_bytes(sf, df, block_id: int, length: int, src_id: str) -> None:
        left = length
        while left > 0:
            chunk = sf.read(min(4 << 20, left))
            if not chunk:
                raise err.AbnormalData(
                    f"block {block_id} truncated on {src_id}")
            df.write(chunk)
            left -= len(chunk)

    @staticmethod
    def _copy_bytes_direct(engine, src_path: str, src_off: int, df,
                           block_id: int, length: int, src_id: str) -> None:
        """Tier-move source read through the direct-IO engine: the cold
        copy bypasses the page cache instead of evicting MEM-tier pages
        to stage a block that is LEAVING the fast tiers. Runs on a
        worker thread (pread_sync blocks on the ring's completion)."""
        done = 0
        while done < length:
            n = min(4 << 20, length - done)
            chunk = engine.pread_sync(src_path, src_off + done, n)
            if not chunk:
                raise err.AbnormalData(
                    f"block {block_id} truncated on {src_id}")
            df.write(chunk)
            done += len(chunk)

    def _move_block(self, block_id: int, dest: TierDir) -> bool:
        """Move a committed block's bytes to `dest` and swap the index
        entry. Returns False (leaving the block where it is) when dest
        lacks room or the block changed underneath. The byte copy runs
        WITHOUT the store lock (a multi-MB copy must not stall every
        other block op on the worker): space is reserved under the lock,
        the copy streams lock-free, and the swap revalidates under the
        lock — a block deleted or evicted mid-copy just discards the new
        copy. Readers holding an fd on the old file keep a complete,
        consistent view (POSIX unlink semantics); new opens resolve the
        new location via GET_BLOCK_INFO."""
        # Phase 1 (locked): validate + reserve destination space.
        with self._lock:
            self._reclaim_locked()
            info = self.blocks.get(block_id)
            if info is None or info.state != BlockState.COMMITTED \
                    or info.tier is dest or block_id in self._moving:
                return False
            if self._read_pins.get(block_id):
                # an active in-process reader snapshots (path, offset)
                # lock-free; a move would tear that pair under it — for
                # a bdev source it would even free the extent mid-read.
                # Refuse moves of ANY pinned block.
                return False
            src_path, src_off, src_tier = info.path, info.offset, info.tier
            length = info.len
            if dest.available < length:
                return False
            if isinstance(dest, BdevTier):
                try:
                    new_off = dest.alloc(block_id, length)
                except err.CapacityExceeded:   # fragmented free list
                    return False
                new_alloc = length
            else:
                dest.used += length            # reservation
                new_off, new_alloc = 0, 0
            self._moving.add(block_id)

        def release_dest():
            if isinstance(dest, BdevTier):
                dest.free(block_id)
            else:
                dest.used -= length

        # Phase 2 (unlocked): stream the bytes. A source tier with a
        # direct-IO engine reads O_DIRECT — promote/demote staging must
        # not flush the page cache the MEM tier and FUSE warm path use.
        engine = src_tier.io_engine
        try:
            with open(src_path, "rb") as sf:
                sf.seek(src_off)

                def copy_to(df) -> None:
                    if engine is not None:
                        self._copy_bytes_direct(engine, src_path, src_off,
                                                df, block_id, length,
                                                src_tier.dir_id)
                    else:
                        self._copy_bytes(sf, df, block_id, length,
                                         src_tier.dir_id)

                if isinstance(dest, BdevTier):
                    with open(dest.path, "r+b") as df:
                        df.seek(new_off)
                        copy_to(df)
                else:
                    dst_path = dest.block_path(block_id, ".mov")
                    with open(dst_path, "wb") as df:
                        copy_to(df)
                    os.replace(dst_path, dest.block_path(block_id, ".blk"))
        except (OSError, err.CurvineError) as e:
            log.warning("move block %d %s -> %s failed: %s", block_id,
                        src_tier.dir_id, dest.dir_id, e)
            if not isinstance(dest, BdevTier):
                try:     # don't leak the partial copy
                    os.unlink(dest.block_path(block_id, ".mov"))
                except OSError:
                    pass
            with self._lock:
                release_dest()
                self._moving.discard(block_id)
            return False

        # Phase 3 (locked): revalidate and swap, or discard the copy.
        # create_temp refuses ids in _moving, so no NEW incarnation of
        # this block can exist yet — the cleanup below only ever removes
        # OUR copy.
        with self._lock:
            self._moving.discard(block_id)
            info = self.blocks.get(block_id)
            if info is None or info.state != BlockState.COMMITTED \
                    or info.tier is not src_tier or info.len != length \
                    or self._read_pins.get(block_id):
                # deleted/evicted mid-copy, or a reader pinned the
                # source during the lock-free copy (swapping tier/offset
                # would tear the pair under their preadv; a bdev source
                # would even free the extent): ours is the stale copy
                release_dest()
                if not isinstance(dest, BdevTier):
                    try:
                        os.unlink(dest.block_path(block_id, ".blk"))
                    except OSError:
                        pass
                return False
            was_extent = info.is_extent
            if was_extent:
                src_tier.free(block_id)
            else:
                try:
                    os.unlink(src_path)
                except FileNotFoundError:
                    pass
                if src_tier.io_engine is not None:
                    src_tier.io_engine.forget(src_path)
                src_tier.used -= length
            # dest accounting already reserved; just swap the entry.
            # Policy handoff: a demotion is an eviction from the fast
            # tier's viewpoint (ghost-eligible — a re-heated block skips
            # probation on its way back up); a promotion is not.
            demoting = int(dest.storage_type) > int(src_tier.storage_type)
            src_tier.policy.on_remove(block_id, evicted=demoting)
            dest.policy.on_admit(block_id, length)
            if self.on_move is not None:
                try:
                    self.on_move(block_id)
                except Exception:  # noqa: BLE001 — the move must land
                    pass
            info.tier, info.offset, info.alloc_len = dest, new_off, new_alloc
            if was_extent:
                src_tier.save_index(self.blocks)
            if isinstance(dest, BdevTier):
                dest.save_index(self.blocks)
            return True

    def _move_candidates_locked(self, tier: TierDir, need: int,
                                demote: bool) -> tuple[list, int, int]:
        """Under the lock: pick LRU victims on `tier` until `need` (or the
        low-water trim target) fits, deciding drop-vs-demote per victim.
        Returns (plan, target_free, projected) where plan is
        [(block_id, dest|None)] — dest None means drop — and projected
        is the bytes free on `tier` if the whole plan executes."""
        self._reclaim_locked()
        target_free = max(need, int(tier.capacity * (1 - self.low_water)))
        now = time.time()
        eligible = [
            b for b in self.blocks.values()
            if b.tier is tier and b.state == BlockState.COMMITTED
            and b.block_id not in self._moving
            # never evict a block with an active reader, and skip
            # leased bdev extents entirely: their free lands in
            # quarantine, so dropping destroys data without making
            # room and demoting burns copy IO for zero freed bytes —
            # the lease lapses within lease_s + lease_slack_s and the
            # next scan takes them
            and not self._read_pins.get(b.block_id)
            and not (isinstance(tier, BdevTier)
                     and tier.free_would_quarantine(b.block_id, now))]
        order = tier.policy.victim_order(
            [(b.block_id, b.atime) for b in eligible])
        by_id = {b.block_id: b for b in eligible}
        victims = [by_id[k] for k in order if k in by_id]
        victims = self._quota_first(tier, victims)
        plan: list[tuple[int, TierDir | None]] = []
        freed = tier.available
        for b in victims:
            if freed >= target_free:
                break
            dest = self._slower_tier_for(tier, b.len) if demote else None
            plan.append((b.block_id, dest))
            freed += b.len if not isinstance(tier, BdevTier) else b.alloc_len
        return plan, target_free, freed

    def _quota_first(self, tier: TierDir, victims: list) -> list:
        """Per-job cache partitions: on tier-0 (MEM and faster), blocks
        of tenants over their tier-0 byte quota are evicted before
        anyone else's — a bulk export that blew past its partition pays
        for the pressure it created, in policy order within each group.
        No quota hook / nobody over quota → order untouched."""
        if self.tier0_quota is None \
                or int(tier.storage_type) > int(StorageType.MEM):
            return victims
        occ = self._tenant_occupancy_locked()
        over = set()
        for tenant, used in occ.items():
            q = self.tier0_quota(tenant)
            if q is not None and q > 0 and used > q:
                over.add(tenant)
        if not over:
            return victims
        return ([b for b in victims if b.tenant in over]
                + [b for b in victims if b.tenant not in over])

    def _tenant_occupancy_locked(self) -> dict[str, int]:
        occ: dict[str, int] = {}
        for b in self.blocks.values():
            if b.state == BlockState.COMMITTED \
                    and int(b.tier.storage_type) <= int(StorageType.MEM):
                occ[b.tenant or "default"] = \
                    occ.get(b.tenant or "default", 0) + b.len
        return occ

    def tenant_occupancy(self) -> dict[str, int]:
        """Committed tier-0 (MEM and faster) bytes per tenant — the
        per-tenant occupancy gauges behind the cache partitions."""
        with self._lock:
            return self._tenant_occupancy_locked()

    def cache_stats(self) -> dict[str, dict[str, int]]:
        """Per-tier-dir admission/hit counters plus a store-wide rollup
        (the worker heartbeats the rollup; `cv report` prints it)."""
        with self._lock:
            out: dict[str, dict[str, int]] = {}
            total: dict[str, int] = {}
            for t in self.tiers:
                s = t.policy.stats()
                out[t.dir_id] = s
                for k, v in s.items():
                    if k in ("small", "main", "ghost"):
                        continue
                    total[k] = total.get(k, 0) + v
            total["misses"] = total.get("misses", 0) + self.miss_total
            out["total"] = total
            return out

    def _slower_tier_for(self, tier: TierDir, size: int) -> TierDir | None:
        """Next tier strictly slower than `tier` with room for `size`.
        Quarantined dirs are never demotion targets."""
        for t in self.tiers:
            if int(t.storage_type) > int(tier.storage_type) \
                    and not t.health.quarantined and t.available >= size:
                return t
        return None

    # ---------- eviction / demotion ----------
    def _evict_locked(self, tier: TierDir, need: int) -> list[int]:
        """Drop-only LRU trim, for callers already holding the lock (the
        synchronous create path): when this fires every tier is full, so
        there is no demotion target anyway — dropping is the only move,
        and it must not stall the write behind multi-MB copies.

        A plan that cannot reach `need` is NOT executed: destroying
        cached blocks without unblocking the allocation that asked is
        pure cache loss (pick_tier falls through to the next tier
        instead)."""
        plan, _target, projected = self._move_candidates_locked(
            tier, need, demote=False)
        if projected < need:
            return []
        evicted = []
        for bid, _dest in plan:
            info = self.blocks.get(bid)
            if info is None:
                continue
            self._remove_locked(info, evicted=True)
            evicted.append(bid)
            self.dropped_total += 1
            self._dropped.append(bid)
        if evicted:
            log.info("evicted %d blocks from %s", len(evicted), tier.dir_id)
        return evicted

    def trim(self, tier: TierDir, need: int,
             demote: bool = True) -> list[int]:
        """LRU-trim committed blocks from `tier` until `need` fits or the
        low-water mark is reached. Cold blocks spill DOWN to the next
        slower tier with room (demotion); only when no slower tier can
        take them are they dropped. Byte copies run without the store
        lock (see _move_block). Returns ids no longer on `tier`."""
        removed, demoted = [], 0
        for _attempt in range(2):      # one retry if planned moves failed
            with self._lock:
                plan, target, _projected = self._move_candidates_locked(
                    tier, need, demote)
            if not plan:
                break
            progress = False
            for bid, dest in plan:
                with self._lock:
                    if tier.available >= target:
                        break
                if dest is not None and self._move_block(bid, dest):
                    removed.append(bid)
                    demoted += 1
                    progress = True
                    continue
                if demote:
                    # the planned destination filled up (the plan shares
                    # one availability snapshot) or the copy failed:
                    # replan against LIVE availability before giving up
                    with self._lock:
                        info = self.blocks.get(bid)
                        dest2 = (self._slower_tier_for(tier, info.len)
                                 if info is not None
                                 and info.tier is tier else None)
                    if dest2 is not None:
                        if dest2 is not dest and \
                                self._move_block(bid, dest2):
                            removed.append(bid)
                            demoted += 1
                            progress = True
                        # a demotion target EXISTS but the copy failed
                        # (transient IO): never destroy a healthy replica
                        # over that — leave the block for the next scan
                        continue
                with self._lock:
                    info = self.blocks.get(bid)
                    if info is not None and info.tier is tier \
                            and info.state == BlockState.COMMITTED \
                            and bid not in self._moving \
                            and not self._read_pins.get(bid) \
                            and not (isinstance(tier, BdevTier)
                                     and tier.free_would_quarantine(bid)):
                        # same futile-drop guard as the planner: a leased
                        # extent's free lands in quarantine — destroying
                        # data without making room
                        self._remove_locked(info, evicted=True)
                        removed.append(bid)
                        self.dropped_total += 1
                        self._dropped.append(bid)
                        progress = True
            with self._lock:
                if tier.available >= target:
                    break
            if not progress:
                break
        if removed:
            with self._lock:
                self.demoted_total += demoted
            log.info("trimmed %d blocks from %s (%d demoted, %d dropped)",
                     len(removed), tier.dir_id, demoted,
                     len(removed) - demoted)
        return removed

    def take_dropped(self) -> list[int]:
        """Ids of the blocks dropped under cache pressure since the last
        call, whichever path dropped them."""
        with self._lock:
            out, self._dropped = self._dropped, []
        return out

    def maybe_evict(self) -> list[int]:
        """Background check: any tier above high-water gets trimmed."""
        out = []
        for tier in self.tiers:
            with self._lock:
                over = tier.capacity \
                    and tier.used > tier.capacity * self.high_water
            if over:
                out.extend(self.trim(tier, 0))
        return out

    def hot_blocks(self, min_reads: int,
                   max_len: int | None = None) -> list[tuple[int, int, int]]:
        """Snapshot of committed blocks with heat >= min_reads, hottest
        first, as (block_id, heat, len) — the single source of the
        promotion predicate for both the host-tier scan and the worker's
        HBM auto-pin."""
        with self._lock:
            return sorted(
                ((b.block_id, b.heat, b.len)
                 for b in self.blocks.values()
                 if b.state == BlockState.COMMITTED
                 and b.heat >= min_reads
                 and (max_len is None or b.len <= max_len)),
                key=lambda t: t[1], reverse=True)

    # ---------- promotion ----------
    def promote_scan(self, min_reads: int = 3,
                     max_bytes: int = 256 << 20) -> list[int]:
        """Hot-data promotion: blocks on slower tiers read >= `min_reads`
        times since the last scan move to the fastest tier with room,
        hottest first; the move may demote the destination's coldest
        blocks downward to make space (never dropping them when a slower
        tier has room). Heat decays by half each scan so a once-hot block
        cools off. Byte copies run without the store lock. Parity: the
        reference README's transparent hot-data promotion headline (its
        code ships write-time tiering only — this EXCEEDS parity)."""
        with self._lock:
            # promotion targets the fastest HEALTHY-enough tier: pinning
            # hot data onto a quarantined dir would race its evacuation
            fastest = next((t for t in self.tiers
                            if not t.health.quarantined), None)
            if fastest is None:
                return []
            hot = [(b.block_id, b.len) for b in sorted(
                (b for b in self.blocks.values()
                 if b.state == BlockState.COMMITTED and b.tier is not fastest
                 and b.heat >= min_reads),
                key=lambda b: b.heat, reverse=True)]
        promoted: list[int] = []
        budget = max_bytes
        for bid, blen in hot:
            if blen > budget:
                continue
            if blen > fastest.capacity:
                # can never fit even an empty tier: don't flush the hot
                # tier chasing an impossible promotion
                continue
            if blen > fastest.available:
                # demote the destination's coldest blocks to make space
                # (the background high-water trim restores headroom after
                # a scan that fills the tier)
                self.trim(fastest, blen, demote=True)
                if blen > fastest.available:
                    continue
            if self._move_block(bid, fastest):
                promoted.append(bid)
                budget -= blen
        with self._lock:
            for b in self.blocks.values():
                b.heat //= 2
        if promoted:
            with self._lock:
                self.promoted_total += len(promoted)
            log.info("promoted %d hot blocks to %s", len(promoted),
                     self.tiers[0].dir_id)
        return promoted

    # ---------- disk health ----------
    def probe_dir(self, tier: TierDir) -> bool:
        """One write/read/unlink media probe against `tier`. Consults
        the fault hook so injected dir faults fail the probe exactly
        like real media would. Blocking — run via asyncio.to_thread.
        Returns True when the round-trip came back intact."""
        path = tier.probe_path
        payload = os.urandom(4096)
        hook = self.fault_hook
        try:
            if hook is not None:
                hook.check_write(path)
            with open(path, "wb") as f:
                f.write(payload)
                f.flush()
                os.fsync(f.fileno())
            if hook is not None:
                hook.check_read(path)
            with open(path, "rb") as f:
                back = f.read()
            if hook is not None and len(back):
                buf = bytearray(back)
                hook.mutate_read(path, buf)
                back = bytes(buf)
            os.unlink(path)
            return back == payload
        except OSError:
            try:
                os.unlink(path)
            except OSError:
                pass
            return False

    def note_io_error(self, tier: TierDir) -> bool:
        """Feed one media IO error into `tier`'s health; True on the
        HEALTHY → SUSPECT edge (the caller schedules probing)."""
        moved = tier.health.note_error()
        if moved:
            log.warning("dir %s marked SUSPECT after repeated IO errors",
                        tier.dir_id)
        return moved

    def quarantined_blocks(self, limit: int = 0) -> list[int]:
        """Committed blocks residing on quarantined dirs — the worker
        advertises (a bounded slice of) these every heartbeat so the
        master can drive evacuation; sorted for deterministic batching."""
        with self._lock:
            out = sorted(b.block_id for b in self.blocks.values()
                         if b.state == BlockState.COMMITTED
                         and b.tier.health.quarantined)
        return out[:limit] if limit else out

    def scrub_ages(self) -> dict[str, float]:
        """dir_id → seconds since the oldest committed block on that dir
        was last scrub-verified (i.e. the staleness of the dir's full
        scrub sweep). Dirs with nothing to scrub report 0."""
        now = time.time()
        with self._lock:
            oldest: dict[str, float] = {}
            for b in self.blocks.values():
                if b.state != BlockState.COMMITTED or b.crc32c is None:
                    continue
                t = b.verified_at or self.started_at
                d = b.tier.dir_id
                if d not in oldest or t < oldest[d]:
                    oldest[d] = t
        return {t.dir_id: max(0.0, now - oldest[t.dir_id])
                if t.dir_id in oldest else 0.0
                for t in self.tiers}

    # ---------- reporting ----------
    def storages(self) -> list[StorageInfo]:
        counts: dict[str, int] = {}
        for b in self.blocks.values():
            counts[b.tier.dir_id] = counts.get(b.tier.dir_id, 0) + 1
        return [t.info(counts.get(t.dir_id, 0)) for t in self.tiers]

    def report(self) -> tuple[dict[int, int], dict[int, int]]:
        """(block_id → len, block_id → storage_type) for committed blocks."""
        held, types = {}, {}
        with self._lock:
            for b in self.blocks.values():
                if b.state == BlockState.COMMITTED:
                    held[b.block_id] = b.len
                    types[b.block_id] = int(b.tier.storage_type)
        return held, types
