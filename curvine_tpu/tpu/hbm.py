"""HBM tier-0: device-resident block cache.

The TPU-native extension over the reference's MEM/SSD/HDD tiers: hot
blocks live in TPU HBM as uint8 jax.Arrays, so a training step's input
fetch is an on-device slice instead of a host→device copy. Capacity is
accounted explicitly; LRU spills back to the host tier (the DRAM tier
keeps the backing file, so spilling is just dropping the device copy)."""

from __future__ import annotations

import logging
import time

import jax
import numpy as np

log = logging.getLogger(__name__)


class HbmExportTable:
    """Peer-addressable view of the HBM tier: block_id → device buffer
    descriptor, advertised in heartbeats and GET_BLOCK_INFO so an
    ICI-adjacent peer can source the replica device-to-device instead of
    re-pulling bytes over TCP (tpu/ici_plane.py).

    Bounded LRU, mirroring the shm-export table (worker/shm.py): the
    advertisement is capability metadata, not ownership — dropping an
    entry only stops advertising; the tier still holds the block."""

    def __init__(self, cap: int = 128):
        from collections import OrderedDict
        self.cap = max(1, int(cap))
        self._entries: "OrderedDict[int, dict]" = OrderedDict()
        self.exports = 0        # lifetime advertisements
        self.evictions = 0      # LRU pressure on the table itself

    def add(self, block_id: int, device_id: int, arr) -> None:
        e = {"device_id": int(device_id),
             "shape": list(arr.shape),
             "dtype": str(arr.dtype),
             "nbytes": int(arr.nbytes)}
        if block_id in self._entries:
            self._entries.pop(block_id)
        elif len(self._entries) >= self.cap:
            self._entries.popitem(last=False)
            self.evictions += 1
        self._entries[block_id] = e
        self.exports += 1

    def remove(self, block_id: int) -> None:
        self._entries.pop(block_id, None)

    def get(self, block_id: int) -> dict | None:
        e = self._entries.get(block_id)
        if e is not None:
            self._entries.move_to_end(block_id)
        return e

    def __contains__(self, block_id: int) -> bool:
        return block_id in self._entries

    def __len__(self) -> int:
        return len(self._entries)

    def snapshot(self, limit: int | None = None) -> list[dict]:
        """Most-recently-exported first, bounded — the heartbeat payload."""
        out = []
        for bid in reversed(self._entries):
            if limit is not None and len(out) >= limit:
                break
            out.append({"block_id": bid, **self._entries[bid]})
        return out


class HbmTier:
    def __init__(self, capacity_bytes: int, device=None,
                 admission: str = "lru", ghost_entries: int = 2048,
                 exports: HbmExportTable | None = None, policy=None):
        from curvine_tpu.common.cache import make_policy
        self.capacity = capacity_bytes
        self.device = device if device is not None else jax.local_devices()[0]
        self.used = 0
        self.placed_at = 0.0    # when a block last landed on this chip
        self._blocks: dict[int, jax.Array] = {}
        self._atime: dict[int, float] = {}
        self.hits = 0
        self.misses = 0
        self.spills = 0
        # peer-addressable advertisement (shared across chips under
        # MultiHbmTier); None → tier is private, nothing advertised
        self.exports = exports
        # ghost-cache admission (common/cache.py): HBM is the scarcest
        # tier of all — an autopin sweep over a cold scan must not spill
        # the hot training blocks, so s3fifo protection applies here too.
        # An injected shared policy (MultiHbmTier) lets a block evicted
        # on one chip re-admit straight to main on ANY chip.
        self.policy = policy if policy is not None else \
            make_policy(admission, ghost_entries=ghost_entries)

    def __contains__(self, block_id: int) -> bool:
        return block_id in self._blocks

    def put(self, block_id: int, data) -> jax.Array:
        """Pin a block (bytes / numpy view) into HBM. Zero-copy on the host
        side: a numpy view (e.g. the client's mmap_view) is handed straight
        to device_put."""
        if block_id in self._blocks:
            self._atime[block_id] = time.monotonic()
            self.policy.on_access(block_id)
            return self._blocks[block_id]
        arr = np.frombuffer(data, dtype=np.uint8) if isinstance(
            data, (bytes, bytearray, memoryview)) else data
        need = arr.nbytes
        if need > self.capacity:
            raise ValueError(f"block of {need}B exceeds HBM tier capacity")
        self._evict_for(need)
        dev_arr = jax.device_put(arr, self.device)
        self._blocks[block_id] = dev_arr
        self._atime[block_id] = self.placed_at = time.monotonic()
        self.used += need
        self.policy.on_admit(block_id, need)
        if self.exports is not None:
            self.exports.add(block_id, self.device.id, dev_arr)
        return dev_arr

    def get(self, block_id: int) -> jax.Array | None:
        arr = self._blocks.get(block_id)
        if arr is None:
            self.misses += 1
            self.policy.misses += 1
            return None
        self.hits += 1
        self.policy.hits += 1
        self._atime[block_id] = time.monotonic()
        self.policy.on_access(block_id)
        return arr

    def drop(self, block_id: int, evicted: bool = False) -> None:
        arr = self._blocks.pop(block_id, None)
        self._atime.pop(block_id, None)
        if arr is not None:
            self.policy.on_remove(block_id, evicted=evicted)
            if self.exports is not None:
                self.exports.remove(block_id)
            self.used -= arr.nbytes
            arr.delete()

    def _evict_for(self, need: int) -> None:
        while self.used + need > self.capacity and self._blocks:
            order = self.policy.victim_order(list(self._atime.items()))
            victim = order[0] if order else min(self._atime,
                                                key=self._atime.get)
            log.debug("hbm tier evicting block %d", victim)
            self.spills += 1
            self.drop(victim, evicted=True)

    def stats(self) -> dict:
        ps = self.policy.stats()
        return {"capacity": self.capacity, "used": self.used,
                "blocks": len(self._blocks), "hits": self.hits,
                "misses": self.misses, "spills": self.spills,
                "ghost_hits": ps.get("ghost_hits", 0),
                "scan_evicted": ps.get("scan_evicted", 0)}


class MultiHbmTier:
    """HBM tier-0 across ALL local chips of a TPU host (a v5e host drives
    4-8). One HbmTier per device with independent capacity accounting;
    placement picks the least-used chip (or an explicit target), and hot
    blocks can be spread as replicas across chips so every consumer
    reads HBM-locally instead of crossing PCIe or ICI.

    One process per chip: building this claims every local chip for the
    process, so it belongs to a worker embedded in the process that runs
    the JAX consumer — a standalone `cv worker` keeps hbm_capacity = 0."""

    def __init__(self, capacity_bytes: int, devices=None,
                 admission: str = "lru", ghost_entries: int = 2048,
                 export_cap: int = 128):
        """``capacity_bytes`` is the TOTAL HBM budget for the tier (the
        operator's `worker.hbm_capacity`), split evenly across the local
        chips — same semantics as the round-2 single-device tier, so the
        advertised capacity doesn't silently multiply by chip count."""
        from curvine_tpu.common.cache import make_policy
        devices = devices if devices is not None else jax.local_devices()
        if not devices:
            raise ValueError("no local devices for the HBM tier")
        per_chip = max(1, capacity_bytes // len(devices))
        # ONE admission policy and ONE export table across all chips:
        # the ghost queue must be tier-wide (a block evicted on chip A
        # and re-broadcast onto chip B is the same hot block — it
        # re-admits straight to main), and peers address the worker's
        # HBM tier as a whole, not a chip
        self.policy = make_policy(admission, ghost_entries=ghost_entries)
        self.exports = HbmExportTable(cap=export_cap)
        self.tiers: dict = {d.id: HbmTier(per_chip, device=d,
                                          exports=self.exports,
                                          policy=self.policy)
                            for d in devices}
        self.devices = list(devices)

    # ---- capacity (per chip, for heartbeat advertisement) ----
    @property
    def capacity(self) -> int:
        return sum(t.capacity for t in self.tiers.values())

    @property
    def used(self) -> int:
        return sum(t.used for t in self.tiers.values())

    def per_device_stats(self) -> list[dict]:
        return [{"device_id": did, **t.stats()}
                for did, t in sorted(self.tiers.items())]

    # ---- placement ----
    def _pick(self) -> "HbmTier":
        # least-used chip; among equally full ones the chip placed on
        # longest ago — a full tier rotates its evictions over all chips
        # instead of churning the first one's few slots
        return min(self.tiers.values(),
                   key=lambda t: (t.used, t.placed_at))

    def _tier_of(self, device) -> "HbmTier":
        did = getattr(device, "id", device)
        t = self.tiers.get(did)
        if t is None:
            raise ValueError(f"device {did} is not part of the HBM tier")
        return t

    def put(self, block_id: int, data, device=None) -> jax.Array:
        """Pin on one chip: the consumer's chip when given, else the
        least-used chip (capacity-balanced placement)."""
        for t in self.tiers.values():         # already resident somewhere?
            if block_id in t:
                if device is None or getattr(device, "id", device) == \
                        t.device.id:
                    return t.get(block_id)
        t = self._tier_of(device) if device is not None else self._pick()
        try:
            return t.put(block_id, data)
        except ValueError as e:
            # hbm_capacity is the TOTAL budget split over len(tiers)
            # chips; a block can only live on ONE chip, so the per-chip
            # share is the real ceiling — make that actionable
            raise ValueError(
                f"{e} (per-chip share: {t.capacity}B = total hbm_capacity "
                f"/ {len(self.tiers)} chips — raise worker.hbm_capacity "
                f"or use a smaller block_size)") from e

    def put_replicated(self, block_id: int, data, k: int | None = None
                       ) -> list[jax.Array]:
        """Spread a hot block as replicas across k chips (all local chips
        by default) — every consumer then reads its own HBM copy. Replica
        chips are chosen least-used-first (ICI-local by construction:
        local_devices share the host's ICI neighborhood)."""
        targets = sorted(self.tiers.values(), key=lambda t: t.used)
        targets = targets[:k if k is not None else len(targets)]
        return [t.put(block_id, data) for t in targets]

    def get(self, block_id: int, device=None) -> jax.Array | None:
        """Prefer the copy on `device` (HBM-local read); fall back to any
        chip holding it."""
        if device is not None:
            t = self.tiers.get(getattr(device, "id", device))
            if t is not None and block_id in t:
                return t.get(block_id)
        for t in self.tiers.values():
            if block_id in t:
                return t.get(block_id)
        return None

    def holders(self, block_id: int) -> list[int]:
        return [did for did, t in sorted(self.tiers.items())
                if block_id in t]

    def drop(self, block_id: int, evicted: bool = False) -> None:
        """``evicted=True`` marks a capacity/pressure drop: the shared
        ghost queue remembers the block so a re-broadcast re-admits
        straight to main. Master-commanded deletes stay evicted=False —
        a deleted block must NOT enjoy fast re-admission."""
        for t in self.tiers.values():
            t.drop(block_id, evicted=evicted)

    def __contains__(self, block_id: int) -> bool:
        return any(block_id in t for t in self.tiers.values())

    def stats(self) -> dict:
        # policy counters come off the ONE shared policy — per-tier
        # sums would multiply-count it by chip count
        ps = self.policy.stats()
        agg = {"capacity": self.capacity, "used": self.used,
               "devices": len(self.tiers),
               "blocks": len({b for t in self.tiers.values()
                              for b in t._blocks}),
               "hits": sum(t.hits for t in self.tiers.values()),
               "misses": sum(t.misses for t in self.tiers.values()),
               "spills": sum(t.spills for t in self.tiers.values()),
               "ghost_hits": ps.get("ghost_hits", 0),
               "scan_evicted": ps.get("scan_evicted", 0),
               "exports": len(self.exports),
               "export_adds": self.exports.exports}
        agg["per_device"] = self.per_device_stats()
        return agg


def export_metrics(tier, registry, prefix: str = "hbm") -> None:
    """Surface HbmTier/MultiHbmTier counters on a MetricsRegistry
    (/metrics): hits, misses, spills, occupancy. Counted since round 2,
    but never exported until now."""
    st = tier.stats()
    registry.gauge(f"{prefix}.hits", st.get("hits", 0))
    registry.gauge(f"{prefix}.misses", st.get("misses", 0))
    registry.gauge(f"{prefix}.spills", st.get("spills", 0))
    registry.gauge(f"{prefix}.ghost_hits", st.get("ghost_hits", 0))
    registry.gauge(f"{prefix}.scan_evicted", st.get("scan_evicted", 0))
    registry.gauge(f"{prefix}.used", st["used"])
    registry.gauge(f"{prefix}.capacity", st["capacity"])
    registry.gauge(f"{prefix}.occupancy",
                   st["used"] / st["capacity"] if st["capacity"] else 0.0)
