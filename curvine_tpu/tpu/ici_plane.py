"""Pod-scale ICI data plane: peer-addressable HBM tier-0.

Three coupled pieces (docs/ici-plane.md):

* **Export advertisement** — a worker with an HBM tier advertises its
  device-resident blocks (device ordinal, ICI mesh coords, buffer
  shape/dtype) through an `HbmExportTable` (tpu/hbm.py). The bounded
  snapshot rides every heartbeat and the per-block flags ride
  GET_BLOCK_INFO, mirroring the shm-export capability negotiation of
  the 100 µs data plane (worker/shm.py).

* **Endpoint registry + device-path pull** — participants that share a
  device domain (workers and SDK loaders embedded on the same TPU host,
  or the whole in-process MiniCluster harness) register an
  `IciEndpoint`. `fetch_device_block` then serves a peer's HBM-resident
  block as a jax.Array moved device-to-device (XLA routes the copy over
  ICI; on the CPU interpret path it degrades to a host-backed device
  copy) — zero bytes on the TCP rail. Anything outside the device
  domain simply misses the registry and falls back to the TCP pull;
  fallback is a COUNTER, never an error.

* **Mesh broadcast rail** — `broadcast_bytes` streams a byte payload to
  every chip as a pipeline of bounded chunks instead of one monolithic
  replicated transfer. On a real pod the chunks ride the ICI fan-out
  back-to-back so every link stays busy (classic pipelined-tree
  broadcast); on the CPU interpret mesh the same chunking keeps each
  transfer inside the runtime's recycled-buffer fast path. The
  topology-derived schedule (`broadcast_schedule`) plans one reader per
  host with log2-depth ICI fan-out rounds after it.
"""

from __future__ import annotations

import logging
import threading
import time
from dataclasses import dataclass, field

import numpy as np

log = logging.getLogger(__name__)

# pipelined-broadcast chunk size: large enough to amortize dispatch,
# small enough that every transfer stays in the runtime's recycled
# buffer pool (the >32MB allocation path re-faults fresh pages per
# transfer and runs ~4x slower on the CPU harness; real TPU runtimes
# have the same preference for bounded staging buffers on the links)
DEFAULT_CHUNK_BYTES = 8 << 20


# --------------------------------------------------------------------
# endpoint registry (process-wide: the device domain)
# --------------------------------------------------------------------

@dataclass
class IciEndpoint:
    """One participant of the device domain: a worker (or embedded SDK
    loader) holding an HBM tier plus its position in the ICI mesh."""

    worker_id: int
    hbm: object                      # HbmTier | MultiHbmTier
    coords: tuple[int, ...] = ()


_lock = threading.Lock()
_endpoints: dict[int, IciEndpoint] = {}


def register_endpoint(worker_id: int, hbm, coords=()) -> IciEndpoint:
    """Join the device domain. Idempotent per worker_id (re-register
    replaces — a restarted worker's stale tier must not serve)."""
    ep = IciEndpoint(worker_id=int(worker_id), hbm=hbm,
                     coords=tuple(coords or ()))
    with _lock:
        _endpoints[ep.worker_id] = ep
    return ep


def unregister_endpoint(worker_id: int) -> None:
    with _lock:
        _endpoints.pop(int(worker_id), None)


def lookup_endpoint(worker_id: int) -> IciEndpoint | None:
    with _lock:
        return _endpoints.get(int(worker_id))


def endpoints() -> list[IciEndpoint]:
    with _lock:
        return list(_endpoints.values())


def fetch_device_block(src_worker_id: int, block_id: int,
                       device=None):
    """Pull a peer's HBM-resident block over the device path.

    Returns a jax.Array (on `device` when given, else wherever the
    source holds it) or None when the peer is outside this device
    domain or no longer holds the block — the caller falls back to the
    TCP rail. Never raises for "not reachable this way": that is the
    fallback contract, not an error."""
    ep = lookup_endpoint(src_worker_id)
    if ep is None or ep.hbm is None:
        return None
    try:
        arr = ep.hbm.get(block_id)
    except Exception as e:      # noqa: BLE001 — a dying tier is a miss
        log.debug("ici fetch of block %d from worker %d failed: %s",
                  block_id, src_worker_id, e)
        return None
    if arr is None:
        return None
    if device is not None:
        import jax
        if device not in arr.devices():
            # device-to-device move: XLA routes this over ICI on a pod;
            # the CPU interpret path degrades to a host-backed copy
            arr = jax.device_put(arr, device)
    return arr


# --------------------------------------------------------------------
# topology-derived broadcast schedule
# --------------------------------------------------------------------

@dataclass
class BroadcastSchedule:
    """Plan for one mesh broadcast: which participant reads from the
    cache (one per host) and the ICI fan-out rounds after it.

    ``rounds`` is a list of lists of (src_index, dst_index) edges over
    the participant order; round k may only use sources that already
    hold the data (the root, or destinations of earlier rounds)."""

    root: int
    order: list[int]
    rounds: list[list[tuple[int, int]]]
    chunk_bytes: int = DEFAULT_CHUNK_BYTES

    def receivers(self) -> set[int]:
        out = {self.root}
        for r in self.rounds:
            for _, dst in r:
                out.add(dst)
        return out

    def depth(self) -> int:
        return len(self.rounds)


def broadcast_schedule(n: int, coords: list[tuple[int, ...]] | None = None,
                       mesh_shape: tuple[int, ...] | None = None,
                       root: int = 0,
                       chunk_bytes: int = DEFAULT_CHUNK_BYTES
                       ) -> BroadcastSchedule:
    """Binomial-tree broadcast plan over ``n`` participants.

    With ``coords`` (ICI positions) the participant order walks outward
    from the root by hop distance, so every tree edge connects
    ICI-adjacent pairs where the torus allows it — each doubling round
    forwards to the nearest not-yet-covered participants. Without
    coords the order is index order (still log2 depth)."""
    from curvine_tpu.master.placement import ici_hops

    if n <= 0:
        raise ValueError("broadcast needs at least one participant")
    idxs = [i for i in range(n) if i != root]
    if coords:
        shape = list(mesh_shape) if mesh_shape else None
        idxs.sort(key=lambda i: (ici_hops(list(coords[root]),
                                          list(coords[i]), shape), i))
    order = [root] + idxs
    rounds: list[list[tuple[int, int]]] = []
    have = 1                      # prefix of `order` that holds the data
    while have < n:
        edges = []
        for k in range(min(have, n - have)):
            # holder k forwards to the next uncovered participant; with
            # hop-sorted order the earliest holders (nearest the root)
            # reach outward to the nearest frontier
            edges.append((order[k], order[have + k]))
        rounds.append(edges)
        have += len(edges)
    return BroadcastSchedule(root=root, order=order, rounds=rounds,
                             chunk_bytes=chunk_bytes)


# --------------------------------------------------------------------
# pipelined mesh broadcast rail
# --------------------------------------------------------------------

@dataclass
class ReplicatedBytes:
    """A byte payload resident on EVERY device of a mesh, as the
    pipeline's bounded chunks. ``np()`` gives the host view (bit-exact
    with the source); ``chunks`` are uint8 jax.Arrays replicated over
    the mesh."""

    length: int
    chunks: list = field(default_factory=list)

    @property
    def nbytes(self) -> int:
        return self.length

    def np(self) -> np.ndarray:
        if not self.chunks:
            return np.empty(0, dtype=np.uint8)
        return np.concatenate(
            [np.asarray(c) for c in self.chunks])[:self.length]


def broadcast_bytes(data, mesh, chunk_bytes: int = DEFAULT_CHUNK_BYTES,
                    counters: dict | None = None) -> ReplicatedBytes:
    """Stream host bytes to every chip of ``mesh`` as pipelined chunks.

    The flat baseline (one replicated device_put of the whole payload)
    serializes one oversized transfer per device; chunking keeps each
    transfer on the runtime's pooled fast path and lets the next chunk's
    fan-out overlap the previous one — the pipelined tree/ring broadcast
    shape. Bit-exact: ``result.np() == bytes(data)``."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    arr = np.frombuffer(data, dtype=np.uint8) if isinstance(
        data, (bytes, bytearray, memoryview)) else np.asarray(data)
    arr = arr.reshape(-1).view(np.uint8)
    rep = NamedSharding(mesh, P())
    t0 = time.perf_counter()
    chunk_bytes = max(1, int(chunk_bytes))
    out = ReplicatedBytes(length=arr.nbytes)
    for off in range(0, max(arr.nbytes, 1), chunk_bytes):
        piece = arr[off:off + chunk_bytes]
        if piece.nbytes == 0 and off:
            break
        # dispatch without blocking: chunk k+1's host-link stage rides
        # behind chunk k's fan-out
        out.chunks.append(jax.device_put(piece, rep))
    for c in out.chunks:
        c.block_until_ready()
    if counters is not None:
        counters["ici.broadcast_bytes"] = \
            counters.get("ici.broadcast_bytes", 0) + arr.nbytes
        counters["ici.broadcast_ms"] = counters.get("ici.broadcast_ms", 0) \
            + int((time.perf_counter() - t0) * 1000)
    return out


def flat_replicate(data, mesh):
    """The pre-tree baseline: one monolithic replicated transfer. Kept
    as the A/B control for the bench gate and the bit-exactness test."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    arr = np.frombuffer(data, dtype=np.uint8) if isinstance(
        data, (bytes, bytearray, memoryview)) else np.asarray(data)
    return jax.block_until_ready(
        jax.device_put(arr.reshape(-1).view(np.uint8),
                       NamedSharding(mesh, P())))
