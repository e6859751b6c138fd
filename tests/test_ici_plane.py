"""ICI data-plane tests (docs/ici-plane.md): torus hop matrix, the
pluggable distance fallback tiers, fault-domain spread placement, the
binomial broadcast schedule, the pipelined broadcast rail (bit-exact vs
the flat baseline), tree-vs-flat checkpoint distribution, and the
peer-HBM replication pull with its TCP fallback contract."""

import asyncio
import math
import os

import numpy as np
import pytest

import jax

from curvine_tpu.common.conf import ClusterConf
from curvine_tpu.master.placement import (
    HOST_FAR, UNKNOWN_FAR, IciPolicy, ici_hops, topology_distance,
)
from curvine_tpu.rpc import RpcCode
from curvine_tpu.rpc.frame import pack, unpack
from curvine_tpu.testing import MiniCluster
from curvine_tpu.tpu import ici_plane

CPUS = jax.devices("cpu")
MB = 1024 * 1024


# --------------------------------------------------------------------
# distance function
# --------------------------------------------------------------------

def test_ici_hops_matrix_2x2x2():
    shape = [2, 2, 2]
    # on a 2-torus every axis is distance 0 or 1 (wrap == direct)
    coords = [(x, y, z) for x in range(2) for y in range(2)
              for z in range(2)]
    for a in coords:
        for b in coords:
            want = sum(int(i != j) for i, j in zip(a, b))
            assert ici_hops(list(a), list(b), shape) == want
    # symmetric, zero on the diagonal
    assert ici_hops([0, 0, 0], [0, 0, 0], shape) == 0
    assert ici_hops([0, 1, 0], [1, 0, 1], shape) == \
        ici_hops([1, 0, 1], [0, 1, 0], shape) == 3


def test_ici_hops_matrix_4x2():
    shape = [4, 2]
    # the 4-axis wraps: 0 -> 3 is one hop the short way round
    assert ici_hops([0, 0], [3, 0], shape) == 1
    assert ici_hops([0, 0], [2, 0], shape) == 2
    assert ici_hops([1, 0], [3, 1], shape) == 3
    # without a mesh shape the distance is plain manhattan (no wrap)
    assert ici_hops([0, 0], [3, 0], None) == 3
    # mismatched / missing coordinates are "very far", never an error
    assert ici_hops([0, 0], [0, 0, 0], shape) == 1 << 16
    assert ici_hops([], [1, 1], shape) == 1 << 16


def test_topology_distance_fallback_tiers():
    # both sides carry coords -> torus hops
    assert topology_distance([0, 0], "a", [1, 1], "b", [4, 2]) == 2
    # coords missing on one side -> host labels decide
    assert topology_distance([], "hostA", [1, 1], "hostA") == 0
    assert topology_distance([], "hostA", [1, 1], "hostB") == HOST_FAR
    # nothing known at all -> farthest tier
    assert topology_distance([], "", [], "") == UNKNOWN_FAR
    # the tiers are strictly ordered: hops < host-far < unknown-far
    assert topology_distance([0, 0], "", [3, 1], "", [4, 2]) < HOST_FAR


# --------------------------------------------------------------------
# placement: fault-domain spread
# --------------------------------------------------------------------

def _mk_worker(i, host, coords, avail=50):
    from curvine_tpu.common.types import (
        StorageInfo, WorkerAddress, WorkerInfo,
    )
    return WorkerInfo(
        address=WorkerAddress(worker_id=i, hostname=host,
                              rpc_port=1000 + i),
        storages=[StorageInfo(capacity=100, available=avail)],
        ici_coords=list(coords))


def test_ici_policy_fault_domain_spread():
    """On a 2x2x2 torus, 3 replicas land on pairwise-distant corners:
    the first stays ICI-near the writer, the rest maximise the min
    distance to everything already chosen."""
    shape = [2, 2, 2]
    ws = [_mk_worker(i, f"host{i}", c) for i, c in enumerate(
        (x, y, z) for x in range(2) for y in range(2) for z in range(2))]
    p = IciPolicy(mesh_shape=shape)
    chosen = p.choose(ws, 3, ici_coords=[0, 0, 0], needed=1)
    coords = [tuple(w.ici_coords) for w in chosen]
    # replica 0 is the writer's own corner (0 hops)
    assert coords[0] == (0, 0, 0)
    # replica 1 is the opposite corner (max-min spread: 3 hops)
    assert coords[1] == (1, 1, 1)
    # once the antipodal pair is taken, every remaining vertex of a
    # 2x2x2 is adjacent to one of them -- the greedy third pick is at
    # the max achievable min distance (1), never co-located
    for i in range(len(coords)):
        for j in range(i + 1, len(coords)):
            assert ici_hops(list(coords[i]), list(coords[j]), shape) >= 1
    assert len(set(coords)) == 3
    # distinct fault domains (hosts) throughout
    assert len({w.address.hostname for w in chosen}) == 3


def test_ici_policy_host_fallback_spread():
    """Workers without mesh coords spread by host label: one replica
    near the writer's host, others on different hosts."""
    ws = [_mk_worker(1, "hostA", []), _mk_worker(2, "hostA", []),
          _mk_worker(3, "hostB", []), _mk_worker(4, "hostC", [])]
    p = IciPolicy()
    chosen = p.choose(ws, 3, client_host="hostA", needed=1)
    assert chosen[0].address.hostname == "hostA"
    assert len({w.address.hostname for w in chosen}) == 3


# --------------------------------------------------------------------
# broadcast schedule
# --------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 2, 3, 5, 8])
def test_broadcast_schedule_properties(n):
    s = ici_plane.broadcast_schedule(n)
    # every participant receives the data exactly once
    assert s.receivers() == set(range(n))
    dsts = [d for r in s.rounds for _, d in r]
    assert len(dsts) == len(set(dsts)) == n - 1
    # a round may only use sources that already hold the data
    have = {s.root}
    for r in s.rounds:
        for src, dst in r:
            assert src in have and dst not in have
        have |= {d for _, d in r}
    # binomial tree: log2 depth
    assert s.depth() == math.ceil(math.log2(n)) if n > 1 else s.depth() == 0


def test_broadcast_schedule_hop_sorted():
    """With coords the fan-out order walks outward from the root by
    torus hop distance: round 1 reaches a nearest neighbor, the far
    corner is reached last."""
    shape = (2, 2, 2)
    coords = [(x, y, z) for x in range(2) for y in range(2)
              for z in range(2)]
    s = ici_plane.broadcast_schedule(8, coords=coords, mesh_shape=shape)
    hops = [ici_hops(list(coords[0]), list(coords[i]), list(shape))
            for i in s.order]
    assert hops == sorted(hops)          # order walks outward
    # round 1: the root forwards to a 1-hop neighbor
    (src, dst), = s.rounds[0]
    assert src == 0
    assert ici_hops(list(coords[0]), list(coords[dst]), list(shape)) == 1
    assert s.receivers() == set(range(8))


# --------------------------------------------------------------------
# broadcast rail: pipelined chunks, bit-exact vs flat
# --------------------------------------------------------------------

def _mesh8():
    from curvine_tpu.tpu.mesh import make_mesh
    if len(CPUS) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh")
    return make_mesh(devices=CPUS, axis_names=("data",))


def test_broadcast_bytes_bit_exact():
    mesh = _mesh8()
    data = os.urandom(3 * MB + 123)
    counters = {}
    rb = ici_plane.broadcast_bytes(data, mesh, chunk_bytes=MB,
                                   counters=counters)
    assert rb.nbytes == len(data)
    assert len(rb.chunks) == 4                    # ceil(3MB+123 / 1MB)
    assert bytes(rb.np()) == data                 # bit-exact reassembly
    flat = ici_plane.flat_replicate(data, mesh)
    assert bytes(np.asarray(flat)) == data
    # every chunk is replicated on all 8 devices
    for c in rb.chunks:
        assert len(c.sharding.device_set) == len(mesh.devices.flat)
    assert counters["ici.broadcast_bytes"] == len(data)
    assert "ici.broadcast_ms" in counters


def test_broadcast_bytes_empty_payload():
    mesh = _mesh8()
    rb = ici_plane.broadcast_bytes(b"", mesh)
    assert rb.nbytes == 0 and bytes(rb.np()) == b""


async def test_distribute_tree_matches_flat():
    """The mesh-tree schedule delivers bit-identical params to the flat
    replicate path."""
    from curvine_tpu.tpu.broadcast import (
        distribute_checkpoint, save_checkpoint,
    )
    mesh = _mesh8()
    rng = np.random.default_rng(7)
    params = {
        "emb": rng.standard_normal((64, 32)).astype(np.float32),
        "mlp": {"w": rng.standard_normal((32, 128)).astype(np.float32),
                "b": np.zeros((128,), dtype=np.float32)},
        "step": np.int32(17),
    }
    async with MiniCluster(workers=1) as mc:
        c = mc.client()
        await save_checkpoint(c, "/ckpt/tree", params)
        tree = await distribute_checkpoint(c, "/ckpt/tree", mesh)
        flat = await distribute_checkpoint(c, "/ckpt/tree", mesh,
                                           schedule="flat")
        t_leaves = jax.tree_util.tree_leaves(tree)
        f_leaves = jax.tree_util.tree_leaves(flat)
        assert len(t_leaves) == len(f_leaves) == 4
        for t, f in zip(t_leaves, f_leaves):
            assert t.shape == f.shape and t.dtype == f.dtype
            np.testing.assert_array_equal(np.asarray(t), np.asarray(f))
            # replicated across the full mesh on both paths
            assert len(t.sharding.device_set) == len(CPUS)


# --------------------------------------------------------------------
# endpoint registry + device-path fetch
# --------------------------------------------------------------------

def test_endpoint_registry_fetch_and_miss():
    from curvine_tpu.tpu.hbm import HbmTier
    tier = HbmTier(4 * MB, device=CPUS[0])
    payload = os.urandom(1024)
    tier.put(77, payload)
    ici_plane.register_endpoint(901, tier, coords=(1, 0))
    try:
        arr = ici_plane.fetch_device_block(901, 77)
        assert arr is not None
        assert bytes(np.asarray(arr)) == payload
        # move to another device of the domain
        arr2 = ici_plane.fetch_device_block(901, 77, device=CPUS[1])
        assert CPUS[1] in arr2.devices()
        assert bytes(np.asarray(arr2)) == payload
        # misses are None, never an error: unknown block, unknown peer
        assert ici_plane.fetch_device_block(901, 999) is None
        assert ici_plane.fetch_device_block(555, 77) is None
    finally:
        ici_plane.unregister_endpoint(901)
    assert ici_plane.fetch_device_block(901, 77) is None


def test_hbm_ghost_readmit_cross_chip():
    """Satellite 6: an HBM eviction ghosts into the SHARED S3-FIFO ghost
    queue, so a re-broadcast re-admits straight to main -- even when the
    block re-lands on a different chip."""
    from curvine_tpu.tpu.hbm import MultiHbmTier
    tier = MultiHbmTier(8 * MB, devices=CPUS[:2], admission="s3fifo")
    tier.put(1, os.urandom(1024), device=CPUS[0])
    assert 1 in tier.policy._small            # probation on first admit
    tier.drop(1, evicted=True)                # eviction -> shared ghost
    assert tier.policy.stats()["ghost"] == 1
    tier.put(1, os.urandom(1024), device=CPUS[1])   # other chip
    assert tier.policy.ghost_hits == 1
    assert 1 in tier.policy._main             # skipped probation
    # master-commanded delete does NOT ghost
    tier.drop(1)
    assert tier.policy.stats()["ghost"] == 0
    # shared export table follows membership across chips
    assert 1 not in tier.exports


# --------------------------------------------------------------------
# replication over the device path (e2e on MiniCluster)
# --------------------------------------------------------------------

def _hbm_conf():
    conf = ClusterConf()
    conf.worker.hbm_capacity = 32 * MB
    return conf


async def _write_and_pin(mc, c, path, data):
    """Write a single-replica block, pin it into the holder's HBM, and
    heartbeat so the master learns the advertisement. Returns
    (block_id, src_worker, dst_worker)."""
    await c.write_all(path, data)
    fb = await c.meta.get_block_locations(path)
    lb = fb.block_locs[0]
    bid = lb.block.id
    src_wid = lb.locs[0].worker_id
    src = next(w for w in mc.workers if w.worker_id == src_wid)
    dst = next(w for w in mc.workers if w.worker_id != src_wid)
    conn = await c.pool.get(src.addr)
    rep = await conn.call(RpcCode.HBM_PIN, data=pack({"block_id": bid}))
    body = rep.header or unpack(rep.data)
    assert body["len"] == len(data)
    await src.heartbeat_once()
    assert bid in mc.master.replication._hbm_blocks.get(src_wid, set())
    return bid, src, dst


async def _wait_replicas(c, path, n, timeout=15.0):
    deadline = asyncio.get_event_loop().time() + timeout
    while True:
        fb = await c.meta.get_block_locations(path)
        if len(fb.block_locs[0].locs) >= n:
            return fb
        if asyncio.get_event_loop().time() > deadline:
            raise AssertionError(f"never reached {n} replicas: "
                                 f"{fb.block_locs[0].locs}")
        await asyncio.sleep(0.1)


async def test_replication_peer_hbm_pull_zero_tcp():
    """A re-replication whose source advertises the block in HBM rides
    the device path: the new replica lands bit-exact with ZERO bytes on
    the source's TCP block-read rail, and the master accounts the
    transfer."""
    async with MiniCluster(workers=2, conf=_hbm_conf()) as mc:
        mc.master.replication.scan_interval_s = 0.3
        c = mc.client()
        data = os.urandom(256 * 1024)
        bid, src, dst = await _write_and_pin(mc, c, "/ici/hot", data)
        src_reads = src.metrics.counters.get("bytes.read", 0)
        mc.master.fs.blocks.desired[bid] = 2
        mc.master.replication.enqueue([bid])
        await _wait_replicas(c, "/ici/hot", 2)
        # the pull went device-to-device
        assert dst.metrics.counters.get("ici.peer_pulls", 0) == 1
        assert dst.metrics.counters.get("ici.tcp_fallbacks", 0) == 0
        # zero TCP block reads served by the source for the copy
        assert src.metrics.counters.get("bytes.read", 0) == src_reads
        # master saw the hint and the via=ici completion
        mcount = mc.master.metrics.counters
        assert mcount.get("replication.ici_hinted", 0) >= 1
        assert mcount.get("replication.ici_transfers", 0) >= 1
        # the landed replica is bit-exact (crc-verified at commit; the
        # destination now serves the same bytes)
        assert dst.store.contains(bid)
        assert await c.read_all("/ici/hot") == data


async def test_replication_falls_back_to_tcp_on_dead_peer():
    """The fallback contract: a hint whose peer left the device domain
    costs one counter, never an error -- the same pull job lands over
    TCP and the block still heals."""
    async with MiniCluster(workers=2, conf=_hbm_conf()) as mc:
        mc.master.replication.scan_interval_s = 0.3
        c = mc.client()
        data = os.urandom(128 * 1024)
        bid, src, dst = await _write_and_pin(mc, c, "/ici/fb", data)
        # peer drops out of the device domain AFTER advertising: the
        # hint is now stale, exactly the race the fallback covers
        ici_plane.unregister_endpoint(src.worker_id)
        try:
            mc.master.fs.blocks.desired[bid] = 2
            mc.master.replication.enqueue([bid])
            await _wait_replicas(c, "/ici/fb", 2)
        finally:
            ici_plane.register_endpoint(src.worker_id, src.hbm,
                                        src.conf.worker.ici_coords)
        assert dst.metrics.counters.get("ici.peer_pulls", 0) == 0
        assert dst.metrics.counters.get("ici.tcp_fallbacks", 0) == 1
        assert await c.read_all("/ici/fb") == data


async def test_replication_with_ici_disabled():
    """worker.ici_transfer=False: no advertisement, no device path --
    replication works exactly as before."""
    conf = _hbm_conf()
    conf.worker.ici_transfer = False
    async with MiniCluster(workers=2, conf=conf) as mc:
        mc.master.replication.scan_interval_s = 0.3
        c = mc.client()
        data = os.urandom(128 * 1024)
        await c.write_all("/ici/off", data)
        fb = await c.meta.get_block_locations("/ici/off")
        bid = fb.block_locs[0].block.id
        src_wid = fb.block_locs[0].locs[0].worker_id
        dst = next(w for w in mc.workers if w.worker_id != src_wid)
        # nothing advertised, nothing registered
        assert not mc.master.replication._hbm_blocks.get(src_wid)
        assert ici_plane.lookup_endpoint(src_wid) is None
        mc.master.fs.blocks.desired[bid] = 2
        mc.master.replication.enqueue([bid])
        await _wait_replicas(c, "/ici/off", 2)
        assert dst.metrics.counters.get("ici.peer_pulls", 0) == 0
        assert dst.metrics.counters.get("ici.tcp_fallbacks", 0) == 0
        assert await c.read_all("/ici/off") == data


async def test_replication_prefers_ici_near_source():
    """Placement A/B: with two LIVE holders the master picks the
    topologically nearest one as the pull source for the destination."""
    from curvine_tpu.common.types import WorkerState

    async with MiniCluster(workers=3, conf=_hbm_conf()) as mc:
        rm = mc.master.replication
        c = mc.client()
        # MiniCluster puts worker i at [i, 0]: whenever the middle worker
        # is the destination both holders sit one hop away, and the tie
        # falls to the iteration order of the port-derived worker ids.
        # Move worker 2 out so all three pairwise distances differ.
        mc.workers[2].conf.worker.ici_coords = [3, 0]
        await mc.workers[2].heartbeat_once()
        data = os.urandom(64 * 1024)
        await c.write_all("/ici/near", data, replicas=2)
        fb = await c.meta.get_block_locations("/ici/near")
        bid = fb.block_locs[0].block.id
        holders = {loc.worker_id for loc in fb.block_locs[0].locs}
        (dst_wid,) = {w.worker_id for w in mc.workers} - holders
        dst_info = mc.master.fs.workers.workers[dst_wid]
        assert dst_info.state == WorkerState.LIVE
        # capture the submit instead of dispatching it
        submitted = {}

        class _Conn:
            async def call(self, code, data=b"", deadline=None):
                submitted.update(unpack(data))

        class _Pool:
            async def get(self, addr):
                return _Conn()

        rm.pool = _Pool()
        mc.master.fs.blocks.desired[bid] = 3
        ok = await rm._replicate(bid)
        assert ok and submitted["block_id"] == bid
        # the chosen source must be the holder nearest the destination
        by_id = mc.master.fs.workers.workers
        src_wid = submitted["source"]["worker_id"]
        want = min(holders, key=lambda wid: ici_hops(
            list(by_id[wid].ici_coords),
            list(by_id[dst_wid].ici_coords)))
        assert src_wid == want
        # both holders pinned nothing: no hint rides a cold source
        assert "ici" not in submitted
