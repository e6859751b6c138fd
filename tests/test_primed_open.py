"""A caller that knows its files up front primes the client with the
list (`CurvineClient.prime`): locations, block info and read reports then
cross once a peer for the list, not once a file. A restore primes its
manifest; the bytes, the checksums they are verified against, the chips
they land on and the worker's heat are those of the per-file path; a
path the master refuses fails its own open alone; and a file never
primed makes the calls it always made."""

import asyncio
import os
import socket
import sys
import threading
import time

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

from curvine_tpu.client import CurvineClient
from curvine_tpu.client.reader import BatchFetcher
from curvine_tpu.common import errors as err
from curvine_tpu.common.conf import ClusterConf
from curvine_tpu.common.qos import READ, AdmissionController
from curvine_tpu.common.types import SetAttrOpts
from curvine_tpu.rpc import RpcCode
from curvine_tpu.rpc.frame import pack, unpack
from curvine_tpu.testing import MiniCluster
from curvine_tpu.worker import shm as wshm

BLOCK = 4096
NEW = ("read.prime.calls", "read.primed.files", "read.primed.blocks",
       "read.reports.merged")


def make_params(seed: int = 3) -> dict:
    """Nine tensors, one of them over four blocks and one over two."""
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return rng.standard_normal(shape).astype(np.float32)

    return {"embed": normal(4, 32, 32), "head": normal(64, 32),
            "layers": [{"w": normal(4, 8, 8), "b": normal(8)}
                       for _ in range(3)],
            "norm": normal(8)}


def blocks_of(params) -> int:
    return sum(-(-x.nbytes // BLOCK) for x in jax.tree.leaves(params))


def mesh_of():
    from curvine_tpu.tpu.mesh import make_mesh
    return make_mesh(devices=jax.devices("cpu")[:4], axis_names=("expert",))


async def flat(c, path):
    from curvine_tpu.tpu.broadcast import load_checkpoint
    return await load_checkpoint(c, path, placer=jax.device_put)


async def tree(c, path):
    from curvine_tpu.tpu.broadcast import _distribute_tree
    return await _distribute_tree(c, path, mesh_of())


async def sharded(c, path):
    from curvine_tpu.tpu.broadcast import _distribute_sharded
    specs = jax.tree.map(
        lambda x: P("expert", None, None) if x.ndim == 3 else P(),
        make_params())
    return await _distribute_sharded(c, path, mesh_of(), specs)


async def saved(mc, params, path="/ckpt"):
    from curvine_tpu.tpu.broadcast import save_checkpoint
    writer = mc.client()
    await save_checkpoint(writer, path, params)
    await writer.close()
    return path


def served(server) -> dict:
    """Requests a server has answered so far, by code name."""
    return {name[4:]: h.count
            for name, h in server.metrics.histograms.items()
            if name.startswith("rpc.")}


def grown(now: dict, before: dict):
    return lambda k: now.get(k, 0) - before.get(k, 0)


def count_connections(monkeypatch) -> list:
    """Every connection a worker's shm channel accepts from now on."""
    accepted, serve = [], wshm.ShmChannel._serve

    def counted(self, conn):
        accepted.append(conn.fileno())
        return serve(self, conn)

    monkeypatch.setattr(wshm.ShmChannel, "_serve", counted)
    return accepted


def heat(mc) -> dict:
    return {bid: info.heat for bid, info in mc.workers[0].store.blocks.items()}


async def restored(mc, entry, path, prime: bool = True):
    """One restore on a client of its own → (params, what grew in the
    client's counters, on the master, on the worker, in the heat)."""
    c = mc.client()
    if not prime:
        async def nothing(paths):
            pass
        c.prime = nothing
    m0, w0, h0 = served(mc.master), served(mc.workers[0]), heat(mc)
    back = await entry(c, path)
    h1 = heat(mc)
    return (back, grown(c.counters, {}), grown(served(mc.master), m0),
            grown(served(mc.workers[0]), w0),
            {b: h1[b] - h0.get(b, 0) for b in h1})


@pytest.mark.parametrize("entry", [flat, tree, sharded])
async def test_a_primed_restore_crosses_once_a_peer(tmp_path, entry,
                                                    monkeypatch):
    params = make_params()
    leaves = jax.tree.leaves(params)
    async with MiniCluster(workers=1, base_dir=str(tmp_path),
                           block_size=BLOCK) as mc:
        path = await saved(mc, params)
        accepted = count_connections(monkeypatch)
        back, grew, master, worker, heated = await restored(mc, entry, path)
        # the manifest's grant on a connection of its own, the tensors'
        # over connections that stay open: one a batch thread at most
        assert grew("read.shm_hits") == blocks_of(params) + 1
        assert 2 <= len(accepted) <= 1 + BatchFetcher.THREADS

        for want, got in zip(leaves, jax.tree.leaves(back)):
            assert np.asarray(got).tobytes() == want.tobytes()
        # the manifest is opened for itself, before anything is known;
        # its tensors cross once: two calls a peer and kind, not 2 × 10
        assert master("get_block_locations_batch") == 1
        assert master("get_block_locations") == 1
        assert worker("get_block_info") == 2 == worker("sc_read_report")
        assert grew("meta.calls") == 2
        assert grew("read.phase.probe.n") == 2
        assert grew("read.phase.locate.n") == len(leaves) + 2
        assert grew("read.phase.close.n") == len(leaves) + 1
        assert grew("read.files") == len(leaves) + 1
        assert grew("read.prime.calls") == 1
        assert grew("read.primed.files") == len(leaves)
        assert grew("read.primed.blocks") == blocks_of(params)
        assert grew("read.reports.merged") == len(leaves)
        assert grew("read.zero_copy_bytes") == sum(x.nbytes for x in leaves)
        assert grew("ckpt.restores") == 1

        # the heat the worker holds when the restore has returned is the
        # per-file path's, block for block
        before = len(accepted)
        _, plain, master, worker, unprimed = await restored(
            mc, entry, path, prime=False)
        assert not any(plain(k) for k in NEW)
        assert len(accepted) - before == blocks_of(params) + 1
        assert master("get_block_locations") == len(leaves) + 1
        assert worker("sc_read_report") == len(leaves) + 1
        assert heated == unprimed and len(heated) == blocks_of(params) + 1
        assert all(n >= 2 for n in heated.values())   # a probe and a read


async def test_the_prime_span_lies_under_the_restore(tmp_path):
    params = make_params()
    async with MiniCluster(workers=1, base_dir=str(tmp_path),
                           block_size=BLOCK) as mc:
        path = await saved(mc, params)
        c = mc.client()
        c.tracer.sample_rate = 1.0
        await flat(c, path)
        spans = c.tracer.store.drain(4096)
        (root,) = [s for s in spans if s["op"] == "ckpt.restore"]
        (prime,) = [s for s in spans if s["op"] == "prime"]
        assert prime["trace_id"] == root["trace_id"]
        assert prime["attrs"] == {"files": len(jax.tree.leaves(params)),
                                  "blocks": blocks_of(params), "workers": 1}
        (batch,) = [s for s in spans
                    if s["op"] == "meta.get_block_locations_batch"]
        assert batch["parent"] == prime["span_id"]


def client_as(mc, user: str) -> CurvineClient:
    conf = ClusterConf()
    conf.client.master_addrs = [mc.master.addr]
    conf.client.block_size = mc.conf.client.block_size
    conf.client.user, conf.client.groups = user, ["staff"]
    c = CurvineClient(conf)
    mc._clients.append(c)
    return c


async def missing(mc, path):
    await mc.client().meta.delete(path)
    return mc.client(), err.FileNotFound


async def denied(mc, path):
    await mc.client().meta.set_attr(path, SetAttrOpts(mode=0o600))
    return client_as(mc, "bob"), err.PermissionDenied


async def freed(mc, path):
    assert await mc.client().meta.free(path) == 1
    return mc.client(), err.BlockNotFound


@pytest.mark.parametrize("spoil", [missing, denied, freed])
async def test_a_refused_path_fails_its_own_open_alone(tmp_path, spoil):
    data = {f"/d/f{i}": bytes([i]) * (BLOCK + 7) for i in range(3)}
    async with MiniCluster(workers=1, base_dir=str(tmp_path),
                           block_size=BLOCK) as mc:
        w = mc.client()
        await w.meta.mkdir("/d", mode=0o755)
        for p, b in data.items():
            await w.write_all(p, b)
        c, raised = await spoil(mc, "/d/f1")
        with pytest.raises(raised) as per_file:       # as it is today
            await c.open("/d/f1")
        before = dict(c.counters)
        await c.prime(list(data))
        with pytest.raises(raised) as primed:
            await c.open("/d/f1")
        assert type(primed.value) is type(per_file.value)
        assert primed.value.code == per_file.value.code
        for p in ("/d/f0", "/d/f2"):
            r = await c.open(p)
            assert bytes(await r.mmap_view(0, r.len)) == data[p]
            await r.close()
        grew = grown(c.counters, before)
        assert grew("meta.calls") == 1 and grew("read.primed.files") == 2
        assert grew("read.files") == 2 and grew("read.primed.blocks") == 4


async def test_a_primed_entry_serves_one_open_and_dies_with_the_client(
        tmp_path):
    async with MiniCluster(workers=1, base_dir=str(tmp_path),
                           block_size=BLOCK) as mc:
        c = mc.client()
        await c.write_all("/a", b"a" * 100)
        await c.write_all("/b", b"b" * 100)
        before = dict(c.counters)
        await c.prime(["/a", "/b"])
        for _ in range(2):
            r = await c.open("/a")
            assert await r.read_all() == b"a" * 100
            await r.close()
        grew = grown(c.counters, before)
        # the second open asked the master, probed and reported for itself
        assert grew("meta.calls") == 2 and grew("read.phase.probe.n") == 2
        assert grew("read.primed.files") == 1 == grew("read.primed.blocks")
        assert grew("read.reports.merged") == 1
        # the first open's count waits for the flush; /b was never opened
        (left,) = c._primed.reads.values()
        assert sum(left.values()) == 1 and set(c._primed.files) == {"/b"}
        w0 = served(mc.workers[0])
        await c.close()
        assert not c._primed.files and not c._primed.blocks
        assert not c._primed.reads
        assert grown(served(mc.workers[0]), w0)("sc_read_report") == 1


@pytest.mark.parametrize("age_s, lease_ms, primed", [(0, 60_000, 1),
                                                     (10, 5_000, 0)])
async def test_a_lease_counts_from_the_batch_and_is_not_used_past_it(
        tmp_path, age_s, lease_ms, primed):
    async with MiniCluster(workers=1, base_dir=str(tmp_path),
                           block_size=BLOCK) as mc:
        c = mc.client()
        await c.write_all("/a", b"a" * 100)
        t0 = time.time()
        await c.prime(["/a"])
        ((bid, (info, sent_at)),) = c._primed.blocks.items()
        assert t0 <= sent_at <= time.time() and "lease_ms" not in info
        # as a bdev tier would have granted it, `age_s` seconds ago
        c._primed.blocks[bid] = (dict(info, lease_ms=lease_ms),
                                 sent_at - age_s)
        before = dict(c.counters)
        r = await c.open("/a")
        assert await r.read_all() == b"a" * 100
        grew = grown(c.counters, before)
        assert grew("read.primed.files") == 1
        assert grew("read.primed.blocks") == primed
        assert grew("read.phase.probe.n") == 1 - primed
        if primed:
            assert r._local_expiry[bid] == sent_at + lease_ms / 1000
        else:
            assert bid not in r._local_expiry       # a file tier: no lease
        await r.close()


async def old_master(tmp_path):
    mc = MiniCluster(workers=1, base_dir=str(tmp_path), block_size=BLOCK)
    await mc.start()
    del mc.master.rpc._handlers[int(RpcCode.GET_BLOCK_LOCATIONS_BATCH)]
    return mc


async def router(tmp_path):
    mc = MiniCluster(workers=1, base_dir=str(tmp_path), block_size=BLOCK,
                     shards=2)
    return await mc.start()


@pytest.mark.parametrize("cluster", [old_master, router])
async def test_a_master_that_takes_no_list_leaves_the_restore_whole(
        tmp_path, cluster):
    params = make_params()
    leaves = jax.tree.leaves(params)
    mc = await cluster(tmp_path)
    try:
        path = await saved(mc, params)
        back, grew, _, worker, _ = await restored(mc, flat, path)
        for want, got in zip(leaves, jax.tree.leaves(back)):
            assert np.asarray(got).tobytes() == want.tobytes()
        assert grew("read.prime.calls") == 1
        assert not any(grew(k) for k in NEW[1:])
        assert grew("read.files") == len(leaves) + 1
        assert grew("read.phase.probe.n") == blocks_of(params) + 1
        assert worker("sc_read_report") == len(leaves) + 1
    finally:
        await mc.stop()


async def test_an_unprimed_read_makes_the_calls_it_made(tmp_path):
    async with MiniCluster(workers=1, base_dir=str(tmp_path),
                           block_size=BLOCK) as mc:
        c = mc.client()
        await c.write_all("/a", b"a" * (2 * BLOCK))
        m0, w0 = served(mc.master), served(mc.workers[0])
        before = dict(c.counters)
        r = await c.open("/a")
        assert r.primed is None
        assert bytes(await r.mmap_view(0, r.len)) == b"a" * (2 * BLOCK)
        await r.close()
        master = grown(served(mc.master), m0)
        worker = grown(served(mc.workers[0]), w0)
        assert master("get_block_locations") == 1
        assert master("get_block_locations_batch") == 0
        assert worker("get_block_info") == 2 and worker("sc_read_report") == 1
        grew = grown(c.counters, before)
        assert grew("meta.calls") == 1 and grew("read.phase.probe.n") == 2
        assert not set(NEW) & set(c.counters)
        await c.flush_reports()                       # nothing to send
        assert grown(served(mc.workers[0]), w0)("sc_read_report") == 1


async def test_the_worker_answers_a_list_block_for_block(tmp_path):
    async with MiniCluster(workers=1, base_dir=str(tmp_path),
                           block_size=BLOCK) as mc:
        c = mc.client()
        await c.write_all("/a", b"a" * (2 * BLOCK))
        fb = await c.meta.get_block_locations("/a")
        ids = [lb.block.id for lb in fb.block_locs]
        h0 = heat(mc)
        conn = await c.pool.get(mc.workers[0].addr)
        rep = await conn.call(RpcCode.GET_BLOCK_INFO,
                              data=pack({"block_ids": [ids[0], 12345, ids[1]]}))
        one, gone, two = unpack(rep.data)["blocks"]
        assert gone["block_id"] == 12345
        assert gone["error_code"] == int(err.ErrorCode.BLOCK_NOT_FOUND)
        single = await conn.call(RpcCode.GET_BLOCK_INFO,
                                 data=pack({"block_id": ids[0]}))
        assert one == (single.header or unpack(single.data))
        assert two["block_id"] == ids[1] and two["shm"] is True
        # each id went through the store's grant, as a probe of its own
        assert {b: heat(mc)[b] - h0[b] for b in ids} == {ids[0]: 2, ids[1]: 1}


@pytest.fixture
def memfd_channel(tmp_path, monkeypatch):
    """A side channel whose grant of block n is a memfd of n bytes of
    b"x" (404: not served; 500: the grant fails) → (its path, the
    connections it accepted, the blocks it granted, in order)."""
    accepted = count_connections(monkeypatch)
    granted = []

    def grant(block_id):
        if block_id == 404:
            raise LookupError(block_id)
        if block_id == 500:
            raise RuntimeError(block_id)
        granted.append(block_id)
        fd = os.memfd_create(f"t{block_id}")
        os.write(fd, b"x" * block_id)
        return fd, block_id

    path = str(tmp_path / "shm.sock")
    channel = wshm.ShmChannel(path, grant)
    channel.start()
    yield path, accepted, granted
    channel.stop()


def test_kept_connections_serve_grant_after_grant(tmp_path, memfd_channel):
    path, accepted, granted = memfd_channel
    conns = wshm.ShmConns()

    def one(sock_path, n):
        (got,) = conns.pipeline(sock_path, [n])
        return got

    try:
        for n in (5, 6, 7):
            fd, length = one(path, n)
            assert length == n and os.pread(fd, 16, 0) == b"x" * n
            os.close(fd)
        # an answer: still kept
        assert isinstance(one(path, 404), LookupError)
        assert len(accepted) == 1 and len(conns._idle[path]) == 1
        # the worker closes a connection that idles: asked again on a new
        conns._idle[path][0].shutdown(socket.SHUT_RDWR)
        fd, length = one(path, 8)
        os.close(fd)
        assert length == 8 and len(accepted) == 2
        assert granted == [5, 6, 7, 8] and len(conns._idle[path]) == 1
        # one-shot, as before, beside them
        fd, _ = wshm.fetch_block_fd(path, 9)
        os.close(fd)
        assert len(accepted) == 3
        # closed: nothing is kept any more, a late grant still answered
        (kept,) = conns._idle[path]
        conns.close()
        assert kept.fileno() == -1
        fd, length = one(path, 3)
        os.close(fd)
        assert length == 3 and conns._idle is None
        assert isinstance(one(str(tmp_path / "nobody.sock"), 1), OSError)
    finally:
        conns.close()


def answers(got: list) -> list:
    """A pipeline's answers as (length, bytes), LookupError or OSError;
    each fd closed."""
    out = []
    for a in got:
        if isinstance(a, Exception):
            assert isinstance(a, (LookupError, OSError)), a
            out.append(LookupError if isinstance(a, LookupError)
                       else OSError)
            continue
        fd, length = a
        out.append((length, os.pread(fd, 1024, 0)))
        os.close(fd)
    return out


def test_pipelined_grants_answer_in_order_on_one_connection(memfd_channel):
    path, accepted, granted = memfd_channel
    conns = wshm.ShmConns()
    try:
        got = answers(conns.pipeline(path, [5, 404, 6, 500, 7]))
        # each block its own fd and length, in the order asked; a refusal
        # and a failed grant are answers of their own block alone
        assert got == [(5, b"x" * 5), LookupError, (6, b"x" * 6), OSError,
                       (7, b"x" * 7)]
        assert granted == [5, 6, 7]
        # one connection for the batch, kept for the next
        assert len(accepted) == 1 and len(conns._idle[path]) == 1
        assert answers(conns.pipeline(path, [8, 9])) \
            == [(8, b"x" * 8), (9, b"x" * 9)]
        assert len(accepted) == 1
        # left before its end: the connection has answers on the way
        gen = conns.pipeline(path, [10, 11])
        answers([next(gen)])
        gen.close()
        assert not conns._idle[path]
    finally:
        conns.close()


@pytest.mark.parametrize("drops, want", [
    # dropped once: the unanswered blocks asked again on a new connection
    ((3,), [(5, b"x" * 5), (6, b"x" * 6), (7, b"x" * 7), (8, b"x" * 8)]),
    # dropped again there: asked once only, the rest get the error
    ((3, 4), [(5, b"x" * 5), (6, b"x" * 6), OSError, OSError]),
])
def test_a_channel_that_drops_mid_batch_is_asked_again_once(
        memfd_channel, monkeypatch, drops, want):
    path, accepted, granted = memfd_channel
    replies, reply = [], wshm.ShmChannel._reply

    def dropping(conn, status, length, fd):
        replies.append(length)
        if len(replies) in drops:
            conn.shutdown(socket.SHUT_RDWR)     # the channel fails here
            return False
        return reply(conn, status, length, fd)

    monkeypatch.setattr(wshm.ShmChannel, "_reply", staticmethod(dropping))
    conns = wshm.ShmConns()
    try:
        assert answers(conns.pipeline(path, [5, 6, 7, 8])) == want
        # a new connection once, however often the channel drops
        assert len(accepted) == 2
        # the grant the channel dropped was asked for again
        assert granted[:4] == [5, 6, 7, 7]
    finally:
        conns.close()



def gate_batches(monkeypatch) -> tuple[threading.Event, list]:
    """Batch threads wait for the event before they take anything, and
    each batch's block ids are kept, in the order sent."""
    gate, batches = threading.Event(), []
    work, pipeline = BatchFetcher._work, wshm.ShmConns.pipeline

    def late(self):
        gate.wait(10)
        work(self)

    def recorded(self, spath, ids, timeout=5.0):
        batches.append(list(ids))
        return pipeline(self, spath, ids, timeout)

    monkeypatch.setattr(BatchFetcher, "_work", late)
    monkeypatch.setattr(wshm.ShmConns, "pipeline", recorded)
    return gate, batches


async def written(mc, data: dict) -> dict:
    """`data` written, one block a file → path: its block id."""
    w = mc.client()
    for p, b in data.items():
        await w.write_all(p, b)
    return {p: (await w.meta.get_block_locations(p)).block_locs[0].block.id
            for p in data}


async def test_a_spoiled_block_of_a_batch_falls_back_alone(tmp_path,
                                                           monkeypatch):
    data = {f"/b/f{i}": bytes([i]) * BLOCK for i in range(7)}
    async with MiniCluster(workers=1, base_dir=str(tmp_path),
                           block_size=BLOCK) as mc:
        ids = await written(mc, data)
        gone, stale, bad = ids["/b/f2"], ids["/b/f3"], ids["/b/f4"]
        channel = mc.workers[0]._shm_channel
        grant = channel.grant

        def spoiled(block_id):
            if block_id == gone:
                raise LookupError(block_id)        # NOT_FOUND
            fd, length = grant(block_id)
            if block_id not in (stale, bad):
                return fd, length
            os.close(fd)
            # another length than the block's, or its length with
            # bytes its checksum does not match
            fake = os.memfd_create("spoiled")
            os.write(fake, b"\xff" * (length + (length if block_id == stale
                                                else 0)))
            return fake, os.fstat(fake).st_size

        channel.grant = spoiled
        gate, batches = gate_batches(monkeypatch)
        c = mc.client()
        await c.prime(list(data))
        readers = [await c.open(p) for p in data]
        before = dict(c.counters)
        views = asyncio.gather(*(r.mmap_view(0, r.len) for r in readers))
        await asyncio.sleep(0.05)        # every block queued before a take
        gate.set()
        for p, r, view in zip(data, readers, await views):
            if view is None:             # the bad copy, flagged: by socket
                view = await r.read_all()
            assert bytes(view) == data[p], p
            await r.close()
        grew = grown(c.counters, before)
        assert batches == [list(ids.values())]
        assert grew("read.fetch.hops") == 1
        assert grew("read.fetch.batched_blocks") == 7
        assert grew("read.shm_hits") == 4 and grew("read.shm_fallbacks") == 3
        assert grew("read.checksum_mismatch") == 1


def open_fds() -> int:
    return len(os.listdir("/proc/self/fd"))


def block_maps() -> int:
    with open("/proc/self/maps") as f:
        return sum("memfd:cv-blk" in line for line in f)


async def test_a_waiter_cancelled_in_a_batch_leaves_nothing_open(
        tmp_path, monkeypatch):
    data = {f"/c/f{i}": bytes([i + 1]) * BLOCK for i in range(2)}
    monkeypatch.setattr(BatchFetcher, "THREADS", 1)   # one kept connection
    async with MiniCluster(workers=1, base_dir=str(tmp_path),
                           block_size=BLOCK) as mc:
        await written(mc, data)
        c = mc.client()
        for _ in range(2):               # exports made, the connection kept
            await c.prime(list(data))
            for p in data:
                r = await c.open(p)
                assert bytes(await r.mmap_view(0, r.len)) == data[p]
                await r.close()
        gate = threading.Event()
        pipeline = wshm.ShmConns.pipeline

        def held(self, spath, ids, timeout=5.0):
            gate.wait(10)
            yield from pipeline(self, spath, ids, timeout)

        monkeypatch.setattr(wshm.ShmConns, "pipeline", held)
        await c.prime(list(data))
        readers = [await c.open(p) for p in data]
        fds, maps = open_fds(), block_maps()
        first, second = (asyncio.ensure_future(r.mmap_view(0, r.len))
                         for r in readers)
        await asyncio.sleep(0.05)        # the first in a batch, held
        first.cancel()
        with pytest.raises(asyncio.CancelledError):
            await first
        gate.set()
        assert bytes(await second) == data["/c/f1"]
        del second                       # its view holds the mapping open
        for r in readers:
            await r.close()
        # the cancelled block was granted, mapped and verified all the
        # same; the fetcher closed what it was owed
        for _ in range(100):
            if (open_fds(), block_maps()) == (fds, maps):
                break
            await asyncio.sleep(0.02)
        assert (open_fds(), block_maps()) == (fds, maps)
        assert c.counters["read.fetch.batched_blocks"] == 3 * len(data)


@pytest.mark.parametrize("threads, cap, switch_s", [
    (BatchFetcher.THREADS, BatchFetcher.CAP, None),
    (8, 3, 1e-5),                      # many small batches, threads racing
])
async def test_a_primed_restore_hands_its_blocks_off_in_batches(
        tmp_path, monkeypatch, threads, cap, switch_s):
    params = {f"t{i:02d}": np.full(256, i, np.float32) for i in range(64)}
    n = len(params)                    # a block each
    monkeypatch.setattr(BatchFetcher, "THREADS", threads)
    monkeypatch.setattr(BatchFetcher, "CAP", cap)
    switch = sys.getswitchinterval()
    try:
        if switch_s is not None:
            sys.setswitchinterval(switch_s)
        async with MiniCluster(workers=1, base_dir=str(tmp_path),
                               block_size=BLOCK) as mc:
            path = await saved(mc, params)
            c = mc.client()
            back = await asyncio.wait_for(flat(c, path), 60)
            fetcher = c._primed.fetcher
    finally:
        sys.setswitchinterval(switch)
    for k, v in params.items():
        assert np.asarray(back[k]).tobytes() == v.tobytes(), k
    grew = grown(c.counters, {})
    assert grew("read.fetch.batched_blocks") == n
    # the manifest's blocks are a hop each (it is not primed)
    manifest = grew("read.phase.grant.n") - n
    assert manifest in (1, 2)
    hops = grew("read.fetch.hops") - manifest
    assert -(-n // cap) <= hops <= (n // 4 if switch_s is None else n)
    # a block each: the phases, and a hand-off split into its two waits
    for p in ("map", "verify", "resume"):
        assert grew(f"read.phase.{p}.n") == n + manifest, p
    assert abs(grew("read.resume.queue.s") + grew("read.resume.wake.s")
               - grew("read.phase.resume.s")) < 1e-9
    assert grew("read.resume.queue.s") > 0 and grew("read.resume.wake.s") > 0
    # closed with the client: no batch thread outlives it
    assert not fetcher._threads and fetcher._closed


async def test_an_unprimed_reader_hops_a_block_and_never_batches(
        tmp_path, monkeypatch):
    def refused(*args, **kw):
        raise AssertionError("an unprimed reader joined a batch")

    monkeypatch.setattr(BatchFetcher, "fetch", refused)
    async with MiniCluster(workers=1, base_dir=str(tmp_path),
                           block_size=BLOCK) as mc:
        c = mc.client()
        await c.write_all("/u", b"u" * (3 * BLOCK))
        before = dict(c.counters)
        r = await c.open("/u")
        assert r.primed is None
        assert bytes(await r.mmap_view(0, r.len)) == b"u" * (3 * BLOCK)
        await r.close()
        grew = grown(c.counters, before)
        assert grew("read.fetch.hops") == 3 == grew("read.phase.resume.n")
        assert "read.fetch.batched_blocks" not in c.counters
        assert not c._primed.fetcher._threads

def test_a_list_is_charged_an_item():
    q = AdmissionController()
    q.set_quota("a", qps=10.0, burst=10.0)
    q.release(q.admit("a", READ))
    q.charge("a", READ, 14)                 # a list of 15, admitted as one
    with pytest.raises(err.Throttled) as refused:
        q.admit("a", READ)
    # five tokens in debt and one to take: 0.6 s at ten a second
    assert 500 <= refused.value.retry_after_ms <= 600
    q.admit("b", READ)                      # another tenant's bucket
    AdmissionController(enabled=False).charge("a", READ, 14)
