"""Shared-memory short-circuit reads (docs/data-plane.md).

The worker exports committed MEM-tier blocks as sealed memfds and hands
the fd to co-located clients over an SCM_RIGHTS side channel; the client
maps it once and serves reads as pure memory accesses — zero RPCs on the
data plane. These tests pin the protocol (capability negotiation, clean
fallback), the resource discipline (fd/mmap LRU, no leaks under churn,
close() flushes heat), and the observability rail (counters reach the
master's read-plane rollup)."""

import asyncio
import fcntl
import gc
import mmap
import os
import sys
import threading

import numpy as np
import pytest

from curvine_tpu.common.conf import ClusterConf
from curvine_tpu.testing import MiniCluster
from curvine_tpu.worker import shm as wshm
from curvine_tpu.rpc import transport

MB = 1024 * 1024

pytestmark = pytest.mark.skipif(
    not wshm.shm_supported(),
    reason="memfd_create/SCM_RIGHTS not available on this platform")


def _fd_count() -> int:
    return len(os.listdir("/proc/self/fd"))


def _fd_target(fd: str) -> str:
    try:
        return os.readlink(f"/proc/self/fd/{fd}")
    except OSError:
        return ""


def _export_fds() -> int:
    """Open fds of this process that are block exports (the tables' own
    and every dup of one)."""
    return sum("memfd:cv-blk-" in _fd_target(fd)
               for fd in os.listdir("/proc/self/fd"))


def _grant(table, block_id: int, path: str, length: int) -> None:
    """One grant as the channel makes it: the fd is the caller's, and
    goes once it is sent."""
    fd, n = table.export(block_id, path, length)
    os.close(fd)
    assert n == length


# ---------------- the hit path: zero-RPC data plane ----------------

async def test_shm_read_skips_rpc_data_plane(tmp_path):
    """Co-located MEM-tier reads are served from the sealed-memfd
    mapping: the hit counter moves, the worker's RPC read path does
    not, and read_range returns a read-only zero-copy view."""
    conf = ClusterConf()
    conf.data_dir = str(tmp_path)
    async with MiniCluster(workers=1, conf=conf, base_dir=str(tmp_path),
                           block_size=MB) as mc:
        c = mc.client()
        payload = os.urandom(MB + 4096)
        await c.write_all("/shm/a.bin", payload)
        r = await c.open("/shm/a.bin")

        for off in (0, 4096, MB - 4096, MB, MB + 100):
            got = await r.pread_view(off, 4096)
            assert bytes(got) == payload[off:off + 4096]
        assert c.counters.get("read.shm_hits", 0) >= 5
        # the data plane never touched the worker's RPC read path
        assert mc.workers[0].metrics.counters.get("bytes.read", 0) == 0
        assert mc.workers[0].metrics.counters.get("shm.grants", 0) >= 1

        # single-block range: a zero-copy view onto the mapping itself
        view = await r.read_range(8192, 4096)
        assert isinstance(view, np.ndarray)
        assert not view.flags.writeable
        assert bytes(view) == payload[8192:8192 + 4096]
        assert c.counters.get("read.zero_copy_bytes", 0) >= 4096
        await r.close()
        await c.close()


async def test_shm_disabled_capability_negotiation(tmp_path):
    """worker.shm_reads=false: GET_BLOCK_INFO advertises no shm
    capability and the client transparently serves the same bytes
    through the fd/socket paths — no shm hit, no error."""
    conf = ClusterConf()
    conf.data_dir = str(tmp_path)
    conf.worker.shm_reads = False
    async with MiniCluster(workers=1, conf=conf, base_dir=str(tmp_path),
                           block_size=MB) as mc:
        assert mc.workers[0].shm is None
        c = mc.client()
        payload = os.urandom(64 * 1024)
        await c.write_all("/shm/off.bin", payload)
        r = await c.open("/shm/off.bin")
        got = await r.pread_view(1000, 5000)
        assert bytes(got) == payload[1000:6000]
        assert c.counters.get("read.shm_hits", 0) == 0
        assert not r._shm_sock and not r._shm_maps
        await r.close()
        await c.close()


async def test_shm_fetch_failure_falls_back(tmp_path, monkeypatch):
    """A client whose side-channel fetch fails (no SCM_RIGHTS, channel
    gone, worker restarted) falls back to the socket/fd path: bytes
    stay correct, the fallback counter records it, and the block is not
    retried against the dead channel."""
    conf = ClusterConf()
    conf.data_dir = str(tmp_path)
    async with MiniCluster(workers=1, conf=conf, base_dir=str(tmp_path),
                           block_size=MB) as mc:
        c = mc.client()
        payload = os.urandom(64 * 1024)
        await c.write_all("/shm/fb.bin", payload)

        def boom(sock_path, block_id, timeout=5.0):
            raise OSError("side channel unavailable")

        monkeypatch.setattr(wshm, "fetch_block_fd", boom)
        r = await c.open("/shm/fb.bin")
        got = await r.pread_view(0, 4096)
        assert bytes(got) == payload[:4096]
        assert c.counters.get("read.shm_fallbacks", 0) >= 1
        assert c.counters.get("read.shm_hits", 0) == 0
        # the failed block stopped advertising: no retry storm
        bid = r.blocks.block_locs[0].block.id
        assert bid not in r._shm_sock
        await r.close()
        await c.close()


# ---------------- resource discipline: LRU, leaks, close ----------------

async def test_shm_fd_lru_churn_no_leak(tmp_path):
    """Block turnover far past both caches (client map LRU + worker
    export LRU) must not grow the process fd table: every eviction
    closes its memfd."""
    conf = ClusterConf()
    conf.data_dir = str(tmp_path)
    blk = 256 * 1024
    async with MiniCluster(workers=1, conf=conf, base_dir=str(tmp_path),
                           block_size=blk) as mc:
        table = mc.workers[0].shm
        table.cap_bytes = MB             # four blocks of 256 KiB
        c = mc.client()
        n_blocks = 16
        payload = os.urandom(n_blocks * blk)
        await c.write_all("/shm/churn.bin", payload)
        r = await c.open("/shm/churn.bin")
        r._SC_CACHE_CAP = 4          # shadow the class FIFO bound

        async def churn(rounds: int) -> None:
            for i in range(rounds):
                off = (i % n_blocks) * blk
                # two at once: the second waits for the first's fetch
                for got in await asyncio.gather(r.pread_view(off, 4096),
                                                r.pread_view(off, 4096)):
                    assert bytes(got) == payload[off:off + 4096]

        await churn(64)              # reach steady state
        gc.collect()
        base = _fd_count()
        await churn(640)             # 10x turnover across both LRUs
        gc.collect()
        assert _fd_count() <= base + 2, \
            "fd table grew under shm block churn (leaked memfd/mmap)"
        assert len(r._shm_maps) <= r._SC_CACHE_CAP
        assert len(table) <= 4 and table.bytes <= MB
        assert table.evictions > 0
        gauges = mc.workers[0].metrics.gauges
        assert gauges["shm.export_bytes"] == table.bytes
        assert gauges["shm.export_entries"] == len(table)
        await r.close()
        assert not r._shm_maps
        await c.close()


async def test_shm_first_map_verifies_off_the_loop_in_place(
        tmp_path, monkeypatch):
    """The checksum of a first map runs on the fetch thread, not on the
    client's loop, over the sealed mapping itself: every byte of the
    block is hashed once and none is copied to be hashed."""
    from curvine_tpu.client import reader as creader
    conf = ClusterConf()
    conf.data_dir = str(tmp_path)
    async with MiniCluster(workers=1, conf=conf, base_dir=str(tmp_path),
                           block_size=4 * MB) as mc:
        c = mc.client()
        payload = os.urandom(2 * MB + 4096)
        await c.write_all("/shm/v.bin", payload)
        seen = []
        real = creader._block_crc

        def spy(algo, data):
            seen.append((threading.get_ident(), type(data),
                         memoryview(data).readonly, len(data)))
            return real(algo, data)

        monkeypatch.setattr(creader, "_block_crc", spy)
        before = dict(c.counters)

        def grew(key):
            return c.counters.get(key, 0) - before.get(key, 0)

        r = await c.open("/shm/v.bin")
        view = await r.mmap_view(0, len(payload))
        assert bytes(view) == payload
        assert bytes(await r.pread_view(4096, 4096)) \
            == payload[4096:8192]            # a warm map hashes nothing
        assert len(seen) == 1
        tid, kind, readonly, n = seen[0]
        assert tid != threading.get_ident()
        assert kind is mmap.mmap and readonly and n == len(payload)
        assert grew("read.verify.bytes") == len(payload)
        assert grew("read.verify.copied_bytes") == 0
        assert "read.verify.copied_bytes" in c.counters
        # stamped on the thread, added on the loop, once a first map
        for p in ("grant", "map", "verify", "resume"):
            assert grew(f"read.phase.{p}.n") == 1, p
            assert grew(f"read.phase.{p}.s") >= 0.0, p
        del view
        await r.close()
        await c.close()


async def test_shm_corrupt_export_is_refused(tmp_path, monkeypatch):
    """An export whose bytes differ from the commit-time checksum never
    reaches the caller: the shm rung refuses it (mismatch counted, the
    replica reported, the mapping and its fd gone) and the read is
    served by the verified remote path."""
    from curvine_tpu.rpc import RpcCode
    conf = ClusterConf()
    conf.data_dir = str(tmp_path)
    async with MiniCluster(workers=1, conf=conf, base_dir=str(tmp_path),
                           block_size=MB) as mc:
        c = mc.client()
        payload = os.urandom(256 * 1024)
        await c.write_all("/shm/bad.bin", payload)
        real_fetch = wshm.fetch_block_fd

        def tampered(sock_path, block_id, timeout=5.0):
            fd, n = real_fetch(sock_path, block_id, timeout)
            data = bytearray(os.pread(fd, n, 0))
            os.close(fd)
            data[n // 2] ^= 0x01
            bad = os.memfd_create("cv-test-bad")
            os.write(bad, data)
            return bad, n

        monkeypatch.setattr(wshm, "fetch_block_fd", tampered)
        r = await c.open("/shm/bad.bin")
        reported = []
        real_call = r.fs.call

        async def call(code, *a, **kw):
            if code == RpcCode.REPORT_UNDER_REPLICATED_BLOCKS:
                reported.append(a[0] if a else kw)
            return await real_call(code, *a, **kw)

        monkeypatch.setattr(r.fs, "call", call)
        bid = r.blocks.block_locs[0].block.id
        assert await r.mmap_view(0, len(payload)) is None
        assert bid not in r._shm_maps and bid not in r._shm_sock
        gc.collect()
        assert not [fd for fd in os.listdir("/proc/self/fd")
                    if "cv-test-bad" in _fd_target(fd)]
        assert await r.read_all() == payload
        assert c.counters.get("read.checksum_mismatch", 0) == 1
        assert c.counters.get("read.shm_fallbacks", 0) == 1
        assert c.counters.get("read.shm_hits", 0) == 0
        await asyncio.sleep(0.05)            # the report is fire-and-forget
        assert [m["block_ids"] for m in reported] == [[bid]]
        await r.close()
        await c.close()


async def test_shm_concurrent_first_reads_share_one_mapping(tmp_path):
    """Concurrent first reads of one block share one fetch: the first
    grants, maps and verifies it on a fetch thread, the others wait for
    that fetch and take its mapping; one mapping is kept, and nothing
    outlives close()."""
    conf = ClusterConf()
    conf.data_dir = str(tmp_path)
    async with MiniCluster(workers=1, conf=conf, base_dir=str(tmp_path),
                           block_size=MB) as mc:
        c = mc.client()
        payload = os.urandom(512 * 1024)
        await c.write_all("/shm/race.bin", payload)
        r = await c.open("/shm/race.bin")
        lb = r.blocks.block_locs[0]
        await r._local_path(lb)              # probe once, ahead of the race

        def memfds() -> int:
            # the worker (in this process too) holds the export's own
            gc.collect()
            return len([fd for fd in os.listdir("/proc/self/fd")
                        if f"cv-blk-{lb.block.id}" in _fd_target(fd)])

        base = memfds()      # (another file's block 1, in this process)
        maps = await asyncio.gather(*(r._shm_map(lb) for _ in range(6)))
        assert all(m is maps[0] for m in maps) and maps[0] is not None
        assert list(r._shm_maps) == [lb.block.id]
        assert c.counters.get("read.phase.grant.n", 0) == 1   # one fetch
        assert c.counters["read.block_fetches"] == 1
        assert c.counters["read.blocks_mapped"] == 1
        assert c.counters.get("read.verify.copied_bytes", 0) == 0
        assert bytes(maps[0][:4096]) == payload[:4096]
        del maps
        # the export, the one map's memfd and the dup that mmap keeps
        assert memfds() == base + 3
        await r.close()
        assert not r._shm_maps and memfds() == base + 1
        await c.close()


# ---------------- a range over several blocks: one view ----------------

def _memfd_maps(name: str = "memfd:cv-") -> int:
    """Mappings of sealed exports in this process (the worker, in this
    process too, copies into its memfds with sendfile and maps none)."""
    with open("/proc/self/maps") as f:
        return sum(name in line for line in f)


def _span_cluster(tmp_path, conf=None, block_size=MB):
    conf = conf or ClusterConf()
    conf.data_dir = str(tmp_path)
    return MiniCluster(workers=1, conf=conf, base_dir=str(tmp_path),
                       block_size=block_size)


def test_spanmap_places_memfds_side_by_side():
    """SpanMap alone: memfds mapped at their offsets read as one
    read-only array; the range lives as long as any array over it and
    not longer — afterwards nothing of the process lies in it."""
    from curvine_tpu.client.spanmap import SpanMap

    def overlapping(lo: int, hi: int) -> list[str]:
        with open("/proc/self/maps") as f:
            return [line for line in f
                    if int(line.split("-")[0], 16) < hi
                    and int(line.split("-")[1].split()[0], 16) > lo]

    page = mmap.PAGESIZE
    parts = [os.urandom(3 * page), os.urandom(page), os.urandom(page + 17)]
    span = SpanMap(sum(map(len, parts)))
    lo = span.view().ctypes.data
    hi = lo + span.nbytes
    assert lo % page == 0 and len(overlapping(lo, hi)) == 1   # reserved
    with pytest.raises(ValueError):
        span.map(0, page, 100, mmap.MAP_SHARED)     # not on a page
    with pytest.raises(ValueError):
        span.map(0, 2 * page, 4 * page, mmap.MAP_SHARED)    # past the end
    at = 0
    for i, part in enumerate(parts):
        fd = os.memfd_create(f"cv-test-span-{i}")
        try:
            os.write(fd, part)
            piece = span.map(fd, len(part), at, mmap.MAP_SHARED
                             | (mmap.MAP_POPULATE if i else 0))
        finally:
            os.close(fd)             # the mapping holds the pages
        assert bytes(piece) == part and not piece.flags.writeable
        at += len(part)
    with pytest.raises(OSError):
        span.map(-1, page, 0, mmap.MAP_SHARED)      # the kernel refuses
    whole = span.view()
    assert whole.base is span and not whole.flags.writeable
    assert bytes(whole) == b"".join(parts)
    assert len(overlapping(lo, hi)) == 3
    inner = whole[page + 1:-3]
    del whole, piece, span
    gc.collect()
    assert bytes(inner) == b"".join(parts)[page + 1:-3]
    del inner
    gc.collect()
    assert not overlapping(lo, hi)
    done = SpanMap(page)
    lo = done.view().ctypes.data
    done.close()
    done.close()                     # once only
    assert not overlapping(lo, lo + page)


@pytest.mark.parametrize("offset,n", [
    (0, 3 * MB + MB // 2 + 7),           # the whole file: 3½ blocks
    (MB - 4096 - 5, 8192 + 11),          # straddles one block boundary
    (MB + 1, 2 * MB),                    # a middle block and both sides
], ids=["whole", "straddle", "interior"])
async def test_span_view_is_one_zero_copy_view(tmp_path, offset, n):
    """A range over consecutive blocks comes back as one read-only view
    of the sealed exports mapped side by side: byte-equal, counted as a
    span view and as zero-copy bytes, every block hashed once where it
    lies, and no buffer assembled (`copy` does not move)."""
    async with _span_cluster(tmp_path) as mc:
        c = mc.client()
        c.tracer.sample_rate = 1.0
        payload = os.urandom(3 * MB + MB // 2 + 7)
        await c.write_all("/shm/span.bin", payload)
        r = await c.open("/shm/span.bin")
        c.tracer.store.clear()
        before = dict(c.counters)

        def grew(key):
            return c.counters.get(key, 0) - before.get(key, 0)

        view = await r.mmap_view(offset, n)
        assert isinstance(view, np.ndarray) and view.dtype == np.uint8
        assert not view.flags.writeable and view.flags.c_contiguous
        assert bytes(view) == payload[offset:offset + n]
        with pytest.raises(ValueError):
            view[0] = 0
        k = (offset + n - 1) // MB - offset // MB + 1
        covered = sum(min(MB, len(payload) - i * MB)
                      for i in range(offset // MB, offset // MB + k))
        assert grew("read.span_views") == 1
        assert grew("read.span_view_blocks") == k
        assert grew("read.span_view_bytes") == n
        assert grew("read.zero_copy_bytes") == n
        assert grew("read.shm_hits") == k
        assert grew("read.verify.bytes") == covered
        assert grew("read.verify.copied_bytes") == 0
        assert grew("read.phase.copy.n") == 0
        for p in ("probe", "grant", "map", "verify", "resume"):
            assert grew(f"read.phase.{p}.n") == k, p
        assert r.served_by() == "shm"
        (sp,) = [s for s in c.tracer.store.drain(4096)
                 if s["op"] == "shm_view"]
        assert sp["attrs"]["blocks"] == k
        assert sp["attrs"]["served_by"] == "shm"
        # each block is mapped once, in its place in the file's range,
        # and the reader holds it there
        under = r.blocks.block_locs[offset // MB:offset // MB + k]
        assert _memfd_maps() == k
        assert set(r._shm_maps) == {lb.block.id for lb in under}
        assert grew("read.block_fetches") == k == grew("read.blocks_mapped")
        assert "kept" not in sp["attrs"]
        assert mc.workers[0].metrics.counters.get("bytes.read", 0) == 0
        # read_range goes through the same door, and finds every block
        # of the range held: nothing is granted, mapped or hashed again
        again = await r.read_range(offset, n, parallel=4)
        assert not again.flags.writeable
        assert bytes(again) == payload[offset:offset + n]
        assert grew("read.span_views") == 2
        assert grew("read.block_fetches") == k
        assert grew("read.verify.bytes") == covered
        assert _memfd_maps() == k
        (sp,) = [s for s in c.tracer.store.drain(4096)
                 if s["op"] == "shm_view"]
        assert sp["attrs"]["kept"] is True
        del view, again
        await r.close()
        await c.close()


async def test_span_view_outlives_close_and_eviction_no_leak(tmp_path):
    """The range belongs to its views: it survives the reader's close
    and the worker's eviction of the exports, it is unmapped when the
    last view is collected, and a loop of open / view / close leaves
    neither mappings nor descriptors behind."""
    async with _span_cluster(tmp_path, block_size=256 * 1024) as mc:
        c = mc.client()
        payload = os.urandom(3 * 256 * 1024 + 1000)
        await c.write_all("/shm/life.bin", payload)

        async def one_view():
            r = await c.open("/shm/life.bin")
            view = await r.mmap_view(0, r.len)
            assert view is not None
            bids = [lb.block.id for lb in r.blocks.block_locs]
            await r.close()
            return view, bids

        base_maps = _memfd_maps()
        view, bids = await one_view()
        tail = view[len(payload) - 5000:]       # a view of the view
        for bid in bids:
            mc.workers[0].shm.invalidate(bid)   # the worker's fds go
        gc.collect()
        assert _memfd_maps() == base_maps + 4
        assert bytes(view) == payload
        del view
        gc.collect()
        assert _memfd_maps() == base_maps + 4   # `tail` holds all of it
        assert bytes(tail) == payload[-5000:]
        del tail
        gc.collect()
        assert _memfd_maps() == base_maps

        for _ in range(5):                      # exports made, pool dialled
            await one_view()
        gc.collect()
        fds, maps = _fd_count(), _memfd_maps()
        for _ in range(50):
            view, _bids = await one_view()
            assert view[-1] == payload[-1]
            del view
        gc.collect()
        assert _memfd_maps() == maps == base_maps
        assert _fd_count() <= fds
        await c.close()


async def test_span_view_refuses_a_corrupt_block(tmp_path, monkeypatch):
    """One export of the four differs from its commit-time checksum: no
    view (and no byte) of the range reaches the caller, the replica is
    flagged, its bytes are unmapped (the three good blocks stay held by
    the reader until it closes), and `read_all` serves the right bytes:
    the good blocks from what is held, the bad one through the verified
    remote path."""
    from curvine_tpu.rpc import RpcCode
    async with _span_cluster(tmp_path) as mc:
        c = mc.client()
        payload = os.urandom(3 * MB + MB // 2)
        await c.write_all("/shm/bad4.bin", payload)
        r = await c.open("/shm/bad4.bin")
        bad_bid = r.blocks.block_locs[2].block.id
        real_fetch = wshm.fetch_block_fd

        def tampered(sock_path, block_id, timeout=5.0):
            fd, n = real_fetch(sock_path, block_id, timeout)
            if block_id != bad_bid:
                return fd, n
            data = bytearray(os.pread(fd, n, 0))
            os.close(fd)
            data[n // 2] ^= 0x01
            bad = os.memfd_create("cv-test-bad")
            os.write(bad, data)
            return bad, n

        monkeypatch.setattr(wshm, "fetch_block_fd", tampered)
        reported = []
        real_call = r.fs.call

        async def call(code, *a, **kw):
            if code == RpcCode.REPORT_UNDER_REPLICATED_BLOCKS:
                reported.append(a[0] if a else kw)
            return await real_call(code, *a, **kw)

        monkeypatch.setattr(r.fs, "call", call)
        assert await r.mmap_view(0, len(payload)) is None
        assert r.served_by() == "none"
        assert bad_bid not in r._shm_sock
        assert r._local_paths[bad_bid] is None
        gc.collect()
        assert _memfd_maps() == 3 and bad_bid not in r._shm_maps
        assert len(r._shm_maps) == 3
        assert not [fd for fd in os.listdir("/proc/self/fd")
                    if "cv-test-bad" in _fd_target(fd)]
        assert c.counters.get("read.checksum_mismatch", 0) == 1
        assert c.counters.get("read.span_views", 0) == 0
        assert c.counters.get("read.zero_copy_bytes", 0) == 0
        assert await r.read_all() == payload
        assert c.counters.get("read.checksum_mismatch", 0) == 1
        assert mc.workers[0].metrics.counters.get("bytes.read", 0) == MB
        assert c.counters["read.block_fetches"] == 4
        assert c.counters["read.blocks_mapped"] == 3
        await asyncio.sleep(0.05)            # the report is fire-and-forget
        assert [m["block_ids"] for m in reported] == [[bad_bid]]
        await r.close()
        gc.collect()
        assert _memfd_maps() == 0
        await c.close()


@pytest.mark.parametrize("case", ["hole", "shm_off", "grant_fails",
                                  "stale_grant"])
async def test_span_view_falls_back_whole(tmp_path, monkeypatch, case):
    """Anything but four shm-served blocks in a row: None, nothing held
    but the blocks that were served (kept by the reader for its next
    read), and the caller's `read_all` returns the file."""
    conf = ClusterConf()
    conf.worker.shm_reads = case != "shm_off"
    async with _span_cluster(tmp_path, conf, 256 * 1024) as mc:
        c = mc.client()
        payload = os.urandom(3 * 256 * 1024 + 512)
        await c.write_all("/shm/fb4.bin", payload)
        if case == "hole":
            await c.meta.resize_file("/shm/fb4.bin", 5 * 256 * 1024)
            payload += bytes(5 * 256 * 1024 - len(payload))
        r = await c.open("/shm/fb4.bin")
        victim = r.blocks.block_locs[1].block.id
        real_fetch = wshm.fetch_block_fd

        def fetch(sock_path, block_id, timeout=5.0):
            if block_id == victim and case == "grant_fails":
                raise LookupError("export dropped")
            fd, n = real_fetch(sock_path, block_id, timeout)
            # a grant of another length than the block: a stale export
            return fd, n - 1 if block_id == victim \
                and case == "stale_grant" else n

        monkeypatch.setattr(wshm, "fetch_block_fd", fetch)
        assert await r.mmap_view(0, r.len) is None
        assert r.served_by() == "none"
        gc.collect()
        held = 3 if case in ("grant_fails", "stale_grant") else 0
        assert _memfd_maps() == len(r._shm_maps) == held
        assert victim not in r._shm_maps
        assert c.counters.get("read.span_views", 0) == 0
        assert c.counters.get("read.zero_copy_bytes", 0) == 0
        if case in ("grant_fails", "stale_grant"):
            assert c.counters.get("read.shm_fallbacks", 0) == 1
            assert victim not in r._shm_sock
            # every granted fd is closed: what is left is the worker's
            # (the channel's thread closes its own once it is sent)
            for _ in range(100):
                if _export_fds() == len(mc.workers[0].shm):
                    break
                await asyncio.sleep(0.02)
            assert _export_fds() == len(mc.workers[0].shm)
        monkeypatch.setattr(wshm, "fetch_block_fd", real_fetch)
        assert await r.read_all() == payload
        await r.close()
        await c.close()


async def test_a_held_place_is_never_mapped_over(tmp_path, monkeypatch):
    """A block the reader forgot (its probe FIFO, a stale probe) while
    views still lie over its place in the file's range: fetched again,
    it is mapped on its own, so a refused checksum takes nothing from
    the views, whose bytes stay as they were. Once the last view is
    collected the place is empty and takes the block again."""
    blk = 256 * 1024
    async with _span_cluster(tmp_path, block_size=blk) as mc:
        c = mc.client()
        payload = os.urandom(4 * blk)
        await c.write_all("/shm/held.bin", payload)
        r = await c.open("/shm/held.bin")
        lb = r.blocks.block_locs[1]
        one = await r.mmap_view(blk + 10, 100)             # inside block 1
        both = await r.mmap_view(2 * blk - 50, 100)        # straddles 1|2
        assert c.counters["read.block_fetches"] == 2
        r._drop_local(lb.block.id)
        assert lb.block.id not in r._shm_maps
        real_fetch = wshm.fetch_block_fd

        def tampered(sock_path, block_id, timeout=5.0):
            fd, n = real_fetch(sock_path, block_id, timeout)
            if block_id != lb.block.id:
                return fd, n
            data = bytearray(os.pread(fd, n, 0))
            os.close(fd)
            data[7] ^= 0x01
            bad = os.memfd_create("cv-test-bad")
            os.write(bad, data)
            return bad, n

        monkeypatch.setattr(wshm, "fetch_block_fd", tampered)
        assert await r.mmap_view(blk + 10, 100) is None
        assert c.counters["read.block_fetches"] == 3
        assert c.counters["read.checksum_mismatch"] == 1
        gc.collect()
        assert bytes(one) == payload[blk + 10:blk + 110]
        assert bytes(both) == payload[2 * blk - 50:2 * blk + 50]
        monkeypatch.setattr(wshm, "fetch_block_fd", real_fetch)
        r._drop_local(lb.block.id)                # forget the refusal
        del one, both
        gc.collect()
        assert _memfd_maps() == 1                 # block 2, held by r
        again = await r.mmap_view(2 * blk - 50, 100)
        assert bytes(again) == payload[2 * blk - 50:2 * blk + 50]
        assert c.counters["read.span_views"] == 2
        assert c.counters["read.block_fetches"] == 4
        del again
        await r.close()
        gc.collect()
        assert _memfd_maps() == 0
        await c.close()


async def test_a_span_view_follows_the_rule_of_its_own_blocks(tmp_path):
    """The rule is the range's, not the file's: in a file of more blocks
    than the probe cache holds, a view over two of them is one zero-copy
    view all the same, and only a view over more blocks than that falls
    back to a copy."""
    blk = 256 * 1024
    async with _span_cluster(tmp_path, block_size=blk) as mc:
        c = mc.client()
        payload = os.urandom(6 * blk)
        await c.write_all("/shm/rule.bin", payload)
        r = await c.open("/shm/rule.bin")
        r._SC_CACHE_CAP = 3                  # fewer than the file's blocks
        for at in (blk - 50, 5 * blk - 50):  # straddle 0|1 and 4|5
            view = await r.mmap_view(at, 100)
            assert bytes(view) == payload[at:at + 100]
        assert c.counters["read.span_views"] == 2
        assert c.counters["read.zero_copy_bytes"] == 200
        assert await r.mmap_view(blk - 50, 3 * blk) is None    # 4 blocks
        got = await r.read_range(blk - 50, 3 * blk)
        assert bytes(got) == payload[blk - 50:4 * blk - 50]
        assert c.counters["read.span_views"] == 2
        del view
        await r.close()
        await c.close()


async def test_span_blocks_are_fetched_together_off_the_loop(
        tmp_path, monkeypatch):
    """The blocks of a range are granted, mapped and verified at once,
    each on a fetch thread of its own, and the loop runs on meanwhile:
    a barrier that only four concurrent grants can pass, and a task
    that keeps ticking while they wait in it."""
    import time
    async with _span_cluster(tmp_path) as mc:
        c = mc.client()
        payload = os.urandom(3 * MB + MB // 2)
        await c.write_all("/shm/par.bin", payload)
        r = await c.open("/shm/par.bin")
        barrier = threading.Barrier(4, timeout=20)
        threads = []
        real_fetch = wshm.fetch_block_fd

        def held(sock_path, block_id, timeout=5.0):
            threads.append(threading.get_ident())
            barrier.wait()       # broken (→ the test fails) if in turn
            time.sleep(0.1)
            return real_fetch(sock_path, block_id, timeout)

        monkeypatch.setattr(wshm, "fetch_block_fd", held)
        ticks = 0

        async def beat():
            nonlocal ticks
            while True:
                await asyncio.sleep(0.005)
                ticks += 1

        heart = asyncio.ensure_future(beat())
        try:
            view = await r.mmap_view(0, len(payload))
        finally:
            heart.cancel()
        assert bytes(view) == payload
        assert len(set(threads)) == 4
        assert threading.get_ident() not in threads
        assert ticks >= 5, "the loop stood still while the blocks came"
        assert c.counters["read.phase.grant.n"] == 4
        assert c.counters["read.phase.grant.s"] >= 0.4    # 4 x 0.1 s
        del view
        await r.close()
        await c.close()


async def test_shm_eviction_mid_read_keeps_view_valid(tmp_path):
    """A zero-copy view handed to the caller outlives eviction of its
    mapping: _drop_shm tolerates the exported buffer (BufferError) and
    the bytes stay correct until the caller releases the view."""
    conf = ClusterConf()
    conf.data_dir = str(tmp_path)
    async with MiniCluster(workers=1, conf=conf, base_dir=str(tmp_path),
                           block_size=MB) as mc:
        c = mc.client()
        payload = os.urandom(MB)
        await c.write_all("/shm/evict.bin", payload)
        r = await c.open("/shm/evict.bin")
        view = await r.read_range(4096, 8192)
        assert bytes(view) == payload[4096:4096 + 8192]
        bid = r.blocks.block_locs[0].block.id
        assert bid in r._shm_maps
        r._drop_shm(bid)             # concurrent eviction
        assert bid not in r._shm_maps
        # the mapping can't actually close while the view holds it
        assert bytes(view) == payload[4096:4096 + 8192]
        del view
        gc.collect()
        await r.close()
        await c.close()


async def test_close_flushes_pending_sc_reads(tmp_path):
    """close() flushes sc-read heat counts below the 512 batch
    threshold and leaves no flush task behind — the worker's
    promotion scans see short sessions too."""
    conf = ClusterConf()
    conf.data_dir = str(tmp_path)
    async with MiniCluster(workers=1, conf=conf, base_dir=str(tmp_path),
                           block_size=MB) as mc:
        c = mc.client()
        await c.write_all("/shm/heat.bin", os.urandom(MB))
        r = await c.open("/shm/heat.bin")
        bid = r.blocks.block_locs[0].block.id
        for i in range(20):          # well under the 512 threshold
            await r.pread_view(i * 4096, 4096)
        assert r._sc_reads, "reads were not accounted for flush"
        h0 = mc.workers[0].store.get(bid, touch=False).heat
        await r.close()
        assert mc.workers[0].store.get(bid, touch=False).heat >= h0 + 20
        assert r._sc_flush_task is None and not r._sc_reads
        assert not r._pf and not r._shm_maps
        await c.close()


# ---------------- unit: exporter, channel, transport pool ----------------

async def test_shm_exporter_seals_and_lru(tmp_path):
    """ShmExporter: the memfd is sealed immutable, carries the block
    bytes, and the LRU closes evicted fds."""
    blocks = {}
    for i in range(3):
        p = tmp_path / f"b{i}"
        p.write_bytes(bytes([i]) * 4096)
        blocks[i] = str(p)
    ex = wshm.ShmExporter(cap_bytes=2 * 4096)
    base = _export_fds()
    fd0 = fd0b = -1
    try:
        fd0, n0 = ex.export(0, blocks[0], 4096)
        assert n0 == 4096
        seals = fcntl.fcntl(fd0, fcntl.F_GET_SEALS)
        assert seals == (fcntl.F_SEAL_SHRINK | fcntl.F_SEAL_GROW
                         | fcntl.F_SEAL_WRITE | fcntl.F_SEAL_SEAL)
        assert os.pread(fd0, 4096, 0) == b"\x00" * 4096
        with pytest.raises(OSError):
            os.pwrite(fd0, b"x", 0)          # sealed: immutable
        fd0b, _ = ex.export(0, blocks[0], 4096)
        # a hit: the caller's own fd onto the same memfd, no new copy
        assert fd0b != fd0 and ex.hits == 1 and ex.exports == 1
        assert os.fstat(fd0b).st_ino == os.fstat(fd0).st_ino
        _grant(ex, 1, blocks[1], 4096)
        _grant(ex, 2, blocks[2], 4096)       # evicts block 0 (LRU)
        assert len(ex) == 2 and ex.evictions == 1 and 0 not in ex
        assert ex.bytes == 2 * 4096
        # eviction closed the table's fd only: ours still read block 0
        assert _export_fds() == base + 2 + 2
        assert os.pread(fd0, 4096, 0) == b"\x00" * 4096
    finally:
        ex.close()
        for fd in (fd0, fd0b):
            if fd >= 0:
                os.close(fd)
    assert len(ex) == 0 and ex.bytes == 0 and _export_fds() == base


def _small_blocks(tmp_path, n: int, size: int) -> dict[int, str]:
    out = {}
    for i in range(n):
        p = tmp_path / f"s{i}"
        p.write_bytes(i.to_bytes(2, "little") * (size // 2))
        out[i] = str(p)
    return out


def test_export_table_copies_a_resident_block_once(tmp_path):
    """300 small blocks under a bound that holds them all — far more
    entries than the 128 the table once stopped at: 300 copies on the
    first pass, none on the second, and a second grant is an fd onto
    the very memfd the first one got."""
    from curvine_tpu.common.metrics import MetricsRegistry
    size = 4096
    blocks = _small_blocks(tmp_path, 300, size)
    m = MetricsRegistry("worker")
    ex = wshm.ShmExporter(cap_bytes=300 * size, metrics=m,
                          cap_entries=300)
    assert m.counters["shm.exports"] == 0    # published from the start
    base = _export_fds()
    try:
        first = {i: ex.export(i, blocks[i], size)[0] for i in blocks}
        assert ex.exports == 300 and ex.hits == 0 and ex.evictions == 0
        assert len(ex) == 300 and ex.bytes == 300 * size
        for i in blocks:
            fd, n = ex.export(i, blocks[i], size)
            try:
                assert n == size
                assert os.fstat(fd).st_ino == os.fstat(first[i]).st_ino
                assert os.pread(fd, size, 0) == \
                    i.to_bytes(2, "little") * (size // 2)
            finally:
                os.close(fd)
        assert ex.exports == 300 and ex.hits == 300 and ex.evictions == 0
        assert m.counters["shm.exports"] == 300
        assert m.counters["shm.export_evictions"] == 0
        assert m.gauges["shm.export_bytes"] == 300 * size
        assert m.gauges["shm.export_entries"] == 300
        for fd in first.values():
            os.close(fd)
        assert _export_fds() == base + 300   # one fd a block, no more
    finally:
        ex.close()
    assert _export_fds() == base
    assert m.gauges["shm.export_bytes"] == 0
    assert m.gauges["shm.export_entries"] == 0


def test_export_table_is_lru_within_its_byte_bound(tmp_path):
    """A bound smaller than the set: the least recently granted block
    leaves first, blocks of different sizes are counted by their bytes,
    and the bytes held never pass the bound."""
    from curvine_tpu.common.metrics import MetricsRegistry
    sizes = {0: 4096, 1: 8192, 2: 4096, 3: 12288, 4: 4096}
    paths = {}
    for i, n in sizes.items():
        p = tmp_path / f"l{i}"
        p.write_bytes(bytes([i]) * n)
        paths[i] = str(p)
    m = MetricsRegistry("worker")
    ex = wshm.ShmExporter(cap_bytes=16384, metrics=m)
    seen = []

    def grant(i):
        _grant(ex, i, paths[i], sizes[i])
        assert ex.bytes <= ex.cap_bytes
        assert m.gauges["shm.export_bytes"] == ex.bytes
        seen.append(ex.bytes)

    try:
        grant(0), grant(1), grant(2)             # 16 KiB: full
        assert ex.evictions == 0 and ex.bytes == 16384
        grant(0)                                 # a hit: 0 is the newest
        grant(4)                                 # 4 KiB more: 1 goes (8 KiB)
        assert 1 not in ex and 0 in ex and 2 in ex and 4 in ex
        assert ex.evictions == 1 and ex.bytes == 12288
        grant(3)                                 # 12 KiB: 2, then 0 go
        assert [i in ex for i in range(5)] == [False, False, False,
                                               True, True]
        assert ex.evictions == 3 and ex.bytes == 16384
        assert m.counters["shm.export_evictions"] == 3
        assert max(seen) <= 16384
    finally:
        ex.close()


def test_export_table_is_lru_within_its_entry_bound(tmp_path):
    """An entry is an open fd, so the table is bounded in entries as
    well as in bytes: with room in bytes for every block, the least
    recently granted one still leaves when the entries are full, and
    the table never holds more fds than its bound."""
    from curvine_tpu.common.metrics import MetricsRegistry
    blocks = _small_blocks(tmp_path, 6, 4096)
    m = MetricsRegistry("worker")
    ex = wshm.ShmExporter(cap_bytes=MB, metrics=m, cap_entries=4)
    base = _export_fds()
    try:
        for i in range(4):
            _grant(ex, i, blocks[i], 4096)
        assert len(ex) == 4 and ex.evictions == 0
        _grant(ex, 0, blocks[0], 4096)           # a hit: 0 is the newest
        _grant(ex, 4, blocks[4], 4096)           # a fifth: 1 goes
        _grant(ex, 5, blocks[5], 4096)           # a sixth: 2 goes
        assert [i in ex for i in range(6)] == [True, False, False,
                                               True, True, True]
        assert ex.evictions == 2 == m.counters["shm.export_evictions"]
        assert ex.bytes == 4 * 4096 == m.gauges["shm.export_bytes"]
        assert m.gauges["shm.export_entries"] == 4
        assert _export_fds() == base + 4
        _grant(ex, 1, blocks[1], 4096)           # copied again, served
        assert ex.exports == 7 and len(ex) == 4 and 3 not in ex
    finally:
        ex.close()
    assert _export_fds() == base


def test_export_table_keeps_nothing_in_a_process_short_of_fds(tmp_path):
    """A grant whose own fd is numbered within half the table's entry
    bound of the process's limit finds it about to run out: the table
    closes what it holds, serves that grant and every later one from a
    copy it does not keep, and is the table it was once fds are
    handed out below the mark again."""
    from curvine_tpu.common.metrics import MetricsRegistry
    blocks = _small_blocks(tmp_path, 10, 4096)
    m = MetricsRegistry("worker")
    ex = wshm.ShmExporter(cap_bytes=MB, metrics=m, cap_entries=16)
    assert ex._fd_mark == wshm._fd_limit() - 8
    base = _export_fds()
    try:
        for i in range(8):
            _grant(ex, i, blocks[i], 4096)
        assert len(ex) == 8 and ex.evictions == 0
        high, ex._fd_mark = ex._fd_mark, 0       # every fd is "too high"
        for i in (7, 8):                         # a hit, then a miss
            fd, n = ex.export(i, blocks[i], 4096)
            try:
                assert os.pread(fd, n, 0) == i.to_bytes(2, "little") * 2048
            finally:
                os.close(fd)
            assert len(ex) == 0 and _export_fds() == base
        assert ex.evictions == 8 == m.counters["shm.export_evictions"]
        assert m.gauges["shm.export_entries"] == 0
        assert m.gauges["shm.export_bytes"] == 0
        ex._fd_mark = high                       # the burst is over
        _grant(ex, 8, blocks[8], 4096)
        _grant(ex, 8, blocks[8], 4096)
        assert 8 in ex and len(ex) == 1 and ex.hits == 2
        assert ex.exports == 8 + 2
    finally:
        ex.close()


def test_entry_bound_is_an_eighth_of_the_fd_limit_when_made():
    """The default entry bound is read once, as the table is made, from
    the process's soft limit on open files, which it leaves as it was:
    under the usual 1,024 a table holds the 128 entries it always
    did."""
    import resource
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    low = 1024 if soft == resource.RLIM_INFINITY else min(soft, 1024)
    try:
        resource.setrlimit(resource.RLIMIT_NOFILE, (low, hard))
        ex = wshm.ShmExporter(cap_bytes=MB)
        warm = wshm.WarmShmCache(cap_bytes=MB)
        assert resource.getrlimit(resource.RLIMIT_NOFILE) == (low, hard)
    finally:
        resource.setrlimit(resource.RLIMIT_NOFILE, (soft, hard))
    assert ex.cap_entries == warm.cap_entries == low // 8
    assert wshm.ShmExporter(cap_bytes=MB, cap_entries=0).cap_entries == 1


def test_export_larger_than_the_bound_is_served_not_kept(tmp_path):
    """A block larger than the whole bound is still granted — copied,
    sealed, handed over — but not kept, and the table is not emptied to
    make room it could never make."""
    blocks = _small_blocks(tmp_path, 3, 4096)
    big = tmp_path / "big"
    big.write_bytes(b"\x07" * 32768)
    ex = wshm.ShmExporter(cap_bytes=3 * 4096)
    base = _export_fds()
    try:
        for i in blocks:
            _grant(ex, i, blocks[i], 4096)
        for _ in range(2):
            fd, n = ex.export(9, str(big), 32768)
            try:
                assert n == 32768
                assert os.pread(fd, n, 0) == b"\x07" * n
                assert fcntl.fcntl(fd, fcntl.F_GET_SEALS) \
                    & fcntl.F_SEAL_WRITE
            finally:
                os.close(fd)
        assert 9 not in ex and len(ex) == 3 and ex.bytes == 3 * 4096
        assert ex.evictions == 0
        assert ex.exports == 5                   # each grant of it a copy
        assert _export_fds() == base + 3         # and its copies are gone
    finally:
        ex.close()


def test_export_mapping_outlives_eviction_and_invalidate(tmp_path):
    """What a client mapped stays readable after the table evicted the
    block, and after the block was invalidated: the table closes its
    own fd, never the pages a client holds."""
    blocks = _small_blocks(tmp_path, 3, 4096)
    ex = wshm.ShmExporter(cap_bytes=2 * 4096)
    try:
        maps = []
        for i in (0, 1):
            fd, n = ex.export(i, blocks[i], 4096)
            maps.append(mmap.mmap(fd, n, prot=mmap.PROT_READ))
            os.close(fd)                         # the client keeps the map
        _grant(ex, 2, blocks[2], 4096)           # evicts 0
        ex.invalidate(1)
        assert 0 not in ex and 1 not in ex and ex.bytes == 4096
        assert ex.evictions == 1                 # an invalidate is not one
        for i, mm in enumerate(maps):
            assert mm[:] == i.to_bytes(2, "little") * 2048
            mm.close()
    finally:
        ex.close()


@pytest.mark.parametrize("table", ["mem", "warm"])
def test_export_of_a_block_that_left_while_copied_is_dropped(tmp_path,
                                                              table):
    """A delete that runs while the first grant is still copying finds
    nothing to invalidate; the table asks once more after the copy went
    in, takes it out again and refuses the grant."""
    blocks = _small_blocks(tmp_path, 2, 4096)
    ex = (wshm.ShmExporter(cap_bytes=8192) if table == "mem"
          else wshm.WarmShmCache(cap_bytes=8192))
    base = _export_fds()
    try:
        with pytest.raises(LookupError):
            ex.export(0, blocks[0], 4096, lambda: False)
        assert 0 not in ex and ex.bytes == 0 and _export_fds() == base
        asked = []
        fd, _ = ex.export(1, blocks[1], 4096,
                          lambda: asked.append(1) or True)
        os.close(fd)
        fd, _ = ex.export(1, blocks[1], 4096,
                          lambda: asked.append(1) or True)
        os.close(fd)
        assert asked == [1]                      # not asked on a hit
        assert 1 in ex and ex.bytes == 4096
    finally:
        ex.close()


def test_export_table_concurrent_grants_stay_exact(tmp_path):
    """Eight threads granting 40 blocks through a table that holds ten:
    the counts add up, the gauge never passes the bound, every fd read
    is the block asked for, and no fd is left."""
    from curvine_tpu.common.metrics import MetricsRegistry
    size = 4096
    blocks = _small_blocks(tmp_path, 40, size)
    m = MetricsRegistry("worker")
    ex = wshm.ShmExporter(cap_bytes=10 * size, metrics=m)
    base = _export_fds()
    over, wrong = [], []

    def worker(k: int) -> None:
        for j in range(200):
            i = (j * 7 + k * 13) % 40
            fd, n = ex.export(i, blocks[i], size)
            if os.pread(fd, 2, 0) != i.to_bytes(2, "little"):
                wrong.append(i)
            os.close(fd)
            if m.gauges["shm.export_bytes"] > ex.cap_bytes:
                over.append(m.gauges["shm.export_bytes"])

    try:
        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not wrong and not over
        st = ex.stats()
        # a raced copy that lost is a hit of the first one's memfd
        assert st["hits"] + st["exports"] == 8 * 200
        assert st["exports"] - st["evictions"] == st["entries"] <= 10
        assert m.counters["shm.exports"] == st["exports"]
        assert _export_fds() == base + st["entries"]
    finally:
        ex.close()
    assert _export_fds() == base


@pytest.mark.parametrize("leaves_by", ["delete", "tier_move"])
async def test_export_leaves_with_its_block(tmp_path, leaves_by):
    """An entry leaves the table when its block does — deleted, or
    moved to another tier: the gauge falls by its bytes and the next
    grant over the side channel is NOT_FOUND, never the old copy."""
    conf = _ssd_conf(tmp_path, with_mem=True)
    async with MiniCluster(workers=1, conf=conf, base_dir=str(tmp_path),
                           block_size=MB) as mc:
        w = mc.workers[0]
        c = mc.client()
        await c.write_all("/shm/a.bin", os.urandom(MB))
        await c.write_all("/shm/b.bin", os.urandom(MB // 2))
        bids = []
        for name in ("a", "b"):
            r = await c.open(f"/shm/{name}.bin")
            await r.pread_view(0, 4096)
            bids.append(r.blocks.block_locs[0].block.id)
            await r.close()
        assert w.shm.cap_bytes == 64 * MB        # the MEM tier's, not 8 GiB
        assert w.shm.cap_entries == wshm.entry_bound()
        assert bids[0] in w.shm and bids[1] in w.shm
        assert w.metrics.gauges["shm.export_bytes"] == MB + MB // 2
        assert w.metrics.counters["shm.exports"] == 2
        sock = w._shm_channel.path
        fd, n = await asyncio.to_thread(wshm.fetch_block_fd, sock, bids[0])
        os.close(fd)
        assert n == MB and w.metrics.counters["shm.exports"] == 2
        if leaves_by == "delete":
            w.store.delete(bids[0])
        else:
            ssd = next(t for t in w.store.tiers
                       if t.storage_type.name == "SSD")
            assert w.store._move_block(bids[0], ssd)
        assert bids[0] not in w.shm and bids[1] in w.shm
        assert w.metrics.gauges["shm.export_bytes"] == MB // 2
        assert w.metrics.gauges["shm.export_entries"] == 1
        assert w.metrics.counters["shm.export_evictions"] == 0
        with pytest.raises(LookupError):
            await asyncio.to_thread(wshm.fetch_block_fd, sock, bids[0])
        assert bids[0] not in w.shm
        await c.close()


def test_conf_with_the_old_export_key_loads(tmp_path):
    """`worker.shm_export_cap` counted entries; a conf file that still
    carries it loads and the key is ignored: the table has no option
    now, its bound in bytes is the 8 GiB that 128 blocks of 64 MiB came
    to (under the MEM tiers' capacity, test_export_leaves_with_its_
    block)."""
    path = tmp_path / "old.toml"
    path.write_text("[worker]\nshm_reads = true\nshm_export_cap = 128\n"
                    "shm_warm_cap_mb = 32\n")
    conf = ClusterConf.load(str(path), env={})
    assert conf.worker.shm_reads and conf.worker.shm_warm_cap_mb == 32
    assert not hasattr(conf.worker, "shm_export_cap")
    assert not hasattr(conf.worker, "shm_export_cap_mb")
    assert wshm.EXPORT_CAP_BYTES == 128 * 64 * MB


async def test_shm_channel_fd_handoff(tmp_path):
    """ShmChannel/fetch_block_fd: the SCM_RIGHTS round trip dups a
    usable fd into the receiver; unknown blocks raise LookupError."""
    data = os.urandom(8192)
    fd = os.memfd_create("cv-test")
    os.write(fd, data)

    def grant(block_id: int):
        if block_id != 7:
            raise LookupError(block_id)
        return os.dup(fd), len(data)         # the channel's to close

    path = wshm.channel_path(os.getpid() % 60_000)
    ch = wshm.ShmChannel(path, grant)
    ch.start()
    try:
        got_fd, n = await asyncio.to_thread(wshm.fetch_block_fd, path, 7)
        assert n == len(data)
        assert got_fd != fd                  # a dup, not the original
        assert os.pread(got_fd, n, 0) == data
        os.close(got_fd)
        # the channel closed what the grant gave it, once it was sent
        base = _fd_count()
        for _ in range(20):
            os.close((await asyncio.to_thread(
                wshm.fetch_block_fd, path, 7))[0])
        for _ in range(50):
            if _fd_count() <= base:
                break
            await asyncio.sleep(0.02)        # the serving threads' exit
        assert _fd_count() <= base
        with pytest.raises(LookupError):
            await asyncio.to_thread(wshm.fetch_block_fd, path, 8)
    finally:
        ch.stop()
        os.close(fd)
    assert not os.path.exists(path)


def test_alloc_aligned_and_registered_pool():
    """transport.alloc_aligned returns page-aligned mmap-backed arrays;
    RegisteredBuffers recycles them under a byte cap."""
    arr = transport.alloc_aligned(300_000)
    assert len(arr) == 300_000
    assert arr.ctypes.data % mmap.PAGESIZE == 0

    pool = transport.RegisteredBuffers(max_bytes=2 * MB,
                                       min_size=64 * 1024,
                                       max_size=MB)
    a = pool.acquire(100_000)
    assert len(a) == 100_000 and a.ctypes.data % mmap.PAGESIZE == 0
    pool.release(a)
    b = pool.acquire(90_000)                 # same power-of-two class
    assert pool.reused == 1
    pool.release(b)
    # over max_size: served aligned but never pooled (nor counted)
    big = pool.acquire(4 * MB)
    assert len(big) == 4 * MB
    held, retained = pool.acquired, pool.retained
    pool.release(big)
    assert pool.acquired == held and pool.retained == retained
    # the cap bounds retention: releases past max_bytes are dropped
    extras = [pool.acquire(MB) for _ in range(4)]
    for e in extras:
        pool.release(e)
    assert pool.retained <= 2 * MB
    pool.drain()


# ---------------- warm cache: zero-syscall reads below MEM ----------------

def _ssd_conf(tmp_path, warm_mb: int = 8, min_reads: int = 3,
              with_mem: bool = False) -> ClusterConf:
    """SSD-backed cluster conf for the warm-cache plane. SSD-only by
    default so the promotion scan can't move the block out from under
    the test; with_mem adds a MEM tier for the invalidation tests."""
    from curvine_tpu.common.conf import TierConf
    conf = ClusterConf()
    conf.data_dir = str(tmp_path)
    tiers = []
    if with_mem:
        tiers.append(TierConf(storage_type="mem",
                              dir=str(tmp_path / "mem"),
                              capacity=64 * MB))
    tiers.append(TierConf(storage_type="ssd", dir=str(tmp_path / "ssd"),
                          capacity=64 * MB))
    conf.worker.tiers = tiers
    conf.worker.shm_warm_cap_mb = warm_mb
    conf.worker.shm_warm_min_reads = min_reads
    return conf


async def _write_ssd(c, path: str, payload: bytes) -> None:
    w = await c.create(path, storage_type="ssd")
    await w.write(payload)
    await w.close()


async def test_warm_shm_export_after_heat(tmp_path):
    """An SSD-tier block that crosses worker.shm_warm_min_reads earns a
    sealed-memfd warm copy: a fresh reader's probe sees the shm_warm
    capability and serves reads from the mapping — warm hit counters
    move, the worker's RPC read path does not."""
    conf = _ssd_conf(tmp_path, min_reads=3)
    async with MiniCluster(workers=1, conf=conf, base_dir=str(tmp_path),
                           block_size=MB) as mc:
        c = mc.client()
        payload = os.urandom(MB)
        await _write_ssd(c, "/warm/a.bin", payload)

        # heat the block past the threshold; close() flushes the
        # SC_READ_REPORT heat rail
        r = await c.open("/warm/a.bin")
        bid = r.blocks.block_locs[0].block.id
        for i in range(5):
            await r.pread_view(i * 4096, 4096)
        await r.close()
        assert mc.workers[0].store.get(bid, touch=False).heat >= 5
        assert c.counters.get("read.shm_warm_hits", 0) == 0

        # a fresh reader probes, sees shm_warm, and maps the warm copy
        r2 = await c.open("/warm/a.bin")
        for off in (0, 4096, MB - 4096):
            got = await r2.pread_view(off, 4096)
            assert bytes(got) == payload[off:off + 4096]
        assert bid in r2._shm_warm
        assert c.counters.get("read.shm_warm_hits", 0) >= 3
        assert c.counters.get("read.shm_hits", 0) == 0
        # the data plane never touched the worker's RPC read path
        assert mc.workers[0].metrics.counters.get("bytes.read", 0) == 0
        assert mc.workers[0].metrics.counters.get("shm.warm_grants",
                                                  0) >= 1
        assert bid in mc.workers[0].shm_warm
        assert mc.workers[0].shm_warm.stats()["exports"] == 1

        # zero-copy view rides the same mapping, marked shm_warm
        view = await r2.read_range(8192, 4096)
        assert isinstance(view, np.ndarray)
        assert not view.flags.writeable
        assert bytes(view) == payload[8192:8192 + 4096]
        assert "shm_warm" in r2.served_by()
        await r2.close()

        # the warm counters ride METRICS_REPORT into the master's
        # read-plane rollup (the `cv report` feed)
        await c.flush_metrics()
        table = await mc.master._shard_table({})
        assert table["read_plane"]["shm_warm_hits"] >= 3
        await c.close()


async def test_warm_advert_rides_sc_report_reply(tmp_path):
    """The very client that created the heat learns the capability from
    the SC_READ_REPORT reply (its probe predates the heat): after a
    flush, the SAME reader switches to the warm rung without re-probing."""
    conf = _ssd_conf(tmp_path, min_reads=3)
    async with MiniCluster(workers=1, conf=conf, base_dir=str(tmp_path),
                           block_size=MB) as mc:
        c = mc.client()
        payload = os.urandom(MB)
        await _write_ssd(c, "/warm/b.bin", payload)
        r = await c.open("/warm/b.bin")
        bid = r.blocks.block_locs[0].block.id
        for i in range(6):          # heat accrues client-side, unflushed
            await r.pread_view(i * 4096, 4096)
        assert bid not in r._shm_warm
        await r._flush_sc_reads()   # reply piggybacks the warm advert
        assert bid in r._shm_warm and r._shm_sock.get(bid)
        got = await r.pread_view(0, 4096)
        assert bytes(got) == payload[:4096]
        assert c.counters.get("read.shm_warm_hits", 0) >= 1
        await r.close()
        await c.close()


async def test_warm_copy_invalidated_on_promote(tmp_path):
    """A tier move drops the warm copy (BlockStore.on_move): the copy
    was admitted under the SSD tier's policy and must not outlive the
    block's tier residency. Reads after the promote stay correct."""
    conf = _ssd_conf(tmp_path, min_reads=2, with_mem=True)
    async with MiniCluster(workers=1, conf=conf, base_dir=str(tmp_path),
                           block_size=MB) as mc:
        c = mc.client()
        payload = os.urandom(MB)
        await _write_ssd(c, "/warm/mv.bin", payload)
        r = await c.open("/warm/mv.bin")
        bid = r.blocks.block_locs[0].block.id
        for i in range(4):
            await r.pread_view(i * 4096, 4096)
        await r.close()
        r2 = await c.open("/warm/mv.bin")
        await r2.pread_view(0, 4096)             # maps the warm copy
        assert bid in mc.workers[0].shm_warm
        promoted = mc.workers[0].store.promote_scan(min_reads=0)
        assert bid in promoted
        assert bid not in mc.workers[0].shm_warm
        assert mc.workers[0].shm_warm.stats()["evictions"] == 0
        # the held mapping still serves (sealed pages outlive the fd);
        # a fresh reader resolves the MEM-tier location cleanly
        got = await r2.pread_view(4096, 4096)
        assert bytes(got) == payload[4096:8192]
        await r2.close()
        r3 = await c.open("/warm/mv.bin")
        assert bytes(await r3.pread_view(0, 8192)) == payload[:8192]
        await r3.close()
        await c.close()


async def test_warm_copy_invalidated_on_delete(tmp_path):
    """Deleting the block fires on_delete into the warm cache too: the
    worker's memfd closes and the entry leaves without ghosting."""
    conf = _ssd_conf(tmp_path, min_reads=2)
    async with MiniCluster(workers=1, conf=conf, base_dir=str(tmp_path),
                           block_size=MB) as mc:
        c = mc.client()
        await _write_ssd(c, "/warm/del.bin", os.urandom(MB))
        r = await c.open("/warm/del.bin")
        bid = r.blocks.block_locs[0].block.id
        for i in range(3):
            await r.pread_view(i * 4096, 4096)
        await r.close()
        r2 = await c.open("/warm/del.bin")
        await r2.pread_view(0, 4096)
        assert bid in mc.workers[0].shm_warm
        await r2.close()
        mc.workers[0].store.delete(bid)
        assert bid not in mc.workers[0].shm_warm
        assert mc.workers[0].shm_warm.stats()["bytes"] == 0
        await c.close()


def test_warm_cache_unit_eviction_and_scan_resistance(tmp_path):
    """WarmShmCache unit contract: byte-bounded eviction through
    S3-FIFO (a one-touch scan leaves through probation, the re-touched
    working set survives), caller-held dups outlive eviction, oversized
    blocks are refused, invalidate is a plain removal."""
    blk = 4096
    paths = {}
    for i in range(12):
        p = tmp_path / f"w{i}"
        p.write_bytes(bytes([i]) * blk)
        paths[i] = str(p)
    cache = wshm.WarmShmCache(cap_bytes=4 * blk, admission="s3fifo")
    try:
        # working set: two blocks, each re-touched (freq >= 1)
        base = _export_fds()
        for h in (0, 1):
            _grant(cache, h, paths[h], blk)
            _grant(cache, h, paths[h], blk)      # hit -> on_access
        assert cache.hits == 2 and cache.exports == 2
        dup, _ = cache.export(2, paths[2], blk)  # one-touch; a client's
        try:
            # one-touch scan far past capacity: probationary entries
            # leave, the re-touched working set never gets displaced
            for s in range(3, 12):
                _grant(cache, s, paths[s], blk)
            assert 0 in cache and 1 in cache
            assert 2 not in cache
            assert cache.evictions > 0
            assert cache.policy.scan_evicted > 0
            assert cache.stats()["bytes"] <= 4 * blk
            # eviction closed the worker's fd, not the client's dup
            assert _export_fds() == base + len(cache) + 1
            assert os.pread(dup, blk, 0) == bytes([2]) * blk
        finally:
            os.close(dup)
        # a block bigger than the whole cache is never worth it
        with pytest.raises(LookupError):
            cache.export(99, paths[0], 5 * blk)
        # invalidate: plain removal, bytes drop, no eviction counted
        ev = cache.evictions
        assert 0 in cache
        cache.invalidate(0)
        assert 0 not in cache and cache.evictions == ev
    finally:
        cache.close()
    assert len(cache) == 0 and cache.stats()["bytes"] == 0


# ---------------- observability: counters reach the master ----------------

async def test_read_plane_rollup_reaches_master(tmp_path):
    """read.shm_* counters ride the METRICS_REPORT push plane and land
    in the master's read-plane rollup (the `cv report` feed)."""
    conf = ClusterConf()
    conf.data_dir = str(tmp_path)
    async with MiniCluster(workers=1, conf=conf, base_dir=str(tmp_path),
                           block_size=MB) as mc:
        c = mc.client()
        await c.write_all("/shm/obs.bin", os.urandom(MB))
        r = await c.open("/shm/obs.bin")
        await r.pread_view(0, 4096)
        await r.read_range(4096, 4096)       # zero-copy view path
        await r.close()
        await c.flush_metrics()
        m = mc.master.metrics.as_dict()
        assert m.get("client.read.shm_hits", 0) >= 2
        assert m.get("client.read.zero_copy_bytes", 0) >= 4096
        table = await mc.master._shard_table({})
        assert table["read_plane"]["shm_hits"] >= 2
        assert table["read_plane"]["zero_copy_bytes"] >= 4096
        await c.close()


# ---------------- the ladder, scaled down to a tier-1 smoke ----------------

async def test_latency_ladder_smoke():
    """One scaled-down open-loop rung (64 clients over a CPU-pinned
    process fleet, Poisson arrivals) completes with zero errors — the
    tier-1 guard for scripts/latency_ladder.py, covering the --cpus
    multi-core tail path."""
    scripts = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    from latency_ladder import run_ladder

    cpus = sorted(os.sched_getaffinity(0))[:2]
    res = await run_ladder(rungs=(64,), duration=1.0, rate=4.0, procs=2,
                           cpus=cpus)
    assert res["cpus"] == cpus
    rung = res["rungs"][0]
    assert rung["clients"] == 64
    assert rung["cpus"] == cpus                  # pinning recorded
    assert rung["errors"] == 0
    assert rung["samples"] > 0
    assert rung["p99_us"] == rung["p99_us"]      # not NaN
    assert rung["p50_us"] <= rung["p99_us"] <= rung["p999_us"]
