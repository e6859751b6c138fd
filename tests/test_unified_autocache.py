"""Auto-cache on open (docs/caching.md): a data set in a mounted
under-store, twice the cache in front of it, read through
`CurvineClient.unified_open`. A miss is served from the UFS and asks the
master for one asynchronous load of the file; the worker's load tasks
fill the MEM tier and eviction makes room; a block dropped under its
reader carries the read to the UFS. The plain reference is a dict of
seeded bytes: a cache is right when no reader can tell it is there."""

import asyncio

import numpy as np
import pytest

from curvine_tpu.client import CurvineClient
from curvine_tpu.client.unified import FallbackReader
from curvine_tpu.client.ufs_reader import UfsReader
from curvine_tpu.common import errors as err
from curvine_tpu.common.types import JobState
from curvine_tpu.master import jobs as jobs_mod
from curvine_tpu.testing import MiniCluster
from curvine_tpu.ufs import create_ufs
from curvine_tpu.ufs import memory as memufs

FILES = 48
MEAN = 30_000                 # 20–40 kB a file; the tier holds 24 of them
ROOT = "/mnt/set"


def reference(seed: int = 7) -> dict[str, bytes]:
    rng = np.random.default_rng(seed)
    return {f"f{i:03d}.bin": rng.integers(
        0, 256, int(rng.integers(20_000, 40_000)), dtype=np.uint8).tobytes()
        for i in range(FILES)}


async def fill_ufs(bucket: str, files: dict[str, bytes]) -> None:
    memufs.reset()
    ufs = create_ufs(f"mem://{bucket}")
    for name, data in files.items():
        await ufs.write_all(f"mem://{bucket}/{name}", data)


def cluster() -> MiniCluster:
    mc = MiniCluster(workers=1, tier_capacity=24 * MEAN,
                     block_size=64 * 1024)
    mc.conf.obs.trace_sample_rate = 1.0     # every span is kept
    return mc


def ops(tracer) -> dict[str, list[dict]]:
    """The spans a tracer holds, by op."""
    out: dict[str, list[dict]] = {}
    for sp in tracer.store.drain(100_000):
        out.setdefault(sp["op"], []).append(sp)
    return out


async def read_through(c: CurvineClient, path: str):
    """The feed's three lines: (bytes, the reader that served them)."""
    r = await c.unified_open(path)
    view = await r.mmap_view(0, r.len)
    data = bytes(view) if view is not None else await r.read_all()
    await r.close()
    return data, r


async def settle(mc: MiniCluster, c: CurvineClient, timeout: float = 20.0):
    """Every load this client asked for has been submitted and has run."""
    end = asyncio.get_running_loop().time() + timeout
    while c._load_submits or mc.master.jobs.live_loads():
        assert asyncio.get_running_loop().time() < end, "loads never ended"
        await asyncio.sleep(0.01)


async def trim(w) -> None:
    """The worker's 1 s eviction tick, and its word to the master."""
    await w._evict_once()
    await asyncio.gather(*w._evict_reports)


def load_jobs(mc: MiniCluster) -> list:
    return [j for j in mc.master.jobs.jobs.values() if j.kind == "load"]


async def test_a_miss_submits_one_load_and_only_under_auto_cache(
        monkeypatch):
    files = reference()
    await fill_ufs("set", files)
    gate = asyncio.Event()
    inner = CurvineClient.load_from_ufs

    async def gated(self, path, replicas=None):
        await gate.wait()
        return await inner(self, path, replicas)

    monkeypatch.setattr(CurvineClient, "load_from_ufs", gated)
    async with cluster() as mc:
        c = mc.client()
        w = mc.workers[0]
        await c.meta.mount(ROOT, "mem://set", auto_cache=True)
        await c.meta.mount("/mnt/plain", "mem://set")
        name = "f000.bin"

        data, r = await read_through(c, f"{ROOT}/{name}")
        assert data == files[name] and isinstance(r, UfsReader)
        while c._load_submits:
            await asyncio.sleep(0.01)
        assert len(load_jobs(mc)) == 1
        assert c.counters["cache.load.submitted"] == 1
        assert mc.master.jobs.live_load(f"{ROOT}/{name}") is not None
        assert mc.master.metrics.gauges["jobs.load.live"] == 1

        # a second open while that load runs: served from the UFS again,
        # no second job
        data, r = await read_through(c, f"{ROOT}/{name}")
        assert data == files[name] and isinstance(r, UfsReader)
        while c._load_submits:
            await asyncio.sleep(0.01)
        assert len(load_jobs(mc)) == 1
        assert c.counters["cache.load.submitted"] == 1
        assert c.counters["cache.load.deduped"] == 1

        # the same file under a mount without auto_cache: no load
        data, r = await read_through(c, f"/mnt/plain/{name}")
        assert data == files[name] and isinstance(r, UfsReader)
        assert not c._load_submits and len(load_jobs(mc)) == 1

        gate.set()
        await settle(mc, c)
        assert load_jobs(mc)[0].state == JobState.COMPLETED
        assert mc.master.metrics.gauges["jobs.load.live"] == 0
        data, r = await read_through(c, f"{ROOT}/{name}")
        assert data == files[name] and isinstance(r, FallbackReader)
        assert not r._fell_back

        # the counters, by what was done: three reads from the UFS, one
        # task that brought the file in
        assert c.counters["read.ufs.files"] == 3
        assert c.counters["read.ufs.bytes"] == 3 * len(files[name])
        assert c.counters["read.phase.ufs.s"] > 0
        assert c.counters["read.files"] == 1
        wc = w.metrics.counters
        assert wc["load.tasks"] == 1 and wc["load.bytes"] == len(files[name])
        assert wc["load.s"] > 0 and "load.failed" not in wc


async def test_epochs_over_a_set_twice_the_cache_read_right():
    files = reference()
    names = sorted(files)
    await fill_ufs("set", files)
    async with cluster() as mc:
        c = mc.client()
        w = mc.workers[0]
        await c.meta.mount(ROOT, "mem://set", auto_cache=True)
        hits = []
        for epoch in range(4):
            order = np.random.default_rng([11, epoch]).permutation(FILES)
            served = 0
            for k, i in enumerate(order):
                data, r = await read_through(c, f"{ROOT}/{names[i]}")
                assert data == files[names[i]], (epoch, names[i])
                served += isinstance(r, FallbackReader) and not r._fell_back
                if k % 8 == 7:
                    await trim(w)
            hits.append(served)
            await settle(mc, c)
            await trim(w)
        assert hits[0] == 0 and all(h > 0 for h in hits[1:]), hits
        wc = w.metrics.counters
        assert wc["blocks.evicted"] > 0
        assert wc["load.tasks"] >= FILES and "load.failed" not in wc
        assert w.store.tiers[0].used <= 24 * MEAN
        assert c.counters["read.ufs.files"] + sum(hits) == 4 * FILES
        # the master was told of every dropped block: it hands out no
        # location of a block that is gone
        for name in names:
            try:
                fb = await c.meta.get_block_locations(f"{ROOT}/{name}")
            except err.FileNotFound:
                continue
            for lb in fb.block_locs:
                assert not lb.locs or w.store.contains(lb.block.id), name


async def test_a_block_dropped_under_its_reader_is_read_from_the_ufs():
    files = reference()
    await fill_ufs("set", files)
    async with cluster() as mc:
        c = mc.client()
        w = mc.workers[0]
        await c.meta.mount(ROOT, "mem://set", auto_cache=True)
        path = f"{ROOT}/f001.bin"
        await read_through(c, path)
        await settle(mc, c)

        r = await c.unified_open(path)
        assert isinstance(r, FallbackReader)
        for lb in r._r.blocks.block_locs:      # dropped before the read
            w.store.delete(lb.block.id)
        view = await r.mmap_view(0, r.len)
        data = bytes(view) if view is not None else await r.read_all()
        await r.close()
        assert data == files["f001.bin"] and r._fell_back
        assert c.counters["read.ufs_fallbacks"] == 1
        assert c.counters["read.ufs.files"] == 2
        # the read that found the copy gone asks for it again
        await settle(mc, c)
        assert c.counters["cache.load.submitted"] == 2
        data, r = await read_through(c, path)
        assert data == files["f001.bin"] and not r._fell_back

        # the spans of what was done (docs/observability.md): two reads
        # from the UFS, one read that fell back, two loads at the worker
        got = ops(c.tracer)
        assert [sp["attrs"]["served_by"] for sp in got["unified_open"]] \
            == ["ufs", "cache", "cache"]
        assert len(got["phase.ufs"]) == 2 and len(got["ufs_fallback"]) == 1
        loads = ops(w.tracer)["load"]
        assert [sp["attrs"]["path"] for sp in loads] == [path, path]


async def test_a_reader_never_sees_a_half_loaded_file(monkeypatch):
    """While the load task writes the file its inode is there and
    incomplete: a reader is served from the UFS, not the bytes so far."""
    files = reference()
    await fill_ufs("set", files)
    created, go_on = asyncio.Event(), asyncio.Event()
    inner = CurvineClient.create

    async def slow_create(self, path, **kw):
        writer = await inner(self, path, **kw)
        if kw.get("storage_policy"):            # the load's create
            created.set()
            await go_on.wait()
        return writer

    monkeypatch.setattr(CurvineClient, "create", slow_create)
    async with cluster() as mc:
        c = mc.client()
        await c.meta.mount(ROOT, "mem://set", auto_cache=True)
        path = f"{ROOT}/f002.bin"
        await read_through(c, path)
        await asyncio.wait_for(created.wait(), 10)
        st = await c.meta.file_status(path)
        assert not st.is_complete
        data, r = await read_through(c, path)
        assert data == files["f002.bin"] and isinstance(r, UfsReader)
        go_on.set()
        await settle(mc, c)
        data, r = await read_through(c, path)
        assert data == files["f002.bin"] and isinstance(r, FallbackReader)


async def test_the_job_table_stays_bounded(monkeypatch):
    monkeypatch.setattr(jobs_mod, "MAX_FINISHED_JOBS", 64)
    memufs.reset()
    ufs = create_ufs("mem://many")
    for i in range(500):
        await ufs.write_all(f"mem://many/s{i:03d}", bytes([i % 251]) * 64)
    async with MiniCluster(workers=1) as mc:
        c = mc.client()
        await c.meta.mount("/many", "mem://many", auto_cache=True)
        for lo in range(0, 500, 50):
            got = await asyncio.gather(*(
                c.meta.submit_load_if_absent(f"/many/s{i:03d}")
                for i in range(lo, lo + 50)))
            assert all(outcome == "submitted" for _, outcome in got)
            await settle(mc, c, 60.0)
        jm = mc.master.jobs
        assert len(jm.jobs) == 64 and jm.live_loads() == 0
        # the worker counts a task after its report is answered, and the
        # store shows a removal once its batch is written
        for _ in range(500):
            if mc.workers[0].metrics.counters["load.tasks"] == 500 and \
                    len(list(mc.master.fs.store.iter_jobs())) == 64:
                break
            await asyncio.sleep(0.01)
        assert mc.workers[0].metrics.counters["load.tasks"] == 500
        assert len(list(mc.master.fs.store.iter_jobs())) == 64
        assert all(j.state == JobState.COMPLETED for j in jm.jobs.values())
        # a finished job that left the table is unknown, not an error of
        # the master's
        with pytest.raises(err.JobNotFound):
            await c.meta.job_status("0" * 16)


async def test_stat_of_a_ufs_only_path_with_the_fast_meta_port():
    """The native read plane answers first once the directory's lease is
    warm; a path that exists only in the under-store is not its to
    deny."""
    files = reference()
    await fill_ufs("set", files)
    async with cluster() as mc:
        c = mc.client()
        info = await c.meta.master_info()
        if not info.fast_addr:
            pytest.skip("no native fast-meta port in this build")
        await c.meta.mount(ROOT, "mem://set", auto_cache=True)
        for name in ("f003.bin", "f004.bin", "f003.bin"):
            st = await c.meta.file_status(f"{ROOT}/{name}")
            assert st.len == len(files[name]) and st.is_complete
        with pytest.raises(err.FileNotFound):
            await c.meta.file_status(f"{ROOT}/absent.bin")
        # cached, then asked again: the inode's status, the same length
        await read_through(c, f"{ROOT}/f003.bin")
        await settle(mc, c)
        st = await c.meta.file_status(f"{ROOT}/f003.bin")
        assert st.len == len(files["f003.bin"]) and st.id != 0


async def test_a_file_the_master_freed_is_not_read_as_a_hole():
    """Cache pressure at the master frees a cold file: the blocks go, the
    inode stays, complete and at its length. A client whose leased status
    still says "cached" must not open that as a file of holes (zeros)."""
    from curvine_tpu.common.types import StorageState
    files = reference()
    await fill_ufs("set", files)
    async with cluster() as mc:
        c = mc.client()
        await c.meta.mount(ROOT, "mem://set", auto_cache=True)
        path = f"{ROOT}/f005.bin"
        await read_through(c, path)
        await settle(mc, c)
        data, r = await read_through(c, path)
        assert isinstance(r, FallbackReader)    # cached; status leased

        mc.master.fs.free(path)                 # the lease is not told
        data, r = await read_through(c, path)
        assert data == files["f005.bin"] and isinstance(r, UfsReader)
        await settle(mc, c)                     # ...and loaded again
        data, r = await read_through(c, path)
        assert data == files["f005.bin"] and isinstance(r, FallbackReader)

        # the master's own pass under pressure tells the lease holders
        await mc.workers[0].heartbeat_once()    # usage reaches the master
        q = mc.master.quota
        q.high_water = q.low_water = 0.0
        assert q.evict_once() >= 1
        for _ in range(200):
            st = await c.meta.file_status(path)
            if st.storage_policy.state == StorageState.UFS:
                break
            await asyncio.sleep(0.01)
        assert st.storage_policy.state == StorageState.UFS
        data, r = await read_through(c, path)
        assert data == files["f005.bin"] and isinstance(r, UfsReader)


async def test_the_cache_alone_serves_no_byte_of_a_freed_file():
    """`open` reads the cache and nothing else: of a file the master has
    freed it holds nothing, and says so instead of handing on zeros. A
    file that was resized past its blocks is still read as a hole."""
    files = reference()
    await fill_ufs("set", files)
    async with cluster() as mc:
        c = mc.client()
        await c.meta.mount(ROOT, "mem://set", auto_cache=True)
        path = f"{ROOT}/f006.bin"
        await read_through(c, path)
        await settle(mc, c)
        assert await (await c.open(path)).read_all() == files["f006.bin"]
        mc.master.fs.free(path)
        with pytest.raises(err.BlockNotFound):
            await c.open(path)
        data, r = await read_through(c, path)
        assert data == files["f006.bin"] and isinstance(r, UfsReader)

        await c.write_all("/plain.bin", b"abc")
        await c.meta.resize_file("/plain.bin", 10)
        assert await (await c.open("/plain.bin")).read_all() \
            == b"abc" + bytes(7)


async def test_the_trim_does_not_wait_for_a_master_that_is_down():
    async with cluster() as mc:
        w = mc.workers[0]
        hung = asyncio.Event()

        async def never(*a, **kw):
            await hung.wait()

        w._bounded_master_call = never
        w.store.take_dropped = lambda: [41, 42]
        await asyncio.wait_for(w._evict_once(), 2.0)
        assert w.metrics.counters["blocks.evicted"] == 2
        assert w._evict_reports             # still talking, not waited for
        hung.set()
        await asyncio.gather(*w._evict_reports)
