"""Driver of the traffic kind "feed_ufs": the feed of drivers/feed.py over
a data set that lives in an under-store and is larger than the cache in
front of it. Set-up writes the set as plain files into a scratch directory
under TMPDIR (not through the client) and mounts that directory at the
configuration's data_root with auto_cache; every file is then opened with
CurvineClient.unified_open → mmap_view, else read_all, so a sample comes
from the MEM tier on a hit and from the UFS on a miss, and the program
decides what it loads and what it drops. The plain reference knows
nothing of hits and misses.

Beside the feed's two numbers the comparison holds `reads_failed` (a read
that surfaced an error: the sample handed on is then zeros, so the run
ends with a verdict) and the guarantee on cached copies: after the window
every file the master reports cached and complete is read through
CurvineClient.open (the cache alone) and folded, against the fold of that
file made again from the seed. `cached_mismatched` counts the copies that
differ, `cached_compared` those that were held against the seed, and
`cached_short` how many of them are missing below a quarter of the files
the tier has room for: a guarantee on cached copies is shown only where
there are some."""

from __future__ import annotations

import asyncio
import os
import shutil
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from perfbench import fold, harness

feed = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "feed.py"))

WRITERS = 8          # threads that make and write the plain files
CHECKERS = 8         # cached copies read back at a time
CACHED_FLOOR = 0.25  # of the files the tier has room for, compared at least


def cached_whole(fb) -> bool:
    """One answer of the master (a FileBlocks) says the file is in the
    cache: complete, with blocks, every block located."""
    return bool(fb.status.is_complete and fb.block_locs
                and all(lb.locs for lb in fb.block_locs))


class Driver(feed.Driver):
    def __init__(self, env):
        super().__init__(env)
        self.ufs_dir = None
        self.reads_failed = 0
        self.cached: dict[int, np.ndarray] = {}   # file → fold of its copy
        self.cached_skipped = 0

    # ------------------------------------------------------------ set-up

    def _write_plain(self, i: int) -> None:
        name = os.path.basename(self.ds.path(self.root, i))
        with open(os.path.join(self.ufs_dir, name), "wb") as f:
            f.write(self.ds.make(i))

    async def prepare(self) -> None:
        from curvine_tpu.obs.profiler import StepProfiler
        from curvine_tpu.tpu.ingest import AsyncDevicePrefetcher
        env = self.env
        # inside the run's own scratch directory (under TMPDIR), which
        # run.py removes however the run ends
        self.ufs_dir = os.path.join(env.conf.data_dir, "ufs")
        os.mkdir(self.ufs_dir)
        t0 = time.perf_counter()
        with ThreadPoolExecutor(WRITERS) as pool:
            await asyncio.gather(*(
                asyncio.get_running_loop().run_in_executor(
                    pool, self._write_plain, i)
                for i in range(self.ds.files)))
        self.write_s = time.perf_counter() - t0
        self.client = env.new_client()
        env.spans.wrap(self.client.meta, "call", "master.rpc")
        env.spans.wrap(self.client.meta, "_fast_call", "master.rpc")
        await self.client.meta.mount(self.root, "file://" + self.ufs_dir,
                                     auto_cache=True)
        self.profiler = StepProfiler()
        self.prefetcher = AsyncDevicePrefetcher(
            self._source(), mesh=None, depth=self.depth,
            device=env.devices[0], profiler=self.profiler)
        for _ in range(self.warm):
            await self._next()

    # ---------------------------------------------------------- the path

    async def _fetch(self, i: int):
        prof, spans = self.profiler, self.env.spans
        t0 = time.perf_counter()
        reader = None
        with spans.span("client.fetch"):
            try:
                reader = await self.client.unified_open(
                    self.ds.path(self.root, i))
                view = await reader.mmap_view(0, reader.len)
                if view is None:
                    view = np.frombuffer(await reader.read_all(),
                                         dtype=np.uint8)
            except asyncio.CancelledError:
                raise
            except Exception as e:  # noqa: BLE001 — counted, run goes on
                self.reads_failed += 1
                if self.reads_failed <= 5:
                    print(f"[feed_ufs] read of file {i} failed: "
                          f"{type(e).__name__}: {e}", file=sys.stderr)
                view = None
            if view is not None:
                self.fetched_bytes += len(view)
        t1 = time.perf_counter()
        sample = None
        if view is not None:
            try:
                sample = self.ds.resized(self.gen.decode(view))
            except ValueError as e:
                self.undecodable += 1
                self._complain(i, reader, view, e)
                try:
                    sample = self.ds.resized(
                        self.gen.decode(view, check=False))
                except ValueError:      # not even the framing is there
                    pass
        if sample is None:
            sample = np.zeros(len(self.ds.sample(i)), np.uint8)
        t2 = time.perf_counter()
        if reader is not None:
            await reader.close()
        prof.record("cache_fetch", t1 - t0, 0 if view is None else len(view))
        prof.record("decode", t2 - t1)
        return sample

    def _complain(self, i: int, reader, view, e) -> None:
        """What was handed on in place of file i, for the first few."""
        if self.undecodable > 5:
            return
        inner = getattr(reader, "_r", reader)
        print(f"[feed_ufs] file {i} undecodable ({e}): {len(view)} bytes "
              f"for {len(self.ds.make(i))} from {type(inner).__name__}"
              f"{' after a fallback' if getattr(reader, '_fell_back', 0) else ''}"
              f", {int(np.count_nonzero(view))} of them not zero, blocks "
              f"{[(lb.block.id, lb.block.len, len(lb.locs)) for lb in getattr(getattr(inner, 'blocks', None), 'block_locs', [])]}",
              file=sys.stderr)

    # --------------------------------------------------------- the close

    async def _cached_copy(self, i: int) -> None:
        """File i as the cache alone serves it, folded; skipped where the
        master does not report it cached and complete, or where it is
        dropped before it is read."""
        from curvine_tpu.common import errors as err
        try:
            reader = await self.client.open(self.ds.path(self.root, i))
        except (err.FileNotFound, err.BlockNotFound):
            return          # never loaded, or freed by the master
        try:
            # the answer this reader was opened on decides, not an
            # earlier one: the file may be freed in between
            if not cached_whole(reader.blocks):
                return
            view = await reader.mmap_view(0, reader.len)
            if view is None:
                view = np.frombuffer(await reader.read_all(),
                                     dtype=np.uint8)
            self.cached[i] = await asyncio.to_thread(fold.host_fold, view)
        except err.CurvineError:
            self.cached_skipped += 1
        finally:
            await reader.close()

    async def release(self) -> None:
        """Stop the pipeline, read back what the cache holds, take the
        mount away and the plain files with it."""
        if self.prefetcher is not None:
            await self.prefetcher.aclose()
        if self.client is not None:
            todo = iter(range(self.ds.files))

            async def checker():
                for i in todo:
                    await self._cached_copy(i)

            await asyncio.gather(*(checker() for _ in range(CHECKERS)))
            await self.client.meta.umount(self.root)
        await super().release()
        shutil.rmtree(self.ufs_dir, ignore_errors=True)

    def compare(self) -> dict:
        out = super().compare()
        with ThreadPoolExecutor(feed.THREADS) as pool:
            ref = dict(zip(self.cached, pool.map(
                lambda i: fold.host_fold(
                    np.frombuffer(self.ds.make(i), dtype=np.uint8)),
                self.cached)))
        wrong = sum(not np.array_equal(got, ref[i])
                    for i, got in self.cached.items())
        print(f"[feed_ufs] cached copies compared {len(self.cached)} "
              f"(dropped while read {self.cached_skipped}), reads failed "
              f"{self.reads_failed}", file=sys.stderr)
        cfg = self.env.cell.config
        floor = int(CACHED_FLOOR * cfg["cluster"]["tier_bytes"]
                    // cfg["record_length"])
        out["failed"] += self.reads_failed
        out["compared"]["reads_failed"] = (self.reads_failed, 0)
        out["compared"]["cached_mismatched"] = (wrong, 0)
        out["compared"]["cached_compared"] = (len(self.cached),
                                              self.ds.files)
        out["compared"]["cached_short"] = (
            max(0, floor - len(self.cached)), 0)
        return out
