"""Driver of the traffic kind "feed": a closed loop of reader tasks on
the client's loop (CurvineClient.open → mmap_view, else read_all) → the
format's plain decode → AsyncDevicePrefetcher → a consumer that takes
each batch and blocks until it is ready on the device. Epoch after epoch
in the seeded order. One unit of work is one batch ready on the device.

Readers, batch size and prefetch depth are the configuration's; the
format (how a file is made, decoded and regenerated) is the
configuration's generator. Set-up starts the pipeline, warms it, and
hands that same pipeline to the window."""

from __future__ import annotations

import asyncio
import collections
import itertools
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from perfbench import fold
from perfbench.cluster import write_files

THREADS = 8          # for the reference's folds


class Driver:
    def __init__(self, env):
        self.env = env
        cfg = env.cell.config
        self.gen = env.cell.module("generators", cfg["generator"])
        self.ds = self.gen.DataSet(env.seed, cfg)
        self.root = cfg["data_root"]
        self.batch_size = int(cfg["batch_size"])
        self.readers = int(cfg["read_threads"])
        self.depth = int(cfg["prefetch_depth"])
        self.warm = int(env.cell.traffic["warm_batches"])
        self.client = None
        self.prefetcher = None
        self.profiler = None
        self.digests: list = []           # one a sample, as delivered
        self.fetched_bytes = 0
        self.undecodable = 0
        self.write_s = 0.0

    # ------------------------------------------------------------ set-up

    async def prepare(self) -> None:
        from curvine_tpu.obs.profiler import StepProfiler
        from curvine_tpu.tpu.ingest import AsyncDevicePrefetcher
        env, ds = self.env, self.ds
        self.client = env.new_client()
        env.spans.wrap(self.client.meta, "call", "master.rpc")
        env.spans.wrap(self.client.meta, "_fast_call", "master.rpc")
        await self.client.meta.mkdir(self.root)
        self.write_s = await write_files(
            self.client, ds.files, ds.make,
            lambda i: ds.path(self.root, i))
        self.profiler = StepProfiler()
        self.prefetcher = AsyncDevicePrefetcher(
            self._source(), mesh=None, depth=self.depth,
            device=env.devices[0], profiler=self.profiler)
        for _ in range(self.warm):
            await self._next()

    # ---------------------------------------------------------- the path

    def _order(self):
        for epoch in itertools.count():
            yield from self.ds.epoch_order(self.env.seed, epoch).tolist()

    async def _fetch(self, i: int):
        prof, spans = self.profiler, self.env.spans
        t0 = time.perf_counter()
        with spans.span("client.fetch"):
            reader = await self.client.open(self.ds.path(self.root, i))
            view = await reader.mmap_view(0, reader.len)
            if view is None:
                view = np.frombuffer(await reader.read_all(), dtype=np.uint8)
            self.fetched_bytes += len(view)
        t1 = time.perf_counter()
        try:
            payload = self.gen.decode(view)
        except ValueError:
            self.undecodable += 1
            payload = self.gen.decode(view, check=False)
        sample = self.ds.resized(payload)
        t2 = time.perf_counter()
        await reader.close()
        prof.record("cache_fetch", t1 - t0, len(view))
        prof.record("decode", t2 - t1)
        return sample

    async def _source(self):
        order = self._order()
        pending = collections.deque(
            asyncio.ensure_future(self._fetch(next(order)))
            for _ in range(self.readers))
        try:
            while True:
                rows = []
                for _ in range(self.batch_size):
                    rows.append(await pending.popleft())
                    pending.append(
                        asyncio.ensure_future(self._fetch(next(order))))
                yield rows[0][None, :] if len(rows) == 1 else np.stack(rows)
        finally:
            for t in pending:
                t.cancel()
            await asyncio.gather(*pending, return_exceptions=True)

    async def _next(self) -> int:
        import jax
        spans = self.env.spans
        with spans.span("feed.next"):
            batch = await anext(self.prefetcher)
        with spans.span("consume"):
            jax.block_until_ready(batch)
            self.digests.append(fold.device_fold_rows(batch))
        return batch.nbytes

    async def units(self):
        while True:
            yield await self._next()

    # ----------------------------------------------------- what is read

    def counters(self) -> dict:
        w = self.env.worker
        stages = {k: h.sum for k, h in
                  self.profiler.metrics.histograms.items()}
        return {"client": dict(self.client.counters),
                "worker": dict(w.metrics.counters),
                "stages": stages,
                "fetched_bytes": self.fetched_bytes,
                "delivered": len(self.digests)}

    def setup_notes(self) -> dict:
        return {"write_s": self.write_s,
                "written_bytes": self.ds.total_bytes}

    # --------------------------------------------------------- the close

    async def release(self) -> None:
        """Stop the pipeline and drop what it holds on the device; the
        digests (two words a sample) stay for the comparison."""
        if self.prefetcher is not None:
            await self.prefetcher.aclose()
            q = self.prefetcher._queue
            while not q.empty():
                q.get_nowait()
        if self.client is not None:
            await self.client.close()

    def compare(self) -> dict:
        """Every sample delivered since the pipeline started, against the
        seeded reference: the fold on the device of the k-th sample
        delivered beside the fold of what the k-th file of the seeded
        order hands on, made again from the seed. Every sample differs
        from every other, so one that is altered, left out, delivered
        twice or out of its turn shows here. Exact: limit 0."""
        import jax
        got = np.concatenate([np.asarray(d) for d in
                              jax.device_get(self.digests)]) \
            if self.digests else np.zeros((0, 2), np.uint32)
        order = list(itertools.islice(self._order(), len(got)))
        need = sorted(set(order))
        with ThreadPoolExecutor(THREADS) as pool:
            ref = dict(zip(need, pool.map(
                lambda i: fold.host_fold(self.ds.sample(i)), need)))
        wrong = sum(not np.array_equal(g, ref[i])
                    for g, i in zip(got, order))
        return {"samples_compared": len(got), "failed": self.undecodable,
                "compared": {"samples_mismatched": (wrong, 0),
                             "samples_undecodable": (self.undecodable, 0)}}
