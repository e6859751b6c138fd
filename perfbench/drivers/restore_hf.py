"""Driver of the traffic kind "restore_hf": a closed loop of whole loads
of one expert-parallel rank's share of a Hugging Face safetensors
checkpoint through the program's own entry point,
`load_safetensors_to_device(client, root, device, select)`: the index,
every shard's header, then the share as byte ranges of the shard files,
placed on the cell's chip. One unit of work is one load with every
tensor of the share ready on the chip. The clients, spans, counters and
the loop are drivers/restore.py's (loaded from beside this file): a new
CurvineClient a load, the previous share deleted from the chip first.
Set-up writes the index and the shards as the generator lays them out,
each shard streamed through one writer.

What is compared: every tensor of every load, folded on the chip,
against the same fold of the tensor made again from the seed — the
bytes the generator put at the range its shard's header names (the
plain reader `parse` of the generator reads them back from the files'
own bytes in `perfbench/tests`). Exact, limit 0: `tensors_mismatched`
(another fold, shape or dtype), `tensors_missing` (a tensor of the
share not handed back), `tensors_misplaced` (not on the cell's chip)
and `tensors_foreign` (a tensor of another rank handed back)."""

from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from curvine_tpu.tpu.broadcast import load_safetensors_to_device
from perfbench import fold, harness

restore = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "restore.py"))


class Driver(restore.Driver):
    def __init__(self, env):
        super().__init__(env)
        if self.placement != "device":
            raise ValueError(f"restore_hf driver places on one device, "
                             f"not {self.placement!r}")
        self.share = set(self.ds.share)
        self.foreign = 0

    # ------------------------------------------------------------ set-up

    async def prepare(self) -> None:
        import asyncio
        ds = self.ds
        t0 = time.perf_counter()
        client = self.env.new_client()
        try:
            await client.meta.mkdir(self.root)
            with ThreadPoolExecutor(len(ds.shards)) as pool:
                await asyncio.gather(*(
                    self._write_shard(client, pool, k)
                    for k in range(len(ds.shards))))
            await client.write_all(f"{self.root}/{self.gen.INDEX}",
                                   ds.index())
        finally:
            await client.close()
        self.write_s = time.perf_counter() - t0

    async def _write_shard(self, client, pool, k: int) -> None:
        """Shard k through one writer, a tensor's bytes made on a thread
        while the one before goes out."""
        import asyncio
        loop = asyncio.get_running_loop()
        chunks = self.ds.shard_chunks(k)
        writer = await client.create(f"{self.root}/{self.ds.shards[k]}",
                                     overwrite=True)
        async with writer:
            nxt = loop.run_in_executor(pool, next, chunks, None)
            while (data := await nxt) is not None:
                nxt = loop.run_in_executor(pool, next, chunks, None)
                await writer.write(data)

    # ---------------------------------------------------------- the path

    async def _restore(self) -> int:
        import jax
        spans = self.env.spans
        t0 = spans.clock()
        await self.release()
        client = self._client()
        try:
            with spans.span("restore"):
                params = await load_safetensors_to_device(
                    client, self.root, self.devices[0], select=self.ds.keeps)
                jax.block_until_ready(params)
            spans.add("restore.whole", t0, spans.clock())
            with spans.span("restore.fold"):
                self._fold(params)
        finally:
            for k, v in client.counters.items():
                self.client_totals[k] = self.client_totals.get(k, 0) + v
            await client.close()
        self.params = params
        self.fetched_bytes += self.ds.share_bytes
        return self.ds.share_bytes

    def _fold(self, params) -> None:
        """Dispatch the fold of every tensor handed back where it lies;
        read its placement, shape and dtype off the array."""
        import ml_dtypes
        want = {self.devices[0]}
        digests = {}
        for name, a in params.items():
            if name not in self.share:
                self.foreign += 1
                continue
            if a.devices() != want:
                self.misplaced += 1
            shape = tuple(self.ds.specs[self.ds.index_of[name]][1])
            if a.shape != shape or a.dtype != ml_dtypes.bfloat16:
                digests[name] = None             # wrong whatever its bits
                continue
            digests[name] = fold.device_fold(a)
        self.restores.append(digests)

    # --------------------------------------------------------- the close

    def setup_notes(self) -> dict:
        return {"write_s": self.write_s,
                "written_bytes": self.ds.total_bytes}

    def compare(self) -> dict:
        """Every tensor of the share of every load since set-up against
        the fold of the tensor made again from the seed."""
        ds = self.ds
        with ThreadPoolExecutor(restore.THREADS) as pool:
            ref = dict(zip(ds.share, pool.map(
                lambda n: fold.host_fold(ds.tensor(ds.index_of[n])),
                ds.share)))
        wrong = missing = compared = 0
        for digests in self.restores:
            for name, want in ref.items():
                if name not in digests:
                    missing += 1
                    continue
                compared += 1
                d = digests[name]
                wrong += d is None or not np.array_equal(
                    np.asarray(d).reshape(-1), want)
        return {"tensors_compared": compared, "failed": 0,
                "compared": {"tensors_mismatched": (wrong, 0),
                             "tensors_missing": (missing, 0),
                             "tensors_misplaced": (self.misplaced, 0),
                             "tensors_foreign": (self.foreign, 0)}}
