"""A feed of files that span blocks (the unet3d shape, at small blocks):
`open` → `mmap_view(0, len)` → a slice of the view → `reader.close()` →
`np.stack` of seven → `AsyncDevicePrefetcher`, in one long-lived client.
What lands on the device is what was written, whatever the file's number
of blocks; a slice outlives its reader and its blocks' exports; nothing
is copied; and the process's descriptors and mappings do not grow with
the number of opens."""

import asyncio
import collections
import gc
import os

import numpy as np
import pytest

from curvine_tpu.common.conf import ClusterConf
from curvine_tpu.testing import MiniCluster
from curvine_tpu.worker import shm as wshm

BLOCK = 128 * 1024
HANDED_ON = 40_000          # bytes of each file that go on to the device
BATCH = 7
READERS = 4

pytestmark = pytest.mark.skipif(
    not wshm.shm_supported(),
    reason="memfd_create/SCM_RIGHTS not available on this platform")


def _fds() -> int:
    return len(os.listdir("/proc/self/fd"))


def _maps(name: str = "") -> int:
    """Mappings of this process; with `name`, those of sealed exports."""
    with open("/proc/self/maps") as f:
        return sum(name in line for line in f)


def _cluster(tmp_path):
    conf = ClusterConf()
    conf.data_dir = str(tmp_path)
    return MiniCluster(workers=1, conf=conf, base_dir=str(tmp_path),
                       block_size=BLOCK)


def _length(blocks: int, i: int) -> int:
    """A length that takes `blocks` blocks, another for every file."""
    return (blocks - 1) * BLOCK + BLOCK // 2 + 1 + 977 * i


async def _write(client, blocks_of: list[int]) -> list[bytes]:
    files = [os.urandom(_length(b, i)) for i, b in enumerate(blocks_of)]
    for i, data in enumerate(files):
        await client.write_all(f"/feed/s{i}.npz", data)
    return files


async def _fetch(client, i: int) -> np.ndarray:
    """One sample as the feed's driver takes it: the slice is all that
    is kept, the reader is closed."""
    reader = await client.open(f"/feed/s{i}.npz")
    view = await reader.mmap_view(0, reader.len)
    assert view is not None and len(view) == reader.len
    sample = view[:HANDED_ON]
    await reader.close()
    return sample


async def _batches(client, order: list[int]):
    """The driver's source: READERS fetches in flight, BATCH samples
    stacked."""
    todo = iter(order)
    pending = collections.deque(
        asyncio.ensure_future(_fetch(client, i))
        for i in [next(todo) for _ in range(READERS)])
    for _ in range(len(order) // BATCH):
        rows = []
        for _ in range(BATCH):
            rows.append(await pending.popleft())
            nxt = next(todo, None)
            if nxt is not None:
                pending.append(asyncio.ensure_future(_fetch(client, nxt)))
        yield np.stack(rows)


def _grew(client, before: dict, key: str) -> float:
    return client.counters.get(key, 0) - before.get(key, 0)


SHAPES = {"1": [1] * 7, "2": [2] * 7, "3": [3] * 7, "4": [4] * 7,
          "5": [5] * 7, "mixed": [3, 1, 5, 2, 4, 2, 3]}


@pytest.mark.parametrize("shape", list(SHAPES))
async def test_batches_of_seven_reach_the_device_as_written(tmp_path,
                                                            shape):
    import jax
    from curvine_tpu.tpu.ingest import AsyncDevicePrefetcher
    blocks_of = SHAPES[shape]
    async with _cluster(tmp_path) as mc:
        c = mc.client()
        files = await _write(c, blocks_of)
        before = dict(c.counters)
        order = [3, 0, 6, 2, 5, 1, 4, 1, 4, 0, 2, 6, 3, 5, 5, 6, 0, 1,
                 2, 3, 4]
        feed = AsyncDevicePrefetcher(_batches(c, order), mesh=None,
                                     depth=2, device=jax.devices()[0])
        got = [np.asarray(jax.block_until_ready(b)) async for b in feed]
        await feed.aclose()
        assert len(got) == 3
        for b, batch in enumerate(got):
            assert batch.shape == (BATCH, HANDED_ON)
            for row, i in zip(batch, order[b * BATCH:(b + 1) * BATCH]):
                assert row.tobytes() == files[i][:HANDED_ON], (b, i)
        multi = sum(blocks_of[i] > 1 for i in order)
        assert _grew(c, before, "read.files") == len(order)
        assert _grew(c, before, "read.span_views") == multi
        assert _grew(c, before, "read.span_view_blocks") == sum(
            blocks_of[i] for i in order if blocks_of[i] > 1)
        assert _grew(c, before, "read.span_view_bytes") == sum(
            len(files[i]) for i in order if blocks_of[i] > 1)
        assert _grew(c, before, "read.zero_copy_bytes") == sum(
            len(files[i]) for i in order)
        assert _grew(c, before, "read.phase.copy.s") == 0
        assert _grew(c, before, "read.phase.copy.n") == 0
        assert mc.workers[0].metrics.counters.get("bytes.read", 0) == 0
        await c.close()


@pytest.mark.parametrize("blocks", [1, 2, 3, 4, 5])
async def test_slice_outlives_close_and_eviction(tmp_path, blocks):
    """The 2 MiB the driver keeps of a 147 MB file is read after the
    reader has closed (the prefetcher's transfer comes later) and may be
    read after the worker has dropped the blocks' exports."""
    async with _cluster(tmp_path) as mc:
        c = mc.client()
        (data,) = await _write(c, [blocks])
        reader = await c.open("/feed/s0.npz")
        view = await reader.mmap_view(0, reader.len)
        head, tail = view[:HANDED_ON], view[len(data) - 999:]
        bids = [lb.block.id for lb in reader.blocks.block_locs]
        assert len(bids) == blocks
        del view
        await reader.close()
        for bid in bids:
            mc.workers[0].shm.invalidate(bid)
        gc.collect()
        assert head.tobytes() == data[:HANDED_ON]
        assert tail.tobytes() == data[-999:]
        # and the next open is served again, from a new export
        again = await _fetch(c, 0)
        assert again.tobytes() == data[:HANDED_ON]
        await c.close()


@pytest.mark.parametrize("blocks", [1, 2, 3, 4, 5])
async def test_a_long_lived_client_holds_no_more_after_200_opens(tmp_path,
                                                                 blocks):
    """Epoch after epoch in one client: after 220 opens the process
    holds the descriptors and the mapped exports it held after the first
    20. Its other mappings may grow by what a thread pool on its way to
    its full size takes (a stack, a guard page and a heap arena a
    thread: tens, once), not by the one or more an open that a leak
    would cost (hundreds)."""
    async with _cluster(tmp_path) as mc:
        c = mc.client()
        files = await _write(c, [blocks] * 5)

        async def epoch_of(n: int) -> None:
            for base in range(0, n, READERS):
                got = await asyncio.gather(*(
                    _fetch(c, (base + j) % len(files))
                    for j in range(READERS)))
                for j, sample in enumerate(got):
                    assert sample[-1] == files[
                        (base + j) % len(files)][HANDED_ON - 1]
                del got, sample

        async def settled() -> tuple:
            await asyncio.sleep(0.05)   # the loop lets go of the last
            gc.collect()                # gather's results
            return _fds(), _maps(), _maps("memfd:cv-")

        await epoch_of(20)
        fds, maps, exports = await settled()
        await epoch_of(200)
        now = await settled()
        assert now[0] <= fds + 4, (fds, now)
        assert now[2] == exports == 0
        assert now[1] <= maps + 64, (maps, now)
        assert c.counters.get("read.phase.copy.s", 0) == 0
        await c.close()
