"""Driver of the traffic kind "restore_sharded": the closed loop of
drivers/restore.py (loaded from beside this file: its clients, spans,
counters and set-up are that driver's) with the placement
"mesh_sharded" — a restore under another layout. The mesh is made over
every chip of the cell with the configuration's axis, the `spec_tree` is
built from the configuration's `layout` (a PartitionSpec a tensor), and
one unit of work is one call of
`distribute_checkpoint(client, root, mesh, spec_tree)` with every shard
ready on its chip.

What is compared is a shard on its own chip: every addressable shard of
every parameter is folded where it lies (no collective in the window)
and kept with the chip it lay on; after the window each is held to the
same fold of the slice the layout gives that chip, cut from the tensor
made again from the seed. Exact: limit 0. `tensors_misplaced` counts a
leaf whose sharding is not the NamedSharding the layout names and a
chip whose shard is of another index than the layout gives it;
`share_bytes_off` is, summed over the restores, how far the bytes all
chips hold together lie from what the layout says they hold (each
divided tensor once, each replicated tensor once a chip)."""

from __future__ import annotations

import math
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from perfbench import fold, harness
from perfbench.cluster import write_files

restore = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "restore.py"))


class Driver(restore.Driver):
    def __init__(self, env):
        super().__init__(env)
        if self.placement != "mesh_sharded":
            raise ValueError(f"restore_sharded driver places "
                             f"'mesh_sharded', not {self.placement!r}")
        self.spec_tree = None
        self.shardings: list = []        # tensor i → the layout's sharding
        self.where: list = []            # tensor i → {chip: its index}
        self.share_bytes = 0             # what all chips hold, by the layout
        self.share_bytes_off = 0

    # ------------------------------------------------------------ set-up

    async def prepare(self) -> None:
        from jax.sharding import NamedSharding, PartitionSpec
        from curvine_tpu.tpu.mesh import make_mesh
        ds = self.ds
        self.mesh = make_mesh(devices=self.devices,
                              axis_names=tuple(ds.layout["mesh_axes"]))
        chips = len(self.devices)
        self.spec_tree = {}
        for i, (name, shape) in enumerate(ds.specs):
            axes = ds.layout_of(i)
            self.spec_tree[name] = PartitionSpec(*axes)
            sharding = NamedSharding(self.mesh, self.spec_tree[name])
            self.shardings.append(sharding)
            self.where.append(sharding.devices_indices_map(tuple(shape)))
            ways = math.prod(self.mesh.shape[a] for a in axes
                             if a is not None)
            self.share_bytes += 2 * ds.sizes[i] * chips // ways
        writer_client = self.env.new_client()
        try:
            await writer_client.meta.mkdir(self.root)
            self.write_s = await write_files(
                writer_client, len(ds), lambda i: ds.tensor(i).tobytes(),
                lambda i: f"{self.root}/{ds.file_name(i)}")
            await writer_client.write_all(f"{self.root}/manifest.json",
                                          ds.manifest())
        finally:
            await writer_client.close()

    # ---------------------------------------------------------- the path

    async def _restore(self) -> int:
        import jax
        from curvine_tpu.tpu.broadcast import distribute_checkpoint
        spans = self.env.spans
        t0 = spans.clock()
        await self.release()
        client = self._client()
        try:
            with spans.span("restore"):
                params = await distribute_checkpoint(
                    client, self.root, self.mesh, self.spec_tree)
                jax.block_until_ready(params)
            spans.add("restore.whole", t0, spans.clock())
            with spans.span("restore.fold"):
                self._fold(params)
        finally:
            for k, v in client.counters.items():
                self.client_totals[k] = self.client_totals.get(k, 0) + v
            await client.close()
        self.params = params
        self.fetched_bytes += self.ds.total_bytes
        return self.ds.total_bytes

    def _fold(self, params) -> None:
        """Dispatch the fold of every shard on the chip it lies on, and
        read each leaf's placement off the array."""
        from jax.sharding import NamedSharding
        digests, held = [], 0
        for i, (name, shape) in enumerate(self.ds.specs):
            a = params.get(name) if isinstance(params, dict) else None
            if a is None:
                continue
            want = self.shardings[i]
            if not (isinstance(a.sharding, NamedSharding)
                    and a.sharding.is_equivalent_to(want, len(shape))):
                self.misplaced += 1
            for shard in a.addressable_shards:
                self.misplaced += \
                    self.where[i].get(shard.device) != shard.index
                held += shard.data.nbytes
                digests.append((i, shard.device,
                                fold.device_fold(shard.data)))
        self.share_bytes_off += abs(held - self.share_bytes)
        self.restores.append(digests)

    # --------------------------------------------------------- the close

    def compare(self) -> dict:
        """Every shard of every restore since set-up, on the chip it lay
        on, against the fold of the slice the layout gives that chip,
        cut from the tensor made again from the seed."""
        ds = self.ds

        def slices_of(i: int) -> dict:
            """chip → fold of its slice of tensor i, each slice once."""
            tensor, folded, out = ds.tensor(i), {}, {}
            for device, index in self.where[i].items():
                key = tuple((s.start, s.stop) for s in index)
                if key not in folded:
                    folded[key] = fold.host_fold(tensor[index])
                out[device] = folded[key]
            return out

        with ThreadPoolExecutor(restore.THREADS) as pool:
            ref = list(pool.map(slices_of, range(len(ds))))
        wrong = missing = compared = 0
        for digests in self.restores:
            missing += len(ds) - len({i for i, _, _ in digests})
            for i, device, digest in digests:
                compared += 1
                wrong += not np.array_equal(
                    np.asarray(digest).reshape(-1), ref[i][device])
        return {"tensors_compared": compared, "failed": 0,
                "compared": {"tensors_mismatched": (wrong, 0),
                             "tensors_missing": (missing, 0),
                             "tensors_misplaced": (self.misplaced, 0),
                             "share_bytes_off": (self.share_bytes_off, 0)}}
