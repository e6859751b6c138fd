"""Driver of the traffic kind "restore": a closed loop of whole
checkpoint restores through the program's own entry points —
`distribute_checkpoint_to_device` onto one chip (placement "device") or
`distribute_checkpoint(client, path, mesh, spec_tree=None)` onto a mesh
of every chip of the cell (placement "mesh_replicated"). One unit of work
is one restore with every parameter ready on every chip.

Each restore gets a new CurvineClient: a restarted trainer has no lease
cache and no mapped exports, while the worker's cache stays warm (the
traffic file states it as `client_per_request`; a loop that keeps one
client comes with the cell that needs it). The parameters of the previous restore are
deleted from the device before the next one starts. Set-up writes the
tensor files in the layout `load_checkpoint` reads, several at a time:
the save path is not what these cells measure."""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from perfbench import fold
from perfbench.cluster import write_files

THREADS = 8          # for the reference's folds


class Driver:
    def __init__(self, env):
        self.env = env
        cfg, traffic = env.cell.config, env.cell.traffic
        self.gen = env.cell.module("generators", cfg["generator"])
        self.ds = self.gen.DataSet(env.seed, cfg)
        self.root = cfg["data_root"]
        self.block = int(cfg["cluster"]["block_size"])
        self.placement = traffic["placement"]
        if not traffic.get("client_per_request", True):
            raise ValueError("restore driver always gives a restore a new "
                             "client")
        if not traffic.get("delete_previous_first", True):
            raise ValueError("restore driver always frees the previous "
                             "parameters first: two copies need not fit")
        self.devices = list(env.devices)
        self.mesh = None
        self.params = None
        self.restores: list[dict] = []    # name → digest array, per restore
        self.misplaced = 0
        self.client_totals: dict[str, float] = {}
        self.fetched_bytes = 0
        self.write_s = 0.0

    # ------------------------------------------------------------ set-up

    async def prepare(self) -> None:
        env, ds = self.env, self.ds
        if self.placement == "mesh_replicated":
            from curvine_tpu.tpu.mesh import make_mesh
            self.mesh = make_mesh(devices=self.devices,
                                  axis_names=("data",))
        elif self.placement != "device":
            raise ValueError(f"unknown placement {self.placement!r}")
        writer_client = env.new_client()
        try:
            await writer_client.meta.mkdir(self.root)
            self.write_s = await write_files(
                writer_client, len(ds), lambda i: ds.tensor(i).tobytes(),
                lambda i: f"{self.root}/{ds.file_name(i)}")
            await writer_client.write_all(f"{self.root}/manifest.json",
                                          ds.manifest())
        finally:
            await writer_client.close()

    def _client(self):
        c = self.env.new_client()
        spans = self.env.spans
        spans.wrap(c.meta, "call", "master.rpc")
        spans.wrap(c.meta, "_fast_call", "master.rpc")
        if spans.on:
            self._span_tensors(c)
        return c

    def _span_tensors(self, client) -> None:
        """A span from each tensor file's open to its reader's close (the
        program closes it once the tensor's transfer is dispatched)."""
        inner_open = client.open
        spans = self.env.spans

        async def spanned_open(path):
            t0 = spans.clock()
            reader = await inner_open(path)
            if not path.endswith(".bin"):
                return reader
            inner_close = reader.close

            async def spanned_close():
                try:
                    return await inner_close()
                finally:
                    spans.add("restore.tensor", t0, spans.clock(),
                              bytes=reader.len,
                              multiblock=reader.len > self.block)

            reader.close = spanned_close
            return reader

        client.open = spanned_open

    # ---------------------------------------------------------- the path

    async def _restore(self) -> int:
        import jax
        from curvine_tpu.tpu.broadcast import (
            distribute_checkpoint, distribute_checkpoint_to_device,
        )
        spans = self.env.spans
        t0 = spans.clock()
        if self.params is not None:
            for a in jax.tree.leaves(self.params):
                a.delete()
            self.params = None
        client = self._client()
        try:
            with spans.span("restore"):
                if self.mesh is not None:
                    params = await distribute_checkpoint(
                        client, self.root, self.mesh, spec_tree=None)
                else:
                    params = await distribute_checkpoint_to_device(
                        client, self.root, self.devices[0])
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
        """Dispatch the fold of every parameter where it lies; on a mesh
        each chip folds its own copy. Placement is read off the arrays."""
        want = set(self.devices)
        digests = {}
        for name, _ in self.ds.specs:
            a = params.get(name) if isinstance(params, dict) else None
            if a is None:
                continue
            if set(a.devices()) != want or not a.is_fully_replicated:
                self.misplaced += 1
            digests[name] = fold.device_fold(a)
        self.restores.append(digests)

    async def units(self):
        while True:
            yield await self._restore()

    # ----------------------------------------------------- what is read

    def counters(self) -> dict:
        return {"client": dict(self.client_totals),
                "worker": dict(self.env.worker.metrics.counters),
                "stages": {},
                "fetched_bytes": self.fetched_bytes,
                "delivered": len(self.restores)}

    def setup_notes(self) -> dict:
        return {"write_s": self.write_s,
                "written_bytes": self.ds.total_bytes}

    # --------------------------------------------------------- the close

    async def release(self) -> None:
        import jax
        if self.params is not None:
            for a in jax.tree.leaves(self.params):
                a.delete()
            self.params = None

    def compare(self) -> dict:
        """Every parameter of every restore since set-up, on every chip,
        against the fold of the tensor made again from the seed. Exact:
        limit 0."""
        idx = range(len(self.ds))
        with ThreadPoolExecutor(THREADS) as pool:
            ref = dict(zip((n for n, _ in self.ds.specs), pool.map(
                lambda i: fold.host_fold(self.ds.tensor(i)), idx)))
        wrong = missing = compared = 0
        for digests in self.restores:
            for name, want in ref.items():
                d = digests.get(name)
                if d is None:
                    missing += 1
                    continue
                for shard in d.addressable_shards:
                    compared += 1
                    wrong += not np.array_equal(
                        np.asarray(shard.data).reshape(-1), want)
        return {"tensors_compared": compared, "failed": 0,
                "compared": {"tensors_mismatched": (wrong, 0),
                             "tensors_missing": (missing, 0),
                             "tensors_misplaced": (self.misplaced, 0)}}
