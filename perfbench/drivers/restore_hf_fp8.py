"""Driver of the traffic kind "restore_hf_fp8": `restore_hf.py`'s closed
loop of whole loads of one rank's share (loaded from beside this file:
its set-up, clients, spans, counters and release), of a checkpoint in
DeepSeek-V3's published FP8 format, through the program's own entry
point with the expansion asked for:
`load_safetensors_to_device(client, root, device, select, dequant=bf16)`.
Set-up also writes `config.json` (with `quantization_config`) beside the
index. One unit of work is one load with every tensor of the share on
the chip, fp8 weights expanded; it counts the share's bytes as the
files hold them (fp8 weights at one byte, their scales too), not the
bf16 they become.

What is compared: every tensor handed back by every load, folded on the
chip, against the fold of the plain reference — an fp8 weight expanded
by the generator's `expand` (float32 numpy from the seeded codes and
scales, rounded once to bf16), any other tensor its seeded bits. Exact,
limit 0: `restore_hf.py`'s four numbers (a bf16 weight with another
fold, shape or dtype is `tensors_mismatched`) and `scales_returned`, a
scale tensor handed back to the caller.

A program whose loader takes no `dequant` cannot run the cell: this
file refuses to load, before any data is written."""

from __future__ import annotations

import inspect
import os
from concurrent.futures import ThreadPoolExecutor

import ml_dtypes
import numpy as np

from curvine_tpu.tpu.broadcast import load_safetensors_to_device
from perfbench import fold, harness

if "dequant" not in inspect.signature(
        load_safetensors_to_device).parameters:
    raise ImportError("load_safetensors_to_device takes no dequant: this "
                      "program cannot expand an fp8 checkpoint")

def fold_pieces(pieces) -> np.ndarray:
    """`fold.host_fold` of arrays laid end to end, one piece held at a
    time: each position weighted where it lies in the whole."""
    s1 = s2 = at = 0
    for piece in pieces:
        flat = np.ascontiguousarray(piece).reshape(-1)
        flat = flat.view(np.dtype(fold._UINT[flat.dtype.itemsize]))
        for off in range(0, flat.size, fold._CHUNK):
            bits = flat[off:off + fold._CHUNK].astype(np.uint32)
            w1, w2 = fold._weights(bits.size, at)
            s1 += int(np.sum(bits * w1, dtype=np.uint32))
            s2 += int(np.sum(bits * w2, dtype=np.uint32))
            at += bits.size
    return np.array([s1 & 0xFFFFFFFF, s2 & 0xFFFFFFFF], dtype=np.uint32)


restore_hf = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "restore_hf.py"))
restore = restore_hf.restore


class Driver(restore_hf.Driver):
    def __init__(self, env):
        super().__init__(env)
        self.scales_returned = 0

    async def prepare(self) -> None:
        await super().prepare()
        client = self.env.new_client()
        try:
            await client.write_all(f"{self.root}/{self.gen.CONFIG}",
                                   self.ds.config_json())
        finally:
            await client.close()

    async def _restore(self) -> int:
        import jax
        import jax.numpy as jnp
        spans = self.env.spans
        t0 = spans.clock()
        await self.release()
        client = self._client()
        try:
            with spans.span("restore"):
                params = await load_safetensors_to_device(
                    client, self.root, self.devices[0],
                    select=self.ds.keeps, dequant=jnp.bfloat16)
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
        read its placement, shape and dtype off the array: bf16 for an
        expanded fp8 weight and a bf16 tensor, float32 for a router
        bias."""
        ds = self.ds
        want = {self.devices[0]}
        digests = {}
        for name, a in params.items():
            if name.endswith(self.gen.SCALE):
                self.scales_returned += 1
                continue
            if name not in self.share:
                self.foreign += 1
                continue
            if a.devices() != want:
                self.misplaced += 1
            i = ds.index_of[name]
            dtype = np.float32 if ds.dtypes[i] == "F32" \
                else ml_dtypes.bfloat16
            if a.shape != tuple(ds.specs[i][1]) or a.dtype != dtype:
                digests[name] = None             # wrong whatever its bits
                continue
            digests[name] = fold.device_fold(a)
        self.restores.append(digests)

    def compare(self) -> dict:
        """Every tensor of the share of every load since set-up against
        the fold of its plain reference, folded piece by piece: an
        expanded weight is never whole in host memory, which holds the
        cluster's tier and exports at the same time."""
        ds = self.ds
        with ThreadPoolExecutor(restore.THREADS) as pool:
            ref = dict(zip(ds.share, pool.map(
                lambda n: fold_pieces(ds.reference(n)), ds.share)))
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
                             "tensors_foreign": (self.foreign, 0),
                             "scales_returned": (self.scales_returned, 0)}}
