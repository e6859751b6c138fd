#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that curvine-tpu still starts on the chip.

Drives the main path once through the entry points a deployment uses:
bytes written through the client, cached by a worker (MEM tier on
/dev/shm), read back up the short-circuit ladder, landed in HBM, pinned in
tier-0, verified and consumed on the device. One process holds the chip:
`cv master` runs as a child that never imports JAX; the worker (tier-0 on)
lives in this process on a loop thread of its own (EmbeddedWorker), beside
the client and the JAX consumer.

Every stage makes its data from --seed and compares what comes out with a
plain host reference (numpy / hashlib on the same bytes) — digests, not
timings. Stages report bytes and seconds only so that a broken timer shows:
an on-device rate above the published peak of the device_kind is a failure.

Exit code 0 and a last stdout line {"ok": true, "device": {...}} only when
every stage passed on a TPU. No TPU, or this file without the repository
around it: non-zero exit and no result."""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import dataclasses
import hashlib
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
MB = 1 << 20
GB = 1 << 30
TIME_LIMIT_S = 1150          # the driver's limit is 1200 s, compile included
NATIVE_LIBS = ("libcurvine_native.so", "libcurvine_kv.so",
               "libcurvine_meta.so", "libcurvine_sdk.so")
MOSAIC = "tpu_custom_call"   # how a compiled Pallas kernel shows in HLO


class SmokeError(Exception):
    """A stage's result disagreed with its reference."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the smoke runs at. The defaults are the real size; tiny() is
    what tests/test_chip_smoke.py drives on the CPU mesh."""
    block_bytes: int = 64 * MB          # client.block_size default
    blocks: int = 64                    # 4 GiB data set
    hbm_capacity: int = 2 * GB          # tier-0, half the data set
    hot_blocks: int = 8                 # heat-driven autopin set
    # the repo's own 1B flagship consumer
    model: tuple = (("vocab", 32_000), ("d_model", 2560), ("n_heads", 20),
                    ("n_layers", 12), ("d_ff", 10240), ("max_seq", 1024),
                    ("dtype", "bfloat16"), ("use_flash_attention", True),
                    ("ce_chunk", 2048))
    batch: int = 16
    seq: int = 1024
    steps: int = 4
    vec_rows: int = 1 << 20
    vec_dim: int = 256
    vec_centers: int = 2048
    nlist: int = 1024
    pq_m: int = 16
    rerank: int = 1024                  # covers a whole cluster of rows
    queries: int = 1024
    ann_batch: int = 256
    mesh_layers: int = 2                # depth of the four-chip checkpoint

    @staticmethod
    def tiny() -> "Sizes":
        return Sizes(
            block_bytes=MB, blocks=24, hbm_capacity=16 * MB, hot_blocks=2,
            model=(("vocab", 512), ("d_model", 64), ("n_heads", 4),
                   ("n_layers", 2), ("d_ff", 128), ("max_seq", 128),
                   ("dtype", "bfloat16"), ("use_flash_attention", True),
                   ("ce_chunk", 64)),
            batch=4, seq=128, steps=3, vec_rows=8192, vec_dim=32,
            vec_centers=128, nlist=32, pq_m=4, rerank=256, queries=64,
            ann_batch=32, mesh_layers=1)


@dataclasses.dataclass
class Ctx:
    seed: int
    sizes: Sizes
    client: object
    worker: object
    devices: list
    peaks: dict | None       # None off-TPU (tests): no rate is judged there
    watch: object            # perfbench.compile_watch.CompileWatch
    rates: list = dataclasses.field(default_factory=list)
    carry: dict = dataclasses.field(default_factory=dict)

    @property
    def device(self):
        return self.devices[0]

    @property
    def on_tpu(self) -> bool:
        return self.device.platform == "tpu"

    def rng(self, *tag: int):
        import numpy as np
        return np.random.default_rng([self.seed, *tag])

    def rate(self, what: str, amount: float, seconds: float,
             peak: str = "hbm_bytes_per_s") -> None:
        """Record an implied on-device rate; above the published peak it
        is a broken timer or a kernel not reading what it claims."""
        rec = {"what": what, "amount": amount,
               "seconds": round(seconds, 6), "per_s": amount / seconds}
        if self.peaks is not None:
            rec["peak"] = self.peaks[peak]
            rec["share"] = round(rec["per_s"] / rec["peak"], 4)
            check(rec["per_s"] <= rec["peak"],
                  f"{what}: implied {rec['per_s']:.3e}/s is above the "
                  f"published {peak} of {rec['peak']:.3e}")
        self.rates.append(rec)


def block_data(ctx: Ctx, i: int):
    """Block i of the data set — regenerated from the seed wherever a
    reference is needed, so no stage trusts bytes that went through the
    cache."""
    import numpy as np
    return ctx.rng(1, i).integers(0, 256, ctx.sizes.block_bytes,
                                  dtype=np.uint8)


def memory_stats(device) -> dict:
    st = device.memory_stats() or {}
    return {k: st[k] for k in ("bytes_in_use", "peak_bytes_in_use",
                               "bytes_limit") if k in st}


# ------------------------------------------------------------------ ingest

async def stage_ingest(ctx: Ctx) -> dict:
    """Write the data set through the client, read it back up the ladder
    into HBM, checksum every block on the device."""
    import jax
    import numpy as np
    from curvine_tpu.tpu import pallas_ops

    sz, c = ctx.sizes, ctx.client
    total = sz.blocks * sz.block_bytes

    def make(i: int):
        blk = block_data(ctx, i)
        return blk, pallas_ops.block_checksum_host(blk)

    refs = []
    t0 = time.perf_counter()
    ahead = 8
    with ThreadPoolExecutor(ahead) as pool:
        async with await c.create("/smoke/data", overwrite=True) as w:
            # bounded look-ahead: generation overlaps the write without
            # holding the whole data set on the host
            pending = [pool.submit(make, i)
                       for i in range(min(ahead, sz.blocks))]
            for i in range(sz.blocks):
                blk, ref = await asyncio.wrap_future(pending.pop(0))
                if i + ahead < sz.blocks:
                    pending.append(pool.submit(make, i + ahead))
                refs.append(ref)
                await w.write(memoryview(blk))
    write_s = time.perf_counter() - t0

    # the library's own native client reads a block back: the fourth
    # native library has to work, not merely load
    from curvine_tpu.sdk import native_sdk
    await c.write_all("/smoke/probe", block_data(ctx, 0)[:MB].tobytes())
    host, port = c.conf.client.master_addrs[0].rsplit(":", 1)

    def native_get() -> bytes:
        nc = native_sdk.NativeCurvineClient(host, int(port))
        try:
            return nc.get("/smoke/probe")
        finally:
            nc.close()

    got = await asyncio.to_thread(native_get)
    check(hashlib.sha256(got).digest()
          == hashlib.sha256(block_data(ctx, 0)[:MB].tobytes()).digest(),
          "native SDK read differs from what was written")

    before = dict(c.counters)
    r = await c.open("/smoke/data")
    check(r.len == total, f"data set is {r.len} bytes, wrote {total}")
    h2d_s = sum_s = 0.0
    timed = 0
    interpret = None
    for i in range(sz.blocks):
        off = i * sz.block_bytes
        view = await r.mmap_view(off, sz.block_bytes)
        if view is None:                # not short-circuit readable
            view = await r.pread_view(off, sz.block_bytes)
        compiles = ctx.watch.compiles
        t0 = time.perf_counter()
        arr = jax.block_until_ready(jax.device_put(view, ctx.device))
        t1 = time.perf_counter()
        interpret = pallas_ops.interpret_for(arr)
        got = pallas_ops.block_checksum(arr)      # int(): waits for it
        t2 = time.perf_counter()
        check(got == refs[i],
              f"block {i}: device checksum {got:#x} != host {refs[i]:#x}")
        if ctx.watch.compiles == compiles:   # block 0 pays the compile
            timed += 1
            h2d_s += t1 - t0
            sum_s += t2 - t1
            ctx.rate(f"ingest.checksum[{i}]", sz.block_bytes, t2 - t1)
        arr.delete()
    await r.close()
    check(interpret == (not ctx.on_tpu),
          f"block_checksum ran with interpret={interpret} on "
          f"{ctx.device.platform}")
    words = jax.ShapeDtypeStruct((sz.block_bytes // 4,), np.int32)
    lowered = pallas_ops._checksum_words.lower(words, interpret=interpret)
    check((MOSAIC in lowered.as_text()) == ctx.on_tpu,
          "checksum kernel: Mosaic call present != running on a TPU")

    d = {k: c.counters.get(k, 0) - before.get(k, 0) for k in
         ("read.zero_copy_bytes", "sc.bytes.read", "read.shm_hits",
          "read.shm_fallbacks", "bytes.read")}
    if d["read.zero_copy_bytes"] == total:
        rung = "shm"
    elif d["sc.bytes.read"] == total:
        rung = "shm+fd" if d["read.shm_hits"] else "fd"
    else:
        rung = "socket"
    return {"bytes": total, "write_s": round(write_s, 3),
            "h2d_s": round(h2d_s, 3), "h2d_bytes": timed * sz.block_bytes,
            "checksum_s": round(sum_s, 3), "rung": rung, "counters": d,
            "interpret": interpret}


# ------------------------------------------------------------------ tier-0

async def _pin_rpc(ctx: Ctx, block_id: int, **kw) -> dict:
    from curvine_tpu.rpc import RpcCode
    from curvine_tpu.rpc.frame import pack, unpack
    conn = await ctx.client.pool.get(ctx.worker.addr)
    rep = await conn.call(RpcCode.HBM_PIN,
                          data=pack({"block_id": block_id, **kw}))
    return rep.header or unpack(rep.data)


async def stage_tier0(ctx: Ctx) -> dict:
    """Tier-0 smaller than the data set: pin through HBM_PIN and through
    the heat-driven autopin, so admission, eviction and arr.delete() all
    happen on the chip; consume pinned blocks in a jit; release."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sz, c, w = ctx.sizes, ctx.client, ctx.worker
    hbm = w.hbm
    fb = await c.meta.get_block_locations("/smoke/data")
    ids = [lb.block.id for lb in fb.block_locs]
    index_of = {bid: i for i, bid in enumerate(ids)}
    slots = sum(t.capacity // sz.block_bytes for t in hbm.tiers.values())
    check(0 < slots < sz.blocks - sz.hot_blocks,
          f"tier-0 holds {slots} blocks of a {sz.blocks}-block data set: "
          f"nothing would be evicted")

    # --- HBM_PIN RPC, past capacity
    n_rpc = min(slots + max(2, slots // 4), sz.blocks - sz.hot_blocks)
    t0 = time.perf_counter()
    for bid in ids[:n_rpc]:
        rep = await _pin_rpc(ctx, bid)
        check(rep["len"] == sz.block_bytes, f"HBM_PIN {bid}: {rep}")
    pin_s = time.perf_counter() - t0
    st = hbm.stats()
    check(st["used"] <= st["capacity"] and st["blocks"] <= slots,
          f"tier-0 over capacity: {st}")
    check(st["spills"] >= n_rpc - slots, f"no eviction happened: {st}")
    spread = {d for bid in ids[:n_rpc] for d in hbm.holders(bid)}
    check(spread == {d.id for d in ctx.devices},
          f"pinned blocks sit on devices {sorted(spread)} of "
          f"{[d.id for d in ctx.devices]}")

    # --- heat-driven autopin: keep reading the hot set like a consumer
    # would; the worker's promote cycle has to notice and pin it
    hot = list(range(sz.blocks - sz.hot_blocks, sz.blocks))
    t0 = time.perf_counter()
    deadline = t0 + 120.0
    # (the worker counts a cycle's pins when the cycle ends, on its own
    # thread — so wait for the count too, not only for residency)
    while not (all(ids[i] in hbm for i in hot)
               and w.metrics.counters.get("blocks.hbm_pinned", 0)
               >= len(hot)):
        check(time.perf_counter() < deadline,
              f"autopin did not pin the hot set in 120 s: "
              f"{w.metrics.counters}, errors {w.executor.errors}")
        r = await c.open("/smoke/data")
        for _ in range(w.conf.worker.promote_min_reads):
            for i in hot:
                view = await r.pread_view(i * sz.block_bytes,
                                          sz.block_bytes)
                check(len(view) == sz.block_bytes, "short hot read")
        await r.close()                 # flushes the read counts (heat)
        await asyncio.sleep(w.conf.worker.promote_interval_ms / 1000)
    autopin_s = time.perf_counter() - t0

    # --- consume what is resident: hbm.get → jit → numpy says the same
    @jax.jit
    def consume(block, salt):
        return jnp.sum(block ^ salt, dtype=jnp.uint32)

    resident = [bid for bid in ids if bid in hbm]
    check(set(ids[i] for i in hot) <= set(resident), "hot set not resident")
    consumed_s = 0.0
    for n, bid in enumerate(resident):
        arr = hbm.get(bid)
        check(arr is not None and arr.nbytes == sz.block_bytes,
              f"hbm.get({bid})")
        salt = np.uint8(1 + n % 250)
        compiles = ctx.watch.compiles
        t0 = time.perf_counter()
        got = int(consume(arr, salt))
        dt = time.perf_counter() - t0
        want = int(np.bitwise_xor(block_data(ctx, index_of[bid]), salt)
                   .sum(dtype=np.uint32))
        check(got == want, f"consume of block {bid}: {got} != {want}")
        if ctx.watch.compiles == compiles:   # not a call that compiled
            consumed_s += dt
            ctx.rate(f"tier0.consume[{bid}]", sz.block_bytes, dt)
    st = hbm.stats()
    mem = memory_stats(ctx.device)
    check(not w.executor.errors, f"worker.executor.errors: "
                                 f"{w.executor.errors}")
    check(w.metrics.counters.get("blocks.corrupt", 0) == 0,
          "blocks.corrupt != 0")

    # --- release the way a deployment does: delete the file, the worker
    # drops the blocks and their device copies
    await c.meta.delete("/smoke/data")
    deadline = time.perf_counter() + 60.0
    while hbm.used:
        check(time.perf_counter() < deadline,
              f"tier-0 still holds {hbm.used} bytes 60 s after delete")
        await asyncio.sleep(0.2)
    return {"bytes": (n_rpc + len(hot)) * sz.block_bytes,
            "slots": slots, "rpc_pins": n_rpc, "rpc_pin_s": round(pin_s, 3),
            "autopinned": len(hot), "autopin_s": round(autopin_s, 3),
            "consumed": len(resident), "consume_s": round(consumed_s, 4),
            "spills": st["spills"], "hits": st["hits"],
            "tier_used": st["used"], "tier_capacity": st["capacity"],
            "devices": sorted(spread), "memory": mem}


# -------------------------------------------------------------- checkpoint

def flagship_host_params(ctx: Ctx, n_layers: int | None = None):
    """(ModelConfig, host param tree) of the flagship: shapes from the
    repo's own init_params, values from the seed. Uniform with the
    variance of init_params' normal — a cache sees names, shapes and
    dtypes; the train step only needs sane scales."""
    import jax
    import numpy as np
    from curvine_tpu.tpu.model import ModelConfig, init_params

    kw = dict(ctx.sizes.model)
    if n_layers is not None:
        kw["n_layers"] = n_layers
    cfg = ModelConfig(**kw)
    shapes = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg))
    leaves, treedef = jax.tree.flatten_with_path(shapes)

    def fill(item):
        n, (path, s) = item
        name = str(getattr(path[-1], "key", path[-1]))
        if len(s.shape) == 1:
            return np.ones(s.shape, s.dtype)
        fan_in = s.shape[1] if name in ("embed", "pos") else s.shape[0]
        a = float(np.sqrt(3.0 / fan_in))
        u = ctx.rng(2, n).random(s.shape, dtype=np.float32)
        return ((2.0 * u - 1.0) * a).astype(s.dtype)

    with ThreadPoolExecutor(8) as pool:
        flat = list(pool.map(fill, enumerate(leaves)))
    return cfg, jax.tree.unflatten(treedef, flat)


def _bits(a):
    import numpy as np
    a = np.asarray(a)
    return a.view(np.dtype(f"u{a.dtype.itemsize}"))


async def stage_checkpoint(ctx: Ctx) -> dict:
    """save_checkpoint → distribute_checkpoint_to_device with the
    flagship's manifest; every tensor bit-exact on the device."""
    import jax
    import numpy as np
    from curvine_tpu.tpu.broadcast import (
        distribute_checkpoint_to_device, save_checkpoint,
    )

    t0 = time.perf_counter()
    cfg, host = await asyncio.to_thread(flagship_host_params, ctx)
    gen_s = time.perf_counter() - t0
    flat_host = jax.tree.leaves(host)
    nbytes = sum(a.nbytes for a in flat_host)
    t0 = time.perf_counter()
    await save_checkpoint(ctx.client, "/smoke/ckpt", host)
    save_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    params = await distribute_checkpoint_to_device(
        ctx.client, "/smoke/ckpt", ctx.device)
    jax.block_until_ready(params)
    load_s = time.perf_counter() - t0
    check(jax.tree.structure(params) == jax.tree.structure(host),
          "checkpoint came back with another tree structure")
    t0 = time.perf_counter()
    for n, (dev, ref) in enumerate(zip(jax.tree.leaves(params), flat_host)):
        check(dev.devices() == {ctx.device}, f"tensor {n} on {dev.devices()}")
        check(dev.dtype == ref.dtype and dev.shape == ref.shape,
              f"tensor {n}: {dev.dtype}{dev.shape} != "
              f"{ref.dtype}{ref.shape}")
        check(np.array_equal(_bits(dev), _bits(ref)),
              f"tensor {n} {ref.shape} is not bit-exact on the device")
    verify_s = time.perf_counter() - t0
    ctx.carry["cfg"], ctx.carry["params"] = cfg, params
    return {"bytes": nbytes, "tensors": len(flat_host),
            "largest": max(a.nbytes for a in flat_host),
            "gen_s": round(gen_s, 3), "save_s": round(save_s, 3),
            "load_s": round(load_s, 3), "verify_s": round(verify_s, 3),
            "memory": memory_stats(ctx.device)}


# -------------------------------------------------------------------- feed

def _dense_attention_host(q, k, v):
    """Plain causal softmax attention in float64 — the flash kernel's
    reference."""
    import numpy as np
    q, k, v = (np.asarray(a, dtype=np.float64) for a in (q, k, v))
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    s = np.where(np.tril(np.ones(s.shape[-2:], dtype=bool)), s, -np.inf)
    p = np.exp(s - s.max(axis=-1, keepdims=True))
    return np.einsum("bhqk,bhkd->bhqd", p / p.sum(-1, keepdims=True), v)


def check_flash_kernel(ctx: Ctx) -> float:
    """The public flash kernel, through the model's own wrapper, against
    the host reference on a small input. TPU only: the kernel has no CPU
    form. Returns the largest absolute error."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from curvine_tpu.tpu import model

    shape = (1, 2, 256, 128)
    q, k, v = (jax.device_put(
        ctx.rng(4, n).standard_normal(shape, dtype=np.float32)
        .astype(jnp.bfloat16), ctx.device) for n in range(3))
    fn = jax.jit(model._flash_attention)
    check(MOSAIC in fn.lower(q, k, v).as_text(),
          "flash attention lowered without a Mosaic call")
    got = np.asarray(fn(q, k, v), dtype=np.float64)
    err = float(np.abs(got - _dense_attention_host(q, k, v)).max())
    # outputs are averages of N(0,1) values; bf16 carries 8 bits
    check(err <= 8 * 2.0 ** -8, f"flash attention off by {err}")
    return err


async def stage_feed(ctx: Ctx) -> dict:
    """write_token_shards → TpuTrainFeed → donated train steps of the
    flagship, starting from the checkpoint the previous stage restored."""
    import jax
    import numpy as np
    from curvine_tpu.tpu.loader import TpuTrainFeed, write_token_shards
    from curvine_tpu.tpu.model import make_optimizer, make_train_step

    sz = ctx.sizes
    cfg, params = ctx.carry.pop("cfg"), ctx.carry.pop("params")
    check(ctx.worker.hbm.used == 0, "tier-0 still holds device memory")
    flash_err = check_flash_kernel(ctx) if ctx.on_tpu else None

    tokens = ctx.rng(3).integers(0, cfg.vocab, sz.batch * sz.seq * sz.steps,
                                 dtype=np.int32)
    await write_token_shards(ctx.client, "/smoke/tok", tokens,
                             shard_tokens=sz.batch * sz.seq)
    feed = TpuTrainFeed(ctx.client, "/smoke/tok", batch=sz.batch,
                        seq_len=sz.seq)
    batches = feed.__aiter__()
    opt = make_optimizer()
    with jax.default_device(ctx.device):
        opt_state = opt.init(params)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    step = jax.jit(make_train_step(cfg, opt, None), donate_argnums=(0, 1))

    tok = await anext(batches)
    check(tok.shape == (sz.batch, sz.seq) and tok.devices() == {ctx.device},
          f"feed batch {tok.shape} on {tok.devices()}")
    t0 = time.perf_counter()
    compiled = await asyncio.to_thread(
        lambda: step.lower(params, opt_state, tok).compile())
    compile_s = time.perf_counter() - t0
    mosaic = compiled.as_text().count(MOSAIC)
    if ctx.on_tpu:
        check(mosaic >= cfg.n_layers,
              f"{mosaic} Mosaic calls in the compiled step: flash "
              f"attention did not engage in all {cfg.n_layers} layers")
    ma = compiled.memory_analysis()     # None where the backend has none
    losses, step_s = [], []
    seen = [np.asarray(tok)]
    mark = None
    try:
        while tok is not None:
            t0 = time.perf_counter()
            # returns at dispatch: the next batch's fetch and transfer
            # overlap the step, the float() below waits for it
            params, opt_state, loss = compiled(params, opt_state, tok)
            tok = await anext(batches, None)
            losses.append(float(loss))
            step_s.append(time.perf_counter() - t0)
            if mark is None:
                mark = ctx.watch.compiles
            if tok is not None:
                seen.append(np.asarray(tok))
    finally:
        await feed.prefetcher.aclose()
    check(ctx.watch.compiles == mark,
          f"{ctx.watch.compiles - mark} compilations after step 1")
    check(len(losses) == sz.steps, f"{len(losses)} steps of {sz.steps}")
    check(np.array_equal(np.concatenate(seen).reshape(-1), tokens),
          "the feed delivered other tokens than were written")
    # random weights, random tokens: the loss starts near ln(vocab)
    ln_v = float(np.log(cfg.vocab))
    check(all(np.isfinite(x) and 0.5 * ln_v < x < 2.0 * ln_v
              for x in losses), f"losses {losses}, ln(vocab) = {ln_v:.2f}")
    for n, dt in enumerate(step_s[1:], 1):
        ctx.rate(f"feed.step[{n}]", 6.0 * n_params * sz.batch * sz.seq, dt,
                 peak="bf16_flops")
    mem = memory_stats(ctx.device)
    for a in jax.tree.leaves((params, opt_state)):
        a.delete()
    return {"bytes": tokens.nbytes, "params": n_params,
            "steps": len(losses), "losses": [round(x, 4) for x in losses],
            "compile_s": round(compile_s, 3),
            "step_s": [round(x, 4) for x in step_s],
            "mosaic_calls": mosaic, "flash_max_err": flash_err,
            "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
            "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
            "input_wait": feed.profiler.summary()["fractions"]
            .get("input_wait"), "memory": mem}


# ------------------------------------------------------------------ vector

def _topk_ok(ids, q, table_n, k: int, tol: float) -> bool:
    """A returned top-k is right when its ids are k distinct live rows
    and none scores (in float32 on the host) more than `tol` under the
    true k-th best — ties inside the device's precision may swap."""
    import numpy as np
    scores = table_n @ (q / np.linalg.norm(q))
    kth = np.partition(scores, -k)[-k]
    return (len(set(ids.tolist())) == k and ids.min() >= 0
            and ids.max() < len(scores)
            and bool(np.all(scores[ids] >= kth - tol)))


def check_pq_kernel(ctx: Ctx, m: int) -> None:
    """pq_lut_scan on the device against a numpy gather, both code
    layouts."""
    import functools
    import jax
    import numpy as np
    from curvine_tpu.tpu.pallas_ops import pq_lut_scan

    rng = ctx.rng(6)
    lut = rng.standard_normal((m, 256), dtype=np.float32)
    codes = rng.integers(0, 256, (1000, m), dtype=np.int32)
    want = lut[np.arange(m)[None, :], codes].sum(axis=1)
    offs = (np.arange(m, dtype=np.int32) * 256)[None, :]
    for pre, cd in ((False, codes), (True, codes + offs)):
        d_lut, d_cd = (jax.device_put(a, ctx.device) for a in (lut, cd))
        fn = jax.jit(functools.partial(pq_lut_scan, pre_offset=pre))
        check((MOSAIC in fn.lower(d_lut, d_cd).as_text()) == ctx.on_tpu,
              "pq_lut_scan: Mosaic call present != running on a TPU")
        got = np.asarray(fn(d_lut, d_cd))
        check(np.allclose(got, want, rtol=1e-5, atol=1e-4),
              f"pq_lut_scan(pre_offset={pre}) off by "
              f"{np.abs(got - want).max()}")


async def stage_vector(ctx: Ctx) -> dict:
    """VectorTable at deployment width: append, exact scan f32 and bf16,
    IVF-PQ index, AnnServer over the Pallas ADC kernel."""
    import numpy as np
    from curvine_tpu.vector import AnnServer, VectorTable
    from curvine_tpu.vector import index as vindex

    sz, dev = ctx.sizes, ctx.device
    k = 10
    # clustered rows: recall on pure noise measures the data, not the index
    t0 = time.perf_counter()
    centers = ctx.rng(5, 0).standard_normal(
        (sz.vec_centers, sz.vec_dim), dtype=np.float32)
    assign = ctx.rng(5, 1).integers(0, sz.vec_centers, sz.vec_rows)
    step = -(-sz.vec_rows // 8)

    def part(n: int):
        rows = slice(n * step, min((n + 1) * step, sz.vec_rows))
        noise = ctx.rng(5, 2, n).random(
            (rows.stop - rows.start, sz.vec_dim), dtype=np.float32)
        return centers[assign[rows]] + (noise - 0.5)

    with ThreadPoolExecutor(8) as pool:
        vecs = np.concatenate(list(pool.map(part, range(8))))
    gen_s = time.perf_counter() - t0
    table = await VectorTable.create(ctx.client, "/smoke/vec", sz.vec_dim)
    # row groups of half a block: each is one block, so reads of it are
    # short-circuit views instead of a copy through read_all
    group = max(1, sz.block_bytes // (sz.vec_dim * 4) // 2)
    t0 = time.perf_counter()
    for off in range(0, sz.vec_rows, group):
        await table.append(vecs[off:off + group])
    append_s = time.perf_counter() - t0

    qrows = ctx.rng(5, 3).integers(0, sz.vec_rows, sz.queries)
    queries = vecs[qrows]
    table_n = vecs / np.linalg.norm(vecs, axis=1, keepdims=True)
    tol = 4 * 2.0 ** -8         # one bf16 pass on the MXU, f32 accumulate
    scan = {}
    exact = None
    for dtype in ("f32", "bf16"):
        await table.knn(queries[:sz.ann_batch], k=k, device=dev,
                        use_index=False, dtype=dtype)     # pin + compile
        t0 = time.perf_counter()
        ids, _ = await table.knn(queries[:sz.ann_batch], k=k, device=dev,
                                 use_index=False, dtype=dtype)
        dt = time.perf_counter() - t0
        itemsize = 4 if dtype == "f32" else 2
        ctx.rate(f"vector.scan.{dtype}",
                 sz.vec_rows * sz.vec_dim * itemsize, dt)
        check(np.array_equal(ids[:, 0], qrows[:sz.ann_batch]),
              f"{dtype} scan: a row is not its own nearest neighbour")
        bad = [n for n in range(8)
               if not _topk_ok(ids[n], queries[n], table_n, k, tol)]
        check(not bad, f"{dtype} scan: top-{k} of queries {bad} disagrees "
                       f"with the host scan beyond {tol}")
        scan[dtype] = round(dt, 4)
        if dtype == "f32":
            exact = ids

    t0 = time.perf_counter()
    await table.create_index(nlist=sz.nlist, metric="cosine", iters=4,
                             device=dev, pq_m=sz.pq_m, cap_pct=90.0)
    build_s = time.perf_counter() - t0
    check_pq_kernel(ctx, sz.pq_m)
    # pallas=True: the ADC scan is the Pallas kernel wherever this runs —
    # compiled on the chip, interpreted on the CPU test mesh
    srv = await AnnServer(table, k=k, metric="cosine", nprobe=8,
                          rerank=sz.rerank, device=dev,
                          max_batch=sz.ann_batch, warm_all=False,
                          pallas=True).start()
    try:
        t0 = time.perf_counter()
        ann, _ = await srv.query_many(queries, batch=sz.ann_batch, depth=4)
        bulk_s = time.perf_counter() - t0
        one = await asyncio.gather(*(srv.query(q) for q in queries[:8]))
    finally:
        await srv.stop()
    used = [key for key in vindex._PQ_SEARCH_FNS if key[4]]
    check(used and all(key[5] == (not ctx.on_tpu) for key in used),
          f"PQ search ran as {used}")
    n_ref = exact.shape[0]
    recall = sum(len(set(a.tolist()) & set(b.tolist()))
                 for a, b in zip(ann[:n_ref], exact)) / (n_ref * k)
    check(recall >= 0.9, f"recall@{k} {recall:.3f} < 0.9 against the "
                         f"exact scan")
    # another batch shape may swap ties in the last float bit
    check(all(i[0] == qrows[n] and len(set(i.tolist())
                                       & set(ann[n].tolist())) >= k - 1
              for n, (i, _) in enumerate(one)),
          "micro-batched query() and query_many() disagree")
    check(table.stale_fallbacks == 0, "the index went stale")
    mem = memory_stats(dev)
    table._dev_cache.clear()
    table._index = None
    await ctx.client.meta.delete("/smoke/vec", recursive=True)
    return {"bytes": vecs.nbytes, "rows": sz.vec_rows, "dim": sz.vec_dim,
            "gen_s": round(gen_s, 3), "append_s": round(append_s, 3),
            "scan_s": scan, "index_build_s": round(build_s, 3),
            "ann_queries": sz.queries, "ann_bulk_s": round(bulk_s, 3),
            "recall_at_10": round(recall, 4), "pq_search": used,
            "memory": mem}


# -------------------------------------------------------------- four chips

async def stage_mesh(ctx: Ctx) -> dict:
    """One process, every local chip: tier-0 replicas, sharded batches, a
    replicated checkpoint and the chip-to-chip block moves — each array
    where it was meant to be, each value what the host says."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from curvine_tpu.tpu import ici_transfer as ici
    from curvine_tpu.tpu import pallas_ops
    from curvine_tpu.tpu.broadcast import (
        distribute_checkpoint, save_checkpoint,
    )
    from curvine_tpu.tpu.ingest import DevicePrefetcher
    from curvine_tpu.tpu.mesh import make_mesh

    sz, c, devs = ctx.sizes, ctx.client, ctx.devices
    n = len(devs)
    all_ids = {d.id for d in devs}
    mesh = make_mesh(devices=devs, axis_names=("data",))

    # --- tier-0: one block replicated onto every chip through the RPC
    blk = block_data(ctx, 0)
    ref = pallas_ops.block_checksum_host(blk)
    await c.write_all("/smoke/mesh-block", blk.tobytes())
    fb = await c.meta.get_block_locations("/smoke/mesh-block")
    bid = fb.block_locs[0].block.id
    rep = await _pin_rpc(ctx, bid, replicas=n)
    check(set(rep["holders"]) == all_ids, f"replicas on {rep['holders']}")
    for d in devs:
        arr = ctx.worker.hbm.get(bid, device=d)
        check(arr.devices() == {d}, f"replica for {d} on {arr.devices()}")
        check(pallas_ops.block_checksum(arr) == ref,
              f"replica on {d} fails its on-device checksum")

    # --- put_sharded batches over the mesh
    rows = 4 * n
    host_batches = [ctx.rng(7, i).integers(0, 32_000, (rows, sz.seq),
                                           dtype=np.int32) for i in range(3)]
    total = jax.jit(lambda x: x.sum(axis=1),
                    out_shardings=NamedSharding(mesh, P()))
    for hb, db in zip(host_batches,
                      DevicePrefetcher(iter(host_batches), mesh, P("data"))):
        shards = db.addressable_shards
        check({s.device.id for s in shards} == all_ids
              and all(s.data.shape == (rows // n, sz.seq) for s in shards),
              f"batch sharded as {[(s.device, s.data.shape) for s in shards]}")
        check(np.array_equal(np.asarray(total(db)), hb.sum(axis=1)),
              "a sharded batch sums to something else on the mesh")

    # --- replicated checkpoint (flagship widths, depth cut)
    _, host = await asyncio.to_thread(flagship_host_params, ctx,
                                      sz.mesh_layers)
    await save_checkpoint(c, "/smoke/ckpt-mesh", host)
    t0 = time.perf_counter()
    params = await distribute_checkpoint(c, "/smoke/ckpt-mesh", mesh)
    ckpt_s = time.perf_counter() - t0
    flat_host = jax.tree.leaves(host)
    for t, (dev, want) in enumerate(zip(jax.tree.leaves(params), flat_host)):
        check({s.device.id for s in dev.addressable_shards} == all_ids
              and dev.sharding.is_fully_replicated,
              f"tensor {t} placed as {dev.sharding}")
        for s in dev.addressable_shards:
            check(np.array_equal(_bits(s.data), _bits(want)),
                  f"tensor {t} is not bit-exact on {s.device}")
    for a in jax.tree.leaves(params):
        a.delete()

    # --- chip-to-chip: replicate, ring shift, per-shard verify
    src = ctx.worker.hbm.get(bid, device=devs[0])
    t0 = time.perf_counter()
    copies = jax.block_until_ready(ici.replicate_to_devices(src, devs))
    repl_s = time.perf_counter() - t0
    for d, arr in zip(devs, copies):
        check(arr.devices() == {d}, f"copy for {d} on {arr.devices()}")
        check(pallas_ops.block_checksum(arr) == ref,
              f"copy on {d} fails its on-device checksum")
    usable = blk[:len(blk) // n * n]
    sc = ici.scatter_block(usable, mesh)
    check({s.device.id for s in sc.addressable_shards} == all_ids,
          "scatter_block left a chip out")
    sums = ici.verify_scattered(sc, mesh)
    check(np.array_equal(sums, usable.reshape(n, -1).astype(np.uint32)
                         .sum(axis=1, dtype=np.uint32)),
          "verify_scattered disagrees with the host sums")
    t0 = time.perf_counter()
    shifted = jax.block_until_ready(ici.ring_shift(sc, mesh, steps=1))
    shift_s = time.perf_counter() - t0
    check(np.array_equal(np.asarray(ici.gather_block(shifted, mesh)),
                         np.roll(usable.reshape(n, -1), 1, axis=0)
                         .reshape(-1)), "ring_shift moved the wrong shards")
    await c.meta.delete("/smoke/mesh-block")
    return {"devices": sorted(all_ids), "block_bytes": blk.nbytes,
            "replica_holders": sorted(rep["holders"]),
            "ckpt_bytes": sum(a.nbytes for a in flat_host),
            "ckpt_s": round(ckpt_s, 3), "replicate_s": round(repl_s, 4),
            "ring_shift_s": round(shift_s, 4),
            "memory": [memory_stats(d) for d in devs]}


STAGES = (("ingest", stage_ingest), ("tier0", stage_tier0),
          ("checkpoint", stage_checkpoint), ("feed", stage_feed),
          ("vector", stage_vector))


async def run_stages(ctx: Ctx, emit=print) -> dict:
    """Every stage in order, each fatal. The mesh stage joins when the
    process sees four chips or more."""
    stages = list(STAGES)
    if len(ctx.devices) >= 4:
        stages.append(("mesh", stage_mesh))
    out = {}
    for name, fn in stages:
        before = ctx.watch.snapshot()
        t0 = time.perf_counter()
        res = await fn(ctx)
        after = ctx.watch.snapshot()
        res["seconds"] = round(time.perf_counter() - t0, 3)
        res["xla"] = {k: round(after[k] - before[k], 3) for k in after}
        out[name] = res
        emit(f"[stage] {name} " + json.dumps(res))
    w = ctx.worker
    check(not w.executor.errors
          and w.metrics.counters.get("blocks.corrupt", 0) == 0,
          f"worker ended with executor.errors {w.executor.errors}, "
          f"blocks.corrupt {w.metrics.counters.get('blocks.corrupt', 0)}")
    return out


# ------------------------------------------------------------- bring-up

def build_native() -> dict:
    """All four native libraries, built from csrc/*.cc where the checkout
    has no csrc/build (the driver's has none), then loaded. On this path
    a library that does not build is an error, not a Python fallback."""
    from curvine_tpu.common import kvnative, native
    from curvine_tpu.master import fastmeta
    from curvine_tpu.sdk import native_sdk
    prebuilt = os.path.isdir(os.path.join(HERE, "csrc", "build"))
    t0 = time.perf_counter()
    for so in NATIVE_LIBS:
        check(native.build(so) is not None, f"{so} did not build")
    loaded = {"checksum": native.available(), "kv": kvnative.available(),
              "fastmeta": fastmeta.available(), "sdk": native_sdk.available()}
    check(all(loaded.values()), f"native libraries did not load: {loaded}")
    return {"prebuilt_dir": prebuilt,
            "build_s": round(time.perf_counter() - t0, 3)}


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def write_conf(workdir: str, shm_dir: str, sizes: Sizes) -> str:
    ports = [_free_port() for _ in range(4)]
    path = os.path.join(workdir, "cluster.toml")
    with open(path, "w") as f:
        f.write(f'''cluster_name = "chip-smoke"
data_dir = "{workdir}"
[master]
hostname = "127.0.0.1"
rpc_port = {ports[0]}
web_port = {ports[1]}
journal_dir = "{workdir}/journal"
meta_engine = "native"
[worker]
hostname = "127.0.0.1"
rpc_port = {ports[2]}
web_port = {ports[3]}
heartbeat_ms = 500
promote_interval_ms = 1000
hbm_capacity = {sizes.hbm_capacity}
[[worker.tiers]]
storage_type = "mem"
dir = "{shm_dir}/mem"
capacity = {4 * sizes.blocks * sizes.block_bytes}
[client]
master_addrs = ["127.0.0.1:{ports[0]}"]
block_size = {sizes.block_bytes}
''')
    return path


@contextlib.asynccontextmanager
async def cluster(sizes: Sizes, workdir: str, shm_dir: str):
    """`cv master` as a child through the CLI (it never imports JAX, and
    is pinned to the CPU platform in case that ever changes); the worker
    on its own loop thread and the client in this process. Yields
    (client, worker, engines)."""
    from curvine_tpu.client import CurvineClient
    from curvine_tpu.common.conf import ClusterConf
    from curvine_tpu.worker.embedded import EmbeddedWorker

    conf_path = write_conf(workdir, shm_dir, sizes)
    conf = ClusterConf.load(conf_path, env={})
    check(conf.client.block_size == sizes.block_bytes
          and conf.worker.hbm_capacity == sizes.hbm_capacity,
          "conf file did not load as written")
    log = open(os.path.join(workdir, "master.out"), "wb")
    master = subprocess.Popen(
        [sys.executable, "-m", "curvine_tpu.cli.main", "--conf", conf_path,
         "master"], cwd=HERE, stdout=log, stderr=subprocess.STDOUT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    embedded = EmbeddedWorker(conf)
    client = None
    try:
        deadline = time.monotonic() + 60.0
        while True:
            if master.poll() is not None or time.monotonic() > deadline:
                with open(log.name, errors="replace") as f:
                    raise SmokeError("cv master did not come up: "
                                     + f.read()[-2000:])
            with socket.socket() as s:
                if s.connect_ex(("127.0.0.1", conf.master.rpc_port)) == 0:
                    break
            await asyncio.sleep(0.1)
        worker = await asyncio.to_thread(embedded.start)
        client = CurvineClient(conf)
        while True:
            info = await client.meta.master_info()
            if info.live_workers:
                break
            check(time.monotonic() < deadline, "worker never registered")
            await asyncio.sleep(0.1)
        check(bool(info.fast_addr), "master serves no native fast-meta port")
        # meta_engine = "native" in the conf: a master that came up at
        # all opened its store with the C++ engine
        engines = {"checksum": "native", "sdk": "native",
                   "master.kv": "native", "master.fastmeta": "native"}
        yield client, worker, engines
    finally:
        if client is not None:
            await client.close()
        await asyncio.to_thread(embedded.stop)
        master.terminate()
        try:
            master.wait(10)
        except subprocess.TimeoutExpired:
            master.kill()
            master.wait()
        log.close()


def host_facts() -> dict:
    mem_kb = 0
    with open("/proc/meminfo") as f:
        for line in f:
            if line.startswith("MemTotal:"):
                mem_kb = int(line.split()[1])
    return {"cpus": os.cpu_count(), "ram_gib": round(mem_kb / MB, 1),
            "shm_gib": round(shutil.disk_usage("/dev/shm").total / GB, 1),
            "shm_free_gib": round(shutil.disk_usage("/dev/shm").free / GB,
                                  1)}


async def smoke(seed: int, sizes: Sizes, devices: list, peaks: dict | None,
                emit=print) -> dict:
    workdir = tempfile.mkdtemp(prefix="curvine-smoke-")
    shm_dir = tempfile.mkdtemp(prefix="curvine-smoke-", dir="/dev/shm")
    from perfbench.compile_watch import compile_watch
    try:
        native = build_native()
        emit("[native] " + json.dumps(native))
        async with cluster(sizes, workdir, shm_dir) as (client, worker,
                                                        engines):
            ctx = Ctx(seed, sizes, client, worker, devices, peaks,
                      compile_watch())
            stages = await run_stages(ctx, emit)
        rates = ctx.rates
        return {"stages": stages, "native": {**native, "engines": engines},
                "rates_checked": len(rates),
                "max_share": max((r.get("share", 0.0) for r in rates),
                                 default=0.0)}
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
        shutil.rmtree(shm_dir, ignore_errors=True)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not all(os.path.isdir(os.path.join(HERE, d))
               for d in ("curvine_tpu", "perfbench")):
        print("chip_smoke: the curvine_tpu and perfbench packages are "
              "not beside this file — nothing to drive", file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found platform {devices[0].platform!r}, "
              f"not a TPU — refusing to run", file=sys.stderr)
        return 2
    from curvine_tpu.tpu.compile_cache import enable_compile_cache
    from perfbench.compile_watch import compile_watch
    from perfbench.peaks import peaks_of

    def overrun(signum, frame):
        raise TimeoutError(f"chip_smoke passed its {TIME_LIMIT_S} s limit")

    signal.signal(signal.SIGALRM, overrun)
    signal.alarm(TIME_LIMIT_S)
    t0 = time.perf_counter()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    peaks = peaks_of(devices[0].device_kind)
    cache = enable_compile_cache()
    print("[host] " + json.dumps({
        **device, **host_facts(), "jax": jax.__version__,
        "compile_cache": cache, "cache_dir_from_env":
        bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "memory": memory_stats(devices[0])}), flush=True)
    try:
        result = asyncio.run(smoke(
            args.seed, Sizes(), devices, peaks,
            emit=lambda line: print(line, flush=True)))
    except Exception as e:  # noqa: BLE001 — the boundary: report, fail
        import traceback
        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 1
    finally:
        signal.alarm(0)
    result.update(seed=args.seed, seconds=round(time.perf_counter() - t0, 1),
                  xla=compile_watch().snapshot(), device=device)
    print("[summary] " + json.dumps(result), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
