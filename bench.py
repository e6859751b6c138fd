"""Benchmark: cached-read GiB/s/chip into HBM + p99 block-fetch latency.

Matches BASELINE.json's metric: warm the cache (DRAM tier), stream blocks
through the client read path (short-circuit local read, as a co-located
TPU-host worker would serve), and land each batch in device HBM via
jax.device_put. Prints ONE JSON line:
  {"metric": ..., "value": GiB/s, "unit": ..., "vs_baseline": ...}

Interpretability keys (round-3 verdict items):
  link_gibs        raw jax.device_put bandwidth of a plain host buffer —
                   proves whether the cache pipeline or the host→device
                   link is the ceiling ("pipeline >= link" measured, not
                   asserted).
  tmpfs_raw_gibs   raw page-cache write rate of this host (fresh-page
                   allocation is ~0.1 GiB/s on some virtualized dev
                   boxes) — the write path's hardware ceiling.
  mfu              cache-fed train-step MFU of the flagship transformer
                   (tpu/model.py), fed through TpuTrainFeed (cache → HBM
                   prefetch → step).

`python bench.py` needs a TPU and exits non-zero without one: a CPU run
never prints under these metric names. The host-only micro-benches below
(`_meta_smoke`, `_rpc_smoke`, ...) stay importable for
scripts/perf_smoke.sh.

vs_baseline: BASELINE.json carries no published number ("published": {});
we use 2.0 GiB/s/chip as the stand-in for the reference's single-stream
cached-read (fio seq, mem tier) until a measured baseline lands.
"""

from __future__ import annotations

import asyncio
import json
import os
import statistics
import sys
import time

BASELINE_GIBS = 2.0
MB = 1024 * 1024

def _pick_shm_dir() -> str:
    for d in ("/dev/shm", "/tmp"):
        if os.path.isdir(d) and os.access(d, os.W_OK):
            return d
    return "."


def _fs_type(path: str) -> str:
    """Filesystem type of the mount holding `path` (best-effort)."""
    try:
        best, fstype = "", "?"
        with open("/proc/mounts") as f:
            for line in f:
                parts = line.split()
                if len(parts) >= 3 and path.startswith(parts[1]) \
                        and len(parts[1]) > len(best):
                    best, fstype = parts[1], parts[2]
        return fstype
    except OSError:
        return "?"


def _direct_io_dir() -> str:
    """A writable dir on a REAL filesystem for the direct-IO microbench
    (tmpfs has no device to bypass the page cache for)."""
    cands = [os.environ.get("BENCH_DIRECT_DIR", ""), os.getcwd(),
             "/var/tmp", "/tmp"]
    for d in cands:
        if d and os.path.isdir(d) and os.access(d, os.W_OK) \
                and _fs_type(d) not in ("tmpfs", "ramfs"):
            return d
    return next(d for d in cands[1:] if d and os.access(d, os.W_OK))


def _direct_io_bench(size_mb: int = 256) -> dict:
    """Cold sequential read through the O_DIRECT ring engine vs the
    buffered pread path, on a real (non-tmpfs) filesystem when one is
    writable. The direct figure bypasses the page cache by construction;
    the buffered figure gets a best-effort drop_caches first and is
    marked `cold:false` when that isn't possible (page-cache numbers
    must never masquerade as device numbers)."""
    import shutil
    import tempfile
    from curvine_tpu.worker.io_engine import DirectIOEngine

    base = tempfile.mkdtemp(prefix="curvine-directio-",
                            dir=_direct_io_dir())
    out = {"direct_io_fs": _fs_type(base)}
    path = os.path.join(base, "cold.bin")
    chunk = 4 * MB
    try:
        buf = os.urandom(chunk)
        with open(path, "wb") as f:
            for _ in range(size_mb * MB // chunk):
                f.write(buf)
            f.flush()
            os.fsync(f.fileno())

        def drop_caches() -> bool:
            try:
                with open("/proc/sys/vm/drop_caches", "w") as f:
                    f.write("1")
                return True
            except OSError:
                return False

        engine = DirectIOEngine(queue_depth=32)
        try:
            dropped = drop_caches()
            seg = engine.segment_bytes
            total = size_mb * MB
            t0 = time.perf_counter()
            # windowed submission at full ring depth — the engine's
            # point is batched in-flight IO, not serialized preads
            window: list = []
            pos = got = 0
            while pos < total or window:
                while pos < total and len(window) < engine.queue_depth:
                    n = min(seg, total - pos)
                    buf = engine.pool.acquire(n)
                    window.append((buf, engine.submit(path, pos, n, buf)))
                    pos += n
                buf, fut = window.pop(0)
                got += fut.result()
                engine.pool.release(buf)
            out["direct_read_gibs"] = round(
                got / (1024 ** 3) / (time.perf_counter() - t0), 3)
            stats = engine.stats()
            out["direct_io_mode"] = stats["mode"]
            if stats["fallbacks"]:
                # the engine ran buffered: stamp WHY, so this artifact
                # can't be mistaken for a page-cache-bypassing result
                out["direct_io_fallback"] = "; ".join(
                    sorted(stats["fallbacks"]))
        finally:
            engine.shutdown()

        dropped = drop_caches()
        out["direct_buffered_cold"] = dropped
        t0 = time.perf_counter()
        n = 0
        with open(path, "rb", buffering=0) as f:
            while c := f.read(chunk):
                n += len(c)
        out["direct_buffered_gibs"] = round(
            n / (1024 ** 3) / (time.perf_counter() - t0), 3)
    except OSError as e:
        out["direct_io_error"] = str(e)
    finally:
        shutil.rmtree(base, ignore_errors=True)
    return out


async def _trace_overhead_bench(file_kb: int = 4096, read_kb: int = 64,
                                ops: int = 600, rounds: int = 3) -> dict:
    """Tracing-overhead gate: hot-path read QPS against a loopback
    MiniCluster with `obs.trace_sample_rate=0.01` (production default)
    must stay within 5% of tracing-off. Remote (RPC) preads so every op
    crosses the instrumented dispatch path; short-circuit would hide
    the cost being measured. Rounds alternate off/on and the BEST of
    each side is compared — noise shows up as slow outliers, and taking
    the max per side filters it without biasing either way.
    Returns {trace_read_qps_off, trace_read_qps_on, trace_overhead_pct}.
    """
    import copy
    import shutil
    import tempfile
    from curvine_tpu.client import CurvineClient
    from curvine_tpu.testing.cluster import MiniCluster

    base = tempfile.mkdtemp(prefix="curvine-traceov-")
    mc = MiniCluster(workers=1, base_dir=base)
    mc.conf.client.short_circuit = False
    mc.conf.obs.trace_sample_rate = 0.01
    out: dict = {}
    try:
        await mc.start()
        c_on = mc.client()
        conf_off = copy.deepcopy(mc.conf)
        conf_off.obs.enabled = False
        c_off = CurvineClient(conf_off)
        path = "/traceov/hot.bin"
        size = file_kb * 1024
        await c_on.write_all(path, os.urandom(size))
        n = read_kb * 1024

        async def qps(client) -> float:
            r = await client.open(path)
            try:
                # warm connections + block-location cache
                for i in range(8):
                    await r.pread((i * n) % (size - n), n)
                t0 = time.perf_counter()
                for i in range(ops):
                    await r.pread((i * n) % (size - n), n)
                return ops / (time.perf_counter() - t0)
            finally:
                await r.close()

        best_off = best_on = 0.0
        for _ in range(rounds):
            best_off = max(best_off, await qps(c_off))
            best_on = max(best_on, await qps(c_on))
        await c_off.close()
        out["trace_read_qps_off"] = round(best_off, 1)
        out["trace_read_qps_on"] = round(best_on, 1)
        out["trace_overhead_pct"] = round(
            max(0.0, (best_off - best_on) / best_off * 100), 2)
    finally:
        try:
            await mc.stop()
        finally:
            shutil.rmtree(base, ignore_errors=True)
    return out


async def _read_verify_overhead_bench(block_kb: int = 1024,
                                      blocks: int = 4, ops: int = 25,
                                      rounds: int = 4) -> dict:
    """End-to-end read-verification gate: whole-file reads over the RPC
    path (full-block reads are exactly where the client recomputes the
    commit-time checksum — partial preads skip it) with client
    verification ON must stay within read_verify_overhead_pct_max of
    OFF. Rounds alternate off/on and the best of each side is compared,
    the same noise filter as _trace_overhead_bench. Returns
    {verify_read_qps_off, verify_read_qps_on, verify_algo,
    read_verify_overhead_pct}."""
    import copy
    import shutil
    import tempfile
    from curvine_tpu.client import CurvineClient
    from curvine_tpu.common import checksum
    from curvine_tpu.testing.cluster import MiniCluster

    base = tempfile.mkdtemp(prefix="curvine-verifyov-")
    mc = MiniCluster(workers=1, base_dir=base,
                     block_size=block_kb * 1024)
    mc.conf.client.short_circuit = False
    out: dict = {"verify_algo": checksum.preferred_algo()}
    try:
        await mc.start()
        c_on = mc.client()
        conf_off = copy.deepcopy(mc.conf)
        conf_off.client.read_verify = False
        c_off = CurvineClient(conf_off)
        path = "/verifyov/data.bin"
        await c_on.write_all(path, os.urandom(block_kb * 1024 * blocks))

        async def qps(client) -> float:
            for _ in range(2):                 # warm connections
                r = await client.open(path)
                await r.read_all()
                await r.close()
            t0 = time.perf_counter()
            for _ in range(ops):
                r = await client.open(path)
                await r.read_all()
                await r.close()
            return ops / (time.perf_counter() - t0)

        best_off = best_on = 0.0
        for _ in range(rounds):
            best_off = max(best_off, await qps(c_off))
            best_on = max(best_on, await qps(c_on))
        await c_off.close()
        out["verify_read_qps_off"] = round(best_off, 1)
        out["verify_read_qps_on"] = round(best_on, 1)
        out["read_verify_overhead_pct"] = round(
            max(0.0, (best_off - best_on) / best_off * 100), 2)
    finally:
        try:
            await mc.stop()
        finally:
            shutil.rmtree(base, ignore_errors=True)
    return out


async def _qos_overhead_bench(file_kb: int = 4096, read_kb: int = 64,
                              ops: int = 600, rounds: int = 3) -> dict:
    """Admission-overhead gate: hot-path read QPS with the QoS admission
    plane ON (default conf: enabled, unlimited buckets, a tenant id on
    every request) must stay within qos_overhead_pct_max of admission
    OFF. Remote (RPC) preads so every op crosses the admitted dispatch
    path — the un-throttled admit is a handful of float compares and a
    dict lookup, and this gate keeps it that way. One cluster, the
    controllers' `enabled` flag toggled between rounds, best-of-each
    side compared (same noise filter as _trace_overhead_bench).
    Returns {qos_read_qps_off, qos_read_qps_on, qos_overhead_pct}."""
    import shutil
    import tempfile
    from curvine_tpu.common.qos import tenant_scope
    from curvine_tpu.testing.cluster import MiniCluster

    base = tempfile.mkdtemp(prefix="curvine-qosov-")
    mc = MiniCluster(workers=1, base_dir=base)
    mc.conf.client.short_circuit = False
    out: dict = {}
    try:
        await mc.start()
        c = mc.client()
        path = "/qosov/hot.bin"
        size = file_kb * 1024
        await c.write_all(path, os.urandom(size))
        n = read_kb * 1024
        ctrls = [mc.master.qos] + [w.qos for w in mc.workers]

        def set_enabled(v: bool) -> None:
            for q in ctrls:
                q.enabled = v

        async def qps() -> float:
            r = await c.open(path)
            try:
                for i in range(8):                # warm connections
                    await r.pread((i * n) % (size - n), n)
                t0 = time.perf_counter()
                for i in range(ops):
                    await r.pread((i * n) % (size - n), n)
                return ops / (time.perf_counter() - t0)
            finally:
                await r.close()

        best_off = best_on = 0.0
        with tenant_scope("bench"):               # real tenant accounting
            await qps()               # cold-start pass, never measured
            for _ in range(rounds):
                set_enabled(False)
                best_off = max(best_off, await qps())
                set_enabled(True)
                best_on = max(best_on, await qps())
        out["qos_read_qps_off"] = round(best_off, 1)
        out["qos_read_qps_on"] = round(best_on, 1)
        out["qos_overhead_pct"] = round(
            max(0.0, (best_off - best_on) / best_off * 100), 2)
    finally:
        try:
            await mc.stop()
        finally:
            shutil.rmtree(base, ignore_errors=True)
    return out


async def _write_replay_overhead_bench(block_kb: int = 1024,
                                       blocks: int = 4, ops: int = 10,
                                       rounds: int = 4) -> dict:
    """Write-pipeline replay-buffer gate (docs/resilience.md "Write
    pipeline"): fault-free whole-file writes over the RPC upload path
    with client.write_replay_buffer ON (the default) must stay within
    write_replay_overhead_pct_max of OFF. The buffer is one bytearray
    append per chunk, cleared at every block seal — this gate keeps it
    that cheap. Rounds alternate off/on and the best of each side is
    compared (same noise filter as _read_verify_overhead_bench).
    Returns {write_replay_gibs_off, write_replay_gibs_on,
    write_replay_overhead_pct}."""
    import copy
    import shutil
    import tempfile
    from curvine_tpu.client import CurvineClient
    from curvine_tpu.testing.cluster import MiniCluster

    base = tempfile.mkdtemp(prefix="curvine-replayov-")
    mc = MiniCluster(workers=1, base_dir=base,
                     block_size=block_kb * 1024)
    mc.conf.client.short_circuit = False
    out: dict = {}
    try:
        await mc.start()
        c_on = mc.client()
        conf_off = copy.deepcopy(mc.conf)
        conf_off.client.write_replay_buffer = False
        c_off = CurvineClient(conf_off)
        size = block_kb * 1024 * blocks
        data = os.urandom(size)

        async def gibs(client, path: str) -> float:
            await client.write_all(path, data)      # warm connections
            t0 = time.perf_counter()
            for _ in range(ops):
                await client.write_all(path, data)
            return ops * size / (time.perf_counter() - t0) / (1024 * MB)

        best_off = best_on = 0.0
        for _ in range(rounds):
            best_off = max(best_off, await gibs(c_off, "/replayov/off.bin"))
            best_on = max(best_on, await gibs(c_on, "/replayov/on.bin"))
        await c_off.close()
        out["write_replay_gibs_off"] = round(best_off, 3)
        out["write_replay_gibs_on"] = round(best_on, 3)
        out["write_replay_overhead_pct"] = round(
            max(0.0, (best_off - best_on) / best_off * 100), 2)
    finally:
        try:
            await mc.stop()
        finally:
            shutil.rmtree(base, ignore_errors=True)
    return out


async def _ec_smoke(cell_mb: int = 1, rounds: int = 3,
                    block_mb: int = 4, reads: int = 3) -> dict:
    """Erasure-coding gate (docs/erasure-coding.md): (a) raw RS(6,3)
    encode throughput through the preferred GF(256) path (native kernel
    when built) — the per-byte budget the background convert job spends
    striping cold blocks; (b) degraded-vs-intact read A/B on a live
    cluster: read_all of a one-stripe rs-2-1 file with every cell up,
    then with the first data cell's holder killed so every read decodes
    inline from the k survivors (the master is kept blind via a long
    lost-timeout, so nothing heals mid-measurement). Returns
    {ec_encode_gibs, ec_read_intact_gibs, ec_read_degraded_gibs,
    ec_degraded_read_overhead_pct}."""
    import shutil
    import tempfile
    from curvine_tpu.common import ec as eclib
    from curvine_tpu.common.types import JobState, SetAttrOpts
    from curvine_tpu.testing.cluster import MiniCluster

    prof = eclib.ECProfile.parse("rs-6-3")
    cells, _cs = eclib.split(os.urandom(prof.k * cell_mb * MB), prof.k)
    best = 0.0
    for _ in range(rounds):
        t0 = time.perf_counter()
        eclib.encode(prof, cells)
        best = max(best, prof.k * cell_mb / 1024
                   / (time.perf_counter() - t0))
    out: dict = {"ec_encode_gibs": round(best, 3)}

    base = tempfile.mkdtemp(prefix="curvine-ecsmoke-")
    mc = MiniCluster(workers=3, base_dir=base, block_size=block_mb * MB,
                     journal=False, lost_timeout_ms=600_000)
    try:
        await mc.start()
        c = mc.client()
        payload = os.urandom(block_mb * MB)
        await c.write_all("/ecsmoke/f.bin", payload)
        await c.meta.set_attr("/ecsmoke/f.bin", SetAttrOpts(ec="rs-2-1"))
        job_id = await c.meta.submit_job("ec_convert", "/ecsmoke/f.bin")

        async def converted():
            while True:
                job = await c.meta.job_status(job_id)
                if job.state == JobState.COMPLETED:
                    break
                if job.state in (JobState.FAILED, JobState.CANCELLED):
                    raise RuntimeError(f"ec_convert: {job.message}")
                await asyncio.sleep(0.05)
            while True:
                fb = await c.meta.get_block_locations("/ecsmoke/f.bin")
                if fb.block_locs and all(
                        lb.ec is not None and not lb.locs
                        for lb in fb.block_locs):
                    return fb
                await asyncio.sleep(0.05)
        fb = await asyncio.wait_for(converted(), 30)

        async def read_gibs() -> float:
            peak = 0.0
            for _ in range(reads):
                r = await c.open("/ecsmoke/f.bin")
                t0 = time.perf_counter()
                got = await r.read_all()
                dt = time.perf_counter() - t0
                await r.close()
                if got != payload:
                    raise RuntimeError("ec A/B read corrupt")
                peak = max(peak, len(payload) / dt / (1024 * MB))
            return peak

        intact = await read_gibs()
        victim_wid = \
            fb.block_locs[0].ec["cells"][0]["locs"][0]["worker_id"]
        victim = next(i for i, w in enumerate(mc.workers)
                      if w.worker_id == victim_wid)
        await mc.kill_worker(victim)
        degraded = await read_gibs()
        if not c.counters.get("read.ec_degraded", 0):
            raise RuntimeError("ec A/B never took the degraded path")
        out["ec_read_intact_gibs"] = round(intact, 3)
        out["ec_read_degraded_gibs"] = round(degraded, 3)
        out["ec_degraded_read_overhead_pct"] = round(
            max(0.0, (intact - degraded) / intact * 100), 2)
    finally:
        try:
            await mc.stop()
        finally:
            shutil.rmtree(base, ignore_errors=True)
    return out


def _tmpfs_raw_gibs(base: str) -> float:
    """Raw sequential write rate to the cache tier's backing dir (the
    hardware ceiling for the write path on this host)."""
    path = os.path.join(base, "rawprobe.bin")
    buf = b"\xab" * (4 * MB)
    best = 0.0
    for _ in range(2):
        t0 = time.perf_counter()
        with open(path, "wb") as f:
            for _ in range(32):              # 128 MiB
                f.write(buf)
        best = max(best, 128 / 1024 / (time.perf_counter() - t0))
        os.unlink(path)
    return best


async def _ann_smoke(n_rows: int = 100_000, dim: int = 128,
                     n_q: int = 1024) -> dict:
    """Small-scale IVF-PQ serving gate for scripts/perf_smoke.sh: the
    same clustered distribution and serving path as the full bench
    (AnnServer.query_many over a PQ index), sized to finish on CPU in
    well under a minute. Returns {vector_ann_qps, vector_ann_recall10}
    for the floor check in scripts/perf_floor.json."""
    import numpy as np
    from curvine_tpu.testing import MiniCluster
    from curvine_tpu.vector import AnnServer, VectorTable
    import jax

    dev = jax.devices()[0]
    rng = np.random.default_rng(5)
    centers = rng.normal(size=(256, dim)).astype(np.float32)
    assign = rng.integers(0, 256, n_rows)
    vecs = (centers[assign]
            + 0.25 * rng.normal(size=(n_rows, dim))).astype(np.float32)
    base = os.path.join(_pick_shm_dir(), f"curvine-annsmoke-{os.getpid()}")
    out: dict = {}
    try:
        async with MiniCluster(workers=1, base_dir=base,
                               tier_capacity=512 * MB,
                               block_size=64 * MB, journal=False,
                               lost_timeout_ms=600_000) as mc:
            c = mc.client()
            t = await VectorTable.create(c, "/smoke/vec", dim)
            await t.append(vecs)
            # nlist tracks the cluster count and rerank covers a whole
            # cluster — same tuning rule as the full bench (the ADC
            # shortlist must contain the query's cluster; within-cluster
            # ranking is the exact re-rank's job)
            await t.create_index(nlist=256, metric="cosine", iters=3,
                                 device=dev, pq_m=16, cap_pct=90.0)
            srv = await AnnServer(t, k=10, metric="cosine", nprobe=8,
                                  rerank=512, device=dev, max_batch=256,
                                  warm_all=False).start()
            queries = vecs[rng.integers(0, n_rows, n_q)]
            await srv.query_many(queries[:256])           # warm
            t0 = time.perf_counter()
            ann_i, _ = await srv.query_many(queries, batch=256, depth=4)
            out["vector_ann_qps"] = round(
                n_q / (time.perf_counter() - t0), 1)
            exact_i, _ = await t.knn(queries[:64], k=10, device=dev,
                                     use_index=False)
            hits = sum(len(set(map(int, a)) & set(map(int, b)))
                       for a, b in zip(ann_i[:64], np.asarray(exact_i)))
            out["vector_ann_recall10"] = round(hits / (64 * 10), 3)
            await srv.stop()
    finally:
        import shutil
        shutil.rmtree(base, ignore_errors=True)
    return out


async def _rpc_smoke(n: int = 3_000, depth: int = 64) -> dict:
    """Transport microbench for scripts/perf_smoke.sh: small-op pings
    against a bare loopback RpcServer with a trivial echo handler — no
    filesystem behind it, so the figure is pure wire/transport cost
    (frame encode, coalesced writer, bulk-recv decode, dispatch).
    Returns {rpc_rtt_us, rpc_pipelined_qps, loop_impl}: serialized
    round-trip latency, small-op throughput with `depth` concurrent
    callers (where send coalescing kicks in), and which event loop ran
    (rpc.uvloop) so numbers stay attributable."""
    from curvine_tpu.rpc import RpcServer
    from curvine_tpu.rpc.client import Connection
    from curvine_tpu.rpc.loops import loop_impl

    async def echo(msg, conn):
        return {"ok": True}

    srv = RpcServer("127.0.0.1", 0, "bench")
    srv.register(9_999, echo)
    await srv.start()
    conn = await Connection(f"127.0.0.1:{srv.port}").connect()
    out: dict = {}
    try:
        hdr = {"p": "/bench/ping"}
        for _ in range(200):                                  # warm
            await conn.call(9_999, dict(hdr))
        t0 = time.perf_counter()
        for _ in range(n):
            await conn.call(9_999, dict(hdr))
        out["rpc_rtt_us"] = round((time.perf_counter() - t0) / n * 1e6, 1)

        async def caller(k: int):
            for _ in range(k):
                await conn.call(9_999, dict(hdr))

        per = max(1, n // depth)
        t0 = time.perf_counter()
        await asyncio.gather(*(caller(per) for _ in range(depth)))
        out["rpc_pipelined_qps"] = round(
            per * depth / (time.perf_counter() - t0), 1)
        out["loop_impl"] = loop_impl()
    finally:
        await conn.close()
        await srv.stop()
    return out


async def _meta_smoke(n_create: int = 8_000, bs: int = 500) -> dict:
    """Metadata write-plane gate for scripts/perf_smoke.sh: batched file
    creates through the RPC + group-commit + KV-batch path on a journal-
    less master (same shape as the full bench's meta_create_qps phase,
    sized for CI). Returns {meta_create_qps} for perf_floor.json."""
    from curvine_tpu.rpc import RpcCode
    from curvine_tpu.testing import MiniCluster
    base = os.path.join(_pick_shm_dir(), f"curvine-metasmoke-{os.getpid()}")
    out: dict = {}
    try:
        async with MiniCluster(workers=0, base_dir=base,
                               journal=False) as mc:
            c = mc.client()
            offs = list(range(0, n_create, bs))

            async def create_batch(lo: int):
                await c.meta.call(RpcCode.CREATE_FILES_BATCH, {"requests": [
                    {"path": f"/smoke/crt/f{j:07d}", "overwrite": True,
                     "block_size": 4 * MB, "replicas": 1,
                     "client_name": c.meta.client_id}
                    for j in range(lo, lo + bs)]}, mutate=True)

            t0 = time.perf_counter()
            for group in range(0, len(offs), 4):
                await asyncio.gather(*(create_batch(lo)
                                       for lo in offs[group:group + 4]))
            out["meta_create_qps"] = round(
                n_create / (time.perf_counter() - t0), 1)
            await c.close()
    finally:
        import shutil
        shutil.rmtree(base, ignore_errors=True)
    return out


async def _shard_smoke(shards: int = 2, n_create: int = 6_000,
                       bs: int = 500, backend: str | None = None,
                       dirs: int = 16) -> dict:
    """Sharded-namespace write-plane gate: the same batched-create storm
    as _meta_smoke, against a master running `shards` metadata shards
    behind the path router. Files spread over `dirs` parent directories
    so the crc32(parent) placement exercises every shard. The backend
    defaults to real child processes when the box has cores to run them
    concurrently and the in-process backend (identical wire path, one
    core) otherwise; the artifact records which ran plus the core count,
    so a flat curve on a 1-core box cannot masquerade as a scaling
    regression. Returns {meta_create_shard_qps, shards, shard_backend,
    cpus} for perf_floor.json / scripts/namespace_scale.py --shards."""
    from curvine_tpu.rpc import RpcCode
    from curvine_tpu.testing import MiniCluster
    cpus = os.cpu_count() or 1
    if backend is None:
        backend = os.environ.get(
            "BENCH_SHARD_BACKEND",
            "process" if cpus > shards else "inproc")
    base = os.path.join(_pick_shm_dir(),
                        f"curvine-shardsmoke-{os.getpid()}-{shards}")
    out: dict = {"shards": shards, "cpus": cpus,
                 "shard_backend": backend if shards > 1 else "none"}
    try:
        async with MiniCluster(workers=0, base_dir=base, journal=False,
                               shards=shards,
                               shard_backend=backend) as mc:
            c = mc.client()
            paths = [f"/smoke/shard/d{j % dirs:02d}/f{j:07d}"
                     for j in range(n_create)]
            # parents up front: the timed storm measures create
            # throughput, not the one-time mkdir broadcast fan-out
            for d in range(dirs):
                await c.meta.mkdir(f"/smoke/shard/d{d:02d}")
            offs = list(range(0, n_create, bs))

            async def create_batch(lo: int):
                await c.meta.call(RpcCode.CREATE_FILES_BATCH, {"requests": [
                    {"path": paths[j], "overwrite": True,
                     "block_size": 4 * MB, "replicas": 1,
                     "client_name": c.meta.client_id}
                    for j in range(lo, min(lo + bs, n_create))]},
                    mutate=True)

            t0 = time.perf_counter()
            for group in range(0, len(offs), 4):
                await asyncio.gather(*(create_batch(lo)
                                       for lo in offs[group:group + 4]))
            out["meta_create_shard_qps"] = round(
                n_create / (time.perf_counter() - t0), 1)
            await c.close()
    finally:
        import shutil
        shutil.rmtree(base, ignore_errors=True)
    return out


async def _read_plane_smoke(n_files: int = 32, stat_ops: int = 3_000,
                            open_iters: int = 300) -> dict:
    """Read fan-out plane gate for scripts/perf_smoke.sh: the stat →
    open → read ladder with the client metadata lease cache OFF vs ON
    (docs/read-plane.md). meta_stat_qps drives serial stats through a
    cache-disabled client — every call crosses the RPC wire;
    meta_stat_cached_qps runs the same serial loop on a default client
    whose entries are lease-warm, so hot stats are local memory. The
    acceptance bar is cached >= 10x uncached: the cache exists to take
    the wire out of the hot stat path, anything under that means it
    doesn't. open_read_p99_ms times the full open + pread(4 KiB) +
    close ladder on the warm client (short-circuit read, stat served
    from cache). Returns {meta_stat_qps, meta_stat_cached_qps,
    meta_cache_speedup, open_read_p99_ms}."""
    import copy
    import shutil
    from curvine_tpu.client import CurvineClient
    from curvine_tpu.testing import MiniCluster

    base = os.path.join(_pick_shm_dir(), f"curvine-readplane-{os.getpid()}")
    out: dict = {}
    try:
        async with MiniCluster(workers=1, base_dir=base,
                               journal=False) as mc:
            c = mc.client()
            paths = [f"/rp/f{i:03d}" for i in range(n_files)]
            await c.meta.mkdir("/rp")
            for p in paths:
                await c.write_all(p, b"\xab" * 4096)
            conf_off = copy.deepcopy(mc.conf)
            conf_off.client.meta_cache = False
            c_off = CurvineClient(conf_off)

            async def stat_qps(client, ops: int) -> float:
                for p in paths:          # warm conns + lease + entries
                    await client.meta.file_status(p)
                t0 = time.perf_counter()
                for j in range(ops):
                    await client.meta.file_status(paths[j % n_files])
                return ops / (time.perf_counter() - t0)

            # the uncached side runs fewer ops: every one is a full
            # round trip, and the figure converges in a few hundred
            out["meta_stat_qps"] = round(
                await stat_qps(c_off, max(200, stat_ops // 4)), 1)
            await c_off.close()
            out["meta_stat_cached_qps"] = round(
                await stat_qps(c, stat_ops), 1)
            out["meta_cache_speedup"] = round(
                out["meta_stat_cached_qps"]
                / max(out["meta_stat_qps"], 1e-9), 1)

            lat = []
            for _ in range(8):                               # warm
                r = await c.open(paths[0])
                await r.pread(0, 4096)
                await r.close()
            for i in range(open_iters):
                t0 = time.perf_counter()
                r = await c.open(paths[i % n_files])
                await r.pread(0, 4096)
                await r.close()
                lat.append(time.perf_counter() - t0)
            lat.sort()
            out["open_read_p99_ms"] = round(
                lat[int(0.99 * len(lat)) - 1] * 1000, 3)
    finally:
        shutil.rmtree(base, ignore_errors=True)
    return out


async def _shm_read_bench(iters: int = 2_000, block_mb: int = 4) -> dict:
    """Shared-memory short-circuit read gate for perf_smoke.sh
    (docs/data-plane.md). Closed-loop p50/p99 of cached 4K pread_view
    against a MEM-tier block, A/B:

      A (shm):    default client — GET_BLOCK_INFO advertises the sealed
                  memfd, the read is an mmap slice, zero RPC data plane
      B (socket): client.short_circuit off — every read crosses the
                  worker RPC socket (the pre-shm co-located path)

    The acceptance bar is shm p99 >= 3x better than the socket p99 for
    co-located reads; shm.grants/read.shm_hits are asserted so a silent
    fallback can't masquerade as a win. shm_read_gibs streams the block
    through pread_view (mmap -> aligned buffer memcpy) for the
    throughput floor. Returns {p99_cached_4k_read_us,
    p50_cached_4k_read_us, socket_p99_cached_4k_read_us,
    shm_p99_speedup, shm_read_gibs}."""
    import copy
    import random
    import shutil
    from curvine_tpu.client import CurvineClient
    from curvine_tpu.testing import MiniCluster

    base = os.path.join(_pick_shm_dir(), f"curvine-shmbench-{os.getpid()}")
    size = block_mb * MB
    slots = size // 4096 - 1
    out: dict = {}

    async def lat_us(client, path: str, n: int) -> list:
        r = await client.open(path)
        rng = random.Random(11)
        for _ in range(16):                                  # warm
            await r.pread_view(rng.randrange(slots) * 4096, 4096)
        lat = []
        for _ in range(n):
            off = rng.randrange(slots) * 4096
            t0 = time.perf_counter()
            await r.pread_view(off, 4096)
            lat.append((time.perf_counter() - t0) * 1e6)
        await r.close()
        lat.sort()
        return lat

    try:
        async with MiniCluster(workers=1, base_dir=base, journal=False,
                               block_size=size) as mc:
            c = mc.client()
            await c.write_all("/shm/hot.bin", os.urandom(size))

            a = await lat_us(c, "/shm/hot.bin", iters)
            hits = c.counters.get("read.shm_hits", 0)
            out["p50_cached_4k_read_us"] = round(a[len(a) // 2], 1)
            out["p99_cached_4k_read_us"] = round(
                a[int(0.99 * len(a)) - 1], 1)
            out["shm_hits"] = int(hits)

            # throughput: stream the whole block through the shm path
            r = await c.open("/shm/hot.bin")
            seg = MB
            reps = 16
            t0 = time.perf_counter()
            for _ in range(reps):
                off = 0
                while off < size:
                    v = await r.pread_view(off, seg)
                    off += len(v)
            out["shm_read_gibs"] = round(
                reps * size / (1024 ** 3) / (time.perf_counter() - t0), 3)
            await r.close()
            await c.close()

            # B side: same cluster, short-circuit off — the socket
            # path. Prefetch off too: the whole-block prefetch window
            # would serve the random reads from client memory and hide
            # the per-read RPC this gate exists to measure.
            conf_b = copy.deepcopy(mc.conf)
            conf_b.client.short_circuit = False
            conf_b.client.enable_smart_prefetch = False
            conf_b.client.read_ahead_chunks = 0
            cb = CurvineClient(conf_b)
            b = await lat_us(cb, "/shm/hot.bin", max(400, iters // 4))
            await cb.close()
            out["socket_p99_cached_4k_read_us"] = round(
                b[int(0.99 * len(b)) - 1], 1)
            out["shm_p99_speedup"] = round(
                out["socket_p99_cached_4k_read_us"]
                / max(out["p99_cached_4k_read_us"], 1e-9), 2)
    finally:
        shutil.rmtree(base, ignore_errors=True)
    return out


async def _warm_shm_read_bench(iters: int = 1_500,
                               block_mb: int = 4) -> dict:
    """Warm-cache shm export gate for perf_smoke.sh (docs/data-plane.md
    warm-cache protocol). The block lives on the SSD tier; a heating
    pass drives its read-heat over worker.shm_warm_min_reads so the
    worker copies it once into a sealed memfd, then A/B:

      A (shm_warm): fresh reader — GET_BLOCK_INFO advertises the warm
                    export, every read is an mmap slice, zero RPCs
      B (socket):   client.short_circuit off — per-read worker RPC

    read.shm_warm_hits and cache.shm_warm.exports are asserted via the
    client counters (warm_hits in the artifact) so a silent fd/socket
    fallback can't masquerade as the warm path. Returns
    {warm_shm_p99_us, warm_socket_p99_us, warm_shm_p99_speedup,
    warm_shm_read_gibs, warm_hits}."""
    import copy
    import random
    import shutil
    from curvine_tpu.client import CurvineClient
    from curvine_tpu.common.conf import ClusterConf, TierConf
    from curvine_tpu.testing import MiniCluster

    base = os.path.join(_pick_shm_dir(),
                        f"curvine-warmbench-{os.getpid()}")
    size = block_mb * MB
    slots = size // 4096 - 1
    out: dict = {}
    conf = ClusterConf()
    conf.worker.tiers = [TierConf(storage_type="ssd",
                                  dir=os.path.join(base, "ssd"),
                                  capacity=256 * MB)]
    conf.client.storage_type = "ssd"

    async def lat_us(client, path: str, n: int) -> list:
        r = await client.open(path)
        rng = random.Random(13)
        for _ in range(16):                                  # warm
            await r.pread_view(rng.randrange(slots) * 4096, 4096)
        lat = []
        for _ in range(n):
            off = rng.randrange(slots) * 4096
            t0 = time.perf_counter()
            await r.pread_view(off, 4096)
            lat.append((time.perf_counter() - t0) * 1e6)
        await r.close()
        lat.sort()
        return lat

    try:
        async with MiniCluster(workers=1, base_dir=base, journal=False,
                               conf=conf, block_size=size) as mc:
            c = mc.client()
            await c.write_all("/warm/hot.bin", os.urandom(size))

            # heating pass: enough short-circuit reads that the
            # SC_READ_REPORT flush (512-pending threshold) lands the
            # block's heat on the worker before the A-side reader opens
            r = await c.open("/warm/hot.bin")
            rng = random.Random(5)
            for _ in range(600):
                await r.pread_view(rng.randrange(slots) * 4096, 4096)
            await r.close()                 # close flushes the residue

            a = await lat_us(c, "/warm/hot.bin", iters)
            out["warm_hits"] = int(c.counters.get("read.shm_warm_hits",
                                                  0))
            out["warm_shm_p50_us"] = round(a[len(a) // 2], 1)
            out["warm_shm_p99_us"] = round(a[int(0.99 * len(a)) - 1], 1)

            # throughput: stream the block through the warm mmap
            r = await c.open("/warm/hot.bin")
            reps = 16
            t0 = time.perf_counter()
            for _ in range(reps):
                off = 0
                while off < size:
                    v = await r.pread_view(off, MB)
                    off += len(v)
            out["warm_shm_read_gibs"] = round(
                reps * size / (1024 ** 3) / (time.perf_counter() - t0),
                3)
            await r.close()
            await c.close()

            # B side: the same SSD block over the worker socket
            conf_b = copy.deepcopy(mc.conf)
            conf_b.client.short_circuit = False
            conf_b.client.enable_smart_prefetch = False
            conf_b.client.read_ahead_chunks = 0
            cb = CurvineClient(conf_b)
            b = await lat_us(cb, "/warm/hot.bin", max(400, iters // 4))
            await cb.close()
            out["warm_socket_p99_us"] = round(
                b[int(0.99 * len(b)) - 1], 1)
            out["warm_shm_p99_speedup"] = round(
                out["warm_socket_p99_us"]
                / max(out["warm_shm_p99_us"], 1e-9), 2)
    finally:
        shutil.rmtree(base, ignore_errors=True)
    return out


async def _ring_recv_bench(reps: int = 24, block_mb: int = 8) -> dict:
    """Registered-receive (io_uring READ_FIXED) A/B for perf_smoke.sh.
    Streams a MEM block over the worker SOCKET path (short-circuit off,
    so every payload remainder rides the sink recv) with rpc.recv_ring
    on vs off. Where io_uring doesn't probe healthy the bench returns
    {ring_skip: true} and the smoke gate skips cleanly — the fallback
    IS the contract on those kernels. recv_fixed_ops is the pool's op
    counter delta over the A side, asserted >0 so a silently-latched
    ring can't report sock numbers as ring numbers. Returns
    {recv_fixed_read_gibs, recv_fixed_off_read_gibs, recv_fixed_ops,
    ring_skip}. The two sides run as alternating passes (best-of-N
    each) so host-throughput drift between "the A minute" and "the B
    minute" can't masquerade as a ring regression."""
    import copy
    import shutil
    from curvine_tpu.client import CurvineClient
    from curvine_tpu.rpc.transport import recv_pool
    from curvine_tpu.testing import MiniCluster

    if recv_pool().ring() is None:
        return {"ring_skip": True}
    base = os.path.join(_pick_shm_dir(),
                        f"curvine-ringbench-{os.getpid()}")
    size = block_mb * MB
    out: dict = {"ring_skip": False}

    async def stream_gibs(client, path: str) -> float:
        r = await client.open(path)
        await r.pread_view(0, MB)                            # warm
        t0 = time.perf_counter()
        for _ in range(reps):
            off = 0
            while off < size:
                v = await r.pread_view(off, MB)
                off += len(v)
        gibs = reps * size / (1024 ** 3) / (time.perf_counter() - t0)
        await r.close()
        return gibs

    try:
        async with MiniCluster(workers=1, base_dir=base, journal=False,
                               block_size=size) as mc:
            c = mc.client()
            await c.write_all("/ring/big.bin", os.urandom(size))
            await c.close()

            conf = copy.deepcopy(mc.conf)
            conf.client.short_circuit = False
            conf.client.enable_smart_prefetch = False
            conf.client.read_ahead_chunks = 0

            conf_b = copy.deepcopy(conf)
            conf_b.rpc.recv_ring = False

            ops0 = recv_pool().stats()["fixed_ops"]
            best_a = best_b = 0.0
            for _ in range(3):
                ca = CurvineClient(conf)
                best_a = max(best_a,
                             await stream_gibs(ca, "/ring/big.bin"))
                await ca.close()
                cb = CurvineClient(conf_b)
                best_b = max(best_b,
                             await stream_gibs(cb, "/ring/big.bin"))
                await cb.close()
            out["recv_fixed_read_gibs"] = round(best_a, 3)
            out["recv_fixed_off_read_gibs"] = round(best_b, 3)
            out["recv_fixed_ops"] = (recv_pool().stats()["fixed_ops"]
                                     - ops0)
    finally:
        shutil.rmtree(base, ignore_errors=True)
    return out


def _cache_scan_bench(hot_n: int = 16, block_kb: int = 1,
                      cap_kb: int = 64, scan_factor: int = 8,
                      touch_every: int = 64) -> dict:
    """Cache-admission scan-resistance A/B for perf_smoke.sh
    (docs/caching.md). One BlockStore per policy (single MEM tier, so
    every eviction is a drop), identical workload: a hot working set is
    written and touched, then `scan_factor`x the tier's capacity of
    one-touch blocks streams through, with the hot set re-read sparser
    than the eviction cadence — the access pattern pure LRU is known to
    lose (each sweep's blocks are younger than the hot set's last
    touch). The hit pct is hot reads that found the block resident.
    The acceptance bar is s3fifo >= 1.3x the lru hit pct; the absolute
    `scan_resist_ratio_min` floor lives in scripts/perf_floor.json.
    Returns {scan_resist_s3fifo_hit_pct, scan_resist_lru_hit_pct,
    scan_resist_ratio, scan_ghost_hits, scan_probation_evictions}."""
    import shutil
    import tempfile
    from curvine_tpu.common.types import StorageType
    from curvine_tpu.worker.storage import BlockStore, TierDir

    size = block_kb * 1024
    n_scan = cap_kb * 1024 * scan_factor // size
    out: dict = {}

    def run(admission: str, root: str) -> tuple[float, dict]:
        mem = TierDir(StorageType.MEM, os.path.join(root, admission),
                      cap_kb * 1024)
        store = BlockStore([mem], high_water=0.9, low_water=0.5,
                           admission=admission)
        for bid in range(hot_n):
            info = store.create_temp(bid, size_hint=size)
            with open(info.path, "wb") as f:
                f.write(b"\0" * size)
            store.commit(bid, size)
        for bid in range(hot_n):
            store.get(bid)
        hits = attempts = 0
        for k in range(n_scan):
            info = store.create_temp(10_000 + k, size_hint=size)
            with open(info.path, "wb") as f:
                f.write(b"\0" * size)
            store.commit(10_000 + k, size)
            if k % touch_every == 0:
                for bid in range(hot_n):
                    attempts += 1
                    if store.contains(bid):
                        hits += 1
                        store.get(bid)
        return hits / max(1, attempts), store.cache_stats()["total"]

    root = tempfile.mkdtemp(prefix="curvine-scanbench-")
    try:
        s3, s3_stats = run("s3fifo", root)
        lru, _ = run("lru", root)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    out["scan_resist_s3fifo_hit_pct"] = round(s3 * 100, 1)
    out["scan_resist_lru_hit_pct"] = round(lru * 100, 1)
    out["scan_resist_ratio"] = round(s3 / max(lru, 0.01), 2)
    out["scan_ghost_hits"] = s3_stats.get("ghost_hits", 0)
    out["scan_probation_evictions"] = s3_stats.get("scan_evicted", 0)
    return out


async def _prefetch_epoch_bench(shards: int = 8, shard_kb: int = 128,
                                batch: int = 8, seq_len: int = 1024,
                                step_s: float = 0.005) -> dict:
    """Epoch-boundary input-wait gate for perf_smoke.sh
    (docs/caching.md). A CacheShardSource with prefetch advise on
    streams TWO consecutive epochs (the boundary re-shuffles the shard
    order) through an AsyncDevicePrefetcher into a consumer simulating
    a fixed-length train step; the StepProfiler attributes every stall.
    The acceptance bar is a steady-state input_wait fraction at or
    under `input_wait_frac_max` across the boundary — the cache plus
    the rolling prefetch window must keep the consumer compute-bound.
    Returns {input_wait_frac, prefetch_steps, prefetch_window_jobs}."""
    import shutil
    import numpy as np
    from curvine_tpu.obs.profiler import StepProfiler
    from curvine_tpu.testing import MiniCluster
    from curvine_tpu.tpu.ingest import AsyncDevicePrefetcher
    from curvine_tpu.tpu.loader import CacheShardSource

    base = os.path.join(_pick_shm_dir(),
                        f"curvine-prefetchbench-{os.getpid()}")
    prof = StepProfiler()
    steps = 0
    try:
        async with MiniCluster(workers=1, base_dir=base, journal=False,
                               block_size=MB) as mc:
            c = mc.client()
            rng = np.random.default_rng(3)
            for i in range(shards):
                tok = rng.integers(0, 2 ** 31, shard_kb * 256,
                                   dtype=np.int32)
                await c.write_all(f"/bench/epoch/shard-{i:03d}.bin",
                                  tok.tobytes())
            src = CacheShardSource(c, "/bench/epoch", batch, seq_len,
                                   shuffle_seed=7, prefetch=True,
                                   prefetch_window=4)
            per_epoch = shards * shard_kb * 256 // (batch * seq_len)

            async def three_epochs():
                for _ in range(3):
                    async for b in src.batches():
                        yield b

            # epoch 0 is warmup outside the measurement (pipeline fill,
            # first listing, first jax dispatch): the gate is the
            # STEADY-STATE input wait across the epoch 1 -> 2 boundary
            pf = AsyncDevicePrefetcher(three_epochs(), None, depth=2)
            async for _ in pf:
                await asyncio.sleep(step_s)       # the simulated step
                steps += 1
                if steps == per_epoch:
                    src.profiler = prof
                    pf.profiler = prof
                elif steps > per_epoch:
                    prof.step_done()
            jobs = sum(1 for j in mc.master.jobs.jobs.values()
                       if j.kind == "prefetch")
    finally:
        shutil.rmtree(base, ignore_errors=True)
    frac = prof.summary()["fractions"]
    return {"input_wait_frac": round(frac.get("input_wait", 0.0), 4),
            "prefetch_steps": steps,
            "prefetch_window_jobs": jobs}


async def _ici_smoke(payload_mb: int = 64, rounds: int = 3) -> dict:
    """ICI data-plane gate (docs/ici-plane.md). Two halves:

    (a) checkpoint broadcast rail A/B — the pipelined chunked mesh
    broadcast (`ici_plane.broadcast_bytes`) against the flat single-put
    replicate over the same device mesh. `ckpt_broadcast_gibs` is
    AGGREGATE delivered bandwidth (payload bytes x devices / wall
    time): chunking keeps every transfer on the runtime's pooled
    staging buffers, so the pipelined rail must hold a multiple of the
    flat baseline (~1.5 GiB/s aggregate on the 8-way CPU mesh).

    (b) peer-HBM replication pull — a re-replication whose source
    advertises the block HBM-resident must ride the device path end to
    end. `ici_peer_pull_ratio` = peer_pulls / (peer_pulls +
    tcp_fallbacks) over the healing round; in this controlled A the
    device domain is intact, so anything under 1.0 means the hint or
    the landing path regressed.

    Returns {ckpt_broadcast_gibs, ckpt_broadcast_flat_gibs,
    ckpt_broadcast_speedup, ici_peer_pull_ratio, ici_peer_pulls} or
    {ici_skip: reason} when the backend cannot form a multi-device
    mesh (e.g. a jaxlib without the virtual-device collectives)."""
    import jax
    from curvine_tpu.common.conf import ClusterConf
    from curvine_tpu.rpc import RpcCode
    from curvine_tpu.rpc.frame import pack
    from curvine_tpu.testing import MiniCluster
    from curvine_tpu.tpu import ici_plane
    from curvine_tpu.tpu.mesh import make_mesh

    try:
        devs = jax.devices()
    except RuntimeError as e:           # backend never came up
        return {"ici_skip": f"no device backend: {e}"}
    if len(devs) < 2:
        return {"ici_skip": f"needs a multi-device mesh, have "
                            f"{len(devs)} device(s)"}
    mesh = make_mesh(devices=devs, axis_names=("data",))
    data = os.urandom(payload_mb << 20)
    out: dict = {}

    # ---- (a) broadcast rail A/B: best-of-rounds on both rails ----
    # Each rail runs its rounds back to back with one untimed warm-up:
    # a checkpoint is MANY tensors streamed through the same bounded
    # chunk pool, so the steady state (buffers recycled) is what the
    # rail delivers in practice — a cold round only measures the
    # allocator faulting fresh pages, and interleaving the rails lets
    # the flat path's whole-payload buffers evict the chunk pool.
    def _best(rail, warmups=2):
        best = float("inf")
        for i in range(rounds + warmups):
            t0 = time.perf_counter()
            res = rail(data, mesh)
            dt = time.perf_counter() - t0
            del res
            if i >= warmups:             # pool takes ~2 rounds to form
                best = min(best, dt)
        return best

    # chunked rail first: its bounded pool is what we are measuring,
    # and the flat rail only benefits from pages already faulted in —
    # running it second keeps the A/B conservative for the speedup
    pipe_s = _best(ici_plane.broadcast_bytes)
    flat_s = _best(ici_plane.flat_replicate)
    agg = len(data) * len(devs) / (1 << 30)
    out["ckpt_broadcast_gibs"] = round(agg / pipe_s, 3)
    out["ckpt_broadcast_flat_gibs"] = round(agg / flat_s, 3)
    out["ckpt_broadcast_speedup"] = round(flat_s / pipe_s, 2)
    out["ckpt_broadcast_devices"] = len(devs)

    # ---- (b) peer-HBM pull over one healing round ----
    conf = ClusterConf()
    conf.worker.hbm_capacity = 32 * 1024 * 1024
    async with MiniCluster(workers=2, conf=conf) as mc:
        mc.master.replication.scan_interval_s = 0.3
        c = mc.client()
        blob = os.urandom(1 << 20)
        await c.write_all("/bench/ici", blob)
        fb = await c.meta.get_block_locations("/bench/ici")
        bid = fb.block_locs[0].block.id
        src_wid = fb.block_locs[0].locs[0].worker_id
        src = next(w for w in mc.workers if w.worker_id == src_wid)
        dst = next(w for w in mc.workers if w.worker_id != src_wid)
        conn = await c.pool.get(src.addr)
        await conn.call(RpcCode.HBM_PIN, data=pack({"block_id": bid}))
        await src.heartbeat_once()
        mc.master.fs.blocks.desired[bid] = 2
        mc.master.replication.enqueue([bid])
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            fb = await c.meta.get_block_locations("/bench/ici")
            if len(fb.block_locs[0].locs) >= 2:
                break
            await asyncio.sleep(0.1)
        pulls = dst.metrics.counters.get("ici.peer_pulls", 0)
        falls = dst.metrics.counters.get("ici.tcp_fallbacks", 0)
        out["ici_peer_pulls"] = int(pulls)
        out["ici_peer_pull_ratio"] = round(
            pulls / max(1, pulls + falls), 3)
    return out


async def _ladder_smoke(clients: int = 64, duration: float = 2.0,
                        rate: float = 10.0) -> dict:
    """Scaled-down open-loop concurrency rung (scripts/latency_ladder.py
    at 64 clients, short duration) so perf_smoke.sh exercises the fleet
    rig without the full 1K walk. The fleet is pinned round-robin
    across cores (the --cpus multi-core tail — recorded beside
    loop_impl in the artifact) so the rung measures cross-core
    contention, not one runqueue time-sharing. Returns {ladder_clients,
    ladder_achieved_qps, ladder_p50_us, ladder_p99_us, ladder_errors,
    ladder_cpus}."""
    scripts = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    from latency_ladder import run_ladder

    procs = min(os.cpu_count() or 2, 4)
    cpus = sorted(os.sched_getaffinity(0))[:procs] \
        if hasattr(os, "sched_getaffinity") else []
    res = await run_ladder(rungs=(clients,), duration=duration,
                           rate=rate, procs=procs, cpus=cpus)
    rung = res["rungs"][0]
    return {"ladder_clients": rung["clients"],
            "ladder_achieved_qps": rung["achieved_qps"],
            "ladder_p50_us": rung["p50_us"],
            "ladder_p99_us": rung["p99_us"],
            "ladder_errors": rung["errors"],
            "ladder_cpus": rung["cpus"]}


async def run_bench(total_mb: int = 256, block_mb: int = 64,
                    latency_block_mb: int = 1, latency_iters: int = 200):
    import jax
    import numpy as np
    from curvine_tpu.testing import MiniCluster

    base = os.path.join(_pick_shm_dir(), f"curvine-bench-{os.getpid()}")
    dev = jax.devices()[0]
    results = {"backend": jax.default_backend(),
               "device_kind": dev.device_kind}
    link_buf = np.random.default_rng(7).integers(
        0, 255, 128 * MB, dtype=np.uint8)
    jax.block_until_ready(jax.device_put(link_buf[:MB], dev))   # warm

    def link_pass() -> float:
        t0 = time.perf_counter()
        jax.block_until_ready(jax.device_put(link_buf, dev))
        return 128 / 1024 / (time.perf_counter() - t0)

    results["dram_to_hbm_gibs"] = max(link_pass() for _ in range(3))

    # room for everything the phases below write (data twice, the 64 MiB
    # checkpoint, the 512 MB vector table and its index): nothing here has
    # a UFS behind it, so a block the tier evicts is a block lost
    async with MiniCluster(workers=1, base_dir=base,
                           tier_capacity=(2 * total_mb + 1536) * MB,
                           block_size=block_mb * MB, journal=False,
                           lost_timeout_ms=600_000) as mc:
        c = mc.client()
        rng = np.random.default_rng(0)
        results["tmpfs_raw_gibs"] = _tmpfs_raw_gibs(base)

        # ---- direct-IO cold read (O_DIRECT ring engine, SSD-tier
        # data plane) vs buffered — device-speed path, page-cache
        # bypassed by construction ----
        results.update(await asyncio.to_thread(
            _direct_io_bench, int(os.environ.get("BENCH_DIRECT_MB", "256"))))

        # ---- write path (short-circuit local write) ----
        payload = rng.integers(0, 255, total_mb * MB, dtype=np.uint8).tobytes()
        # warm pass: page-cache/tmpfs fresh-page allocation is the machine
        # ceiling on some hosts; measure the software path on warm pages
        await c.write_all("/bench/warm", payload)
        await c.meta.delete("/bench/warm")
        write_rates = []
        for i in range(3):
            t0 = time.perf_counter()
            await c.write_all("/bench/data", payload)
            write_rates.append(total_mb / 1024 / (time.perf_counter() - t0))
            if i < 2:
                await c.meta.delete("/bench/data")
        results["write_gibs"] = max(write_rates)

        # ---- throughput: cached read → HBM ----
        # short-circuit fast path: zero-copy mmap views over the block files
        # handed straight to device_put (pipelined: next view maps while the
        # previous transfer is in flight).
        r = await c.open("/bench/data")
        views = []
        offset = 0
        while offset < r.len:
            n = min(block_mb * MB, r.len - offset)
            view = await r.mmap_view(offset, n)
            if view is None:                 # remote worker: RPC copy path
                view = np.frombuffer(await r.pread(offset, n), dtype=np.uint8)
            views.append(view)
            offset += n
        jax.block_until_ready(jax.device_put(views[0][:1024], dev))

        def hbm_pass() -> float:
            t0 = time.perf_counter()
            futures = [jax.device_put(v, dev) for v in views]
            jax.block_until_ready(futures)
            read_bytes = sum(len(v) for v in views)
            return read_bytes / (1024 ** 3) / (time.perf_counter() - t0)

        # a raw link pass is INTERLEAVED with each pipeline pass, so the
        # pipeline/link ratio compares like with like under host load
        hbm_rates, link_rates = [], []
        for _ in range(4):
            link_rates.append(link_pass())
            hbm_rates.append(hbm_pass())
        results["read_gibs_into_hbm"] = max(hbm_rates)
        results["link_gibs"] = max(link_rates)
        results["pipeline_vs_link"] = max(hbm_rates) / max(link_rates)

        # ---- host-only cached read (no device) for reference ----
        r2 = await c.open("/bench/data")
        host_rates = []
        for _ in range(2):
            t0 = time.perf_counter()
            n = 0
            off = 0
            while off < r2.len:
                view = await r2.pread_view(off, block_mb * MB)
                if not len(view):
                    break
                n += len(view)
                off += len(view)
            host_rates.append(n / (1024 ** 3) / (time.perf_counter() - t0))
        results["read_gibs_host"] = max(host_rates)

        # ---- metadata QPS (reference headline: "100K+ QPS") ----
        # pipelined stat storm: many in-flight FILE_STATUS calls multiplex
        # by req-id over pooled connections
        await c.meta.mkdir("/bench/meta")
        for i in range(32):
            await c.meta.create_file(f"/bench/meta/f{i:02d}", block_size=MB)
            await c.meta.complete_file(f"/bench/meta/f{i:02d}", 0)
        conc = 64
        per_worker = 62
        total_calls = conc * per_worker        # numerator = actual calls

        async def stat_worker(k: int) -> None:
            for j in range(per_worker):
                await c.meta.file_status(f"/bench/meta/f{(k + j) % 32:02d}")

        t0 = time.perf_counter()
        await asyncio.gather(*(stat_worker(k) for k in range(conc)))
        results["meta_qps"] = total_calls / (time.perf_counter() - t0)

        # ---- metadata WRITE plane: batched file creates through the
        # RPC + inode-tree + KV-batch path (native C++ LSM engine by
        # default — conf master.meta_engine). This perf cluster runs
        # journal=False like every other bench phase, so the figure is
        # the non-WAL write plane; 4 batches stay in flight so it
        # measures server throughput, not client round trips.
        from curvine_tpu.rpc import RpcCode
        t0 = time.perf_counter()
        n_create = 20_000
        bs = 500

        async def create_batch(lo: int):
            await c.meta.call(RpcCode.CREATE_FILES_BATCH, {"requests": [
                {"path": f"/bench/crt/f{j:07d}", "overwrite": True,
                 "block_size": 4 * MB, "replicas": 1,
                 "client_name": c.meta.client_id}
                for j in range(lo, lo + bs)]}, mutate=True)

        offs = list(range(0, n_create, bs))
        for group in range(0, len(offs), 4):
            await asyncio.gather(*(create_batch(lo)
                                   for lo in offs[group:group + 4]))
        results["meta_create_qps"] = n_create / (time.perf_counter() - t0)
        await c.meta.delete("/bench/crt", recursive=True)

        # ---- META_BATCH: heterogeneous batched mutations (mkdir/create/
        # delete in one RPC), the client-side half of group commit
        t0 = time.perf_counter()
        async def meta_batch_batch(lo: int):
            await c.meta.meta_batch(
                [{"op": "create", "path": f"/bench/crtb/f{j:07d}",
                  "overwrite": True, "block_size": 4 * MB, "replicas": 1}
                 for j in range(lo, lo + bs)])

        for group in range(0, len(offs), 4):
            await asyncio.gather(*(meta_batch_batch(lo)
                                   for lo in offs[group:group + 4]))
        results["meta_create_batch_qps"] = \
            n_create / (time.perf_counter() - t0)
        await c.meta.delete("/bench/crtb", recursive=True)

        # ---- wire transport: small-op round trip + pipelined QPS on a
        # bare echo server (the denominator under every meta figure)
        results.update(await _rpc_smoke())

        # ---- native metadata read plane (C++ mirror, fast port) ----
        # the C++ load generator pipelines stats at the C++ server so
        # neither side is bounded by Python (this is the path that meets
        # the reference's multithreaded-Rust 100K+ headline)
        from curvine_tpu.master import fastmeta as _fm
        fast_port = getattr(mc.master.fastmeta, "port", None) \
            if getattr(mc.master, "fastmeta", None) else None
        if fast_port:
            host = mc.master.addr.rsplit(":", 1)[0]
            loop = asyncio.get_running_loop()
            results["meta_qps_native"] = await loop.run_in_executor(
                None, _fm.bench_stat, host, fast_port,
                "/bench/meta/f00", "root", 150_000, 64)

        # ---- p99 block-fetch latency ----
        await c.write_all("/bench/small",
                          rng.integers(0, 255, latency_block_mb * MB,
                                       dtype=np.uint8).tobytes())
        lat = []
        r3 = await c.open("/bench/small")
        for _ in range(latency_iters):
            t0 = time.perf_counter()
            data = await r3.pread_view(0, latency_block_mb * MB)
            lat.append(time.perf_counter() - t0)
            assert len(data) == latency_block_mb * MB
        lat.sort()
        results["p99_block_fetch_ms"] = lat[int(0.99 * len(lat)) - 1] * 1000
        results["p50_block_fetch_ms"] = statistics.median(lat) * 1000

        # ---- HBM tier-0: reads once blocks are pinned on-device ----
        import jax.numpy as jnp
        from curvine_tpu.tpu.hbm import HbmTier
        tier = HbmTier((total_mb + 64) * MB, device=dev)
        fb = await c.meta.get_block_locations("/bench/data")
        r_pin = await c.open("/bench/data")
        for lb in fb.block_locs:
            view = await r_pin.mmap_view(lb.offset, lb.block.len)
            if view is None:
                view = np.frombuffer(await r_pin.pread(lb.offset,
                                                       lb.block.len),
                                     dtype=np.uint8)
            tier.put(lb.block.id, view)
        blocks = [tier.get(lb.block.id) for lb in fb.block_locs]
        reps = 8

        @jax.jit
        def consume(bs, salt):
            return sum(jnp.sum(b ^ salt, dtype=jnp.uint32) for b in bs)

        consume(blocks, jnp.uint8(0)).block_until_ready()   # compile
        t0 = time.perf_counter()
        for i in range(reps):
            consume(blocks, jnp.uint8(i + 1)).block_until_ready()
        hbm_s = time.perf_counter() - t0
        results["hbm_tier_read_gibs"] = (
            reps * sum(b.nbytes for b in blocks) / (1024 ** 3) / hbm_s)

        # ---- checkpoint broadcast (model distribution, overlapped) ----
        from curvine_tpu.tpu.broadcast import (
            distribute_checkpoint_to_device, save_checkpoint,
        )
        rng2 = np.random.default_rng(1)
        ckpt = {f"w{i}": rng2.normal(size=(1024, 1024)).astype(np.float32)
                for i in range(16)}                      # 64 MiB of weights
        await save_checkpoint(c, "/bench/ckpt", ckpt)
        await distribute_checkpoint_to_device(c, "/bench/ckpt", dev)  # warm
        ckpt_bytes = sum(a.nbytes for a in ckpt.values())
        best = 0.0
        for _ in range(3):
            t0 = time.perf_counter()
            rep = await distribute_checkpoint_to_device(c, "/bench/ckpt", dev)
            jax.block_until_ready(rep)
            best = max(best,
                       ckpt_bytes / (1024 ** 3) / (time.perf_counter() - t0))
        results["ckpt_broadcast_gibs"] = best

        # ---- vector-table scan → device knn (device-resident table) ----
        from curvine_tpu.vector import VectorTable
        dim = 256
        n_rows = 500_000
        table = await VectorTable.create(c, "/bench/vec", dim)
        # mixture-of-gaussians rows (1024 centers, sigma 0.25): real
        # embedding spaces are clustered — IVF recall on PURE noise
        # measures the data, not the index (r4's bench did that)
        centers = rng2.normal(size=(1024, dim)).astype(np.float32)
        assign = rng2.integers(0, 1024, n_rows)
        vecs = (centers[assign]
                + 0.25 * rng2.normal(size=(n_rows, dim))).astype(np.float32)
        await table.append(vecs)
        await table.knn(vecs[0], k=8, device=dev)   # pin + compile warm-up
        # a scan stream: dispatches pipeline on-device, one sync at the end
        # (per-call host syncs would measure the round trip, not the scan)
        reps = 8
        t0 = time.perf_counter()
        outs = [await table.knn(vecs[123 + i], k=8, device=dev,
                                materialize=False) for i in range(reps)]
        ids = np.asarray(outs[-1][0])
        scan_s = time.perf_counter() - t0
        assert int(ids[0, 0]) == 123 + reps - 1
        results["vector_scan_mrows_s"] = reps * n_rows / scan_s / 1e6

        # ---- IVF-PQ ANN serving: batched, device-resident, pipelined ----
        # (one query per dispatch benches the dispatch round trip, not
        # the index — and the flat-IVF gather of full fp32 candidate
        # rows is memory-bandwidth-bound at ~40 QPS on CPU. The PQ path
        # scans 8-bit codes via per-query LUTs (32 bytes/candidate
        # instead of 1 KiB) and re-ranks the ADC survivors exactly;
        # capped lists stop paying worst-case padding. QPS ladder +
        # roofline: docs/ann-serving.md.)
        from curvine_tpu.vector import AnnServer
        # tuning rule (docs/ann-serving.md): nlist tracks the data's
        # cluster count (1024 centers) so probed lists are small, and
        # rerank covers a whole cluster — the ADC shortlist's job is to
        # isolate the query's cluster; within-cluster ranking is the
        # exact re-rank's
        t0 = time.perf_counter()
        await table.create_index(nlist=1024, metric="cosine", iters=4,
                                 device=dev, pq_m=16, cap_pct=90.0)
        results["vector_index_build_s"] = time.perf_counter() - t0
        n_q = 4096
        queries = vecs[rng2.integers(0, n_rows, n_q)]
        # recall@10 vs the exact scan on a subset (the honesty check:
        # QPS without recall is a random-number generator)
        exact_i, _ = await table.knn(queries[:64], k=10, device=dev,
                                     use_index=False)
        exact_i = np.asarray(exact_i)

        def _recall10(ann_i) -> float:
            hits = sum(len(set(map(int, a)) & set(map(int, b)))
                       for a, b in zip(ann_i[:64], exact_i))
            return hits / (64 * 10)

        srv = await AnnServer(table, k=10, metric="cosine", nprobe=8,
                              rerank=512, device=dev, max_batch=256,
                              warm_all=False).start()     # bulk-only
        await srv.query_many(queries[:256])            # warm
        t0 = time.perf_counter()
        ann_i, _ = await srv.query_many(queries, batch=256, depth=4)
        ann_s = time.perf_counter() - t0
        # PQ is the serving default now; both keys record the same run
        results["vector_ann_qps"] = n_q / ann_s
        results["vector_ann_pq_qps"] = results["vector_ann_qps"]
        results["vector_ann_recall10"] = _recall10(ann_i)
        results["vector_ann_pq_recall10"] = results["vector_ann_recall10"]
        await srv.stop()

        # flat IVF over the same capped lists (the pre-PQ serving path,
        # kept measured so the ladder in docs/ann-serving.md stays live)
        srv = await AnnServer(table, k=10, metric="cosine", nprobe=8,
                              use_pq=False, device=dev, max_batch=256,
                              warm_all=False).start()
        await srv.query_many(queries[:256])            # warm
        n_q_flat = 512
        t0 = time.perf_counter()
        flat_i, _ = await srv.query_many(queries[:n_q_flat], batch=256,
                                         depth=4)
        results["vector_ann_flat_qps"] = \
            n_q_flat / (time.perf_counter() - t0)
        results["vector_ann_flat_recall10"] = _recall10(flat_i)
        await srv.stop()

        # the serving-shaped number: CONCURRENT callers awaiting
        # AnnServer.query(), coalesced by the micro-batch collector —
        # includes queueing + padding + per-caller fan-out, not just the
        # device scan
        srv = await AnnServer(table, k=10, metric="cosine", nprobe=8,
                              rerank=512, device=dev, max_batch=256,
                              max_wait_ms=2.0).start()
        await asyncio.gather(*(srv.query(q) for q in queries[:256]))
        n_served = 3072
        t0 = time.perf_counter()
        await asyncio.gather(*(srv.query(q) for q in queries[:n_served]))
        results["vector_ann_served_qps"] = \
            n_served / (time.perf_counter() - t0)
        results["vector_ann_batch_occupancy"] = \
            round(srv.stats()["batch_occupancy"], 3)
        await srv.stop()

        # ---- bf16-resident scan: half the HBM traffic of the f32 scan ----
        await table.knn(vecs[0], k=8, device=dev, use_index=False,
                        dtype="bf16")        # re-pin in bf16 + compile
        t0 = time.perf_counter()
        outs = [await table.knn(vecs[123 + i], k=8, device=dev,
                                use_index=False, materialize=False,
                                dtype="bf16") for i in range(reps)]
        ids = np.asarray(outs[-1][0])
        bf16_s = time.perf_counter() - t0
        assert int(ids[0, 0]) == 123 + reps - 1
        results["vector_scan_bf16_mrows_s"] = reps * n_rows / bf16_s / 1e6

        # ---- cache-fed train-step MFU (flagship model) ----
        # the 1B step needs ~15 GiB of a 16 GB chip (9.2 GiB temp + 5.7
        # GiB donated state, from XLA's memory analysis): everything the
        # earlier phases left on the device goes first
        for lb in fb.block_locs:
            tier.drop(lb.block.id)
        del blocks, outs
        table._dev_cache.clear()
        table._index = None
        results.update(await _mfu_bench(c, dev, jax))

        # ---- fio-style workloads over a real kernel FUSE mount ----
        results.update(await _fuse_bench(c))

        await c.close()
    import shutil
    shutil.rmtree(base, ignore_errors=True)

    # ---- sharded namespace: create-QPS A/B curve (same storm at
    # shards=1/2/4; shards=1 is the unsharded master, the true A side) ----
    if os.environ.get("BENCH_SHARDS", "1") != "0":
        rs = [await _shard_smoke(s) for s in (1, 2, 4)]
        results["meta_create_shard_curve"] = {
            str(r["shards"]): r["meta_create_shard_qps"] for r in rs}
        results["meta_create_shard_qps"] = rs[-1]["meta_create_shard_qps"]
        results["shard_backend"] = rs[-1]["shard_backend"]
        results["shard_cpus"] = rs[-1]["cpus"]

    # ---- read fan-out plane: stat/open/read ladder, lease cache
    # off vs warm (docs/read-plane.md) ----
    results.update(await _read_plane_smoke())

    # ---- 100 us-class data plane: shm short-circuit A/B + the
    # open-loop concurrency rung (docs/data-plane.md) ----
    if os.environ.get("BENCH_SHM", "1") != "0":
        results.update(await _shm_read_bench())
        results.update(await _warm_shm_read_bench())
        results.update(await _ring_recv_bench())
    if os.environ.get("BENCH_LADDER", "1") != "0":
        results.update(await _ladder_smoke())

    # ---- ICI data plane: broadcast rail A/B + peer-HBM pull
    # (docs/ici-plane.md) ----
    if os.environ.get("BENCH_ICI", "1") != "0":
        results.update(await _ici_smoke())
    return results


async def _mfu_bench(c, dev, jax) -> dict:
    """Train the flagship transformer fed from the cache; report MFU =
    model FLOPs (6·params·tokens) / step time / chip peak."""
    import numpy as np
    from curvine_tpu.tpu.loader import TpuTrainFeed, write_token_shards
    from curvine_tpu.tpu.model import (
        ModelConfig, init_params, make_optimizer, make_train_step,
    )

    # 1B-param flagship: d_model 2560 / 20 heads → head_dim 128 so the
    # Pallas flash-attention kernel engages; chunked CE keeps the
    # [B·L, 32K] f32 logits out of HBM.
    cfg = ModelConfig(vocab=32_000, d_model=2560, n_heads=20,
                      n_layers=12, d_ff=10240, max_seq=1024,
                      dtype="bfloat16", use_flash_attention=True,
                      ce_chunk=2048)
    batch, seq, steps = 16, 1024, 6

    tokens = np.random.default_rng(3).integers(
        0, cfg.vocab, batch * seq * (steps + 2), dtype=np.int32)
    await write_token_shards(c, "/bench/tok", tokens,
                             shard_tokens=batch * seq)

    with jax.default_device(dev):
        params = init_params(jax.random.PRNGKey(0), cfg)
        opt = make_optimizer()
        opt_state = opt.init(params)
        # donate params/opt_state: the 1B config's 8 GiB of state must
        # update in place or HBM holds two copies across the step
        step = jax.jit(make_train_step(cfg, opt, None),
                       donate_argnums=(0, 1))

        n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))

        async def timed_steps(batches) -> list[float]:
            """Pipelined loop: batch k+1's host fetch + device transfer
            overlap step k's compute (the step call returns at dispatch;
            only the sync point at each iteration's end blocks)."""
            nonlocal params, opt_state
            times, prev_loss = [], None
            nxt = await anext(batches, None)
            while nxt is not None:
                t0 = time.perf_counter()
                tok = jax.device_put(nxt, dev)
                params, opt_state, prev_loss = step(params, opt_state, tok)
                nxt = await anext(batches, None)   # overlaps the step
                jax.block_until_ready((params, prev_loss))
                times.append(time.perf_counter() - t0)
            return times

        # cache-fed pass (the real path: shards → short-circuit mmap →
        # host batches → HBM)
        feed = TpuTrainFeed(c, "/bench/tok", batch=batch, seq_len=seq)
        cache_times = await timed_steps(feed.prefetcher)
        if len(cache_times) > 1:
            cache_times = cache_times[1:]        # drop compile step

        # synthetic pass (same arrays, no loader) — the overlap proof:
        # cache-fed step time / synthetic step time ≈ 1.0 means ingest
        # fully hides behind compute
        tok0 = np.random.default_rng(5).integers(
            0, cfg.vocab, (batch, seq), dtype=np.int32)

        async def synth():
            for _ in range(steps):
                yield tok0

        synth_times = await timed_steps(synth())
    step_s = statistics.median(cache_times)
    synth_s = statistics.median(synth_times)
    flops = 6.0 * n_params * batch * seq
    from curvine_tpu.tpu.peaks import peaks_of
    return {"mfu": flops / step_s / peaks_of(dev)["bf16_flops"],
            "train_step_ms": step_s * 1000,
            "train_step_synth_ms": synth_s * 1000,
            "ingest_overlap_ratio": step_s / synth_s if synth_s else 0.0,
            "model_params_m": n_params / 1e6}


async def _fuse_bench(c) -> dict:
    """fio-equivalent over a real /dev/fuse kernel mount (the reference's
    headline bench is fio over FUSE; no fio binary is baked into this
    image, so the same access patterns run as plain POSIX IO): seq write,
    seq read, random 4 KiB reads. Skipped when /dev/fuse is absent."""
    import shutil as sh
    import tempfile
    if not (os.path.exists("/dev/fuse") and sh.which("fusermount")):
        return {}
    from curvine_tpu.fuse.mount import fusermount_mount, fusermount_umount
    from curvine_tpu.fuse.ops import CurvineFuseFs
    from curvine_tpu.fuse.session import FuseSession

    mnt = tempfile.mkdtemp(prefix="curvine-fio-")
    out = {}
    session = None
    sess_task = None

    from curvine_tpu.common.conf import FuseConf
    from curvine_tpu.fuse.mount import tune_readahead_retry

    async def mount():
        fd = fusermount_mount(mnt)
        fs = CurvineFuseFs(c, uid=os.getuid(), gid=os.getgid())
        s = FuseSession(fs, fd)
        t = asyncio.ensure_future(s.run())
        await s.ready.wait()
        # the production default via the production helper: what ships
        # is what gets measured
        await tune_readahead_retry(mnt, FuseConf().read_ahead_kb,
                                   attempts=5, delay_s=0.2)
        return s, t

    def remount_sync():
        # cold phases: a fresh mount = fresh superblock = empty kernel
        # page cache for the file (warm numbers measure the page cache
        # that FOPEN_KEEP_CACHE leaves behind — fio's own warm-cache
        # semantics; writeback is deliberately not negotiated)
        fusermount_umount(mnt)

    try:
        session, sess_task = await mount()
        total = 64 * MB

        def write_and_warm():
            buf = os.urandom(4 * MB)
            t0 = time.perf_counter()
            with open(f"{mnt}/fio.bin", "wb") as f:
                for _ in range(total // len(buf)):
                    f.write(buf)
            r = {"fuse_seq_write_gibs": total / (1024 ** 3)
                 / (time.perf_counter() - t0)}
            # WARM means page-cache-served (fio warm-read semantics):
            # pages cached by a previous READ survive via KEEP_CACHE.
            # Pages cached by the WRITE above do NOT survive the reopen —
            # AUTO_INVAL_DATA drops them because mtime changed (that IS
            # close-to-open consistency, not a bug; r4's warm<cold was
            # this first pass being daemon-served). Pass 1 warms, pass 2
            # is the measurement.
            with open(f"{mnt}/fio.bin", "rb", buffering=0) as f:
                while f.read(4 * MB):
                    pass
            t0 = time.perf_counter()
            n = 0
            with open(f"{mnt}/fio.bin", "rb", buffering=0) as f:
                while chunk := f.read(4 * MB):
                    n += len(chunk)
            r["fuse_warm_read_gibs"] = n / (1024 ** 3) \
                / (time.perf_counter() - t0)
            import random
            rng = random.Random(0)
            fd2 = os.open(f"{mnt}/fio.bin", os.O_RDONLY)
            iters = 2048
            t0 = time.perf_counter()
            for _ in range(iters):
                os.pread(fd2, 4096, rng.randrange(0, total - 4096))
            os.close(fd2)
            r["fuse_warm_rand4k_iops"] = iters / (time.perf_counter() - t0)
            return r

        # the mount is served by THIS event loop: POSIX calls must run in
        # a thread or they deadlock against the FUSE session
        out = await asyncio.to_thread(write_and_warm)

        sess_task.cancel()
        await asyncio.to_thread(remount_sync)
        session.stop()
        await asyncio.sleep(0.3)
        session, sess_task = await mount()

        def rand_job(seed: int, iters: int = 512) -> None:
            # ONE read-loop shape for both the serial and j4 figures
            import random
            rng = random.Random(seed)
            fd2 = os.open(f"{mnt}/fio.bin", os.O_RDONLY)
            try:
                for _ in range(iters):
                    os.pread(fd2, 4096, rng.randrange(0, total - 4096))
            finally:
                os.close(fd2)

        def cold_rand():
            iters = 512
            t0 = time.perf_counter()
            rand_job(0, iters)
            return {"fuse_rand4k_iops": iters / (time.perf_counter() - t0)}

        out.update(await asyncio.to_thread(cold_rand))

        def cold_rand_j4():
            # fio numjobs=4 shape: 4 reader threads against the same
            # mount — the session dispatches concurrently, so this is
            # the daemon's rand-read THROUGHPUT (iodepth-1 per job);
            # plain fuse_rand4k_iops stays the serial-latency figure.
            # Seeds 101.. so no job replays cold_rand's seed-0 offsets
            # (those are in the page cache now — KEEP_CACHE hits would
            # inflate the figure).
            import threading
            iters, jobs = 512, 4
            done: list[int] = []

            def job(seed):
                rand_job(seed, iters)
                done.append(1)

            ts = [threading.Thread(target=job, args=(101 + s,))
                  for s in range(jobs)]
            t0 = time.perf_counter()
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            dt = time.perf_counter() - t0
            if len(done) != jobs:       # a job died: no silent inflation
                raise RuntimeError(
                    f"rand4k j4: only {len(done)}/{jobs} jobs finished")
            return {"fuse_rand4k_iops_j4": jobs * iters / dt}

        out.update(await asyncio.to_thread(cold_rand_j4))

        sess_task.cancel()
        await asyncio.to_thread(remount_sync)
        session.stop()
        await asyncio.sleep(0.3)
        session, sess_task = await mount()

        def cold_seq():
            t0 = time.perf_counter()
            n = 0
            with open(f"{mnt}/fio.bin", "rb", buffering=0) as f:
                while chunk := f.read(4 * MB):
                    n += len(chunk)
            return {"fuse_seq_read_gibs": n / (1024 ** 3)
                    / (time.perf_counter() - t0)}

        out.update(await asyncio.to_thread(cold_seq))
    except Exception as e:  # noqa: BLE001 — FUSE denied (container policy
        # etc.) must not discard every other measured result
        print(f"fuse bench skipped: {e}", file=sys.stderr)
    finally:
        if sess_task is not None:
            sess_task.cancel()
        try:
            fusermount_umount(mnt)
        except Exception:
            pass
        if session is not None:
            session.stop()
        sh.rmtree(mnt, ignore_errors=True)
    return out


def main():
    total_mb = int(os.environ.get("BENCH_TOTAL_MB", "256"))
    # this process is the only one that touches JAX (one process per
    # chip); without a TPU there is nothing to measure under these names
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench: JAX found platform {dev.platform!r}, not a TPU — "
              "refusing to run", file=sys.stderr)
        return 2
    from curvine_tpu.tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    # optional rpc.uvloop (CURVINE_RPC_UVLOOP=1): swap the policy before
    # the loop exists; the artifact's loop_impl records what actually ran
    from curvine_tpu.common.conf import ClusterConf
    from curvine_tpu.rpc.loops import install_event_loop
    install_event_loop(ClusterConf.load().rpc)
    results = asyncio.run(run_bench(total_mb=total_mb))
    value = round(results["read_gibs_into_hbm"], 3)
    out = {
        "metric": "cached-read GiB/s/chip into HBM",
        "value": value,
        "unit": "GiB/s",
        "vs_baseline": round(value / BASELINE_GIBS, 3),
        "backend": results["backend"],
        "device_kind": results["device_kind"],
        "dram_to_hbm_gibs": round(results["dram_to_hbm_gibs"], 3),
        "link_gibs": round(results["link_gibs"], 3),
        "pipeline_vs_link": round(results.get("pipeline_vs_link", 0), 3),
        "meta_qps": round(results.get("meta_qps", 0), 1),
        "meta_create_qps": round(results.get("meta_create_qps", 0), 1),
        "meta_create_batch_qps": round(
            results.get("meta_create_batch_qps", 0), 1),
        "meta_qps_native": round(results.get("meta_qps_native", 0), 1),
        "meta_create_shard_qps": round(
            results.get("meta_create_shard_qps", 0), 1),
        "meta_create_shard_curve": results.get(
            "meta_create_shard_curve", {}),
        "shard_backend": results.get("shard_backend", "none"),
        "shard_cpus": results.get("shard_cpus", os.cpu_count() or 1),
        "meta_stat_qps": round(results.get("meta_stat_qps", 0), 1),
        "meta_stat_cached_qps": round(
            results.get("meta_stat_cached_qps", 0), 1),
        "meta_cache_speedup": round(
            results.get("meta_cache_speedup", 0), 1),
        "open_read_p99_ms": round(results.get("open_read_p99_ms", 0), 3),
        "p99_cached_4k_read_us": round(
            results.get("p99_cached_4k_read_us", 0), 1),
        "p50_cached_4k_read_us": round(
            results.get("p50_cached_4k_read_us", 0), 1),
        "socket_p99_cached_4k_read_us": round(
            results.get("socket_p99_cached_4k_read_us", 0), 1),
        "shm_p99_speedup": round(results.get("shm_p99_speedup", 0), 2),
        "shm_read_gibs": round(results.get("shm_read_gibs", 0), 3),
        "shm_hits": int(results.get("shm_hits", 0)),
        "ladder_clients": int(results.get("ladder_clients", 0)),
        "ladder_achieved_qps": round(
            results.get("ladder_achieved_qps", 0), 1),
        "ladder_p50_us": round(results.get("ladder_p50_us", 0), 1),
        "ladder_p99_us": round(results.get("ladder_p99_us", 0), 1),
        "ladder_errors": int(results.get("ladder_errors", 0)),
        "rpc_rtt_us": round(results.get("rpc_rtt_us", 0), 1),
        "rpc_pipelined_qps": round(results.get("rpc_pipelined_qps", 0), 1),
        "loop_impl": results.get("loop_impl", "asyncio"),
        "p99_block_fetch_ms": round(results["p99_block_fetch_ms"], 3),
        "p50_block_fetch_ms": round(results["p50_block_fetch_ms"], 3),
        "read_gibs_host": round(results["read_gibs_host"], 3),
        "write_gibs": round(results["write_gibs"], 3),
        "tmpfs_raw_gibs": round(results["tmpfs_raw_gibs"], 3),
        "direct_read_gibs": results.get("direct_read_gibs", 0),
        "direct_buffered_gibs": results.get("direct_buffered_gibs", 0),
        "direct_buffered_cold": results.get("direct_buffered_cold", False),
        "direct_io_mode": results.get("direct_io_mode", "off"),
        "direct_io_fs": results.get("direct_io_fs", "?"),
        "hbm_tier_read_gibs": round(results.get("hbm_tier_read_gibs", 0), 3),
        "ckpt_broadcast_gibs": round(results.get("ckpt_broadcast_gibs", 0), 3),
        "vector_scan_mrows_s": round(results.get("vector_scan_mrows_s", 0), 3),
        "vector_ann_qps": round(results.get("vector_ann_qps", 0), 1),
        "vector_ann_recall10": round(
            results.get("vector_ann_recall10", 0), 3),
        "vector_ann_pq_qps": round(results.get("vector_ann_pq_qps", 0), 1),
        "vector_ann_pq_recall10": round(
            results.get("vector_ann_pq_recall10", 0), 3),
        "vector_ann_flat_qps": round(
            results.get("vector_ann_flat_qps", 0), 1),
        "vector_ann_flat_recall10": round(
            results.get("vector_ann_flat_recall10", 0), 3),
        "vector_ann_served_qps": round(
            results.get("vector_ann_served_qps", 0), 1),
        "vector_ann_batch_occupancy": results.get(
            "vector_ann_batch_occupancy", 0),
        "vector_index_build_s": round(
            results.get("vector_index_build_s", 0), 2),
        "vector_scan_bf16_mrows_s": round(
            results.get("vector_scan_bf16_mrows_s", 0), 3),
        "fuse_seq_read_gibs": round(results.get("fuse_seq_read_gibs", 0), 3),
        "fuse_seq_write_gibs": round(results.get("fuse_seq_write_gibs", 0), 3),
        "fuse_rand4k_iops": round(results.get("fuse_rand4k_iops", 0), 1),
        "fuse_rand4k_iops_j4": round(
            results.get("fuse_rand4k_iops_j4", 0), 1),
        "fuse_warm_read_gibs": round(results.get("fuse_warm_read_gibs", 0), 3),
        "fuse_warm_rand4k_iops": round(
            results.get("fuse_warm_rand4k_iops", 0), 1),
        "mfu": round(results.get("mfu", 0), 4),
        "train_step_ms": round(results.get("train_step_ms", 0), 2),
        "train_step_synth_ms": round(
            results.get("train_step_synth_ms", 0), 2),
        "ingest_overlap_ratio": round(
            results.get("ingest_overlap_ratio", 0), 4),
        "model_params_m": round(results.get("model_params_m", 0), 1),
        "baseline_note": "stand-in 2.0 GiB/s (no published baseline)",
    }
    if "direct_io_fallback" in results:
        out["direct_io_fallback"] = results["direct_io_fallback"]
    print(json.dumps(out))


if __name__ == "__main__":
    sys.exit(main())
