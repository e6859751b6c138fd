#!/usr/bin/env python
"""Open-loop concurrency ladder for the cached-read data plane.

Measures p50/p99/p999 latency of cached 4K reads under a PROCESS FLEET
of co-located clients with Poisson (open-loop) arrivals, stepping the
fleet 64 -> 1024 clients (docs/data-plane.md: ladder methodology).
Open-loop means latency includes queueing delay: an arrival is stamped
when the Poisson clock says it should happen, not when the client got
around to issuing it — so an overloaded rung shows its real tail
instead of the coordinated-omission mirage a closed loop reports.

Usage:
    python scripts/latency_ladder.py                    # 64,256,1024
    python scripts/latency_ladder.py --rungs 64,256 --duration 3 \
        --out benchmarks/latency_ladder.json
    python scripts/latency_ladder.py --quick            # smoke rung

The rig runs a MiniCluster (master + 1 MEM-tier worker) in this
process, writes one block-sized file, then forks worker PROCESSES
(``--procs``), each hosting an equal share of the rung's client
coroutines — real processes so 1K clients exercise 1K connections and
the SCM_RIGHTS side channel across address spaces, not one event loop
pretending. ``--no-shm`` reruns the same ladder with worker.shm_reads
off for A/B comparison."""

from __future__ import annotations

import argparse
import asyncio
import json
import math
import os
import random
import subprocess
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

READ_SIZE = 4096
MB = 1024 * 1024


# ---------------- child process: a share of one rung's fleet ---------

async def _one_client(master_addr: str, path: str, rate: float,
                      duration: float, seed: int, short_circuit: bool,
                      lat_us: list, errors: list) -> None:
    from curvine_tpu.client.unified import CurvineClient
    from curvine_tpu.common.conf import ClusterConf
    conf = ClusterConf()
    conf.client.master_addrs = [master_addr]
    conf.client.short_circuit = short_circuit
    c = CurvineClient(conf)
    rng = random.Random(seed)
    try:
        r = await c.open(path)
        slots = max(1, r.len // READ_SIZE - 1)
        # warm-up (excluded): block-info probe, fd/shm hand-off, conns
        for _ in range(3):
            await r.pread_view(rng.randrange(slots) * READ_SIZE,
                               READ_SIZE)
        loop = asyncio.get_running_loop()
        start = loop.time()
        t = start
        pending: list[asyncio.Task] = []

        async def one(sched: float) -> None:
            off = rng.randrange(slots) * READ_SIZE
            try:
                await r.pread_view(off, READ_SIZE)
                lat_us.append((loop.time() - sched) * 1e6)
            except Exception:  # noqa: BLE001 — counted, rung continues
                errors.append(1)

        while True:
            t += rng.expovariate(rate)
            if t - start >= duration:
                break
            now = loop.time()
            if t > now:
                await asyncio.sleep(t - now)
            # the arrival is stamped at its SCHEDULED time: if this
            # client fell behind, the backlog shows up as latency
            pending.append(asyncio.ensure_future(one(t)))
            if len(pending) >= 256:
                done = [p for p in pending if p.done()]
                for p in done:
                    pending.remove(p)
        if pending:
            await asyncio.gather(*pending, return_exceptions=True)
        await r.close()
    finally:
        await c.close()


async def _worker_main(cfg: dict) -> dict:
    lat_us: list = []
    errors: list = []
    await asyncio.gather(*(
        _one_client(cfg["master_addr"], cfg["path"], cfg["rate"],
                    cfg["duration"], cfg["seed"] + i,
                    cfg.get("short_circuit", True), lat_us, errors)
        for i in range(cfg["clients"])))
    return {"lat_us": lat_us, "errors": len(errors)}


# ---------------- parent: cluster + fleet orchestration --------------

def _pct(sorted_us: list, q: float) -> float:
    if not sorted_us:
        return float("nan")
    i = min(len(sorted_us) - 1, int(q * len(sorted_us)))
    return sorted_us[i]


def _parse_cpus(spec: str) -> list[int]:
    """'0-3,8' → [0, 1, 2, 3, 8]. Empty spec → [] (no pinning)."""
    cores: list[int] = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "-" in part:
            lo, hi = part.split("-", 1)
            cores.extend(range(int(lo), int(hi) + 1))
        else:
            cores.append(int(part))
    return cores


def _spawn_fleet(master_addr: str, path: str, clients: int, procs: int,
                 rate: float, duration: float, seed: int,
                 short_circuit: bool, cpus: list[int] | None = None) -> dict:
    """Run one rung: `procs` child processes splitting `clients`
    open-loop client coroutines; returns merged latency stats. With
    ``cpus``, child i is pinned to cpus[i % len(cpus)] — the multi-core
    tail rung: fleets stop time-sharing one scheduler runqueue and the
    ladder measures cross-core contention instead of context-switch
    noise."""
    procs = max(1, min(procs, clients))
    share = [clients // procs + (1 if i < clients % procs else 0)
             for i in range(procs)]
    children = []
    for i, k in enumerate(share):
        cfg = {"master_addr": master_addr, "path": path, "clients": k,
               "rate": rate, "duration": duration,
               "seed": seed + 10_000 * i, "short_circuit": short_circuit}
        if cpus:
            cfg["cpu"] = cpus[i % len(cpus)]
        p = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--_worker"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            cwd=_REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"})
        p.stdin.write(json.dumps(cfg).encode())
        p.stdin.close()
        children.append(p)
    lat: list = []
    errors = 0
    deadline = time.time() + duration + 60
    for p in children:
        out = p.stdout.read()
        p.wait(timeout=max(1, deadline - time.time()))
        if p.returncode != 0:
            raise RuntimeError(f"ladder worker exited {p.returncode}")
        res = json.loads(out)
        lat.extend(res["lat_us"])
        errors += res["errors"]
    lat.sort()
    return {"clients": clients, "procs": procs,
            "cpus": list(cpus) if cpus else [],
            "rate_per_client": rate, "duration_s": duration,
            "samples": len(lat), "errors": errors,
            "offered_qps": round(clients * rate, 1),
            "achieved_qps": round(len(lat) / duration, 1),
            "p50_us": round(_pct(lat, 0.50), 1),
            "p99_us": round(_pct(lat, 0.99), 1),
            "p999_us": round(_pct(lat, 0.999), 1)}


async def run_ladder(rungs=(64, 256, 1024), duration: float = 5.0,
                     rate: float = 50.0, procs: int = 0,
                     shm: bool = True, block_mb: int = 4,
                     short_circuit: bool = True, seed: int = 7,
                     cpus: list[int] | None = None) -> dict:
    """Spin up the cluster, write the hot file, walk the rungs."""
    from curvine_tpu.common.conf import ClusterConf
    from curvine_tpu.testing import MiniCluster
    import shutil
    if not procs:
        procs = min(os.cpu_count() or 4, 8)
    base = tempfile.mkdtemp(prefix="cv-ladder-")
    conf = ClusterConf()
    conf.data_dir = base
    conf.worker.shm_reads = shm
    size = block_mb * MB
    mc = MiniCluster(workers=1, base_dir=base, conf=conf,
                     block_size=size)
    await mc.start()
    out = {"read_size": READ_SIZE, "file_mb": block_mb,
           "shm": shm, "short_circuit": short_circuit,
           "cpus": list(cpus) if cpus else [], "rungs": []}
    try:
        c = mc.client()
        payload = os.urandom(size)
        await c.write_all("/ladder/hot.bin", payload)
        await c.close()
        for n in rungs:
            rung = await asyncio.to_thread(
                _spawn_fleet, mc.master.addr, "/ladder/hot.bin", n,
                procs, rate, duration, seed, short_circuit, cpus)
            out["rungs"].append(rung)
            print(f"  {n:>5} clients  {rung['achieved_qps']:>9.0f} qps  "
                  f"p50 {rung['p50_us']:>8.1f}us  "
                  f"p99 {rung['p99_us']:>8.1f}us  "
                  f"p999 {rung['p999_us']:>9.1f}us  "
                  f"({rung['samples']} samples, {rung['errors']} errors)",
                  file=sys.stderr)
    finally:
        await mc.stop()
        shutil.rmtree(base, ignore_errors=True)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rungs", default="64,256,1024",
                    help="comma-separated client counts")
    ap.add_argument("--duration", type=float, default=5.0,
                    help="seconds of open-loop load per rung")
    ap.add_argument("--rate", type=float, default=50.0,
                    help="Poisson arrivals/sec per client")
    ap.add_argument("--procs", type=int, default=0,
                    help="fleet processes (0 = min(cpus, 8))")
    ap.add_argument("--block-mb", type=int, default=4)
    ap.add_argument("--cpus", default="",
                    help="pin fleet processes round-robin across these "
                         "cores, e.g. '0-3' or '0,2,4,6' (recorded in "
                         "the artifact; empty = no pinning)")
    ap.add_argument("--no-shm", action="store_true",
                    help="disable worker.shm_reads (A/B baseline)")
    ap.add_argument("--no-short-circuit", action="store_true",
                    help="force every read through the socket path")
    ap.add_argument("--quick", action="store_true",
                    help="one 64-client smoke rung, short duration")
    ap.add_argument("--out", default="",
                    help="write the JSON artifact here")
    ap.add_argument("--_worker", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args._worker:
        cfg = json.loads(sys.stdin.read())
        cpu = cfg.get("cpu")
        if cpu is not None and hasattr(os, "sched_setaffinity"):
            try:
                os.sched_setaffinity(0, {int(cpu)})
            except OSError:
                pass        # core offline/cpuset-restricted: run unpinned
        res = asyncio.run(_worker_main(cfg))
        sys.stdout.write(json.dumps(res))
        return 0

    rungs = [int(r) for r in args.rungs.split(",") if r.strip()]
    duration = args.duration
    if args.quick:
        rungs, duration = [64], min(duration, 2.0)
    result = asyncio.run(run_ladder(
        rungs=rungs, duration=duration, rate=args.rate,
        procs=args.procs, shm=not args.no_shm,
        block_mb=args.block_mb,
        short_circuit=not args.no_short_circuit, seed=7,
        cpus=_parse_cpus(args.cpus)))
    result["generated_at"] = time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                           time.gmtime())
    text = json.dumps(result, indent=2)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
