#!/usr/bin/env python
"""Namespace-scale curve: drive 1M -> 5M -> 10M file creations through the
master write path (journal group commit + KV batch) on the native KV
engine and measure, at each milestone:

  * creation rate (cumulative and over the last interval)
  * process RSS (the KV store keeps the namespace OUT of RAM; only the
    bounded inode/dentry caches and the engine memtable should grow)
  * compaction debt (KV segment count waiting for merge)
  * average journal group size

then time a cold restart (journal-tail replay over the KV applied_seq).

In-process by design: the curve isolates the metadata write path itself
(journal + store + group commit), not the RPC plane.

Usage:
  python scripts/namespace_scale.py                  # full 10M curve
  python scripts/namespace_scale.py --quick          # 50K CI smoke
  python scripts/namespace_scale.py --files 2000000 --milestones 1000000,2000000
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

FILES_PER_DIR = 4096


def rss_mb() -> float:
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) / 1024.0
    except OSError:
        pass
    return 0.0


def kv_segments(store) -> int:
    kv = getattr(store, "kv", None)
    if kv is None:
        return 0
    segs = getattr(kv, "segment_count", None)
    if segs is None:
        segs = len(getattr(kv, "segments", ()))
    return int(segs)


def build_fs(base: str, engine: str, fsync: bool, group_ms: float):
    from curvine_tpu.common.journal import GroupCommitter, Journal
    from curvine_tpu.master.filesystem import MasterFilesystem
    from curvine_tpu.master.store import KvMetaStore

    journal = Journal(os.path.join(base, "journal"), fsync=fsync)
    store = KvMetaStore(os.path.join(base, "meta"), engine=engine)
    fs = MasterFilesystem(journal=journal, store=store)
    fs.recover()
    fs.committer = GroupCommitter(journal, fs.store, window_ms=group_ms,
                                  max_entries=1024)
    return fs


async def run(args) -> dict:
    base = args.base_dir
    shutil.rmtree(base, ignore_errors=True)
    os.makedirs(base, exist_ok=True)
    fs = build_fs(base, args.engine, args.fsync, args.group_ms)
    engine = type(fs.store.kv).__name__
    milestones = sorted(int(m) for m in args.milestones.split(","))
    total = max(args.files, milestones[-1])

    points = []
    t_start = time.perf_counter()
    t_prev, n_prev = t_start, 0
    i = 0
    for ms in milestones:
        while i < ms:
            hi = min(i + args.batch, ms)
            for j in range(i, hi):
                if j % FILES_PER_DIR == 0:
                    fs.mkdir(f"/d{j // FILES_PER_DIR}", create_parent=False)
                d, _ = divmod(j, FILES_PER_DIR)
                fs.create_file(f"/d{d}/f{j}", block_size=4 << 20,
                               client_name="nsscale")
            i = hi
            # the ack point: one journal flush + one KV batch per group
            await fs.committer.sync()
        now = time.perf_counter()
        point = {
            "files": i,
            "elapsed_s": round(now - t_start, 1),
            "creates_per_s": round(i / (now - t_start), 1),
            "interval_creates_per_s": round((i - n_prev) / (now - t_prev), 1),
            "rss_mb": round(rss_mb(), 1),
            "kv_segments": kv_segments(fs.store),
            "avg_group_size": round(
                fs.committer.entries / max(1, fs.committer.groups), 1),
        }
        points.append(point)
        print(json.dumps(point), flush=True)
        t_prev, n_prev = now, i

    # cold restart: KV already holds applied_seq; recovery replays only
    # the journal tail past it
    fs.flush_group()
    count_before = fs.tree.count()
    fs.journal.close()
    fs.store.close()
    t0 = time.perf_counter()
    fs2 = build_fs_existing(base, args.engine, args.fsync, args.group_ms)
    restart_s = time.perf_counter() - t0
    count_after = fs2.tree.count()
    fs2.journal.close()
    fs2.store.close()

    out = {
        "engine": engine,
        "files": total,
        "fsync": args.fsync,
        "group_ms": args.group_ms,
        "batch": args.batch,
        "curve": points,
        "restart_s": round(restart_s, 3),
        "inodes_before_restart": count_before,
        "inodes_after_restart": count_after,
        "ok": count_before == count_after,
    }
    if not args.keep:
        shutil.rmtree(base, ignore_errors=True)
    return out


def build_fs_existing(base: str, engine: str, fsync: bool, group_ms: float):
    """Reopen WITHOUT wiping — the restart-time measurement."""
    return build_fs(base, engine, fsync, group_ms)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--files", type=int, default=10_000_000)
    p.add_argument("--milestones", default="1000000,5000000,10000000")
    p.add_argument("--quick", action="store_true",
                   help="50K-file smoke (tests/test_namespace_scale.py, slow)")
    p.add_argument("--batch", type=int, default=1024,
                   help="creates per group-commit sync (the RPC-equivalent)")
    p.add_argument("--engine", default="native",
                   choices=["native", "python", "auto"])
    p.add_argument("--fsync", action="store_true")
    p.add_argument("--group-ms", type=float, default=1.0)
    p.add_argument("--base-dir", default="/tmp/curvine-nsscale")
    p.add_argument("--keep", action="store_true",
                   help="keep the journal/meta dirs after the run")
    p.add_argument("--out", default="",
                   help="also write the result JSON to this path")
    args = p.parse_args()
    if args.quick:
        args.files = 50_000
        args.milestones = "50000"
    res = asyncio.run(run(args))
    print(json.dumps(res, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(res, f, indent=2)
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
