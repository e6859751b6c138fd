"""`cv` command-line interface.

Parity: curvine-cli/src/ (cmds/fs/* ls,mkdir,put,get,cat,rm,mv,stat,touch,
chmod,chown,count,df,du,free,blocks; cmds/report,node,mount,umount,load,
load_status,load_cancel,bench) plus server daemons (curvine-server bin)."""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

from curvine_tpu.common import errors as err
from curvine_tpu.common.conf import ClusterConf
from curvine_tpu.common.types import JobState, SetAttrOpts


def _conf(args) -> ClusterConf:
    conf = ClusterConf.load(getattr(args, "conf", None))
    if getattr(args, "master", None):
        conf.client.master_addrs = [args.master]
    return conf


def _human(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024:
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024
    return f"{n:.1f}PiB"


def _mode_str(st) -> str:
    kind = "d" if st.is_dir else ("l" if st.target else "-")
    bits = "rwxrwxrwx"
    out = "".join(b if st.mode & (1 << (8 - i)) else "-"
                  for i, b in enumerate(bits))
    return kind + out


async def _client(args):
    from curvine_tpu.client import CurvineClient
    return CurvineClient(_conf(args))


# ---------------- fs commands ----------------

async def cmd_ls(args):
    c = await _client(args)
    try:
        for st in await c.meta.list_status(args.path):
            ts = time.strftime("%Y-%m-%d %H:%M", time.localtime(st.mtime / 1000))
            print(f"{_mode_str(st)} {st.replicas:>2} {st.owner:>8} "
                  f"{st.group:>8} {st.len:>12} {ts} {st.path}")
    finally:
        await c.close()


async def cmd_mkdir(args):
    c = await _client(args)
    try:
        await c.meta.mkdir(args.path, create_parent=True)
        print(f"created {args.path}")
    finally:
        await c.close()


async def cmd_put(args):
    c = await _client(args)
    try:
        total = 0
        t0 = time.perf_counter()
        w = await c.create(args.dst, overwrite=args.force)
        with open(args.src, "rb") as f:
            while chunk := f.read(4 * 1024 * 1024):
                await w.write(chunk)
                total += len(chunk)
        await w.close()
        dt = time.perf_counter() - t0
        print(f"put {args.src} -> {args.dst}: {_human(total)} "
              f"in {dt:.2f}s ({_human(total / max(dt, 1e-9))}/s)")
    finally:
        await c.close()


async def cmd_get(args):
    c = await _client(args)
    try:
        # unified open: freed/uncached files under mounts stream from
        # the UFS instead of reading an empty cache entry
        r = await c.unified_open(args.src)
        cc = c.conf.client
        t0 = time.perf_counter()
        total = 0
        with open(args.dst, "wb") as f:
            if r.len >= cc.large_file_size and cc.read_parallel > 1:
                # large file: sharded parallel windows (each window's
                # slices stream from different workers concurrently)
                window = max(cc.read_chunk_size * cc.read_parallel,
                             64 << 20)
                while total < r.len:
                    buf = await r.read_range(total,
                                             min(window, r.len - total),
                                             cc.read_parallel)
                    if len(buf) == 0:
                        break
                    f.write(buf)
                    total += len(buf)
            else:
                async for chunk in r.chunks():
                    f.write(chunk)
                    total += len(chunk)
        dt = time.perf_counter() - t0
        print(f"get {args.src} -> {args.dst}: {_human(total)} "
              f"in {dt:.2f}s ({_human(total / max(dt, 1e-9))}/s)")
    finally:
        await c.close()


async def cmd_cat(args):
    c = await _client(args)
    try:
        r = await c.unified_open(args.path)
        async for chunk in r.chunks():
            sys.stdout.buffer.write(chunk)
        sys.stdout.buffer.flush()
    finally:
        await c.close()


async def cmd_rm(args):
    c = await _client(args)
    try:
        await c.meta.delete(args.path, recursive=args.recursive)
        print(f"deleted {args.path}")
    finally:
        await c.close()


async def cmd_mv(args):
    c = await _client(args)
    try:
        await c.meta.rename(args.src, args.dst)
        print(f"renamed {args.src} -> {args.dst}")
    finally:
        await c.close()


async def cmd_stat(args):
    c = await _client(args)
    try:
        st = await c.meta.file_status(args.path)
        print(json.dumps(st.to_wire(), indent=2, default=str))
    finally:
        await c.close()


async def cmd_touch(args):
    c = await _client(args)
    try:
        if not await c.meta.exists(args.path):
            await c.write_all(args.path, b"")
        else:
            import curvine_tpu.common.types as t
            await c.meta.set_attr(args.path, SetAttrOpts(mtime=t.now_ms()))
        print(f"touched {args.path}")
    finally:
        await c.close()


async def cmd_chmod(args):
    c = await _client(args)
    try:
        await c.meta.set_attr(args.path, SetAttrOpts(mode=int(args.mode, 8)))
    finally:
        await c.close()


async def cmd_chown(args):
    c = await _client(args)
    try:
        owner, _, group = args.owner.partition(":")
        await c.meta.set_attr(args.path, SetAttrOpts(
            owner=owner or None, group=group or None))
    finally:
        await c.close()


async def _summary(c, path):
    cs = await c.content_summary(path)
    return cs["length"], cs["file_count"], cs["directory_count"]


async def cmd_du(args):
    c = await _client(args)
    try:
        size, files, dirs = await _summary(c, args.path)
        print(f"{_human(size)}\t{args.path}")
    finally:
        await c.close()


async def cmd_count(args):
    c = await _client(args)
    try:
        size, files, dirs = await _summary(c, args.path)
        print(f"{dirs:>12} {files:>12} {_human(size):>12} {args.path}")
    finally:
        await c.close()


async def cmd_df(args):
    c = await _client(args)
    try:
        info = await c.meta.master_info()
        used = info.capacity - info.available
        pct = 100 * used / info.capacity if info.capacity else 0
        print(f"Filesystem  Size  Used  Avail  Use%")
        print(f"curvine  {_human(info.capacity)}  {_human(used)}  "
              f"{_human(info.available)}  {pct:.0f}%")
    finally:
        await c.close()


async def cmd_free(args):
    c = await _client(args)
    try:
        n = await c.meta.free(args.path, recursive=args.recursive)
        print(f"freed {n} cached files under {args.path}")
    finally:
        await c.close()


async def cmd_blocks(args):
    c = await _client(args)
    try:
        fb = await c.meta.get_block_locations(args.path)
        for lb in fb.block_locs:
            if lb.ec is not None and not lb.locs:
                cells = " ".join(
                    f"{cell['block_id']}@" + (",".join(
                        str(a["worker_id"]) for a in cell["locs"]) or "-")
                    for cell in lb.ec["cells"])
                print(f"block {lb.block.id} offset={lb.offset} "
                      f"len={lb.block.len} ec={lb.ec['profile']} "
                      f"cells=[{cells}]")
                continue
            locs = ",".join(f"{l.hostname}:{l.rpc_port}" for l in lb.locs)
            print(f"block {lb.block.id} offset={lb.offset} "
                  f"len={lb.block.len} locs=[{locs}]")
    finally:
        await c.close()


# ---------------- cluster commands ----------------

async def cmd_report(args):
    c = await _client(args)
    try:
        info = await c.meta.master_info()
        print(f"Active master: {info.active_master}")
        print(f"Inodes: {info.inode_num}  Blocks: {info.block_num}")
        print(f"Capacity: {_human(info.capacity)}  "
              f"Available: {_human(info.available)}")
        from curvine_tpu.common.types import WorkerState
        retired = [w for w in info.lost_workers
                   if w.state == WorkerState.DECOMMISSIONED]
        print(f"Live workers: {len(info.live_workers)}  "
              f"Lost workers: {len(info.lost_workers) - len(retired)}"
              + (f"  Decommissioned: {len(retired)}" if retired else ""))
        for w in info.live_workers:
            tiers = ", ".join(
                f"{s.storage_type.name}:{_human(s.available)}/{_human(s.capacity)}"
                + (f"!{s.health.upper()}" if s.health != "healthy" else "")
                for s in w.storages)
            coords = f" ici={w.ici_coords}" if w.ici_coords else ""
            print(f"  worker {w.address.worker_id} "
                  f"{w.address.hostname}:{w.address.rpc_port} [{tiers}]{coords}")
        # monitor + watchdog rollup (parity: master_monitor.rs); a
        # pre-r5 master has no CLUSTER_HEALTH handler — degrade quietly
        try:
            h = await c.meta.cluster_health()
        except err.CurvineError:
            return
        line = f"Health: {h['status']} ({h['role']})"
        if h.get("problems"):
            line += " — " + "; ".join(h["problems"])
        print(line)
        wd = h.get("watchdog") or {}
        for o in wd.get("stuck_ops", []):
            print(f"  STUCK op {o['op']}({o['detail']}) for {o['age_s']}s")
        for l in wd.get("long_held_locks", []):
            print(f"  LONG-HELD lock {l['path']} by {l['owner']} "
                  f"for {l['age_s']}s")
        # sharded-namespace table (empty / absent on unsharded masters)
        # + the read fan-out plane rollup riding the same RPC
        try:
            rp = await c.meta.read_plane_stats()
        except err.CurvineError:
            return
        mcache = rp.get("meta_cache") or {}
        hits, misses = mcache.get("hits", 0), mcache.get("misses", 0)
        if hits + misses:
            print(f"Meta cache: {hits / (hits + misses) * 100:.1f}% hit "
                  f"rate ({int(hits)}/{int(hits + misses)} lookups)  "
                  f"invalidations: {int(mcache.get('invalidations', 0))}")
        ls = rp.get("leases")
        if ls:
            print(f"Read leases: {ls.get('dirs', 0)} dirs  "
                  f"{ls.get('holders', 0)} holders  "
                  f"pushes: {ls.get('pushes', 0)} "
                  f"({ls.get('push_errors', 0)} errors)  "
                  f"ttl: {ls.get('ttl_ms', 0)} ms")
        fm = rp.get("fastmeta")
        if fm:
            line = (f"Fast meta: served: {fm.get('served', 0)}  "
                    f"fallbacks: {fm.get('fallbacks', 0)}")
            if fm.get("shard_hits"):
                line += "  shard hits: " + "/".join(
                    str(h) for h in fm["shard_hits"])
            print(line)
        wp = rp.get("write_plane")
        if wp:
            print(f"Write plane: failovers: "
                  f"{int(wp.get('replica_failover', 0))}  "
                  f"replayed: {_human(int(wp.get('block_replay_bytes', 0)))}  "
                  f"degraded commits: {int(wp.get('degraded_commits', 0))}")
        dp = rp.get("read_plane")
        if dp:
            print(f"Read plane: shm hits: {int(dp.get('shm_hits', 0))}  "
                  f"warm hits: {int(dp.get('shm_warm_hits', 0))}  "
                  f"fallbacks: {int(dp.get('shm_fallbacks', 0))}"
                  f"/{int(dp.get('shm_warm_fallbacks', 0))} warm  "
                  f"zero-copy: "
                  f"{_human(int(dp.get('zero_copy_bytes', 0)))}")
        hl = rp.get("replication")
        if hl:
            print(f"Healing rail: replicates: "
                  f"{int(hl.get('replicates', 0))}  "
                  f"evacuates: {int(hl.get('evacuates', 0))}  "
                  f"reconstructs: {int(hl.get('reconstructs', 0))}  "
                  f"retires: {int(hl.get('retires', 0))}  "
                  f"verdicts: {int(hl.get('verdict.bit_rot', 0))} bit-rot"
                  f" / {int(hl.get('verdict.truncated', 0))} truncated")
        ep = rp.get("ec_plane")
        if ep:
            print(f"EC plane: stripes committed: "
                  f"{int(ep.get('stripes_committed', 0))}  "
                  f"degraded reads: {int(ep.get('degraded_reads', 0))}")
        cp = rp.get("cache_plane")
        if cp:
            tier0 = cp.pop("tier0", None)
            store = cp.pop("store", {})
            for tier in sorted(cp):
                st = cp[tier]
                misses = int(st.get("misses",
                                    store.get("misses", 0) if tier == "mem"
                                    else 0))
                print(f"Cache plane [{tier}]: hits: "
                      f"{int(st.get('hits', 0))}  misses: {misses}  "
                      f"ghost hits: {int(st.get('ghost_hits', 0))}  "
                      f"scan evicted: {int(st.get('scan_evicted', 0))}  "
                      f"admits: {int(st.get('admits', 0))}")
            if tier0:
                occ = "  ".join(f"{t}={_human(int(b))}"
                                for t, b in sorted(tier0.items()))
                print(f"Cache plane [tier0 occupancy]: {occ}")
        ip = rp.get("ici_plane")
        if ip:
            # broadcast GiB/s = aggregate delivered bandwidth of the
            # tree-scheduled checkpoint rail (bytes × replicas / time)
            gibs = ""
            if ip.get("broadcast_ms"):
                gibs = (f"  broadcast: "
                        f"{ip.get('broadcast_bytes', 0) / (1 << 30) / (ip['broadcast_ms'] / 1000):.2f} GiB/s")
            print(f"ICI plane: hbm exports: "
                  f"{int(ip.get('hbm_exports', 0))}  "
                  f"peer pulls: {int(ip.get('peer_pulls', 0))}  "
                  f"ici transfers: {int(ip.get('transfers', 0))}  "
                  f"tcp fallbacks: {int(ip.get('tcp_fallbacks', 0))}"
                  f"{gibs}")
        rows = rp.get("shards") or []
        if rows:
            print(f"Namespace shards: {len(rows)}")
            print("  shard  state        qps   inodes   blocks  "
                  "jseq  qdepth  addr")
            for r in rows:
                print(f"  {r.get('shard', '?'):>5}  "
                      f"{r.get('state', '?'):<11}  "
                      f"{r.get('qps', 0):>5.0f}  "
                      f"{r.get('inodes', 0):>7}  {r.get('blocks', 0):>7}  "
                      f"{r.get('journal_seq', 0):>4}  "
                      f"{r.get('queue_depth', 0):>6}  {r.get('addr', '')}")
        # tenants table (admission plane; absent on a pre-QoS master —
        # degrade quietly like the shard table)
        try:
            qs = await c.meta.tenant_stats()
        except err.CurvineError:
            return
        tenants = qs.get("tenants") or {}
        if tenants:
            print(f"Tenants: {len(tenants)}  "
                  f"shed_level={qs.get('shed_level', 0)}")
            print("  tenant            qps  quota  prio  inflight  "
                  "admitted  throttled  shed")
            for name in sorted(tenants):
                t = tenants[name]
                quota = t.get("quota_qps", 0)
                print(f"  {name:<15} {t.get('qps', 0):>6.1f}  "
                      f"{'inf' if not quota else f'{quota:.0f}':>5}  "
                      f"{t.get('priority', 0):>4}  "
                      f"{t.get('inflight', 0):>8}  "
                      f"{t.get('admitted', 0):>8}  "
                      f"{t.get('throttled', 0):>9}  {t.get('shed', 0):>4}")
        # raft membership table (absent on single-node / raft-less
        # masters — degrade quietly like the tables above)
        try:
            rs = await c.meta.raft_status()
        except err.CurvineError:
            return
        if rs and rs.get("voters"):
            print(f"Raft: term={rs.get('term', 0)} "
                  f"leader={rs.get('leader_id', 0)} "
                  f"commit={rs.get('commit_seq', 0)} "
                  f"conf_ver={rs.get('conf_ver', 0)}")
            match = rs.get("match") or {}
            last = rs.get("last_seq", 0)
            print("  node  role     lag  addr")
            for role, members in (("voter", rs.get("voters") or {}),
                                  ("learner", rs.get("learners") or {})):
                for nid in sorted(members, key=int):
                    if int(nid) == rs.get("leader_id"):
                        lag = "-"
                    elif str(nid) in match or nid in match:
                        m = match.get(str(nid), match.get(nid, 0))
                        lag = str(max(0, last - m))
                    else:
                        lag = "?"
                    print(f"  {nid:>4}  {role:<7}  {lag:>3}  {members[nid]}")
    finally:
        await c.close()


async def cmd_node(args):
    c = await _client(args)
    try:
        action = getattr(args, "action", "list") or "list"
        if action == "list":
            info = await c.meta.master_info()
            for w in info.live_workers + info.lost_workers:
                print(f"{w.address.worker_id}\t"
                      f"{w.address.hostname}:{w.address.rpc_port}\t"
                      f"{w.state.name}")
            return
        from curvine_tpu.common.types import WorkerState
        if not args.worker_id or not str(args.worker_id).isdigit():
            print(f"usage: cv node {action} <worker_id>  "
                  f"(see `cv node list`)", file=sys.stderr)
            raise SystemExit(2)
        state = await c.meta.decommission_worker(
            int(args.worker_id), on=action == "decommission")
        print(f"worker {args.worker_id}: {WorkerState(state).name}"
              if state >= 0 else
              f"worker {args.worker_id}: intent cleared (not registered)")
    finally:
        await c.close()


async def cmd_raft(args):
    """Raft membership lifecycle: status / add / remove / transfer.

    ``add`` joins the target as a *learner*; the leader auto-promotes it
    to voter once its replication lag drops under ``raft_promote_lag``.
    ``remove`` drops a voter or learner (the leader refuses to remove
    itself — transfer first). ``transfer`` drains leadership to the
    most-caught-up voter, or to an explicit node id."""
    c = await _client(args)
    try:
        action = args.action
        if action == "status":
            rs = await c.meta.raft_status()
            print(f"node={rs.get('node_id')} role={rs.get('role')} "
                  f"term={rs.get('term')} leader={rs.get('leader_id')} "
                  f"commit={rs.get('commit_seq')} "
                  f"last={rs.get('last_seq')} "
                  f"conf_ver={rs.get('conf_ver')}")
            for role, members in (("voter", rs.get("voters") or {}),
                                  ("learner", rs.get("learners") or {})):
                for nid in sorted(members, key=int):
                    print(f"  {role} {nid} {members[nid]}")
            if rs.get("transferring"):
                print("  (leadership transfer in progress)")
            return
        if action == "add":
            if not args.node_id or not args.addr:
                print("usage: cv raft add <node_id> <host:port>",
                      file=sys.stderr)
                raise SystemExit(2)
            rep = await c.meta.raft_member_change(
                "add_learner", int(args.node_id), args.addr)
            print(f"learner {args.node_id} added "
                  f"(conf_ver={rep.get('ver', '?')}); "
                  f"auto-promotes when caught up")
            return
        if action == "remove":
            if not args.node_id:
                print("usage: cv raft remove <node_id>", file=sys.stderr)
                raise SystemExit(2)
            rep = await c.meta.raft_member_change(
                "remove", int(args.node_id))
            print(f"node {args.node_id} removed "
                  f"(conf_ver={rep.get('ver', '?')})")
            return
        # transfer: node_id optional — leader picks the most caught-up
        target = int(args.node_id) if args.node_id else None
        new_leader = await c.meta.raft_transfer(target)
        print(f"leadership transferred to node {new_leader}")
    finally:
        await c.close()


_DUR = {"s": 1000, "m": 60_000, "h": 3_600_000, "d": 86_400_000}


def _dur_ms(s: str | None) -> int:
    if not s:
        return 0
    s = s.strip().lower()
    if s[-1] in _DUR:
        return int(float(s[:-1]) * _DUR[s[-1]])
    return int(s)               # bare number: milliseconds


async def cmd_mount(args):
    from curvine_tpu.common.types import TtlAction
    c = await _client(args)
    try:
        props = dict(kv.split("=", 1) for kv in (args.prop or []))
        ttl_ms = _dur_ms(args.ttl)
        m = await c.meta.mount(
            args.cv_path, args.ufs_path, properties=props,
            auto_cache=args.auto_cache, ttl_ms=ttl_ms,
            ttl_action=int(TtlAction[args.ttl_action.upper()]) if ttl_ms
            else 0,
            storage_type=args.storage or "",
            block_size=args.block_size, replicas=args.replicas,
            access_mode="r" if args.read_only else "rw")
        extras = []
        if m.ttl_ms:
            extras.append(f"ttl={m.ttl_ms}ms/{m.ttl_action.name.lower()}")
        if m.access_mode == "r":
            extras.append("read-only")
        if m.storage_type:
            extras.append(f"storage={m.storage_type}")
        tail = f" [{', '.join(extras)}]" if extras else ""
        print(f"mounted {m.ufs_path} at {m.cv_path} (id={m.mount_id}){tail}")
    finally:
        await c.close()


async def cmd_umount(args):
    c = await _client(args)
    try:
        await c.meta.umount(args.cv_path)
        print(f"unmounted {args.cv_path}")
    finally:
        await c.close()


async def cmd_mounts(args):
    c = await _client(args)
    try:
        for m in await c.meta.mount_table():
            print(f"{m.cv_path} -> {m.ufs_path} "
                  f"(auto_cache={m.auto_cache}, write={m.write_type.name})")
    finally:
        await c.close()


async def cmd_load(args):
    c = await _client(args)
    try:
        job_id = await c.meta.submit_load(args.path, recursive=True,
                                          replicas=args.replicas)
        print(f"submitted load job {job_id}")
        if args.wait:
            while True:
                job = await c.meta.job_status(job_id)
                done = sum(1 for t in job.tasks
                           if t.state == JobState.COMPLETED)
                print(f"  {job.state.name}: {done}/{len(job.tasks)} tasks")
                if job.state in (JobState.COMPLETED, JobState.FAILED,
                                 JobState.CANCELLED):
                    if job.message:
                        print(f"  {job.message}", file=sys.stderr)
                    break
                await asyncio.sleep(1)
    finally:
        await c.close()


async def cmd_quota(args):
    c = await _client(args)
    try:
        from curvine_tpu.common.types import SetAttrOpts
        if args.action == "set":
            add = {}
            if args.bytes is not None:
                add["quota.bytes"] = str(args.bytes).encode()
            if args.files is not None:
                add["quota.files"] = str(args.files).encode()
            await c.meta.set_attr(args.path, SetAttrOpts(add_x_attr=add))
            print(f"quota set on {args.path}: {add}")
        elif args.action == "clear":
            await c.meta.set_attr(args.path, SetAttrOpts(
                remove_x_attr=["quota.bytes", "quota.files"]))
            print(f"quota cleared on {args.path}")
        else:
            st = await c.meta.file_status(args.path)
            size, files, dirs = await _summary(c, args.path)
            qb = st.x_attr.get("quota.bytes")
            qf = st.x_attr.get("quota.files")
            fmt = lambda v: v.decode() if isinstance(v, bytes) else (v or "-")
            print(f"{args.path}: bytes={fmt(qb)} (used {size})  "
                  f"files={fmt(qf)} (used {files})")
    finally:
        await c.close()


async def cmd_export(args):
    c = await _client(args)
    try:
        job_id = await c.meta.submit_export(args.path)
        print(f"submitted export job {job_id}")
        if args.wait:
            while True:
                job = await c.meta.job_status(job_id)
                done = sum(1 for t in job.tasks
                           if t.state == JobState.COMPLETED)
                print(f"  {job.state.name}: {done}/{len(job.tasks)} tasks")
                if job.state in (JobState.COMPLETED, JobState.FAILED,
                                 JobState.CANCELLED):
                    if job.message:
                        print(f"  {job.message}", file=sys.stderr)
                    break
                await asyncio.sleep(1)
    finally:
        await c.close()


async def cmd_load_status(args):
    c = await _client(args)
    try:
        job = await c.meta.job_status(args.job_id)
        print(json.dumps(job.to_wire(), indent=2, default=str))
    finally:
        await c.close()


async def cmd_load_cancel(args):
    c = await _client(args)
    try:
        await c.meta.cancel_job(args.job_id)
        print(f"cancelled {args.job_id}")
    finally:
        await c.close()


# ---------------- erasure coding ----------------

async def cmd_ec(args):
    """EC controls (docs/erasure-coding.md): `set-policy` stamps an
    RS(k,m) profile on a file or directory subtree; `convert` submits
    the job that stripes its cold replicated blocks and retires the
    extra copies once each stripe commits."""
    from curvine_tpu.common.ec import ECProfile
    c = await _client(args)
    try:
        if args.action == "set-policy":
            if not args.profile:
                print("usage: cv ec set-policy <path> <rs-K-M>",
                      file=sys.stderr)
                raise SystemExit(2)
            prof = ECProfile.parse(args.profile)    # validate before RPC
            await c.meta.set_attr(args.path, SetAttrOpts(ec=prof.name))
            print(f"ec policy {prof.name} set on {args.path}")
            return
        job_id = await c.meta.submit_job("ec_convert", args.path)
        print(f"submitted ec convert job {job_id}")
        if args.wait:
            while True:
                job = await c.meta.job_status(job_id)
                done = sum(1 for t in job.tasks
                           if t.state == JobState.COMPLETED)
                print(f"  {job.state.name}: {done}/{len(job.tasks)} tasks")
                if job.state in (JobState.COMPLETED, JobState.FAILED,
                                 JobState.CANCELLED):
                    if job.message:
                        print(f"  {job.message}", file=sys.stderr)
                    break
                await asyncio.sleep(1)
    finally:
        await c.close()


async def cmd_fsck(args):
    """Stripe audit: walk every block of <path>. Replicated blocks just
    report their live copy count; erasure-coded blocks check each cell
    for a live holder and the stripe for fault-domain spread (two cells
    on one worker die together). --repair reports lost cells to the
    master so reconstruction starts now instead of at the next scan."""
    from curvine_tpu.common.ec import ECProfile
    from curvine_tpu.rpc import RpcCode
    c = await _client(args)
    problems = 0
    missing: list[int] = []
    try:
        fb = await c.meta.get_block_locations(args.path)
        for lb in fb.block_locs:
            if lb.ec is None or lb.locs:
                state = "ok" if lb.locs else "MISSING"
                if not lb.locs:
                    problems += 1
                    missing.append(lb.block.id)
                print(f"block {lb.block.id} replicated x{len(lb.locs)} "
                      f"[{state}]")
                continue
            prof = ECProfile.parse(lb.ec["profile"])
            cells = lb.ec["cells"]
            lost = [cell["block_id"] for cell in cells
                    if not cell["locs"]]
            holders = [a["worker_id"] for cell in cells
                       for a in cell["locs"][:1]]
            crowded = len(holders) - len(set(holders))
            if len(lost) > prof.m:
                state = "LOST"          # past decodability: m+1 gone
            elif lost:
                state = "DEGRADED"
            elif crowded:
                state = "crowded"
            else:
                state = "ok"
            if lost:
                problems += 1
                missing.extend(lost)
            line = (f"block {lb.block.id} {prof.name} cells "
                    f"{len(cells) - len(lost)}/{len(cells)} live")
            if crowded:
                line += f", {crowded} co-located"
            print(f"{line} [{state}]")
        if args.repair and missing:
            await c.meta.call(RpcCode.REPORT_UNDER_REPLICATED_BLOCKS,
                              {"block_ids": missing})
            print(f"reported {len(missing)} lost cells/blocks for repair")
        if problems:
            print(f"fsck: {problems} problem block(s) under {args.path}",
                  file=sys.stderr)
            return 1
        print(f"fsck: {args.path} healthy")
    finally:
        await c.close()


async def cmd_bench(args):
    from curvine_tpu.client import CurvineClient
    c = CurvineClient(_conf(args))
    try:
        size = args.size_mb * 1024 * 1024
        data = os.urandom(min(size, 8 * 1024 * 1024))
        path = "/cv-bench-tmp"
        t0 = time.perf_counter()
        w = await c.create(path, overwrite=True)
        written = 0
        while written < size:
            await w.write(data[:min(len(data), size - written)])
            written += len(data)
        await w.close()
        wdt = time.perf_counter() - t0
        t0 = time.perf_counter()
        r = await c.open(path)
        total = 0
        async for chunk in r.chunks():
            total += len(chunk)
        rdt = time.perf_counter() - t0
        await c.meta.delete(path)
        print(f"write: {_human(written / wdt)}/s   read: {_human(total / rdt)}/s")
    finally:
        await c.close()


# ---------------- daemons ----------------

async def cmd_master(args):
    from curvine_tpu.common.logging import setup as log_setup
    from curvine_tpu.master import MasterServer
    from curvine_tpu.web.server import WebServer
    conf = _conf(args)
    log_setup(log_file=os.path.join(conf.data_dir, "logs", "master.log"))
    m = MasterServer(conf)
    await m.start()
    web = WebServer(conf.master.web_port, master=m)
    await web.start()
    print(f"master at {m.addr}, web at :{web.port}")
    await asyncio.Event().wait()


async def cmd_worker(args):
    from curvine_tpu.common.logging import setup as log_setup
    from curvine_tpu.worker import WorkerServer
    conf = _conf(args)
    log_setup(log_file=os.path.join(conf.data_dir, "logs", "worker.log"))
    w = WorkerServer(conf)
    await w.start()
    from curvine_tpu.web.server import WebServer
    web = WebServer(conf.worker.web_port, worker=w)
    await web.start()
    print(f"worker {w.worker_id} at {w.addr}, web at :{web.port}")
    await asyncio.Event().wait()


async def cmd_health(args):
    """Machine-readable cluster-health rollup (monitor + watchdog);
    exit code 0 healthy / 1 degraded / 2 critical-or-unreachable so
    scripts and liveness probes can gate on it (an unreachable or
    pre-r5 master is the WORST case, never 'degraded')."""
    c = await _client(args)
    try:
        h = await c.meta.cluster_health()
    except err.CurvineError as e:
        print(json.dumps({"status": "unreachable", "error": str(e)}))
        return 2
    finally:
        await c.close()
    print(json.dumps(h, indent=None if args.compact else 1))
    return {"healthy": 0, "degraded": 1}.get(h.get("status"), 2)


async def cmd_trace(args):
    """Fetch one trace's spans (master + workers via GET_SPANS collect)
    and render the assembled tree. Trace ids come from slow-op log
    lines, `/api/trace`, or Tracer.last_trace_id."""
    from curvine_tpu.obs.trace import assemble_tree, render_tree
    c = await _client(args)
    try:
        spans = await c.get_trace(args.trace_id)
        if not spans:
            print(f"no spans collected for trace {args.trace_id} "
                  "(unsampled, expired from the ring, or wrong id)",
                  file=sys.stderr)
            return 1
        if args.json:
            print(json.dumps(assemble_tree(spans), indent=1, default=str))
        else:
            print(render_tree(assemble_tree(spans), args.trace_id))
    finally:
        await c.close()


async def cmd_gateway(args):
    """Serve the S3 and WebHDFS protocol gateways over the namespace."""
    from curvine_tpu.client import CurvineClient
    from curvine_tpu.gateway.s3 import S3Gateway
    from curvine_tpu.gateway.webhdfs import WebHdfsGateway
    conf = _conf(args)
    client = CurvineClient(conf)
    # front-door admission: the gateway runs its own controller (HTTP-
    # level quotas per access key) and the tenant id it derives rides
    # every downstream RPC, so master/worker quotas see the same caller
    from curvine_tpu.common.qos import AdmissionController
    qos = AdmissionController.from_conf(conf.qos,
                                        slow_op_ms=conf.obs.slow_op_ms)
    s3 = S3Gateway(client, port=args.s3_port, host="0.0.0.0",
                   credentials=conf.gateway.s3_credentials(),
                   qos=qos,
                   gc_interval_s=conf.gateway.stale_gc_interval_s)
    hdfs = WebHdfsGateway(client, port=args.webhdfs_port, host="0.0.0.0")
    await s3.start()
    await hdfs.start()
    print(f"s3 gateway :{s3.port}, webhdfs gateway :{hdfs.port}")
    await asyncio.Event().wait()


async def cmd_fuse(args):
    from curvine_tpu.fuse.mount import mount_and_serve
    conf = _conf(args)
    if args.mountpoint:
        conf.fuse.mount_point = args.mountpoint
    if getattr(args, "metrics_port", None):
        conf.fuse.metrics_port = int(args.metrics_port)
    await mount_and_serve(conf)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="cv", description="curvine-tpu CLI")
    p.add_argument("--conf", help="cluster config TOML")
    p.add_argument("--master", help="master addr host:port")
    sub = p.add_subparsers(dest="cmd", required=True)

    def add(name, fn, *spec, **kw):
        sp = sub.add_parser(name, **kw)
        for s in spec:
            sp.add_argument(*s[0], **s[1])
        sp.set_defaults(fn=fn)
        return sp

    A = lambda *a, **k: (a, k)
    add("ls", cmd_ls, A("path"))
    add("mkdir", cmd_mkdir, A("path"))
    add("put", cmd_put, A("src"), A("dst"),
        A("--force", action="store_true"))
    add("get", cmd_get, A("src"), A("dst"))
    add("cat", cmd_cat, A("path"))
    add("rm", cmd_rm, A("path"), A("-r", "--recursive", action="store_true"))
    add("mv", cmd_mv, A("src"), A("dst"))
    add("stat", cmd_stat, A("path"))
    add("touch", cmd_touch, A("path"))
    add("chmod", cmd_chmod, A("mode"), A("path"))
    add("chown", cmd_chown, A("owner"), A("path"))
    add("du", cmd_du, A("path"))
    add("count", cmd_count, A("path"))
    add("df", cmd_df)
    add("free", cmd_free, A("path"),
        A("-r", "--recursive", action="store_true"))
    add("blocks", cmd_blocks, A("path"))
    add("report", cmd_report)
    add("trace", cmd_trace, A("trace_id"),
        A("--json", action="store_true"))
    add("health", cmd_health,
        A("--compact", action="store_true"))
    add("node", cmd_node,
        A("action", nargs="?", default="list",
          choices=["list", "decommission", "recommission"]),
        A("worker_id", nargs="?"))
    add("raft", cmd_raft,
        A("action", choices=["status", "add", "remove", "transfer"]),
        A("node_id", nargs="?"),
        A("addr", nargs="?"))
    add("mount", cmd_mount, A("cv_path"), A("ufs_path"),
        A("--auto-cache", dest="auto_cache", action="store_true"),
        A("--prop", action="append"),
        A("--ttl", help="cached-copy TTL, e.g. 30s/10m/2h/7d"),
        A("--ttl-action", dest="ttl_action", default="free",
          choices=["none", "delete", "free"]),
        A("--read-only", dest="read_only", action="store_true",
          help="reject user mutations under the mount (loads still cache)"),
        A("--storage", choices=["hbm", "mem", "ssd", "hdd"],
          help="tier for cached copies"),
        A("--block-size", dest="block_size", type=int, default=0),
        A("--replicas", type=int, default=0))
    add("umount", cmd_umount, A("cv_path"))
    add("mounts", cmd_mounts)
    add("load", cmd_load, A("path"), A("--replicas", type=int, default=1),
        A("--wait", action="store_true"))
    add("export", cmd_export, A("path"), A("--wait", action="store_true"))
    add("quota", cmd_quota, A("action", choices=["get", "set", "clear"]),
        A("path"), A("--bytes", type=int), A("--files", type=int))
    add("load-status", cmd_load_status, A("job_id"))
    add("load-cancel", cmd_load_cancel, A("job_id"))
    add("ec", cmd_ec,
        A("action", choices=["set-policy", "convert"]),
        A("path"),
        A("profile", nargs="?"),
        A("--wait", action="store_true"))
    add("fsck", cmd_fsck, A("path"),
        A("--repair", action="store_true"))
    add("bench", cmd_bench, A("--size-mb", type=int, default=256))
    add("master", cmd_master)
    add("worker", cmd_worker)
    add("fuse", cmd_fuse, A("--mountpoint"), A("--metrics-port"))
    add("gateway", cmd_gateway, A("--s3-port", type=int, default=9900),
        A("--webhdfs-port", type=int, default=9870))
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    try:
        rc = asyncio.run(args.fn(args))
        return rc if isinstance(rc, int) else 0
    except KeyboardInterrupt:
        return 130
    except Exception as e:  # noqa: BLE001 — CLI boundary
        print(f"error: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
