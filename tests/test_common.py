"""Unit tests: types wire roundtrip, path, conf, errors, journal, metrics.

Mirrors reference tests: curvine-common/tests/ (proto roundtrips, conf,
fs_error) and journal_test.rs."""

import dataclasses
import os
import textwrap
import tomllib

import pytest

from curvine_tpu.common import errors as err
from curvine_tpu.common.conf import ClusterConf, TierConf
from curvine_tpu.common.journal import Journal
from curvine_tpu.common.metrics import MetricsRegistry
from curvine_tpu.common.path import Path, norm_path
from curvine_tpu.common.types import (
    CommitBlock, ExtendedBlock, FileBlocks, FileStatus, LocatedBlock,
    MasterInfo, MountInfo, StoragePolicy, StorageType, TtlAction,
    WorkerAddress, WorkerInfo, StorageInfo,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_wire_roundtrip():
    st = FileStatus(id=7, path="/a/b", name="b", len=123, replicas=2,
                    storage_policy=StoragePolicy(storage_type=StorageType.SSD,
                                                 ttl_ms=1000,
                                                 ttl_action=TtlAction.FREE),
                    x_attr={"k": b"v"})
    d = st.to_wire()
    back = FileStatus.from_wire(d)
    assert back == st
    assert back.storage_policy.storage_type == StorageType.SSD

    lb = LocatedBlock(block=ExtendedBlock(id=5, len=10),
                      locs=[WorkerAddress(worker_id=1, hostname="h",
                                          rpc_port=1234)],
                      storage_types=[StorageType.MEM])
    fb = FileBlocks(status=st, block_locs=[lb])
    back = FileBlocks.from_wire(fb.to_wire())
    assert back.block_locs[0].locs[0].rpc_port == 1234
    assert back.block_locs[0].storage_types == [StorageType.MEM]

    wi = WorkerInfo(address=WorkerAddress(worker_id=9),
                    storages=[StorageInfo(capacity=100, available=40)],
                    ici_coords=[1, 2])
    mi = MasterInfo(live_workers=[wi])
    back = MasterInfo.from_wire(mi.to_wire())
    assert back.live_workers[0].address.worker_id == 9
    assert back.live_workers[0].capacity == 100


def test_path():
    p = Path("cv://host:99/a/b/c")
    assert p.scheme == "cv" and p.authority == "host:99"
    assert p.path == "/a/b/c" and p.name == "c"
    assert p.parent().path == "/a/b"
    assert Path("/x/../y").path == "/y"
    assert Path("/a//b/./c").path == "/a/b/c"
    assert Path("/").is_root and Path("/").components() == []
    assert norm_path("s3://bucket/k") == "/k"
    with pytest.raises(err.InvalidPath):
        Path("relative/path")
    with pytest.raises(err.InvalidPath):
        Path("/a/../../b")
    assert Path("/a").join("b", "c").path == "/a/b/c"


def test_conf_load(tmp_path):
    f = tmp_path / "curvine.toml"
    f.write_text("""
cluster_name = "t1"
[master]
rpc_port = 7777
[worker]
hostname = "w1"
[[worker.tiers]]
storage_type = "ssd"
dir = "/tmp/ssd"
capacity = 1024
[client]
block_size = 1048576
""")
    c = ClusterConf.load(str(f))
    assert c.cluster_name == "t1"
    assert c.master.rpc_port == 7777
    assert c.worker.tiers[0].storage_type == "ssd"
    assert c.worker.tiers[0].capacity == 1024
    assert c.client.block_size == 1048576


def test_error_taxonomy():
    e = err.CurvineError.from_wire(int(err.ErrorCode.FILE_NOT_FOUND), "gone")
    assert isinstance(e, err.FileNotFound)
    assert not e.retryable
    assert err.RpcTimeout("t").retryable
    assert err.NotLeader("n").retryable


def test_journal_replay(tmp_path):
    j = Journal(str(tmp_path / "j"))
    for i in range(10):
        j.append("op", {"i": i})
    j.close()

    j2 = Journal(str(tmp_path / "j"))
    snap, entries = j2.recover()
    assert snap is None
    assert [a["i"] for _, _, a, _ in entries] == list(range(10))
    assert j2.seq == 10
    # continue appending, snapshot, more entries
    j2.append("op", {"i": 10})
    j2.write_snapshot({"state": "s11"})
    j2.append("op", {"i": 11})
    j2.close()

    j3 = Journal(str(tmp_path / "j"))
    snap, entries = j3.recover()
    assert snap == {"state": "s11"}
    assert [a["i"] for _, _, a, _ in entries] == [11]


def test_journal_torn_tail(tmp_path):
    j = Journal(str(tmp_path / "j"))
    j.append("op", {"i": 0})
    j.append("op", {"i": 1})
    j.close()
    # corrupt: truncate mid-entry
    seg = [f for f in os.listdir(j.dir) if f.startswith("edits-")][0]
    full = os.path.join(j.dir, seg)
    size = os.path.getsize(full)
    with open(full, "ab") as f:
        f.truncate(size - 3)
    j2 = Journal(str(tmp_path / "j"))
    _, entries = j2.recover()
    assert [a["i"] for _, _, a, _ in entries] == [0]


def test_metrics():
    m = MetricsRegistry("test")
    m.inc("reqs")
    m.inc("reqs", 2)
    m.gauge("cap", 5)
    with m.timer("lat"):
        pass
    text = m.prometheus_text()
    assert "curvine_test_reqs 3" in text
    assert "curvine_test_cap 5" in text
    assert "curvine_test_lat_count 1" in text
    snap = m.snapshot()
    assert snap["counters"]["reqs"] == 3


def test_retry_cache_dedup():
    """Retried non-idempotent mutations replay the cached response.
    Parity: fs_retry_cache.rs."""
    from curvine_tpu.master.retry_cache import RetryCache
    rc = RetryCache(capacity=3, ttl_ms=10_000)
    rc.put(("c1", 1), b"resp1")
    assert rc.get(("c1", 1)) == b"resp1"
    assert rc.get(("c1", 2)) is None
    # capacity eviction (LRU)
    rc.put(("c1", 2), b"r2")
    rc.put(("c1", 3), b"r3")
    rc.get(("c1", 1))               # touch 1 → LRU is 2
    rc.put(("c1", 4), b"r4")
    assert rc.get(("c1", 2)) is None
    assert rc.get(("c1", 1)) == b"resp1"
    # ttl expiry
    rc2 = RetryCache(ttl_ms=0)
    rc2.put(("x", 1), b"v")
    import time
    time.sleep(0.01)
    assert rc2.get(("x", 1)) is None


async def test_retry_cache_end_to_end():
    """The same (client_id, call_id) mutation applied twice returns the
    first response and doesn't double-apply."""
    from curvine_tpu.testing import MiniCluster
    from curvine_tpu.rpc import RpcCode
    async with MiniCluster(workers=1) as mc:
        c = mc.client()
        req = {"path": "/dedup", "create_parent": True,
               "client_id": c.meta.client_id, "call_id": 424242}
        rep1 = await c.meta.call(RpcCode.MKDIR, dict(req))
        inodes = mc.master.fs.tree.count()
        rep2 = await c.meta.call(RpcCode.MKDIR, dict(req))  # "retry"
        assert rep1 == rep2
        assert mc.master.fs.tree.count() == inodes


def test_journal_snapshot_interval(tmp_path):
    """Auto-checkpoint after N entries; old segments garbage-collected."""
    import os
    from curvine_tpu.master.filesystem import MasterFilesystem
    from curvine_tpu.common.journal import Journal
    fs = MasterFilesystem(journal=Journal(str(tmp_path)),
                          snapshot_interval=10)
    for i in range(25):
        fs.mkdir(f"/snapdir/d{i}")
    names = os.listdir(tmp_path)
    assert any(n.startswith("snapshot-") for n in names)
    # recovery from snapshot + tail entries
    fs2 = MasterFilesystem(journal=Journal(str(tmp_path)))
    fs2.recover()
    for i in range(25):
        assert fs2.tree.resolve(f"/snapdir/d{i}") is not None


# ---------------- scheduled executor ----------------

def test_scheduled_executor_periodic_and_cancel():
    import asyncio
    from curvine_tpu.common.executor import ScheduledExecutor

    async def main():
        ex = ScheduledExecutor("t")
        hits = []
        ex.submit_periodic("tick", lambda: hits.append(1), 0.02,
                           initial_delay_s=0.0)
        fails = []
        def boom():
            fails.append(1)
            raise RuntimeError("tick error must not kill the schedule")
        ex.submit_periodic("boom", boom, 0.02, initial_delay_s=0.0)
        ex.submit_delayed("later", lambda: hits.append("late"), 0.05)
        await asyncio.sleep(0.2)
        assert len(hits) >= 3
        assert "late" in hits
        assert len(fails) >= 3              # kept running through errors
        assert ex.errors["boom"] >= 3
        ex.cancel("tick")
        n = len(hits)
        await asyncio.sleep(0.06)
        assert [h for h in hits[n:] if h == 1] == []
        await ex.stop()
        assert ex.names() == []

    asyncio.run(main())


def test_hand_rolled_codecs_cover_all_fields():
    """FileStatus/StoragePolicy have hand-rolled wire codecs (hot path);
    this guards against silently dropping fields added later."""
    import dataclasses
    from curvine_tpu.common.types import FileStatus, StoragePolicy
    for cls in (FileStatus, StoragePolicy):
        wire = set(cls().to_wire())
        declared = {f.name for f in dataclasses.fields(cls)}
        assert wire == declared, (cls.__name__, wire ^ declared)
        # and from_wire round-trips every field
        inst = cls()
        back = cls.from_wire(inst.to_wire())
        assert back == inst


def test_conf_env_overrides(tmp_path):
    """CURVINE_<SECTION>_<FIELD> env vars beat file values — the
    container/k8s configuration path (deploy/)."""
    f = tmp_path / "c.toml"
    f.write_text('[worker]\nrpc_port = 8996\n')
    c = ClusterConf.load(str(f), env={
        "CURVINE_WORKER_RPC_PORT": "9996",
        "CURVINE_CLIENT_MASTER_ADDRS": "m1:8995,m2:8995",
        "CURVINE_MASTER_HOSTNAME": "0.0.0.0",
        "CURVINE_CLIENT_SHORT_CIRCUIT": "false",
        "CURVINE_DATA_DIR": "/data",
        "CURVINE_CONF": "/ignored",
        "CURVINE_NO_SUCH_FIELD": "x",
        "CURVINE_WORKER_TIERS": "not-applied",   # structured: TOML-only
    })
    assert c.worker.rpc_port == 9996
    assert c.client.master_addrs == ["m1:8995", "m2:8995"]
    assert c.master.hostname == "0.0.0.0"
    assert c.client.short_circuit is False
    assert c.data_dir == "/data"
    assert c.worker.tiers and c.worker.tiers[0].storage_type == "mem"


def _conf_mismatches(obj, data: dict, where: str = ""):
    """Keys of a parsed conf file that name no field of the conf object,
    or whose value did not land on it."""
    fields = {f.name for f in dataclasses.fields(obj)}
    for k, v in data.items():
        if k not in fields:
            yield f"{where}{k}: no such field"
        elif k == "tiers":
            tier_fields = {f.name for f in dataclasses.fields(TierConf)}
            for t in v:
                yield from (f"{where}{k}.{tk}: no such field"
                            for tk in t if tk not in tier_fields)
            if getattr(obj, k) != [TierConf(**t) for t in v]:
                yield f"{where}{k}: not loaded"
        elif dataclasses.is_dataclass(getattr(obj, k)):
            yield from _conf_mismatches(getattr(obj, k), v, f"{where}{k}.")
        elif getattr(obj, k) != v:
            yield f"{where}{k}: {getattr(obj, k)!r} loaded for {v!r}"


@pytest.mark.parametrize("shipped", [
    "etc/curvine-cluster.toml",
    "deploy/conf/compose.toml",
    "deploy/k8s/curvine-conf.yaml",
])
def test_shipped_conf_names_only_fields_that_exist(shipped, tmp_path):
    """ClusterConf.load skips a key it does not know, so an option that
    left the program stays in a shipped file unnoticed: every section
    and key of the files we ship names a field (a `tiers` entry: of
    TierConf), and the loaded conf holds the file's values."""
    with open(os.path.join(REPO, shipped)) as f:
        text = f.read()
    if shipped.endswith(".yaml"):   # the TOML is a ConfigMap block scalar
        block = text.split("curvine-cluster.toml: |\n", 1)[1].splitlines()
        text = textwrap.dedent("\n".join(
            ln for ln in block if not ln.strip() or ln.startswith("    ")))
    data = tomllib.loads(text)
    assert {"master", "worker", "client"} <= set(data)
    f = tmp_path / "shipped.toml"
    f.write_text(text)
    conf = ClusterConf.load(str(f), env={})
    assert list(_conf_mismatches(conf, data)) == []


# ---------------- group commit (journal batching) ----------------

def _segment_frames(path):
    """Parse [off, frame_len] for each whole frame in a segment file."""
    import struct
    with open(path, "rb") as f:
        data = f.read()
    hdr = struct.Struct(">II")
    out, off = [], 0
    while off + hdr.size <= len(data):
        length, _crc = hdr.unpack_from(data, off)
        out.append((off, hdr.size + length))
        off += hdr.size + length
    return out


def _only_segment(j):
    segs = [f for f in os.listdir(j.dir) if f.startswith("edits-")]
    assert len(segs) == 1
    return os.path.join(j.dir, segs[0])


def test_journal_append_batch_roundtrip(tmp_path):
    j = Journal(str(tmp_path / "j"))
    j.append("op", {"i": 0})
    seqs = j.append_batch([("op", {"i": 1}), ("op", {"i": 2}),
                           ("op", {"i": 3})])
    assert seqs == [2, 3, 4]
    assert j.seq == 4
    j.append("op", {"i": 4})
    j.close()
    j2 = Journal(str(tmp_path / "j"))
    _, entries = j2.recover()
    assert [a["i"] for _, _, a, _ in entries] == [0, 1, 2, 3, 4]
    assert j2.seq == 5


def test_journal_append_batch_torn_mid_batch(tmp_path):
    """A torn tail landing MID-BATCH must replay only the whole entries
    of the batch and position seq after the last good one."""
    j = Journal(str(tmp_path / "j"))
    j.append_batch([("op", {"i": i}) for i in range(4)])
    j.close()
    full = _only_segment(j)
    frames = _segment_frames(full)
    assert len(frames) == 4
    # cut INTO the 3rd frame of the batch: entries 0,1 stay whole
    cut = frames[2][0] + 5
    with open(full, "ab") as f:
        f.truncate(cut)
    j2 = Journal(str(tmp_path / "j"))
    _, entries = j2.recover()
    assert [a["i"] for _, _, a, _ in entries] == [0, 1]
    assert j2.seq == 2
    # the journal must be appendable right where the tear was truncated
    j2.append("op", {"i": 99})
    j2.close()
    j3 = Journal(str(tmp_path / "j"))
    _, entries = j3.recover()
    assert [a["i"] for _, _, a, _ in entries] == [0, 1, 99]
    assert j3.seq == 3


def test_journal_append_batch_bad_crc_mid_batch(tmp_path):
    """A corrupt frame mid-batch truncates there: whole entries before it
    replay, everything after (same batch!) is discarded."""
    j = Journal(str(tmp_path / "j"))
    j.append_batch([("op", {"i": i}) for i in range(5)])
    j.close()
    full = _only_segment(j)
    frames = _segment_frames(full)
    off, flen = frames[2]
    with open(full, "r+b") as f:
        f.seek(off + flen - 1)       # flip a payload byte of frame 3
        b = f.read(1)
        f.seek(off + flen - 1)
        f.write(bytes([b[0] ^ 0xFF]))
    j2 = Journal(str(tmp_path / "j"))
    _, entries = j2.recover()
    assert [a["i"] for _, _, a, _ in entries] == [0, 1]
    assert j2.seq == 2


def test_journal_unflushed_append_then_sync(tmp_path):
    j = Journal(str(tmp_path / "j"))
    j.append("op", {"i": 0}, flush=False)
    j.append("op", {"i": 1}, flush=False)
    j.sync()
    j.close()
    j2 = Journal(str(tmp_path / "j"))
    _, entries = j2.recover()
    assert [a["i"] for _, _, a, _ in entries] == [0, 1]


async def test_group_committer_coalesces(tmp_path):
    """Concurrent mutations awaiting the group barrier land in FEWER
    journal flushes than ops, and all survive a reopen."""
    import asyncio
    from curvine_tpu.common.journal import GroupCommitter
    from curvine_tpu.master.filesystem import MasterFilesystem
    from curvine_tpu.master.store import KvMetaStore

    j = Journal(str(tmp_path / "j"))
    fs = MasterFilesystem(journal=j,
                          store=KvMetaStore(str(tmp_path / "kv"),
                                            engine="python"))
    fs.recover()
    fs.committer = GroupCommitter(j, fs.store, window_ms=0.0)

    async def one(i: int):
        fs.mkdir(f"/g{i}")
        await fs.committer.sync()

    await asyncio.gather(*(one(i) for i in range(64)))
    assert fs.committer.entries == 64
    assert fs.committer.groups < 64          # coalesced
    j.close()
    fs.store.close()

    j2 = Journal(str(tmp_path / "j"))
    fs2 = MasterFilesystem(journal=j2,
                           store=KvMetaStore(str(tmp_path / "kv"),
                                             engine="python"))
    fs2.recover()
    for i in range(64):
        assert fs2.exists(f"/g{i}")


async def test_group_rollback_keeps_earlier_entries(tmp_path):
    """A failed apply MID-GROUP must not drop earlier staged entries."""
    import asyncio
    from curvine_tpu.common.journal import GroupCommitter
    from curvine_tpu.master.filesystem import MasterFilesystem
    from curvine_tpu.master.store import KvMetaStore

    j = Journal(str(tmp_path / "j"))
    fs = MasterFilesystem(journal=j,
                          store=KvMetaStore(str(tmp_path / "kv"),
                                            engine="python"))
    fs.recover()
    fs.committer = GroupCommitter(j, fs.store, window_ms=0.0)
    fs.mkdir("/ok1")
    with pytest.raises(err.CurvineError):
        fs.create_file("/missing/parent/f", create_parent=False)
    fs.mkdir("/ok2")
    await fs.committer.sync()
    assert fs.exists("/ok1") and fs.exists("/ok2")
    j.close()
    fs.store.close()
    j2 = Journal(str(tmp_path / "j"))
    fs2 = MasterFilesystem(journal=j2,
                           store=KvMetaStore(str(tmp_path / "kv"),
                                             engine="python"))
    fs2.recover()
    assert fs2.exists("/ok1") and fs2.exists("/ok2")
    assert not fs2.exists("/missing")
