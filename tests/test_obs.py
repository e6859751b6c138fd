"""Observability plane: tracing primitives, propagation, collection,
histogram interpolation, StepProfiler, and the MiniCluster e2e trace.

docs/observability.md is the companion; the e2e test here is the
acceptance criterion: one traced cached read assembles into a tree with
spans from client, master AND worker, correct parent/child links, and
monotone span intervals."""

import asyncio
import logging
import os

import pytest

from curvine_tpu.common.metrics import Histogram, MetricsRegistry
from curvine_tpu.obs.profiler import StepProfiler
from curvine_tpu.obs.trace import (
    TRACE_KEY, SpanCtx, SpanStore, Tracer, assemble_tree, current_ctx,
    render_tree,
)
from curvine_tpu.testing import MiniCluster

KB = 1024


# ---------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------

def test_span_ctx_wire_roundtrip():
    ctx = SpanCtx("ab12cd34ef56ab78", 0x1234, True)
    hdr = ctx.stamp({})
    assert TRACE_KEY in hdr
    back = SpanCtx.from_header(hdr)
    assert back.trace_id == ctx.trace_id
    assert back.span_id == ctx.span_id
    assert back.sampled is True
    # absent / hostile headers are not traces
    assert SpanCtx.from_header({}) is None
    assert SpanCtx.from_header(None) is None
    assert SpanCtx.from_header({TRACE_KEY: "garbage"}) is None
    assert SpanCtx.from_header({TRACE_KEY: [1]}) is None


def test_span_store_is_a_bounded_ring():
    store = SpanStore(capacity=16)
    for i in range(100):
        store.append({"trace_id": f"t{i}", "span_id": i})
    assert len(store) == 16
    assert store.appended == 100
    # oldest fell off the head
    assert store.for_trace("t0") == []
    assert store.for_trace("t99")
    drained = store.drain(max_n=1000)
    assert len(drained) == 16 and len(store) == 0


def test_tracer_sampling_and_backstops():
    m = MetricsRegistry("t")
    tr = Tracer("client", sample_rate=0.0, slow_op_ms=10_000,
                metrics=m)
    # unsampled + ok + fast → dropped
    with tr.span("op_ok"):
        pass
    assert len(tr.store) == 0
    assert m.counters["trace.spans_dropped"] == 1
    # unsampled but ERROR → always recorded
    with pytest.raises(ValueError):
        with tr.span("op_err"):
            raise ValueError("boom")
    spans = list(tr.store.drain())
    assert len(spans) == 1 and spans[0]["status"] == "error"
    assert "boom" in spans[0]["attrs"]["error"]
    # unsampled but SLOW → always recorded (slow threshold 0.0s here)
    slow = Tracer("client", sample_rate=0.0, slow_op_ms=0)
    slow.slow_s = 1e-9
    with slow.span("op_slow"):
        pass
    assert len(slow.store) == 1
    # sampled=1.0 → recorded
    full = Tracer("client", sample_rate=1.0)
    with full.span("op"):
        pass
    assert len(full.store) == 1
    # disabled → no-op spans, nothing recorded, no ambient ctx
    off = Tracer("client", sample_rate=1.0, enabled=False)
    with off.span("op") as sp:
        assert sp.ctx is None
        assert current_ctx() is None
    assert len(off.store) == 0


def test_ambient_context_nesting_and_inheritance():
    tr = Tracer("client", sample_rate=1.0)
    assert current_ctx() is None
    with tr.start_trace("root", sampled=True) as root:
        assert current_ctx() is root.ctx
        with tr.span("child") as child:
            assert child.ctx.trace_id == root.ctx.trace_id
            assert child.parent_id == root.ctx.span_id
            assert current_ctx() is child.ctx
        assert current_ctx() is root.ctx
    assert current_ctx() is None
    spans = tr.store.for_trace(root.ctx.trace_id)
    assert {s["op"] for s in spans} == {"root", "child"}
    # an explicit wire parent wins over the ambient context
    wire = SpanCtx("feedfeedfeedfeed", 77, True)
    with tr.span("server_side", parent=wire) as sp:
        assert sp.ctx.trace_id == "feedfeedfeedfeed"
        assert sp.parent_id == 77


def test_assemble_and_render_tree():
    spans = [
        {"trace_id": "t", "span_id": 1, "parent": 0, "component": "client",
         "op": "read", "start": 1.0, "dur": 0.5, "status": "ok",
         "attrs": {}},
        {"trace_id": "t", "span_id": 2, "parent": 1, "component": "worker",
         "op": "read_block", "start": 1.1, "dur": 0.3, "status": "ok",
         "attrs": {}},
        # orphan (parent never collected) surfaces as an extra root
        {"trace_id": "t", "span_id": 9, "parent": 404, "component": "x",
         "op": "stray", "start": 0.5, "dur": 0.1, "status": "ok",
         "attrs": {}},
    ]
    roots = assemble_tree(spans)
    assert len(roots) == 2
    main = next(r for r in roots if r["span_id"] == 1)
    assert [c["span_id"] for c in main["children"]] == [2]
    text = render_tree(roots, "t")
    assert "client:read" in text and "worker:read_block" in text
    assert "3 spans" in text


# ---------------------------------------------------------------------
# histogram interpolation + overflow (satellite)
# ---------------------------------------------------------------------

def test_histogram_quantile_interpolates_within_bucket():
    h = Histogram()
    # 100 observations all inside the (0.05, 0.1] bucket
    for _ in range(100):
        h.observe(0.07)
    p50 = h.quantile(0.5)
    # old behavior returned the 0.1 upper bound exactly; interpolation
    # must land strictly inside the bucket
    assert 0.05 < p50 < 0.1
    # spread across two buckets: median sits in the second's range
    h2 = Histogram()
    for _ in range(50):
        h2.observe(0.02)     # (0.01, 0.025]
    for _ in range(50):
        h2.observe(0.2)      # (0.1, 0.25]
    assert 0.01 < h2.quantile(0.25) <= 0.025
    assert 0.1 < h2.quantile(0.75) <= 0.25


def test_histogram_overflow_not_clamped_to_10s():
    h = Histogram()
    for _ in range(10):
        h.observe(60.0)          # a minute — way past the 10s top bucket
    assert h.overflow == 10
    assert h.max == 60.0
    # p99 of all-overflow observations must exceed the old 10.0 clamp
    assert h.quantile(0.99) > 10.0
    # mixed: fast ops + a slow tail — p50 stays fast, p99 sees the tail
    h2 = Histogram()
    for _ in range(95):
        h2.observe(0.001)
    for _ in range(5):
        h2.observe(30.0)
    assert h2.quantile(0.5) <= 0.001
    assert h2.quantile(0.99) > 10.0
    assert h2.overflow == 5
    snap_reg = MetricsRegistry("x")
    snap_reg.histograms["h"] = h2
    snap = snap_reg.snapshot()["histograms"]["h"]
    assert snap["overflow"] == 5 and snap["max"] == 30.0


# ---------------------------------------------------------------------
# StepProfiler
# ---------------------------------------------------------------------

def test_step_profiler_stages_and_summary():
    p = StepProfiler()
    p.record("cache_fetch", 0.010, nbytes=4096)
    p.record("decode", 0.002)
    p.record("host_to_hbm", 0.005, nbytes=4096)
    p.record("compute_wait", 0.020)
    with p.measure("input_wait"):
        pass
    p.step_done()
    snap = p.snapshot()
    assert snap["steps"] == 1
    assert snap["stages"]["cache_fetch"]["bytes"] == 4096
    assert snap["stages"]["compute_wait"]["count"] == 1
    summary = p.summary()
    fr = summary["fractions"]
    assert abs(sum(fr.values()) - 1.0) < 1e-6
    # compute_wait dominates this synthetic step
    assert max(fr, key=fr.get) == "compute_wait"
    text = p.prometheus_text()
    assert "curvine_ingest_stage_compute_wait" in text
    assert "curvine_ingest_steps 1" in text


async def test_step_profiler_through_train_feed():
    """The profiler wired through CacheShardSource +
    AsyncDevicePrefetcher attributes real pipeline time."""
    import numpy as np
    from curvine_tpu.tpu.loader import TpuTrainFeed, write_token_shards
    async with MiniCluster(workers=1) as mc:
        c = mc.client()
        tokens = np.arange(4 * 64, dtype=np.int32)
        await write_token_shards(c, "/prof", tokens, shard_tokens=128)
        feed = TpuTrainFeed(c, "/prof", batch=2, seq_len=32, depth=1)
        n = 0
        async for _batch in feed:
            n += 1
        assert n == 4 * 64 // (2 * 32)
        snap = feed.profiler.snapshot()
        assert snap["steps"] == n
        assert snap["stages"]["cache_fetch"]["count"] >= 2   # 2 shards
        assert snap["stages"]["host_to_hbm"]["count"] == n
        # one wait per step, plus the final get that returned DONE
        assert snap["stages"]["input_wait"]["count"] >= n


# ---------------------------------------------------------------------
# e2e: the acceptance trace
# ---------------------------------------------------------------------

async def test_trace_e2e_cached_read(tmp_path):
    """One traced cached read → /api/trace/<id> assembles ≥4 spans
    across client, master and worker with correct parent/child links
    and monotone intervals."""
    import aiohttp
    from curvine_tpu.web.server import WebServer
    mc = MiniCluster(workers=1, base_dir=str(tmp_path))
    mc.conf.obs.trace_sample_rate = 1.0
    mc.conf.client.short_circuit = False   # exercise the worker RPC leg
    await mc.start()
    try:
        c = mc.client()
        await c.write_all("/obs/a.bin", b"t" * (256 * KB))
        with c.tracer.start_trace("e2e_read", sampled=True) as root:
            r = await c.open("/obs/a.bin")
            try:
                data = await r.read_all()
            finally:
                await r.close()
        assert data == b"t" * (256 * KB)
        tid = root.ctx.trace_id

        spans = await c.get_trace(tid)
        assert len(spans) >= 4
        comps = {s["component"] for s in spans}
        assert {"client", "master", "worker"} <= comps

        by_id = {s["span_id"]: s for s in spans}
        roots = [s for s in spans if s["parent"] not in by_id]
        assert len(roots) == 1 and roots[0]["op"] == "e2e_read"
        # parent/child links: the master span hangs off a client meta
        # span; the worker span hangs off a client read_block span
        master_span = next(s for s in spans if s["component"] == "master")
        assert by_id[master_span["parent"]]["component"] == "client"
        worker_span = next(s for s in spans
                           if s["component"] == "worker")
        assert by_id[worker_span["parent"]]["component"] == "client"
        # monotone intervals: children start within (and after the
        # start of) their parent's window; durations are non-negative
        eps = 0.05
        for s in spans:
            assert s["dur"] >= 0.0
            p = by_id.get(s["parent"])
            if p is not None:
                assert s["start"] >= p["start"] - eps
                assert s["start"] + s["dur"] <= \
                    p["start"] + p["dur"] + eps

        # the web endpoint serves the assembled tree
        web = WebServer(0, master=mc.master, host="127.0.0.1")
        await web.start()
        try:
            base = f"http://127.0.0.1:{web.port}"
            async with aiohttp.ClientSession() as s:
                async with s.get(f"{base}/api/trace/{tid}") as resp:
                    j = await resp.json()
                    assert j["span_count"] >= 4
                    assert len(j["roots"]) == 1
                    assert j["roots"][0]["op"] == "e2e_read"
                    assert j["roots"][0]["children"]
                # span-store occupancy gauge rides /metrics
                async with s.get(f"{base}/metrics") as resp:
                    text = await resp.text()
                    assert "curvine_master_trace_spans_stored" in text
                    assert "curvine_master_rpc_get_block_locations" in text
        finally:
            await web.stop()
    finally:
        await mc.stop()


async def test_trace_header_rides_the_wire(tmp_path):
    """TRACE_KEY propagates exactly like deadline_ms: stamped by the
    client under an active span, visible to server dispatch."""
    async with MiniCluster(workers=1, base_dir=str(tmp_path)) as mc:
        seen = {}

        async def spy(server_name, msg):
            if TRACE_KEY in msg.header:
                seen[msg.code] = list(msg.header[TRACE_KEY])
            return True

        mc.master.rpc.fault_hook = spy
        c = mc.client()
        from curvine_tpu.rpc import RpcCode
        # meta.call directly: exists() may detour to the native fast
        # plane, which is a different (untraced) port
        with c.tracer.start_trace("wire", sampled=True) as root:
            await c.meta.call(RpcCode.EXISTS, {"path": "/"})
        mc.master.rpc.fault_hook = None
        got = seen.get(int(RpcCode.EXISTS))
        assert got is not None, "trace context never crossed the wire"
        assert got[0] == root.ctx.trace_id and got[2] == 1
        # without an explicit root, the meta op heads its own trace and
        # the (unsampled, rate=0) decision still propagates — standard
        # head sampling: downstream error spans can link to the trace
        seen.clear()
        c.tracer.sample_rate = 0.0
        mc.master.rpc.fault_hook = spy
        await c.meta.call(RpcCode.EXISTS, {"path": "/"})
        mc.master.rpc.fault_hook = None
        got = seen.get(int(RpcCode.EXISTS))
        assert got is not None and got[2] == 0


async def test_traced_write_and_replication_fanout(tmp_path):
    """A traced write links client → worker write_block_stream spans;
    the master's replication fan-out roots its own trace that reaches
    the destination worker AND the source peer."""
    async with MiniCluster(workers=2, base_dir=str(tmp_path)) as mc:
        mc.conf.obs.trace_sample_rate = 1.0
        c = mc.client()
        c.tracer.sample_rate = 1.0
        c.conf.client.short_circuit = False
        with c.tracer.start_trace("e2e_write", sampled=True) as root:
            await c.write_all("/obsw/w.bin", os.urandom(64 * KB),
                              replicas=1)
        spans = await c.get_trace(root.ctx.trace_id)
        ops = {(s["component"], s["op"]) for s in spans}
        assert ("worker", "write_block_stream") in ops
        assert ("master", "complete_file") in ops

        # force an under-replicated block (desired 2, held once) and
        # exercise the master's replication fan-out directly
        mc.master.replication.tracer.sample_rate = 1.0
        fb = await c.meta.get_block_locations("/obsw/w.bin")
        bid = fb.block_locs[0].block.id
        mc.master.fs.blocks.desired[bid] = 2
        ok = await mc.master.replication._replicate(bid)
        assert ok
        tid = mc.master.replication.tracer.last_trace_id
        assert tid is not None
        await asyncio.sleep(0.2)        # let worker spans finish
        spans = (await mc.master.collect_trace(tid))["spans"]
        ops = {(s["component"], s["op"]) for s in spans}
        assert ("master", "replicate_block") in ops
        assert ("worker", "submit_block_replication_job") in ops


# ---------------------------------------------------------------------
# one read, accounted from inside: phases, the server's own time, the
# profiler's clock (docs/observability.md, "The phases of a read")
# ---------------------------------------------------------------------

READ_PHASES = ("locate", "probe", "grant", "resume", "map", "verify",
               "copy", "close")


def test_timed_counts_and_spans_and_step_done_walks_no_quantiles():
    tr = Tracer("client", sample_rate=1.0)
    counters: dict = {}
    from curvine_tpu.obs.trace import Timed
    import time
    t0 = time.perf_counter()
    for _ in range(3):
        with Timed(counters, "read.phase.x", tr.span("phase.x")):
            pass
    assert counters["read.phase.x.n"] == 3
    assert 0.0 <= counters["read.phase.x.s"] <= time.perf_counter() - t0
    spans = tr.store.drain()
    assert [s["op"] for s in spans] == ["phase.x"] * 3
    # `mono` is perf_counter() at the span's start: CLOCK_MONOTONIC
    assert all(t0 <= s["mono"] <= time.perf_counter() for s in spans)
    # a disabled tracer still counts
    off = Tracer("client", enabled=False)
    with Timed(counters, "read.phase.x", off.span("phase.x")):
        pass
    assert counters["read.phase.x.n"] == 4
    # the feed's hot path gauges nothing
    p = StepProfiler()
    p.record("decode", 0.002)
    p.step_done()
    assert not p.metrics.gauges


class _SlowOpLines(logging.Handler):
    """The slow-op lines `obs.trace` logs while installed, parsed into
    dicts of their key=value fields."""

    def __init__(self):
        super().__init__()
        self.lines: list[dict] = []

    def emit(self, rec):
        msg = rec.getMessage()
        if msg.startswith("slow-op "):
            fields, attrs = msg.split(" attrs=")
            self.lines.append({"attrs": attrs, **dict(
                kv.split("=", 1) for kv in fields.split()[1:])})

    def __enter__(self):
        logging.getLogger("curvine_tpu.obs.trace").addHandler(self)
        return self

    def __exit__(self, *exc):
        logging.getLogger("curvine_tpu.obs.trace").removeHandler(self)


def test_detail_spans_never_log_and_share_the_operations_trace():
    """Every slow operation says so, as before; a detail span (a phase
    inside one) never does, is ambient like any span, and lands in the
    ring under the trace id the operation's line prints."""
    import time
    slow = Tracer("client", sample_rate=0.0, slow_op_ms=1)
    with _SlowOpLines() as said:
        with slow.span("open"):
            with slow.span("meta.get_block_locations"):
                with slow.span("phase.probe", detail=True) as ph:
                    assert current_ctx() is ph.ctx
                    time.sleep(0.003)
    assert [m["op"] for m in said.lines] == ["meta.get_block_locations",
                                             "open"]
    assert len({m["trace_id"] for m in said.lines}) == 1
    spans = slow.spans_for(said.lines[0]["trace_id"])
    assert [s["op"] for s in spans] == [
        "phase.probe", "meta.get_block_locations", "open"]
    by_op = {s["op"]: s for s in spans}
    assert by_op["phase.probe"]["parent"] \
        == by_op["meta.get_block_locations"]["span_id"]


async def test_slow_op_line_leads_to_the_phase_unsampled(tmp_path):
    """The default mode but for the threshold: nothing sampled, no
    profiler. Each slow-op line's trace id finds, in the ring, the
    phases of that very read: a bare mmap_view (the feed) has no span
    round it, so it is the operation and says so itself; a restore is
    one trace, and the line of `ckpt.restore` or of any `open` in it
    leads to every tensor's phases."""
    import jax
    import numpy as np
    from curvine_tpu.tpu.broadcast import load_checkpoint, save_checkpoint
    async with MiniCluster(workers=1, base_dir=str(tmp_path)) as mc:
        c = mc.client()
        c.tracer.sample_rate = 0.0
        await c.write_all("/slow/a.bin", os.urandom(256 * KB))
        await save_checkpoint(c, "/slow/ck", {"w": np.ones(4096, np.float32)})
        c.tracer.slow_s = 1e-9              # every span is slow
        c.tracer.store.clear()
        with _SlowOpLines() as said:
            r = await c.open("/slow/a.bin")
            assert await r.mmap_view(0, r.len) is not None
            await r.close()
            await load_checkpoint(c, "/slow/ck", placer=jax.device_put)
        ring = c.tracer.store.drain(4096)
        assert all(s["trace_id"] in {m["trace_id"] for m in said.lines}
                   for s in ring), "a record no line leads to"
        # of the steps only the two with no span round them spoke
        assert [m["op"] for m in said.lines if m["op"].startswith(
            ("phase.", "mmap_view", "shm_view", "read_all", "ckpt.t",
             "ckpt.p", "ckpt.ready"))] == ["mmap_view", "phase.close"]

        def trace_of(op, **attrs):
            (line,) = [m for m in said.lines if m["op"] == op
                       and all(f"'{k}': '{v}'" in m["attrs"]
                               for k, v in attrs.items())]
            return {s["op"] for s in ring
                    if s["trace_id"] == line["trace_id"]}

        assert {"mmap_view", "shm_view", "phase.probe", "phase.grant",
                "phase.map", "phase.verify"} <= trace_of("mmap_view")
        in_restore = trace_of("ckpt.restore")
        assert {"ckpt.tensor", "open", "meta.get_block_locations",
                "mmap_view", "phase.probe", "phase.grant", "ckpt.place",
                "ckpt.ready_wait", "phase.close"} <= in_restore
        assert trace_of("open", path="/slow/ck/t00000.bin") == in_restore


async def test_short_circuit_read_accounts_every_phase(tmp_path):
    """A one-block file as a view, a two-block file as a view and
    through read_all, all co-located: every phase of the ladder is
    counted where its work is done, they sum to no more than the reads'
    wall, and each rung names itself."""
    import time
    async with MiniCluster(workers=1, base_dir=str(tmp_path),
                           block_size=128 * KB) as mc:
        c = mc.client()
        c.tracer.sample_rate = 1.0
        one, two = os.urandom(64 * KB), os.urandom(256 * KB)
        await c.write_all("/ph/one.bin", one)
        await c.write_all("/ph/two.bin", two)
        c.tracer.store.clear()
        before = dict(c.counters)
        t0 = time.perf_counter()
        r = await c.open("/ph/one.bin")
        view = await r.mmap_view(0, r.len)
        await r.close()
        r = await c.open("/ph/two.bin")
        data = await r.read_all()               # bytes: assembled
        await r.close()
        wall = time.perf_counter() - t0
        assert bytes(view) == one and data == two

        def grew(k):
            return c.counters.get(k, 0) - before.get(k, 0)

        assert grew("read.files") == 2
        for p in READ_PHASES:
            assert grew(f"read.phase.{p}.n") >= 1, p
            assert grew(f"read.phase.{p}.s") >= 0.0, p
        # the buffer of the two-block read: made, grown once, handed on
        assert grew("read.phase.copy.n") == 3
        assert sum(grew(f"read.phase.{p}.s") for p in READ_PHASES) <= wall
        assert 0.0 <= grew("read.probe.srv_handle_s") \
            <= grew("read.phase.probe.s")
        # as a view the two blocks come at once, each on a thread: their
        # phases are counted a block and overlap in the view's wall
        r = await c.open("/ph/two.bin")
        assert bytes(await r.mmap_view(0, r.len)) == two
        await r.close()
        assert grew("read.phase.copy.n") == 3
        assert grew("read.phase.grant.n") == 5
        spans = c.tracer.store.drain(4096)
        served = {(s["op"], s["attrs"]["path"]): s["attrs"]["served_by"]
                  for s in spans if s["op"] in ("mmap_view", "read_all")}
        assert served == {("mmap_view", "/ph/one.bin"): "shm",
                          ("mmap_view", "/ph/two.bin"): "shm",
                          ("read_all", "/ph/two.bin"): "shm"}
        ops = {s["op"] for s in spans}
        assert "shm_view" in ops
        assert {f"phase.{p}" for p in READ_PHASES
                if p not in ("locate", "resume")} <= ops
        assert "open" in ops          # the span of `locate`


def test_timed_samples_the_cpu_clock(monkeypatch):
    """`cpu=True` reads the thread's CPU clock on one block in
    CPU_SAMPLE and counts that block's wall beside it; the other blocks
    count their wall alone."""
    import time
    from curvine_tpu.obs import trace
    c: dict = {}
    monkeypatch.setattr(trace, "CPU_SAMPLE", 1)
    for _ in range(3):
        with trace.Timed(c, "k", cpu=True):
            t = time.perf_counter()
            while time.perf_counter() - t < 0.002:
                pass
    assert c["k.n"] == 3 and c["k.cpu_wall_s"] == pytest.approx(c["k.s"])
    assert 0 < c["k.cpu_s"] <= c["k.cpu_wall_s"]
    monkeypatch.setattr(trace, "CPU_SAMPLE", 1e12)
    with trace.Timed(c, "k", cpu=True):
        pass
    assert c["k.n"] == 4 and c["k.s"] > c["k.cpu_wall_s"]
    with trace.Timed(c, "plain"):
        pass
    assert set(c) - {k for k in c if k.startswith("k.")} \
        == {"plain.s", "plain.n"}


async def test_a_fetch_hand_off_splits_and_its_steps_count_cpu(
        tmp_path, monkeypatch):
    """The hand-off of a shm fetch is its wait for a thread (`queue`) and
    its wait for the loop (`wake`), which sum to `resume` whether the
    block is mapped alone or beside its neighbours; the steps on the
    fetch thread count that thread's CPU beside their wall, and an
    owning host copy on the loop does too."""
    import numpy as np
    from curvine_tpu.obs import trace
    from curvine_tpu.tpu.broadcast import load_checkpoint, save_checkpoint
    monkeypatch.setattr(trace, "CPU_SAMPLE", 1)     # every block read
    async with MiniCluster(workers=1, base_dir=str(tmp_path),
                           block_size=128 * KB) as mc:
        c = mc.client()
        one, two = os.urandom(64 * KB), os.urandom(256 * KB)
        await c.write_all("/ho/one.bin", one)
        await c.write_all("/ho/two.bin", two)
        before = dict(c.counters)
        for path, data in (("/ho/one.bin", one), ("/ho/two.bin", two)):
            r = await c.open(path)
            assert bytes(await r.mmap_view(0, r.len)) == data
            await r.close()

        def grew(k):
            return c.counters.get(k, 0) - before.get(k, 0)

        assert grew("read.phase.resume.n") == 3      # one block, then two
        queue, wake = grew("read.resume.queue.s"), grew("read.resume.wake.s")
        assert queue > 0 and wake > 0
        assert abs(queue + wake - grew("read.phase.resume.s")) < 1e-9
        for p in ("grant", "map", "verify"):
            assert grew(f"read.phase.{p}.n") == 3, p
            assert 0 <= grew(f"read.phase.{p}.cpu_s") \
                <= grew(f"read.phase.{p}.s"), p
            assert grew(f"read.phase.{p}.cpu_wall_s") \
                == pytest.approx(grew(f"read.phase.{p}.s")), p
        # the phases that await count no CPU: other tasks ran meanwhile
        assert not any(k.endswith(".cpu_s") for k in c.counters
                       if k.startswith(("read.phase.probe",
                                        "read.phase.locate",
                                        "read.phase.close")))
        # no placer: each tensor copied into host memory on the loop
        params = {"w": np.arange(4096, dtype=np.float32)}
        await save_checkpoint(c, "/ho/ck", params)
        back = await load_checkpoint(c, "/ho/ck")
        np.testing.assert_array_equal(back["w"], params["w"])
        assert c.counters["ckpt.host_copy.n"] == 1
        assert 0 <= c.counters["ckpt.host_copy.cpu_s"] \
            <= c.counters["ckpt.host_copy.s"]


async def test_srv_rides_the_reply_and_leaves_the_header(tmp_path):
    """The server's [queue_us, handle_us] is popped before the caller
    sees the header; a peer that sends none gives None; per master call
    the server's time is within the client's wall."""
    from curvine_tpu.rpc import RpcCode
    from curvine_tpu.rpc.frame import (
        SRV_KEY, Flags, pack, response_for, unpack,
    )
    from curvine_tpu.rpc.server import RpcServer
    async with MiniCluster(workers=1, base_dir=str(tmp_path)) as mc:
        c = mc.client()
        await c.write_all("/srv/a.bin", b"s" * (64 * KB))
        fb = await c.meta.get_block_locations("/srv/a.bin")
        lb = fb.block_locs[0]
        conn = await c.pool.get(mc.workers[0].addr)
        rep = await conn.call(RpcCode.GET_BLOCK_INFO,
                              data=pack({"block_id": lb.block.id}))
        # what client/reader.py::_local_path parses, as before
        info = rep.header or unpack(rep.data) or {}
        assert SRV_KEY not in info and SRV_KEY not in rep.header
        assert info["path"] and os.path.exists(info["path"])
        queue_us, handle_us = rep.srv
        assert queue_us >= 0 and handle_us >= 0
        assert rep.srv_seconds() == (queue_us / 1e6, handle_us / 1e6)
        cs = c.counters
        assert cs["meta.calls"] >= 2
        assert 0.0 < cs["meta.srv_handle_s"] <= cs["meta.wall_s"]
        assert cs["meta.srv_handle_s"] + cs["meta.srv_queue_s"] \
            <= cs["meta.wall_s"]
        # telemetry about telemetry counts nothing: an idle client's
        # flush has no delta of its own making
        await c.flush_metrics()
        calls = cs["meta.calls"]
        await c.flush_metrics()
        assert cs["meta.calls"] == calls

    # a peer that answers by itself (an older server, the native plane)
    srv = RpcServer("127.0.0.1", 0, name="old")

    async def answers_itself(msg, sconn):
        await sconn.send(response_for(msg, header={"x": 1},
                                      flags=Flags.RESPONSE | Flags.EOF))

    srv.register(RpcCode.EXISTS, answers_itself)
    await srv.start()
    try:
        from curvine_tpu.rpc.client import Connection
        old = await Connection(srv.addr).connect()
        try:
            rep = await old.call(RpcCode.EXISTS)
            assert rep.header == {"x": 1}
            assert rep.srv is None and rep.srv_seconds() is None
        finally:
            await old.close()
    finally:
        await srv.stop()


async def test_spans_share_the_profilers_clock(tmp_path, monkeypatch):
    """While a jax.profiler session is open every span is also a
    TraceAnnotation cv.<component>.<op>; with none open, none is built."""
    import jax.profiler
    built = []

    class Spy(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            built.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)

    async def one_read(c, path):
        r = await c.open(path)
        try:
            assert await r.mmap_view(0, r.len) is not None
        finally:
            await r.close()

    async with MiniCluster(workers=1, base_dir=str(tmp_path / "mc")) as mc:
        c = mc.client()
        c.tracer.sample_rate = 0.0          # whatever sampling decided
        await c.write_all("/prof/a.bin", os.urandom(64 * KB))
        await one_read(c, "/prof/a.bin")
        assert built == []
        trace_dir = str(tmp_path / "trace")
        jax.profiler.start_trace(trace_dir)
        try:
            await one_read(c, "/prof/a.bin")
        finally:
            jax.profiler.stop_trace()
        now = len(built)
        await one_read(c, "/prof/a.bin")
        assert len(built) == now            # the session is over
    assert {"cv.client.open", "cv.client.mmap_view",
            "cv.client.phase.probe", "cv.client.phase.grant",
            "cv.worker.get_block_info"} <= set(built)
    import glob
    (pb,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    names = {e.name
             for plane in jax.profiler.ProfileData.from_file(pb).planes
             for line in plane.lines for e in line.events
             if e.name.startswith("cv.")}
    assert any(n.startswith("cv.client.phase.") for n in names), names
    assert any(n.startswith("cv.worker.") for n in names), names


def test_obs_rpc_and_master_import_no_jax():
    """obs/ takes JAX from sys.modules and never imports it: the
    `cv master` child must stay off the chip."""
    import subprocess
    import sys
    code = ("import sys\n"
            "import curvine_tpu.obs, curvine_tpu.rpc\n"
            "import curvine_tpu.master.server\n"
            "from curvine_tpu.obs.trace import Tracer\n"
            "with Tracer('master', sample_rate=1.0).span('op'):\n"
            "    pass\n"
            "sys.exit(int(any(m == 'jax' or m.startswith('jax.')\n"
            "                 for m in sys.modules)))\n")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert subprocess.run([sys.executable, "-c", code], cwd=repo,
                          timeout=120).returncode == 0


async def test_load_checkpoint_accounts_its_phases(tmp_path):
    """ckpt.* counters per restore, one ckpt.tensor span a tensor, and
    the reader's spans hang off it."""
    import jax
    import numpy as np
    from curvine_tpu.tpu.broadcast import load_checkpoint, save_checkpoint
    async with MiniCluster(workers=1, base_dir=str(tmp_path)) as mc:
        c = mc.client()
        c.tracer.sample_rate = 1.0
        params = {"w": np.arange(4096, dtype=np.float32).reshape(64, 64),
                  "b": np.ones(64, np.float32),
                  "e": np.arange(512, dtype=np.int32)}
        await save_checkpoint(c, "/ckpt/ph", params)
        c.tracer.store.clear()
        before = dict(c.counters)
        back = await load_checkpoint(c, "/ckpt/ph", placer=jax.device_put)
        for k, v in params.items():
            np.testing.assert_array_equal(np.asarray(back[k]), v)

        def grew(k):
            return c.counters.get(k, 0) - before.get(k, 0)

        assert grew("ckpt.restores") == 1
        assert grew("ckpt.place.n") == 3 and grew("ckpt.place.s") >= 0
        assert grew("ckpt.ready_wait.n") == 1
        assert 0 <= grew("ckpt.ready_wait.s") <= grew("ckpt.wall_s")
        assert grew("ckpt.place.s") <= grew("ckpt.wall_s")
        spans = c.tracer.store.drain(4096)
        tensors = [s for s in spans if s["op"] == "ckpt.tensor"]
        assert sorted(s["attrs"]["name"] for s in tensors) \
            == ["t00000.bin", "t00001.bin", "t00002.bin"]
        assert sorted(s["attrs"]["bytes"] for s in tensors) \
            == [256, 2048, 16384]
        assert all(s["attrs"]["blocks"] == 1
                   and s["attrs"]["served_by"] == "shm" for s in tensors)
        (root,) = [s for s in spans if s["op"] == "ckpt.restore"]
        assert root["parent"] == 0 and root["attrs"]["path"] == "/ckpt/ph"
        assert {s["parent"] for s in tensors} == {root["span_id"]}
        assert {s["trace_id"] for s in spans} == {root["trace_id"]}
        ids = {s["span_id"] for s in tensors}
        for op in ("open", "mmap_view", "ckpt.place", "phase.close"):
            kids = [s for s in spans if s["op"] == op
                    and s["parent"] in ids]
            assert len(kids) == 3, op


async def test_load_checkpoint_takes_a_multiblock_tensor_as_a_view(
        tmp_path, monkeypatch):
    """A tensor larger than a block restores bit-exact through the
    block-spanning view: `ckpt.tensor` says shm and how many blocks,
    and `read_all` is called for the manifest alone."""
    import jax
    import numpy as np
    from curvine_tpu.client.reader import FsReader
    from curvine_tpu.tpu.broadcast import load_checkpoint, save_checkpoint
    async with MiniCluster(workers=1, base_dir=str(tmp_path),
                           block_size=64 * 1024) as mc:
        c = mc.client()
        c.tracer.sample_rate = 1.0
        rng = np.random.default_rng(7)
        params = {"big": rng.standard_normal((300, 256)).astype(np.float32),
                  "small": np.arange(512, dtype=np.int32)}
        await save_checkpoint(c, "/ckpt/mb", params)
        copied = []
        real_read_all = FsReader.read_all

        async def read_all(self, *a, **kw):
            copied.append(self.path)
            return await real_read_all(self, *a, **kw)

        monkeypatch.setattr(FsReader, "read_all", read_all)
        c.tracer.store.clear()
        before = dict(c.counters)
        back = await load_checkpoint(c, "/ckpt/mb", placer=jax.device_put)
        for k, v in params.items():
            assert np.asarray(back[k]).tobytes() == v.tobytes()
        assert copied == ["/ckpt/mb/manifest.json"]
        by_bytes = {s["attrs"]["bytes"]: s["attrs"]
                    for s in c.tracer.store.drain(4096)
                    if s["op"] == "ckpt.tensor"}
        assert by_bytes[300 * 256 * 4]["blocks"] == 5
        assert by_bytes[2048]["blocks"] == 1
        assert {a["served_by"] for a in by_bytes.values()} == {"shm"}

        def grew(k):
            return c.counters.get(k, 0) - before.get(k, 0)

        assert grew("read.span_views") == 1
        assert grew("read.span_view_bytes") == 300 * 256 * 4
        assert grew("read.zero_copy_bytes") == 300 * 256 * 4 + 2048
        assert grew("read.verify.copied_bytes") == 0
