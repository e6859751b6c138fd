"""Streaming file reader with short-circuit local reads and read-ahead.

Parity: curvine-client/src/file/ FsReader. Worker selection is local-first
(same host) falling back to the first live location — with short-circuit:
when the block file is on this host, bypass RPC and read (mmap) directly,
the path the reference takes for fuse/local clients."""

from __future__ import annotations

import asyncio
import collections
import contextvars
import logging
import mmap
import os
import threading
import time
import zlib
from contextlib import nullcontext

from curvine_tpu.common import errors as err  # noqa: F401
from curvine_tpu.common.types import (
    ExtendedBlock, FileBlocks, LocatedBlock, WorkerAddress,
)
from curvine_tpu.obs.trace import Timed
from curvine_tpu.rpc import RpcCode, transport
from curvine_tpu.rpc.client import ConnectionPool
from curvine_tpu.rpc.deadline import Deadline
from curvine_tpu.rpc.frame import pack, unpack

log = logging.getLogger(__name__)


def _block_crc(algo: str, data) -> tuple[int | None, int]:
    """Checksum `data` with the block's commit-time algorithm, and the
    bytes that had to be copied to do it (0: hashed where they lie).
    Checksum None → algorithm unknown to this client (skip verification,
    e.g. during a rolling upgrade that introduced a new algo on the
    workers first)."""
    if algo == "crc32":
        return zlib.crc32(data), 0
    if algo == "crc32c":
        from curvine_tpu.common import native
        return native.crc32c_counted(data)
    return None, 0


def pick_loc(lb: LocatedBlock, client_host: str):
    """The location a read of `lb` starts at: local-first (same host),
    else the first one the master listed."""
    if not lb.locs:
        raise err.BlockNotFound(
            f"block {lb.block.id} has no live locations")
    for loc in lb.locs:
        if client_host and client_host in (loc.hostname, loc.ip_addr):
            return loc
    return lb.locs[0]


def probe_addr(lb: LocatedBlock, client_host: str) -> str | None:
    """The worker a short-circuit probe of `lb` goes to — the block's
    preferred location, where that is on this host — or None: nobody
    asks for the local path of a block read over the socket."""
    loc = pick_loc(lb, client_host)
    if client_host in (loc.hostname, loc.ip_addr) or \
            loc.ip_addr in ("127.0.0.1", "localhost"):
        return FsReader._addr(loc)
    return None


def sc_reads_by_worker(into: dict[str, dict[int, int]],
                       reads: dict[int, int],
                       addr_of: dict[int, str]) -> None:
    """Add per-block short-circuit read counts to `into`, keyed by the
    worker that granted each block."""
    for bid, n in reads.items():
        addr = addr_of.get(bid)
        if addr is not None:
            per = into.setdefault(addr, {})
            per[bid] = per.get(bid, 0) + n


async def report_sc_reads(pool: ConnectionPool, addr: str,
                          block_reads: dict[int, int]) -> dict:
    """One SC_READ_REPORT to the granting worker (heat accounting only:
    a failure is logged, not raised) → the warm-cache adverts its reply
    piggybacks, block id → shm socket: blocks whose heat just crossed
    the worker's shm_warm threshold."""
    try:
        conn = await pool.get(addr)
        rep = await conn.call(RpcCode.SC_READ_REPORT,
                              data=pack({"block_reads": block_reads}))
    except (err.CurvineError, OSError) as e:
        log.debug("sc read report to %s failed: %s", addr, e)
        return {}
    hdr = rep.header if isinstance(rep.header, dict) else {}
    return hdr.get("shm_warm") or {}


# what a waiter of a block's fetch is told when that fetch ended without
# an answer (its task cancelled, an error raised): fetch it yourself
_AGAIN = object()


class _Fetch:
    """One block's fetch, queued in a BatchFetcher or served by it: what
    `FsReader._fetch_shm` is given, the waiter's future and context, and
    what the batch thread brings back (`result` or `exc`)."""

    __slots__ = ("reader", "lb", "algo", "spent", "into", "fut", "ctx",
                 "result", "exc", "hop")

    def __init__(self, reader, lb, algo, spent, into, fut):
        self.reader, self.lb, self.algo = reader, lb, algo
        self.spent, self.into, self.fut = spent, into, fut
        self.ctx = contextvars.copy_context()
        self.result = self.exc = None
        self.hop = False         # the first block of its batch


class BatchFetcher:
    """The shm fetches of a primed client's readers, in batches
    (docs/data-plane.md). A reader's block is queued here and awaited;
    up to THREADS batch threads each take what is queued for one
    worker's channel, up to CAP blocks, without waiting for more, and
    send all of the batch's requests at once over one kept connection
    (`ShmConns.pipeline`). As each reply is read, that block is mapped
    and verified on the batch thread (`FsReader._map_verify`, the body a
    thread of its own runs for an unprimed reader) and its result posted
    to a list the loop drains: only a post to an empty list wakes the
    loop, so one wake delivers every result finished since the last.
    A block's refusal, stale grant, failed map or bad checksum is its
    own (the waiter falls back or flags as from a thread of its own).
    A waiter cancelled before its result is delivered leaves that result
    to the fetcher, which closes its fd and mapping. A thread that finds
    nothing queued exits; `close` stops them.

    Counted on the loop, into the reader's counters: read.fetch.hops
    (one a batch: a thread taking work) and read.fetch.batched_blocks.
    Span `fetch_batch` (attrs blocks, worker) on the batch thread; each
    block's phases run in its waiter's context, under its own spans."""

    # chosen by a sweep of a primed restore on a TPU v5e host (PERF.md §6):
    # eight threads were slower than seventeen of a block each, two best
    THREADS = 2
    CAP = 64

    def __init__(self, conns):
        self.conns = conns
        self._lock = threading.Lock()
        self._queued: dict[str, collections.deque] = {}
        self._done: list[_Fetch] = []
        self._threads: set[threading.Thread] = set()
        self._closed = False

    def fetch(self, reader, spath: str, lb: LocatedBlock, algo, spent: dict,
              into: tuple | None = None) -> asyncio.Future:
        """Queue a block's fetch from the channel at `spath` → the
        future of `FsReader._fetch_shm`'s tuple (or of its error)."""
        job = _Fetch(reader, lb, algo, spent, into,
                     asyncio.get_running_loop().create_future())
        t = None
        with self._lock:
            if self._closed:
                raise OSError("the client is closed")
            self._queued.setdefault(spath, collections.deque()).append(job)
            if len(self._threads) < self.THREADS:
                t = threading.Thread(target=self._work, daemon=True,
                                     name="cv-fetch-batch")
                self._threads.add(t)
        if t is not None:
            t.start()
        return job.fut

    def _work(self) -> None:
        me = threading.current_thread()
        while True:
            with self._lock:
                got = self._take()
                if got is None:
                    self._threads.discard(me)
                    return
            self._serve(*got)

    def _take(self) -> tuple[str, list[_Fetch]] | None:
        """Under the lock: up to CAP queued fetches of one channel, the
        channel then moved behind the others."""
        for spath in list(self._queued):
            q = self._queued.pop(spath)
            jobs = []
            while q and len(jobs) < self.CAP:
                job = q.popleft()
                if not job.fut.done():      # its waiter is gone: skip it
                    jobs.append(job)
            if q:
                self._queued[spath] = q
            if jobs:
                return spath, jobs
        return None

    def _serve(self, spath: str, jobs: list[_Fetch]) -> None:
        first = jobs[0]
        first.hop = True
        replies = self.conns.pipeline(spath, [j.lb.block.id for j in jobs])
        try:
            # in the trace of the first block's waiter
            with first.ctx.run(first.reader._span, "fetch_batch",
                               blocks=len(jobs), worker=spath):
                for job in jobs:
                    job.ctx.run(self._one, job, replies)
                    self._post(job)
        finally:
            replies.close()

    @staticmethod
    def _one(job: _Fetch, replies) -> None:
        """One block of a batch, in its waiter's context, as a thread of
        its own runs it: its reply (`grant`: the wait for it from when
        the thread turns to this block; the first block's holds the
        batch's send), then `_map_verify`. `t_start` is that turn, so the
        wait behind the batch-mates ahead is the hand-off's `queue`;
        `t_end` is the post."""
        reader, spent = job.reader, job.spent
        spent["t_start"] = time.perf_counter()
        try:
            with reader._phase("grant", spent):
                got = next(replies)
            if isinstance(got, Exception):
                job.exc = got
            else:
                job.result = reader._map_verify(*got, job.lb, job.algo,
                                                spent, job.into)
        except Exception as e:  # noqa: BLE001 — the waiter's to handle
            job.exc = e
        finally:
            spent["t_end"] = time.perf_counter()

    def _post(self, job: _Fetch) -> None:
        with self._lock:
            self._done.append(job)
            if len(self._done) > 1:
                return               # a wake is on its way already
        try:
            job.fut.get_loop().call_soon_threadsafe(self._deliver)
        except RuntimeError:         # the loop is closed: nobody takes them
            with self._lock:
                done, self._done = self._done, []
            for j in done:
                self._discard(j)

    def _deliver(self) -> None:
        """On the loop: every result posted since the last wake."""
        with self._lock:
            done, self._done = self._done, []
        for job in done:
            c = job.reader.counters
            c["read.fetch.batched_blocks"] = \
                c.get("read.fetch.batched_blocks", 0) + 1
            if job.hop:
                c["read.fetch.hops"] = c.get("read.fetch.hops", 0) + 1
            if job.fut.done():
                self._discard(job)
            elif job.exc is not None:
                job.fut.set_exception(job.exc)
            else:
                job.fut.set_result(job.result)

    @staticmethod
    def _discard(job: _Fetch) -> None:
        """Close what a fetch whose waiter is gone was owed; a block of a
        range leaves its mapping to the range (unmapped with it)."""
        if job.result is not None:
            fd, _length, mm, _got, _copied = job.result
            FsReader._unmap(fd, mm if job.into is None else None)

    def close(self) -> None:
        """Refuse new fetches, fail those no thread has taken (their
        readers fall back), and wait for the batches in hand: no thread
        outlives the client."""
        with self._lock:
            self._closed = True
            queued, self._queued = self._queued, {}
            threads = list(self._threads)
        for q in queued.values():
            for job in q:
                if not job.fut.done():
                    job.fut.set_exception(OSError("the client is closed"))
        for t in threads:
            t.join(timeout=10.0)


class Primed:
    """What a client holds for a caller that named its files up front
    (CurvineClient.prime), one answer a peer for the whole list where
    each reader would have asked for itself: the master's answer a path
    (`files`: its FileBlocks, or the error its open raises), the
    co-located workers' GET_BLOCK_INFO answer a block (`blocks`: info
    and the batch's send time, the lease clock), and the short-circuit
    read counts of the readers closed since, by granting worker
    (`reads`), until CurvineClient.flush_reports sends them. An entry
    serves once: it is popped by the open, or the probe, that uses it."""

    def __init__(self):
        from curvine_tpu.worker.shm import ShmConns
        self.files: dict[str, FileBlocks | err.CurvineError] = {}
        self.blocks: dict[int, tuple[dict, float]] = {}
        self.reads: dict[str, dict[int, int]] = {}
        # the list's readers fetch their blocks' fds in batches, over
        # connections to the workers' shm channels that stay open
        self.conns = ShmConns()
        self.fetcher = BatchFetcher(self.conns)

    def close(self) -> None:
        """Drop what was not taken, stop the batch fetcher and close the
        kept connections; `reads` is the client's to send first
        (flush_reports)."""
        self.files.clear()
        self.blocks.clear()
        self.fetcher.close()
        self.conns.close()

    def take_block(self, block_id: int) -> tuple[dict, float] | None:
        """The primed answer for a block unless its lease has run out
        (the reader then probes for itself, as if nothing was primed)."""
        ent = self.blocks.pop(block_id, None)
        if ent is not None:
            lease = ent[0].get("lease_ms")
            if lease and time.time() >= ent[1] + lease / 1000:
                return None
        return ent


class ReadDetector:
    """Sequential/random access-pattern detector driving prefetch.

    Parity: curvine-client/src/file/read_detector.rs:25 — default
    Sequential, `threshold` contiguous reads confirm Sequential.
    Adaptation for a positional API (FUSE never calls seek): the
    reference flips to Random on an explicit seek; here TWO consecutive
    non-contiguous positional reads flip to Random (one isolated jump
    keeps the current pattern, matching the reference's 'mixed read'
    scenario), and explicit seeks still flip immediately."""

    def __init__(self, threshold: int = 3, enabled: bool = True):
        self.enabled = enabled
        self.threshold = max(1, threshold)
        self.last_pos = -1
        self.seq_count = 0
        self.sequential = True

    def record_seek(self) -> None:
        if not self.enabled:
            return
        self.seq_count = 0
        self.last_pos = -1
        self.sequential = False

    def record_read(self, start: int, end: int) -> None:
        if not self.enabled:
            return
        if self.last_pos < 0 or start == self.last_pos:
            self.seq_count += 1
            if self.seq_count >= self.threshold:
                self.sequential = True
        else:
            if self.seq_count == 0:
                # second consecutive jump: this stream is random
                self.sequential = False
            self.seq_count = 0
        self.last_pos = end


class FsReader:
    def __init__(self, fs_client, path: str, file_blocks: FileBlocks,
                 pool: ConnectionPool, chunk_size: int = 512 * 1024,
                 short_circuit: bool = True, read_ahead: int = 2,
                 counters: dict | None = None,
                 smart_prefetch: bool = True, seq_threshold: int = 3,
                 health=None, op_deadline_ms: int = 0, tracer=None,
                 verify: bool = True, primed: Primed | None = None):
        # the client's primed answers, where this reader was opened from
        # one (CurvineClient.open): its probes look there first and its
        # read counts merge there at close. None: it asks for itself
        self.primed = primed
        # shared per-client WorkerHealth scoreboard (client/health.py):
        # replica choice deprioritizes open-circuit workers and every
        # remote outcome feeds back into it
        self.health = health
        # shared per-client Tracer (obs/trace.py): each public read op
        # is a span, and every remote replica ATTEMPT gets its own child
        # span — a failover shows as an error span, never as a gap
        self.tracer = tracer
        # default end-to-end budget per read op (0 = none); explicit
        # deadline_ms args on read methods override per call
        self.op_deadline_ms = op_deadline_ms
        self.read_ahead = read_ahead
        self.fs = fs_client
        self.path = path
        self.blocks = file_blocks
        self.pool = pool
        self.chunk_size = chunk_size
        self.short_circuit = short_circuit
        self.pos = 0
        self.len = file_blocks.status.len
        # interval index over block offsets: positional reads bisect
        # instead of scanning block_locs per call (4K FUSE traffic pays
        # the scan on EVERY op), with a last-hit cursor for the
        # sequential case (next read lands in the same or next block)
        self._block_offs = [lb.offset for lb in file_blocks.block_locs]
        self._last_block_idx = 0
        # positional prefetch: while the detector says sequential, the
        # next read_ahead chunk-aligned segments of REMOTE blocks are
        # fetched in the background (short-circuit segments are already
        # one page-cache preadv — prefetch would only add a copy)
        self.detector = ReadDetector(seq_threshold, smart_prefetch)
        self._pf: dict[int, object] = {}     # seg offset -> Task|ndarray
        self._pf_order: list[int] = []
        self._local_paths: dict[int, str | None] = {}
        # block_id -> (fd, path it was opened for): a re-probe that
        # lands on a new path (tier move) must not reuse the old fd
        self._local_fds: dict[int, tuple[int, str]] = {}
        # bdev tiers: the block is an extent at this base offset inside
        # the tier's shared backing file
        self._local_offs: dict[int, int] = {}
        # bdev grants carry a lease (worker quarantines freed extents for
        # 2x this); past expiry the cached (path, offset) must be
        # re-probed before the next fd read
        self._local_expiry: dict[int, float] = {}
        # direct-IO capability advertised by GET_BLOCK_INFO: the serving
        # tier reads O_DIRECT through a submission ring of this depth —
        # read_range sizes its slice fan-out to it (0 = not advertised)
        self.direct_queue_depth = 0
        # short-circuit reads bypass the worker, so heat is reported
        # back: per-block read counts, flushed periodically + on close
        self._sc_reads: dict[int, int] = {}
        self._sc_addr: dict[int, str] = {}
        self._sc_pending = 0
        self._sc_flush_task: asyncio.Task | None = None
        self.counters = counters if counters is not None else {}
        # end-to-end integrity: every read that covers a FULL block is
        # checked against the block's commit-time checksum (carried on
        # the READ_BLOCK EOF frame / GET_BLOCK_INFO reply). A mismatch
        # means bytes changed somewhere between the writer's commit and
        # this process — bad media, a torn page, a buggy middlebox — and
        # is treated as a replica failure: count, tell the master (so
        # re-replication heals from a good copy), fail over.
        self.verify = verify
        # block_id -> (crc, algo) captured from GET_BLOCK_INFO for the
        # short-circuit paths (remote reads get it on the EOF frame)
        self._block_crc: dict[int, tuple[int, str]] = {}
        # shared-memory short-circuit (docs/data-plane.md): the worker
        # advertised a sealed-memfd side channel for these blocks; maps
        # are block_id -> (memfd, mmap), verified once at map time (on
        # the fetch thread, in place) and bounded by the same
        # _SC_CACHE_CAP FIFO as the fd cache (_drop_local closes both)
        self._shm_sock: dict[int, str] = {}
        self._shm_maps: dict[int, tuple[int, mmap.mmap]] = {}
        # a block being fetched → the futures of the callers that wait
        # for that fetch instead of starting their own (`_shm_map`)
        self._shm_flights: dict[int, list[asyncio.Future]] = {}
        # a file of several blocks: one range of addresses for all of
        # them, each block mapped into its place on first use and kept
        # there (`_file_span`); its entries in _shm_maps have fd -1.
        # None until first asked for, False for a file of one block
        self._range = None
        # offsets of the range's places that hold a block or are being
        # filled: nothing is mapped over them (`_shm_fetch`) until they
        # are let go (`SpanMap.hold`)
        self._slots: set[int] = set()
        # block ids whose shm capability is a WARM export (below-MEM
        # tier; docs/data-plane.md): same protocol, separate accounting
        # (read.shm_warm_hits / read.shm_warm_fallbacks, served_by
        # "shm_warm"). Learned from the GET_BLOCK_INFO probe or the
        # SC_READ_REPORT reply when heat crosses the worker's threshold.
        self._shm_warm: set[int] = set()
        # registered receive buffers (rpc/transport.py): caller-visible
        # destinations >= _aligned_min are page-aligned mmap-backed so
        # remote payloads scatter straight into device-ingestible
        # memory; prefetch segments cycle through the bounded pool
        rc = getattr(pool, "rpc_conf", None)
        self._aligned_min = getattr(rc, "recv_aligned_min",
                                    transport._ALIGNED_MIN)
        self._recv_pool = transport.recv_pool()
        if rc is not None:
            self._recv_pool.max_bytes = rc.recv_registered_bytes
        # which path served the current read op (span attribute)
        self._serve_paths: set[str] = set()

    # ---------------- positioning ----------------

    def seek(self, pos: int) -> None:
        if pos < 0 or pos > self.len:
            raise err.InvalidArgument(f"seek {pos} out of [0, {self.len}]")
        if pos != self.pos:
            self.detector.record_seek()
        self.pos = pos

    def _locate(self, offset: int) -> tuple[LocatedBlock, int] | None:
        locs = self.blocks.block_locs
        if not locs:
            return None
        # sequential fast path: same block as last time, or the next one
        i = self._last_block_idx
        if i < len(locs) and locs[i].offset <= offset:
            if offset < locs[i].offset + locs[i].block.len:
                return locs[i], offset - locs[i].offset
            if i + 1 < len(locs) and offset < (locs[i + 1].offset
                                               + locs[i + 1].block.len):
                self._last_block_idx = i + 1
                return locs[i + 1], offset - locs[i + 1].offset
        import bisect
        i = bisect.bisect_right(self._block_offs, offset) - 1
        if i < 0:
            return None
        lb = locs[i]
        if offset >= lb.offset + lb.block.len:
            return None
        self._last_block_idx = i
        return lb, offset - lb.offset

    def blocks_under(self, offset: int, n: int) -> int:
        """How many of the file's blocks the range [offset, offset+n)
        lies in."""
        import bisect
        if n <= 0:
            return 0
        first = max(0, bisect.bisect_right(self._block_offs, offset) - 1)
        return bisect.bisect_left(self._block_offs, offset + n) - first

    def _pick_loc(self, lb: LocatedBlock):
        return pick_loc(lb, self.fs.client_host)

    @staticmethod
    def _addr(loc) -> str:
        return f"{loc.ip_addr or loc.hostname}:{loc.rpc_port}"

    def _failover_locs(self, lb: LocatedBlock) -> list:
        """Replica try-order: local-first, then breaker-aware — workers
        behind an open circuit sink to the end so a wedged replica is
        only paid for when no healthy one exists."""
        preferred = self._pick_loc(lb)
        locs = [preferred] + [l for l in lb.locs if l is not preferred]
        if self.health is not None:
            locs = self.health.order(locs, key=self._addr)
        return locs

    def _span(self, op: str, detail: bool = False, **attrs):
        """Tracer span (or a no-op when untraced). `detail`: a step
        inside an operation, which raises no slow-op line of its own
        (with no span round it, it is the operation: Tracer.span)."""
        if self.tracer is None:
            return nullcontext()
        return self.tracer.span(op, attrs=attrs or None, detail=detail)

    def _phase(self, phase: str, into: dict | None = None):
        """One phase of a read, timed at the one place its work is done
        (docs/observability.md has the table): seconds and count into
        the client's counters as read.phase.<phase>.s / .n, always on,
        and the span phase.<phase>. A fetch thread never writes the
        counters: it times into a dict of its own (`into`), which the
        loop adds when the thread has handed back; a step there never
        yields, so its thread's CPU seconds come beside the wall, on a
        sample of the steps (read.phase.<phase>.cpu_s / .cpu_wall_s)."""
        return Timed(self.counters if into is None else into,
                     f"read.phase.{phase}",
                     self._span(f"phase.{phase}", detail=True),
                     cpu=into is not None)

    # ---------------- hole regions ----------------

    def _hole_len(self, offset: int) -> int:
        """Bytes of HOLE at `offset`: no block covers it but it is
        inside the file (resize-extended past the last written block).
        Served as zeros through the cached read path instead of a short
        read (parity: reference block_reader_hole.rs)."""
        if offset >= self.len:
            return 0
        for lb in self.blocks.block_locs:
            if lb.offset > offset:
                return lb.offset - offset
        return self.len - offset

    def _deadline(self, deadline_ms) -> Deadline | None:
        """Per-op budget: the explicit per-call override, else the
        configured default, else None. Accepts an existing Deadline so
        multi-step callers can share one budget."""
        if isinstance(deadline_ms, Deadline):
            return deadline_ms
        if deadline_ms is None:
            deadline_ms = self.op_deadline_ms
        return Deadline.after_ms(deadline_ms) if deadline_ms else None

    # ---------------- short-circuit ----------------

    # short-circuit probe cache cap: entries (including negative "not
    # local" answers) are FIFO-evicted past this, so a block that moved
    # since its probe is re-probed eventually even if no read fails
    _SC_CACHE_CAP = 256

    def _drop_local(self, bid: int) -> None:
        """Forget every cached short-circuit handle for a block: the
        probe result went stale (block evicted/evacuated/truncated under
        PR 8 healing). The next read re-probes or goes remote."""
        self._local_paths.pop(bid, None)
        self._local_offs.pop(bid, None)
        self._local_expiry.pop(bid, None)
        cached = self._local_fds.pop(bid, None)
        if cached is not None:
            try:
                os.close(cached[0])
            except OSError:
                pass
        self._drop_shm(bid)

    def _drop_shm(self, bid: int) -> None:
        """Close a block's shm map + memfd. A zero-copy view still held
        by a caller keeps the mapping alive past this close (BufferError
        → the mmap object stays open until the last view is released and
        GC finishes it) — eviction can never tear pages out from under a
        live read. The fd closes either way; the map holds the pages.
        A block of the file's range (fd -1) is let go the same way: its
        place stays mapped while a view holds it, and nothing is mapped
        over it until the last view is collected (`SpanMap.hold`)."""
        self._shm_sock.pop(bid, None)
        self._shm_warm.discard(bid)
        ent = self._shm_maps.pop(bid, None)
        if ent is not None and ent[0] >= 0:
            self._unmap(*ent)

    @staticmethod
    def _unmap(fd: int, mm: mmap.mmap | None) -> None:
        if mm is not None:
            try:
                mm.close()
            except BufferError:
                pass
        try:
            os.close(fd)
        except OSError:
            pass

    async def _local_path(self, lb: LocatedBlock) -> str | None:
        """Resolve the on-disk path for a co-located block (cached)."""
        bid = lb.block.id
        if bid in self._local_paths:
            return self._local_paths[bid]
        if not lb.locs:
            return None          # EC stripe (or locationless): no probe
        path = None
        addr = probe_addr(lb, self.fs.client_host) \
            if self.short_circuit else None
        if addr is not None:
            ent = self.primed.take_block(bid) \
                if self.primed is not None else None
            if ent is not None:
                self._count("read.primed.blocks")
                path = self._use_block_info(bid, *ent, addr)
            else:
                try:
                    with self._phase("probe"):
                        conn = await self.pool.get(addr)
                        # lease clocks start at request SEND, not reply
                        # arrival: the worker grants after our send, so
                        # send + lease_ms always undershoots the worker's
                        # expiry no matter how long the reply took — a
                        # delayed reply can never extend the window past
                        # what the worker's quarantine covers
                        sent_at = time.time()
                        rep = await conn.call(RpcCode.GET_BLOCK_INFO,
                                              data=pack({"block_id": bid}))
                    srv = rep.srv_seconds()
                    if srv is not None:
                        # the worker's own share of the probe's wall
                        self._count("read.probe.srv_handle_s", srv[1])
                    path = self._use_block_info(
                        bid, rep.header or unpack(rep.data) or {},
                        sent_at, addr)
                except err.CurvineError as e:
                    log.debug("short-circuit probe failed for %d: %s", bid, e)
        while len(self._local_paths) >= self._SC_CACHE_CAP:
            self._drop_local(next(iter(self._local_paths)))
        self._local_paths[bid] = path
        return path

    def _use_block_info(self, bid: int, info: dict, sent_at: float,
                        addr: str) -> str | None:
        """Take in the GET_BLOCK_INFO answer of the worker at `addr` for
        a block, asked at `sent_at` by this reader or for it (Primed) →
        the block's local path, or None where this host cannot see it."""
        if info.get("direct_io"):
            self.direct_queue_depth = max(
                self.direct_queue_depth, int(info.get("queue_depth", 0)))
        if info.get("crc32") is not None:
            self._block_crc[bid] = (
                info["crc32"], info.get("crc_algo", "crc32"))
        p = info.get("path")
        if not p or not os.path.exists(p):
            return None
        self._local_offs[bid] = info.get("offset", 0)
        self._sc_addr[bid] = addr
        lease = info.get("lease_ms")
        if lease:
            self._local_expiry[bid] = sent_at + lease / 1000
        if info.get("shm") and info.get("shm_sock"):
            # worker offers the sealed-memfd side channel for this
            # block: the next read fetches the fd and maps it (shm wins
            # over the preadv fd path)
            self._shm_sock[bid] = info["shm_sock"]
            if info.get("shm_warm"):
                self._shm_warm.add(bid)
        return p

    async def _revalidate(self, lb: LocatedBlock) -> None:
        """A leased (bdev-extent) grant expired: re-probe GET_BLOCK_INFO
        and, if the block moved (different path/offset) or left the
        worker, drop the stale fd so reads can't land in a reallocated
        extent of the shared backing file."""
        bid = lb.block.id
        old_path = self._local_paths.get(bid)
        old_off = self._local_offs.get(bid, 0)
        self._local_paths.pop(bid, None)
        self._local_expiry.pop(bid, None)
        path = await self._local_path(lb)   # fresh probe
        if path != old_path or self._local_offs.get(bid, 0) != old_off:
            cached = self._local_fds.pop(bid, None)
            if cached is not None:
                try:
                    os.close(cached[0])
                except OSError:
                    pass
            self._drop_shm(bid)

    # ---------------- shared-memory short-circuit ----------------

    def _count(self, key: str, n: float = 1) -> None:
        self.counters[key] = self.counters.get(key, 0) + n

    def _mark(self, path: str) -> None:
        self._serve_paths.add(path)

    def served_by(self) -> str:
        """The rungs that served the current read op, as each marked
        itself where it returned ("shm", "local", "remote", …)."""
        return "+".join(sorted(self._serve_paths)) or "none"

    def _shm_hit(self, bid: int) -> None:
        """Account one shm-served read to the right plane: warm-cache
        exports (below-MEM tier) keep their own counters so the
        read-plane rollup separates them from MEM exports."""
        if bid in self._shm_warm:
            self._count("read.shm_warm_hits")
            self._mark("shm_warm")
        else:
            self._count("read.shm_hits")
            self._mark("shm")

    def _shm_fallback(self, bid: int) -> None:
        self._count("read.shm_warm_fallbacks" if bid in self._shm_warm
                    else "read.shm_fallbacks")

    async def _shm_map(self, lb: LocatedBlock):
        """The block's shm mapping, fetched on first use (`_shm_fetch`)
        and kept: every later read of the block is a pure memory access.
        Single-flight: a caller that finds the block's fetch in flight
        waits for it and takes what it brings, so however many reads
        arrive at once a block is granted, mapped and verified once. A
        block of a file of several blocks lies in the file's range
        (`_file_span`): what comes back is then its slice of the range
        (a read-only array), else an `mmap` of the block alone.
        None → caller uses the fd/socket paths."""
        bid = lb.block.id
        while True:
            ent = self._shm_maps.get(bid)
            if ent is not None:
                return ent[1]
            waiting = self._shm_flights.get(bid)
            if waiting is None:
                break
            fut = asyncio.get_running_loop().create_future()
            waiting.append(fut)
            got = await fut
            if got is not _AGAIN:
                return got
        if not self.short_circuit or not lb.locs:
            return None
        self._shm_flights[bid] = waiting = []
        got = _AGAIN                # the fetch ended without an answer
        try:
            got = await self._shm_fetch(lb)
            return got
        finally:
            del self._shm_flights[bid]
            for fut in waiting:
                if not fut.done():
                    fut.set_result(got)

    async def _shm_fetch(self, lb: LocatedBlock):
        """One block's fetch: connect to the worker's SCM_RIGHTS side
        channel (blocking socket → thread; asyncio can't carry ancillary
        fds), map the sealed memfd read-only — on its own, or into its
        place in the file's range — and verify the full block ONCE
        against the commit-time checksum. All three run on a fetch
        thread (`_fetch`: a batch's, for a primed reader); the loop
        compares the checksum it brings back and does everything that
        touches this reader's state. Counted: read.block_fetches a
        block granted, read.blocks_mapped a block kept.

        A block goes into its place in the range only where that place
        is empty: a place that holds a verified block may lie under
        views handed out, so nothing is mapped over it until the last
        of them and this reader have let it go (`SpanMap.hold`), and a
        block that finds it taken is mapped on its own. A fetch that
        ends with nothing there a view could have seen (no grant, a
        failed map, a checksum refused) empties it at once."""
        bid = lb.block.id
        if bid not in self._local_paths:
            await self._local_path(lb)      # probe captures shm_sock
        spath = self._shm_sock.get(bid)
        if spath is None:
            return None
        want, algo = self._block_crc.get(bid, (None, None))
        if not self.verify:
            algo = None
        span = self._file_span()
        into = None
        if span is not None and lb.offset % mmap.PAGESIZE == 0 \
                and lb.offset + lb.block.len <= span.nbytes \
                and lb.offset not in self._slots:
            # held from here on, also by a fetch that is cancelled while
            # its thread may still map there
            self._slots.add(lb.offset)
            into = (span, lb.offset)
        spent: dict[str, float] = {}
        t_submit = time.perf_counter()
        try:
            fd, length, mm, got, copied = await self._fetch(
                spath, lb, algo, spent, into)
        except (LookupError, OSError, ValueError) as e:
            # worker dropped the export / channel gone: stop retrying
            # this block, serve it through fd/socket instead
            log.debug("shm fetch for block %d failed: %s", bid, e)
            if into is not None:
                self._slots.discard(lb.offset)
            self._shm_sock.pop(bid, None)
            self._shm_fallback(bid)
            return None
        finally:
            self._count_fetch(spent, t_submit)
        self._count("read.block_fetches")
        if got is not None:
            self._count_verify(length, copied)
        if into is not None:
            os.close(fd)             # the range's mapping holds the pages
            fd = -1
        if mm is None:
            # a grant of another length than the block's is a stale
            # export: stop asking for it. A map that failed may be
            # tried again
            if into is not None:
                self._slots.discard(lb.offset)
            else:
                os.close(fd)
            if length != lb.block.len or length <= 0:
                self._shm_sock.pop(bid, None)
            self._shm_fallback(bid)
            return None
        if got is not None and got != want:
            self._sc_corrupt(lb)    # flags the replica, drops the caches
            if into is None:
                self._unmap(fd, mm)
            else:
                # no view lies over a place filled by this fetch
                span.unmap(lb.offset, length)
                self._slots.discard(lb.offset)
            self._shm_fallback(bid)
            return None
        if into is not None:
            mm = span.hold(lb.offset, length, self._slots.discard)
        self._shm_maps[bid] = (fd, mm)
        self._count("read.blocks_mapped")
        return mm

    def _file_span(self):
        """The range of addresses that this reader maps the blocks of a
        file of several blocks into, side by side (a `SpanMap` the size
        of the file's blocks, made on first use; addresses only, no
        memory behind a place until its block is mapped there): a read
        that straddles blocks or spans many is then a slice of mappings
        already held. None for a file of one block."""
        if self._range is None:
            self._range = False
            locs = self.blocks.block_locs
            if len(locs) > 1:
                from curvine_tpu.client.spanmap import SpanMap
                try:
                    self._range = SpanMap(locs[-1].offset
                                          + locs[-1].block.len)
                except (OSError, ValueError) as e:
                    log.debug("no address range for %s: %s", self.path, e)
        return self._range or None

    def _count_fetch(self, spent: dict, t_submit: float) -> None:
        """What a fetch thread stamped into `spent`, counted here, on
        the loop: fetch threads never write the counters. `resume` is
        what the hand-off cost beside the work in the thread, in two
        parts by the thread's stamps: `queue`, submit → the thread
        running (the pool of fetch threads, the GIL), and `wake`, the
        thread returned → this task running again (the client's loop).
        `resume` is their sum: the thread's own statements between its
        phases (microseconds) are in neither. A task cancelled while its
        thread runs finds no return stamp: its hand-off is `queue`."""
        now = time.perf_counter()
        started = spent.pop("t_start", None)
        ended = spent.pop("t_end", now)
        if started is None:
            return                  # no thread ran: no hand-off to count
        for key, v in list(spent.items()):
            self._count(key, v)
        queue, wake = started - t_submit, now - min(ended, now)
        self._count("read.phase.resume.s", queue + wake)
        self._count("read.phase.resume.n")
        self._count("read.resume.queue.s", queue)
        self._count("read.resume.wake.s", wake)

    def _fetch(self, spath: str, lb: LocatedBlock, algo: str | None,
               spent: dict, into: tuple | None = None):
        """The hand-off of one block's fetch to a thread → awaitable of
        `_fetch_shm`'s tuple. A primed reader is one of many: its block
        joins the client's batches (`BatchFetcher`), a thread hop and a
        wake of the loop for many blocks. Any other reader's is a thread
        hop of its own, counted here (read.fetch.hops)."""
        if self.primed is not None:
            return self.primed.fetcher.fetch(self, spath, lb, algo, spent,
                                             into)
        self._count("read.fetch.hops")
        return asyncio.to_thread(self._fetch_shm, spath, lb, algo, spent,
                                 into)

    def _fetch_shm(self, spath: str, lb: LocatedBlock, algo: str | None,
                   spent: dict, into: tuple | None = None) -> tuple:
        """On the fetch thread: `fetch_block_fd` (phase `grant`), then
        `_map_verify`. → its tuple; touches nothing of the reader's
        state. It stamps when it starts and when it returns (`t_start`,
        `t_end`: no phase of their own) for `_count_fetch`'s split of
        the hand-off."""
        from curvine_tpu.worker import shm
        spent["t_start"] = time.perf_counter()
        try:
            with self._phase("grant", spent):
                fd, length = shm.fetch_block_fd(spath, lb.block.id)
            return self._map_verify(fd, length, lb, algo, spent, into)
        finally:
            spent["t_end"] = time.perf_counter()

    def _map_verify(self, fd: int, length: int, lb: LocatedBlock,
                    algo: str | None, spent: dict,
                    into: tuple | None = None) -> tuple:
        """On a fetch thread, for a granted memfd: map it (`map`) and,
        given the commit-time `algo`, checksum the mapping where it lies
        (`verify`). The first touch of every page and the hash run here,
        without the GIL, not on the loop. Each phase has its span and
        leaves its seconds in `spent`, so the awaiting task can tell the
        work from its own wait to run again. `into` = (SpanMap, offset):
        the block is one of a range's and is mapped there, beside its
        neighbours, not on its own. → (fd, granted length, mapping or
        None, checksum or None, bytes copied to hash)."""
        mm = got = None
        copied = 0
        if length == lb.block.len and length > 0:
            # a block that is verified has every page read right away:
            # the kernel maps them all in this one call (MAP_POPULATE)
            # at a tenth of what a trap a page costs the hash (0.6
            # against 6.3 us a page on a v5e host's VM, and the traps
            # of all threads of a process take turns). mmap() runs
            # without the GIL. Read-only either way (PROT_READ)
            flags = mmap.MAP_SHARED | (
                mmap.MAP_POPULATE if algo is not None else 0)
            try:
                with self._phase("map", spent):
                    mm = mmap.mmap(fd, length, flags=flags,
                                   prot=mmap.PROT_READ) if into is None \
                        else into[0].map(fd, length, into[1], flags)
            except (OSError, ValueError):
                pass
        if mm is not None and algo is not None:
            with self._phase("verify", spent):
                got, copied = _block_crc(algo, mm)
        return fd, length, mm, got, copied

    async def _shm_read_into(self, lb: LocatedBlock, block_off: int,
                             out) -> int:
        """Fill ``out`` from the block's shm mapping (one memcpy, zero
        RPCs, zero syscalls); 0 → not shm-served, use the next path."""
        mm = await self._shm_map(lb)
        if mm is None:
            return 0
        import numpy as np
        n = len(out)
        out[:n] = np.frombuffer(mm, dtype=np.uint8, count=n,
                                offset=block_off)
        self._note_sc_read(lb.block.id, n)
        self._shm_hit(lb.block.id)
        return n

    async def _shm_view(self, offset: int, n: int):
        """Zero-copy numpy view onto a shm-mapped block range — the
        whole point of the shm plane: read_range/mmap_view return a
        read-only slice of the sealed mapping itself, no RPC, no copy.
        A range over several blocks is a slice of their mappings side
        by side (`_span_view`). None → a block not shm-served."""
        if n <= 0:
            return None
        located = self._locate(offset)
        if located is None:
            return None
        lb, block_off = located
        if block_off + n > lb.block.len:
            return await self._span_view(offset, n)
        kept = lb.block.id in self._shm_maps
        with self._span("shm_view", detail=True, block=lb.block.id,
                        n=n) as sp:
            mm = await self._shm_map(lb)
            if sp is not None:
                sp.set_attr("served_by", "none" if mm is None else
                            "shm_warm" if lb.block.id in self._shm_warm
                            else "shm")
                if kept:
                    sp.set_attr("kept", True)
        if mm is None:
            return None
        import numpy as np
        self._note_sc_read(lb.block.id, n)
        self._shm_hit(lb.block.id)
        self._count("read.zero_copy_bytes", n)
        return np.frombuffer(mm, dtype=np.uint8, count=n,
                             offset=block_off)

    def _span_blocks(self, offset: int, n: int) -> list | None:
        """The consecutive blocks under [offset, offset+n) if they can
        lie side by side in one range of addresses: replicated blocks
        with a location each, no hole between or after them, and every
        one but the last a whole number of pages (the next starts where
        it ends)."""
        import bisect
        locs = self.blocks.block_locs
        i = bisect.bisect_right(self._block_offs, offset) - 1
        if i < 0:
            return None
        lbs: list[LocatedBlock] = []
        end = offset + n
        at = locs[i].offset
        while at < end:
            if i >= len(locs) or locs[i].offset != at:
                return None          # a hole
            lb = locs[i]
            if not lb.locs or lb.block.len <= 0:
                return None          # an EC stripe, or locationless
            lbs.append(lb)
            at += lb.block.len
            i += 1
        if any(lb.block.len % mmap.PAGESIZE for lb in lbs[:-1]) \
                or len(lbs) > self._SC_CACHE_CAP:
            return None
        return lbs

    async def _span_view(self, offset: int, n: int):
        """`_shm_view` for a range that spans blocks: a slice of the
        file's range (`_file_span`) once every block under it is mapped
        there and verified (`_shm_map` each, all at once; a block mapped
        before, by any read of this reader, is not fetched again). The
        loop only compares the checksums the fetch threads bring back.
        The rule is the range's own (`_span_blocks`), whatever the rest
        of the file holds. All blocks or nothing: None if any of them is
        not served by the shm rung or is not in its place in the range
        (mapped on its own), and then no byte of the range has reached
        the caller (a block that failed its checksum is flagged and
        unmapped as on the one-block path; the blocks that passed stay
        held). A block's place belongs to the views over it as much as
        to this reader: it stays mapped until the last of them is
        collected, whatever is closed or evicted before."""
        if not self.short_circuit:
            return None
        lbs = self._span_blocks(offset, n)
        span = self._file_span() if lbs is not None else None
        if span is None:
            return None
        kept = all(lb.block.id in self._shm_maps for lb in lbs)
        with self._span("shm_view", detail=True, block=lbs[0].block.id,
                        n=n, blocks=len(lbs)) as sp:
            got = await asyncio.gather(*map(self._shm_map, lbs),
                                       return_exceptions=True)
            for res in got:
                if isinstance(res, BaseException):
                    raise res
            ok = all(m is not None and self._shm_maps.get(
                lb.block.id, (0,))[0] < 0 for lb, m in zip(lbs, got))
            if sp is not None:
                sp.set_attr("served_by", "none" if not ok else
                            "+".join(sorted({
                                "shm_warm" if lb.block.id in self._shm_warm
                                else "shm" for lb in lbs})))
                if kept:
                    sp.set_attr("kept", True)
        if not ok:
            return None
        for lb in lbs:
            lo = max(offset, lb.offset)
            hi = min(offset + n, lb.offset + lb.block.len)
            self._note_sc_read(lb.block.id, hi - lo)
            self._shm_hit(lb.block.id)
        self._count("read.zero_copy_bytes", n)
        self._count("read.span_views")
        self._count("read.span_view_blocks", len(lbs))
        self._count("read.span_view_bytes", n)
        return span.window(offset, n, got)

    def _alloc_out(self, n: int):
        """Caller-visible read destination: page-aligned mmap-backed
        (registered-receive style, numpy/HBM-view friendly) from
        rpc.recv_aligned_min up; small reads stay on the heap."""
        import numpy as np
        if n >= self._aligned_min:
            return transport.alloc_aligned(n)
        return np.empty(n, dtype=np.uint8)

    # ---------------- read integrity ----------------

    def _flag_corrupt(self, lb: LocatedBlock, loc) -> None:
        """A read of block `lb` from `loc` failed checksum verification:
        count it and tell the master (fire-and-forget) so the bad replica
        is retired and re-replicated from a good copy. The caller then
        treats the attempt as a read failure and fails over."""
        self.counters["read.checksum_mismatch"] = \
            self.counters.get("read.checksum_mismatch", 0) + 1
        log.warning("block %d from %s failed checksum verification",
                    lb.block.id, self._addr(loc))

        async def _report():
            try:
                await self.fs.call(
                    RpcCode.REPORT_UNDER_REPLICATED_BLOCKS,
                    {"block_ids": [lb.block.id],
                     "worker_id": loc.worker_id})
            except Exception as e:  # noqa: BLE001 — scrub is the backstop
                log.debug("corrupt-replica report failed: %s", e)
        asyncio.ensure_future(_report())

    def _count_verify(self, hashed: int, copied: int) -> None:
        self._count("read.verify.bytes", hashed)
        self._count("read.verify.copied_bytes", copied)

    def _sc_verify_ok(self, lb: LocatedBlock, data) -> bool:
        """Verify a FULL-block read of the fd rung against the
        commit-time checksum from GET_BLOCK_INFO. On mismatch: flag the
        replica and drop every local cache for the block so this read
        (and the next) goes through the remote failover path instead."""
        ent = self._block_crc.get(lb.block.id)
        if ent is None:
            return True
        want, algo = ent
        with self._phase("verify"):
            got, copied = _block_crc(algo, data)
        if got is None:
            return True
        self._count_verify(len(data), copied)
        if got == want:
            return True
        self._sc_corrupt(lb)
        return False

    def _sc_corrupt(self, lb: LocatedBlock) -> None:
        """A short-circuit copy of the block failed verification."""
        self._flag_corrupt(lb, self._pick_loc(lb))
        bid = lb.block.id
        self._local_paths[bid] = None
        self._local_offs.pop(bid, None)
        self._local_expiry.pop(bid, None)
        cached = self._local_fds.pop(bid, None)
        if cached is not None:
            try:
                os.close(cached[0])
            except OSError:
                pass
        self._drop_shm(bid)

    # ---------------- short-circuit read accounting ----------------

    def _note_sc_read(self, block_id: int, nbytes: int) -> None:
        self.counters["sc.bytes.read"] = \
            self.counters.get("sc.bytes.read", 0) + max(0, nbytes)
        self._sc_reads[block_id] = self._sc_reads.get(block_id, 0) + 1
        self._sc_pending += 1
        if self._sc_pending >= 512 and (self._sc_flush_task is None
                                        or self._sc_flush_task.done()):
            self._sc_flush_task = asyncio.ensure_future(
                self._flush_sc_reads())

    async def _flush_sc_reads(self) -> None:
        """Report accumulated per-block short-circuit read counts to the
        granting workers (fire-and-forget; heat accounting only)."""
        reads, self._sc_reads = self._sc_reads, {}
        self._sc_pending = 0
        by_addr: dict[str, dict[int, int]] = {}
        sc_reads_by_worker(by_addr, reads, self._sc_addr)
        for addr, block_reads in by_addr.items():
            # The reply piggybacks warm-cache adverts.  The
            # GET_BLOCK_INFO probe ran before the heat accrued, so
            # this is how the very client that created the heat
            # learns it can switch to the shm_warm rung.
            warm = await report_sc_reads(self.pool, addr, block_reads)
            for bid, sock in warm.items():
                self._shm_sock[int(bid)] = sock
                self._shm_warm.add(int(bid))

    # ---------------- reads ----------------

    async def read(self, n: int = -1, deadline_ms=None) -> bytes:
        if n < 0:
            n = self.len - self.pos
        n = min(n, self.len - self.pos)
        if n <= 0:
            return b""
        dl = self._deadline(deadline_ms)
        with self._span("read", path=self.path, n=n):
            first = await self._read_some(self.pos, n, deadline=dl)
            self.pos += len(first)
            if len(first) == n or not first:
                return first      # common case: one block segment, no copy
            with self._phase("copy"):
                out = bytearray(first)
            while len(out) < n:
                got = await self._read_some(self.pos, n - len(out),
                                            deadline=dl)
                if not got:
                    break
                with self._phase("copy"):
                    out += got
                self.pos += len(got)
            with self._phase("copy"):
                return bytes(out)

    async def read_all(self, deadline_ms=None) -> bytes:
        self.seek(0)
        self._serve_paths = set()
        with self._span("read_all", detail=True, path=self.path,
                        n=self.len) as sp:
            data = await self.read(self.len, deadline_ms=deadline_ms)
            if sp is not None:
                sp.set_attr("served_by", self.served_by())
        return data

    async def pread(self, offset: int, n: int, deadline_ms=None) -> bytes:
        """Positional read without moving the cursor."""
        dl = self._deadline(deadline_ms)
        with self._span("pread", path=self.path, offset=offset, n=n):
            out = bytearray()
            while len(out) < n and offset + len(out) < self.len:
                got = await self._read_some(offset + len(out), n - len(out),
                                            deadline=dl)
                if not got:
                    break
                out += got
            return bytes(out)

    async def pread_view(self, offset: int, n: int, deadline_ms=None):
        """Positional read returning a numpy uint8 buffer — the fast path:
        co-located segments are preadv'd straight into the output buffer
        (aligned allocation → THP-friendly, no intermediate bytes objects);
        remote segments stream into the same buffer, served from the
        sequential prefetch window when it has them. Use for device
        ingest and FUSE reads; `pread` stays for bytes consumers."""
        n = max(0, min(n, self.len - offset))
        out = self._alloc_out(n)
        self._serve_paths = set()
        with self._span("pread_view", path=self.path, offset=offset,
                        n=n) as sp:
            filled = await self._read_into(
                offset, out, use_prefetch=True,
                deadline=self._deadline(deadline_ms))
            if sp is not None:
                sp.set_attr("served_by", self.served_by())
        self.detector.record_read(offset, offset + filled)
        self._prefetch_topup(offset + filled)
        return out[:filled]

    async def _read_into(self, offset: int, out, *,
                         use_prefetch: bool = False,
                         deadline: Deadline | None = None) -> int:
        """Fill the numpy buffer `out` from `offset`; returns bytes
        filled (short on EOF / replica loss). The single positional-read
        core under pread_view and read_range."""
        n = len(out)
        filled = 0
        while filled < n:
            pos = offset + filled
            if use_prefetch:
                got = await self._pf_read_into(pos, out[filled:])
                if got > 0:
                    filled += got
                    continue
            located = self._locate(pos)
            if located is None:
                # hole region (resized past the written blocks): zeros
                nh = min(self._hole_len(pos), n - filled)
                if nh <= 0:
                    break
                out[filled:filled + nh] = 0
                self.counters["hole.bytes.read"] = \
                    self.counters.get("hole.bytes.read", 0) + nh
                self._mark("hole")
                filled += nh
                continue
            lb, block_off = located
            seg = min(n - filled, lb.block.len - block_off)
            if self._ec_active(lb):
                import numpy as np
                data = await self._read_ec(lb, block_off, seg,
                                           deadline)
                if not data:
                    break
                out[filled:filled + len(data)] = np.frombuffer(
                    data, dtype=np.uint8)
                filled += len(data)
                continue
            # shared-memory first: zero RPCs AND zero syscalls once the
            # block is mapped (the fd path below still costs a preadv)
            got = await self._shm_read_into(lb, block_off,
                                            out[filled:filled + seg])
            if got > 0:
                filled += got
                continue
            fd = await self._local_fd(lb)
            if fd is not None:
                base = self._local_offs.get(lb.block.id, 0)
                view = memoryview(out[filled:filled + seg])
                got = os.preadv(fd, [view], base + block_off)
                if self.verify and block_off == 0 \
                        and got == lb.block.len \
                        and not self._sc_verify_ok(lb, view[:got]):
                    fd = None     # bad local bytes: re-read remotely
                elif got < seg:
                    # short local read: the block file shrank or moved
                    # under us (eviction, healing evacuation) — drop the
                    # stale path/fd and re-read this segment remotely
                    self._drop_local(lb.block.id)
                    fd = None
                else:
                    self._note_sc_read(lb.block.id, got)
                    self._mark("local")
                    filled += got
            if fd is None:
                # remote: stream chunks straight into the output buffer
                got = await self._readinto_remote(
                    lb, block_off, memoryview(out[filled:filled + seg]),
                    deadline=deadline)
                if got <= 0:
                    break
                filled += got
        return filled

    async def read_range(self, offset: int, n: int, parallel: int = 1,
                         deadline_ms=None):
        """Read [offset, offset+n) as a numpy buffer, optionally SHARDED
        across `parallel` concurrent slice readers — the single-hot-file
        accelerator (parity: curvine-client/src/file/fs_reader_parallel.rs:27,
        slice split + per-slice readers). Each slice streams
        independently (its own pooled connections for remote blocks), so
        one large file saturates multiple workers/replicas instead of
        one socket.

        Shm-mapped ranges skip ALL of that: the return is a read-only
        zero-copy view onto the sealed mapping itself, or onto the
        mappings of the range's blocks side by side."""
        import numpy as np
        n = max(0, min(n, self.len - offset))
        if n == 0:
            return np.empty(0, dtype=np.uint8)
        dl = self._deadline(deadline_ms)
        self._serve_paths = set()
        with self._span("read_range", path=self.path, offset=offset,
                        n=n, parallel=parallel) as sp:
            view = await self._shm_view(offset, n)
            if view is not None:
                if sp is not None:
                    # _shm_view marked shm or shm_warm as appropriate
                    sp.set_attr("served_by", self.served_by())
                return view
            out = self._alloc_out(n)
            got = await self._read_range(offset, n, parallel, out, dl)
            if sp is not None:
                sp.set_attr("served_by", self.served_by())
            return got

    async def _read_range(self, offset: int, n: int, parallel: int,
                          out, dl):
        qd = self.direct_queue_depth
        if qd > 0:
            if parallel <= 1 and n >= 4 * self.chunk_size:
                # direct-IO worker: fan out to keep its submission ring
                # full even when the caller didn't ask for parallelism
                parallel = min(qd, max(1, n // (4 * self.chunk_size)))
            else:
                # never oversubscribe the ring — excess slices would
                # just queue behind each other at the engine
                parallel = min(parallel, qd) if parallel > 1 else parallel
        if parallel <= 1 or n < 4 * self.chunk_size:
            got = await self._read_into(offset, out, use_prefetch=True,
                                        deadline=dl)
            return out[:got]
        # contiguous slices, chunk-aligned so streams don't shear chunks
        per = -(-n // parallel)
        per = max(self.chunk_size, (per // self.chunk_size)
                  * self.chunk_size or per)
        bounds = [(s, min(s + per, n)) for s in range(0, n, per)]
        got = await asyncio.gather(
            *(self._read_into(offset + s, out[s:e], deadline=dl)
              for s, e in bounds))
        # a short slice mid-file truncates the result there
        total = 0
        for (s, e), g in zip(bounds, got):
            total = s + g
            if g < e - s:
                break
        return out[:total]

    # ---------------- sequential prefetch (positional reads) ----------

    def _seg_start(self, off: int) -> int:
        """Canonical prefetch-segment start covering `off`: chunk-aligned
        within its block (segments never straddle blocks — each maps to
        one remote stream)."""
        located = self._locate(off)
        if located is None:
            return -1
        lb, block_off = located
        return lb.offset + (block_off // self.chunk_size) * self.chunk_size

    def _prefetch_topup(self, from_off: int) -> None:
        """While the pattern is sequential, keep the next `read_ahead`
        segments of known-REMOTE blocks in flight. Never prefetches
        short-circuit blocks: their reads are one page-cache preadv —
        a prefetch would only add a copy."""
        if not self.detector.enabled or not self.detector.sequential \
                or self.read_ahead <= 0:
            return
        off = from_off
        scheduled = 0
        while scheduled < self.read_ahead and off < self.len:
            s = self._seg_start(off)
            if s < 0:
                return
            located = self._locate(s)
            lb, block_off = located
            if self._ec_active(lb):
                # EC stripes bypass prefetch: the decode path manages
                # its own per-cell fan-out, and a prefetched segment
                # would double-read the cells
                return
            seg_len = min(self.chunk_size - (block_off % self.chunk_size),
                          lb.offset + lb.block.len - s, self.len - s)
            if self._local_paths.get(lb.block.id, "?") is not None:
                # local (or not probed yet): the direct path handles it
                return
            if s not in self._pf:
                self._pf[s] = asyncio.ensure_future(
                    self._fetch_seg(s, seg_len))
                self._pf_order.append(s)
            off = s + seg_len
            scheduled += 1
        # bound the window: drop segments behind the consumer
        while len(self._pf_order) > 2 * self.read_ahead + 2:
            old = self._pf_order.pop(0)
            ent = self._pf.pop(old, None)
            if isinstance(ent, asyncio.Task):
                ent.cancel()

    async def _fetch_seg(self, s: int, seg_len: int):
        located = self._locate(s)
        if located is None:
            raise err.BlockNotFound(f"prefetch segment at {s}")
        lb, block_off = located
        # registered receive buffer: prefetch segments are internal
        # (consumed by copy, then released), so they cycle through the
        # bounded aligned pool instead of churning fresh allocations
        buf = self._recv_pool.acquire(seg_len)
        got = await self._readinto_remote(lb, block_off, memoryview(buf))
        return buf[:got]

    async def _pf_read_into(self, off: int, out) -> int:
        """Serve a positional read from the prefetch window; 0 → miss
        (caller reads directly)."""
        if not self._pf:
            return 0
        s = self._seg_start(off)
        ent = self._pf.get(s)
        if ent is None:
            return 0
        if isinstance(ent, asyncio.Task):
            try:
                buf = await ent
            except (err.CurvineError, asyncio.CancelledError, OSError):
                self._pf.pop(s, None)
                return 0
            self._pf[s] = buf
        else:
            buf = ent
        rel = off - s
        if rel >= len(buf):
            self._pf.pop(s, None)
            return 0
        n = min(len(out), len(buf) - rel)
        out[:n] = buf[rel:rel + n]
        self.counters["pf.bytes.read"] = \
            self.counters.get("pf.bytes.read", 0) + n
        self._mark("prefetch")
        if rel + n >= len(buf):
            self._pf.pop(s, None)        # fully consumed
            if s in self._pf_order:
                self._pf_order.remove(s)
            self._recv_pool.release(buf)  # back to the registered pool
        return n

    async def _readinto_remote(self, lb: LocatedBlock, block_off: int,
                               sink: memoryview,
                               deadline: Deadline | None = None) -> int:
        locs = self._failover_locs(lb)
        last_err: Exception | None = None
        for i, loc in enumerate(locs):
            addr = self._addr(loc)
            # hop budget = remaining / replicas-left: a wedged first
            # replica burns a fraction of the budget, never all of it
            hop = None
            if deadline is not None:
                deadline.check(f"read block {lb.block.id}")
                hop = deadline.sub(len(locs) - i)
            try:
                # one span per replica ATTEMPT: a failed first replica
                # leaves a status=error span in the trace, not a gap
                eof: dict = {}
                with self._span("read_block", addr=addr,
                                block=lb.block.id):
                    conn = await self.pool.get(addr)
                    got = await conn.call_readinto(
                        RpcCode.READ_BLOCK, sink, header={
                            "block_id": lb.block.id, "offset": block_off,
                            "len": len(sink), "chunk_size": self.chunk_size},
                        deadline=hop, eof_header=eof)
                if self.verify and block_off == 0 \
                        and got == lb.block.len \
                        and eof.get("block_crc32") is not None:
                    have, copied = _block_crc(
                        eof.get("block_crc_algo", ""), sink[:got])
                    if have is not None:
                        self._count_verify(got, copied)
                        if have != eof["block_crc32"]:
                            self._flag_corrupt(lb, loc)
                            raise err.AbnormalData(
                                f"block {lb.block.id} from {addr} "
                                f"failed checksum verification")
                if self.health is not None:
                    self.health.ok(addr)
                # readinto scatter: payload bytes landed directly in
                # the caller's (aligned) buffer — no intermediate copy
                self._count("read.zero_copy_bytes", max(0, got))
                self._mark("remote")
                return got
            except err.CurvineError as e:
                if self.health is not None:
                    self.health.fail(addr, worker_id=loc.worker_id)
                last_err = e
        raise last_err or err.BlockNotFound(f"block {lb.block.id} unreadable")

    def _fd_for(self, block_id: int, path: str) -> int | None:
        """Open (and cache) the block file fd. Once open, the fd stays
        valid even if the worker moves the block between tiers (POSIX
        unlink semantics keep the old copy complete); if the path is
        already gone — the block was promoted/demoted/evicted between the
        probe and this open — drop the cached path and let the caller
        fall back to the socket read. The cache is keyed by the path the
        fd was opened for: a concurrent revalidation that resolved a NEW
        path (tier move) must not pair the old fd with the new offset."""
        cached = self._local_fds.get(block_id)
        if cached is not None:
            fd, fd_path = cached
            if fd_path == path:
                return fd
            try:
                os.close(fd)
            except OSError:
                pass
            self._local_fds.pop(block_id, None)
        try:
            fd = os.open(path, os.O_RDONLY)
        except OSError:
            self._drop_local(block_id)
            return None
        self._local_fds[block_id] = (fd, path)
        return fd

    async def _local_fd(self, lb: LocatedBlock) -> int | None:
        """Short-circuit probe + open in one step: None → use the socket
        path. Leased grants (bdev extents) re-probe past expiry."""
        exp = self._local_expiry.get(lb.block.id)
        if exp is not None and time.time() >= exp:
            await self._revalidate(lb)
        local = await self._local_path(lb)
        if local is None:
            return None
        return self._fd_for(lb.block.id, local)

    async def mmap_view(self, offset: int, n: int):
        """Short-circuit read of a co-located block range into a fresh
        numpy buffer — one preadv from the page cache, handed straight to
        jax.device_put with no further Python copies. (Named for the
        original mmap implementation; fd+preadv beats mmap here because
        per-page fault cost dwarfs the copy on virtualized hosts.)
        Returns None when the range isn't short-circuit readable.

        Shm-mapped blocks ARE true zero-copy here again: the sealed
        mapping serves a read-only view with no preadv and no buffer,
        and so does a range over several of them (`_span_view`)."""
        self._serve_paths = set()
        with self._span("mmap_view", detail=True, path=self.path,
                        offset=offset, n=n) as sp:
            view = await self._mmap_view(offset, n)
            if sp is not None:
                # "none": not short-circuit readable, the caller falls
                # to read_all
                sp.set_attr("served_by", self.served_by())
        return view

    async def _mmap_view(self, offset: int, n: int):
        import numpy as np
        view = await self._shm_view(offset, n)
        if view is not None:
            return view
        located = self._locate(offset)
        if located is None:
            return None
        lb, block_off = located
        if block_off + n > lb.block.len:
            return None
        fd = await self._local_fd(lb)
        if fd is None:
            return None
        buf = np.empty(n, dtype=np.uint8)
        base = self._local_offs.get(lb.block.id, 0)
        with self._phase("copy"):
            got = os.preadv(fd, [memoryview(buf)], base + block_off)
        if got != n:
            # stale probe (block shrank/moved): drop the cached handles
            # so the caller's fallback path re-probes instead of looping
            self._drop_local(lb.block.id)
            return None
        if self.verify and block_off == 0 and n == lb.block.len \
                and not self._sc_verify_ok(lb, buf):
            return None       # caller falls back to the verified path
        self._note_sc_read(lb.block.id, n)
        self._mark("local")
        return buf

    # ---------------- erasure-coded reads ----------------

    @staticmethod
    def _ec_active(lb: LocatedBlock) -> bool:
        """Committed stripe with its replicas retired: reads go through
        the cells. While replicas still exist (mid-conversion) they keep
        serving — the descriptor only takes over once locs drain."""
        return lb.ec is not None and not lb.locs

    def _cell_live(self, cell: dict) -> bool:
        """A cell is worth dialing only via a location not behind an
        open breaker: a dead holder costs a connect timeout PER CHUNK
        otherwise, collapsing degraded throughput. Open-circuit cells
        count as lost; the breaker half-opens after open_s, so the
        intact path comes back by itself once the holder recovers."""
        if not cell["locs"]:
            return False
        if self.health is None:
            return True
        return any(
            self.health.allow(f"{a.get('ip_addr') or a.get('hostname')}:"
                              f"{a.get('rpc_port')}")
            for a in cell["locs"])

    async def _read_cell(self, ec: dict, cell: dict, off: int, n: int,
                         deadline: Deadline | None = None) -> bytes:
        """Read [off, off+n) of one stripe cell, with the same replica
        failover, breaker accounting, and EOF-checksum verification as a
        plain block — a cell IS a first-class block, just located via
        the stripe descriptor instead of lb.locs."""
        clb = LocatedBlock(
            block=ExtendedBlock(id=cell["block_id"], len=ec["cell_size"]),
            locs=[WorkerAddress.from_wire(a) for a in cell["locs"]])
        if not clb.locs:
            raise err.BlockNotFound(
                f"cell {cell['block_id']} has no live locations")
        locs = self._failover_locs(clb)
        last_err: Exception | None = None
        for i, loc in enumerate(locs):
            hop = None
            if deadline is not None:
                deadline.check(f"read cell {cell['block_id']}")
                hop = deadline.sub(len(locs) - i)
            try:
                with self._span("read_cell", addr=self._addr(loc),
                                block=cell["block_id"]):
                    return await self._read_from(loc, clb, off, n,
                                                 deadline=hop)
            except err.CurvineError as e:
                last_err = e
        raise last_err or err.BlockNotFound(
            f"cell {cell['block_id']} unreadable")

    async def _read_ec(self, lb: LocatedBlock, block_off: int, n: int,
                       deadline: Deadline | None = None) -> bytes:
        """Serve [block_off, block_off+n) of an erasure-coded block.

        Intact path: zero decode — scatter-gather exactly the needed
        byte ranges of the covering DATA cells (cell j holds block bytes
        [j*cell_size, (j+1)*cell_size)). Degraded path: the codec is
        positionwise-linear, so the same relative byte window of any k
        surviving cells (parity included) decodes just the needed range
        inline, under the caller's deadline budget. Stripe tail padding
        never reaches callers — reads clamp to block_len."""
        from curvine_tpu.common.ec import ECProfile
        ec = lb.ec
        prof = ECProfile.parse(ec["profile"])
        cs = ec["cell_size"]
        n = min(n, ec.get("block_len", lb.block.len) - block_off)
        if n <= 0:
            return b""
        a, b = block_off, block_off + n
        spans = []             # (data cell index, intra-cell start, end)
        for j in range(a // cs, (b - 1) // cs + 1):
            spans.append((j, max(a - j * cs, 0), min(b - j * cs, cs)))
        cells = ec["cells"]
        if all(self._cell_live(cells[j]) for j, _s, _e in spans):
            try:
                parts = await asyncio.gather(
                    *(self._read_cell(ec, cells[j], s, e - s, deadline)
                      for j, s, e in spans))
                if all(len(p) == e - s
                       for p, (_j, s, e) in zip(parts, spans)):
                    return b"".join(parts)
            except err.CurvineError:
                pass           # a holder died mid-read: degrade below
        return await self._read_ec_degraded(prof, ec, spans, deadline)

    async def _read_ec_degraded(self, prof, ec: dict, spans: list,
                                deadline: Deadline | None) -> bytes:
        from curvine_tpu.common import ec as eclib
        cells = ec["cells"]
        lo = min(s for _j, s, _e in spans)
        hi = max(e for _j, _s, e in spans)
        slots: list[bytes | None] = [None] * (prof.k + prof.m)
        lost: list[int] = []
        got = 0
        for idx, cell in enumerate(cells):
            if got >= prof.k:
                break
            if not self._cell_live(cell):
                lost.append(cell["block_id"])
                continue
            try:
                data = await self._read_cell(ec, cell, lo, hi - lo,
                                             deadline)
            except err.CurvineError:
                lost.append(cell["block_id"])
                continue
            if len(data) != hi - lo:
                lost.append(cell["block_id"])
                continue
            slots[idx] = data
            got += 1
        if got < prof.k:
            raise err.BlockNotFound(
                f"block {ec['cells'][0]['block_id']}: only {got}/{prof.k}"
                f" stripe cells readable — stripe lost")
        data_cells = eclib.decode(prof, slots)
        self._count("read.ec_degraded")
        self._mark("ec_degraded")
        if lost:
            # fire-and-forget: tell the master which cells are gone so
            # reconstruction starts now, not at the next scrub/scan
            async def _report(ids=tuple(lost)):
                try:
                    await self.fs.call(
                        RpcCode.REPORT_UNDER_REPLICATED_BLOCKS,
                        {"block_ids": list(ids)})
                except Exception as e:  # noqa: BLE001 — scan backstops
                    log.debug("lost-cell report failed: %s", e)
            asyncio.ensure_future(_report())
        return b"".join(bytes(data_cells[j][s - lo:e - lo])
                        for j, s, e in spans)

    async def _read_some(self, offset: int, n: int,
                         deadline: Deadline | None = None) -> bytes:
        located = self._locate(offset)
        if located is None:
            # hole region (resized past the written blocks): zeros
            nh = min(self._hole_len(offset), n)
            if nh <= 0:
                return b""
            self.counters["hole.bytes.read"] = \
                self.counters.get("hole.bytes.read", 0) + nh
            return b"\x00" * nh
        lb, block_off = located
        n = min(n, lb.block.len - block_off)
        if self._ec_active(lb):
            return await self._read_ec(lb, block_off, n, deadline)
        mm = await self._shm_map(lb)
        if mm is not None:
            # bytes API: one mandatory copy (bytes are owning), still
            # zero RPCs and zero syscalls
            self._note_sc_read(lb.block.id, n)
            self._shm_hit(lb.block.id)
            return bytes(mm[block_off:block_off + n])
        fd = await self._local_fd(lb)
        if fd is not None:
            base = self._local_offs.get(lb.block.id, 0)
            data = os.pread(fd, n, base + block_off)
            if self.verify and block_off == 0 \
                    and len(data) == lb.block.len \
                    and not self._sc_verify_ok(lb, data):
                pass        # bad local bytes: fall through to remote
            elif len(data) < n:
                # stale probe (block shrank/moved): drop and go remote
                self._drop_local(lb.block.id)
            else:
                self._note_sc_read(lb.block.id, len(data))
                self._mark("local")
                return data
        # failover across replica locations (local-first, breaker-aware)
        locs = self._failover_locs(lb)
        last_err: Exception | None = None
        for i, loc in enumerate(locs):
            hop = None
            if deadline is not None:
                deadline.check(f"read block {lb.block.id}")
                hop = deadline.sub(len(locs) - i)
            try:
                with self._span("read_block", addr=self._addr(loc),
                                block=lb.block.id):
                    data = await self._read_from(loc, lb, block_off, n,
                                                 deadline=hop)
                self._mark("remote")
                return data
            except err.CurvineError as e:
                log.warning("read block %d from %s:%d failed (%s), "
                            "trying next replica", lb.block.id,
                            loc.hostname, loc.rpc_port, e)
                last_err = e
        # all replicas failed: refresh locations from the master once
        # (only while the budget still has room to use them)
        if deadline is not None and deadline.expired:
            raise last_err or err.RpcTimeout(
                f"block {lb.block.id}: deadline budget exhausted")
        self.blocks = await self.fs.get_block_locations(self.path,
                                                        deadline=deadline)
        refreshed = self._locate(offset)
        if refreshed is not None and refreshed[0].locs:
            lb2, off2 = refreshed
            for loc in lb2.locs:
                try:
                    return await self._read_from(
                        loc, lb2, off2,
                        min(n, lb2.block.len - off2), deadline=deadline)
                except err.CurvineError as e:
                    last_err = e
        raise last_err or err.BlockNotFound(f"block {lb.block.id} unreadable")

    async def _read_from(self, loc, lb: LocatedBlock, offset: int, n: int,
                         deadline: Deadline | None = None) -> bytes:
        addr = self._addr(loc)
        block_id = lb.block.id
        out = bytearray()
        eof: dict = {}
        try:
            conn = await self.pool.get(addr)
            async for m in conn.call_stream(RpcCode.READ_BLOCK, header={
                    "block_id": block_id, "offset": offset, "len": n,
                    "chunk_size": self.chunk_size}, deadline=deadline):
                if len(m.data):
                    out += m.data
                if m.is_eof and m.header:
                    eof = m.header
            if self.verify and offset == 0 and len(out) == lb.block.len \
                    and eof.get("block_crc32") is not None:
                have, copied = _block_crc(eof.get("block_crc_algo", ""),
                                          out)
                if have is not None:
                    self._count_verify(len(out), copied)
                    if have != eof["block_crc32"]:
                        self._flag_corrupt(lb, loc)
                        raise err.AbnormalData(
                            f"block {block_id} from {addr} failed "
                            f"checksum verification")
        except err.CurvineError:
            if self.health is not None:
                self.health.fail(addr, worker_id=loc.worker_id)
            raise
        if self.health is not None:
            self.health.ok(addr)
        return bytes(out)

    async def chunks(self, chunk_size: int | None = None,
                     read_ahead: int | None = None):
        """Sequential whole-file chunk stream with pipelined read-ahead:
        the next `read_ahead` chunks are fetched while the consumer works
        on the current one (conf: client.read_ahead_chunks)."""
        chunk_size = chunk_size or self.chunk_size
        read_ahead = read_ahead if read_ahead is not None else self.read_ahead
        self.seek(0)
        pending: list[asyncio.Task] = []
        offset = 0

        def schedule() -> None:
            nonlocal offset
            while len(pending) < max(1, read_ahead) and offset < self.len:
                n = min(chunk_size, self.len - offset)
                pending.append(asyncio.ensure_future(
                    self._pread_bytes(offset, n)))
                offset += n

        try:
            schedule()
            while pending:
                data = await pending.pop(0)
                schedule()
                if not data:
                    break
                self.pos += len(data)
                yield data
        finally:
            for t in pending:
                t.cancel()

    async def _pread_bytes(self, offset: int, n: int) -> bytes:
        out = bytearray()
        while len(out) < n:
            got = await self._read_some(offset + len(out), n - len(out))
            if not got:
                break
            out += got
        return bytes(out)

    async def close(self) -> None:
        with self._phase("close"):
            await self._close()

    async def _close(self) -> None:
        # prefetch window: cancel AND await, so no task outlives the
        # reader (a cancelled-never-awaited task warns at loop teardown
        # and pins its receive buffer)
        tasks = [ent for ent in self._pf.values()
                 if isinstance(ent, asyncio.Task)]
        for ent in self._pf.values():
            if isinstance(ent, asyncio.Task):
                ent.cancel()
            else:
                self._recv_pool.release(ent)
        self._pf.clear()
        self._pf_order.clear()
        if tasks:
            await asyncio.gather(*tasks, return_exceptions=True)
        # heat accounting: drain the in-flight flush, then flush the
        # residual below the 512 batch threshold — pending counts must
        # never be silently dropped at close
        t, self._sc_flush_task = self._sc_flush_task, None
        if t is not None:
            if not t.done():
                try:
                    await t
                except (Exception, asyncio.CancelledError):  # noqa: BLE001
                    pass
            elif not t.cancelled():
                t.exception()     # retrieve, or the loop warns later
        if self._sc_reads and self.primed is not None:
            # one report a worker for all the caller's files, sent by
            # CurvineClient.flush_reports before the caller is done
            sc_reads_by_worker(self.primed.reads, self._sc_reads,
                               self._sc_addr)
            self._sc_reads, self._sc_pending = {}, 0
            self._count("read.reports.merged")
        elif self._sc_reads:
            await self._flush_sc_reads()
        for fd, _path in self._local_fds.values():
            try:
                os.close(fd)
            except OSError:
                pass
        self._local_fds.clear()
        for bid in list(self._shm_maps):
            self._drop_shm(bid)
        self._range = None            # the views handed out keep it mapped
