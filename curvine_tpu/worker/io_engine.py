"""Direct-IO block data plane for the SSD/HDD tiers.

The role the reference fills with its SPDK user-space bdev stack
(orpc/src/io/spdk_bdev.rs, spdk_env.rs, spdk_poller.rs): cold block
reads and tier-move copies go to the device with O_DIRECT — bypassing
the page cache so the MEM tier and the FUSE warm path keep their pages —
through a batched submission/completion ring.

Architecture (what "ring" means here):

  caller (event loop / worker thread)
      │  submit(path, offset, aligned buf)  →  concurrent Future
      ▼
  submission queue  ──batch──►  ring thread(s)
                                 ├─ io_uring (ctypes; kernel ≥5.6):
                                 │  one ring owner thread keeps up to
                                 │  `queue_depth` OP_READ SQEs in flight,
                                 │  reaps CQEs as they land
                                 └─ fallback: `threads` workers each
                                    drain the queue with preadv
                                    (the "preadv2-on-threads" plan)
      │
      ▼
  future resolves with bytes-read (or the OSError)

Every data buffer comes from an mmap-backed pool (page-aligned — the
O_DIRECT contract) and is reused across requests; `read_into` handles
offset/length alignment by over-reading the covering aligned span and
memcpy-ing the requested slice out.

Graceful degradation, per request: a filesystem that rejects O_DIRECT
(EINVAL/ENOTSUP — tmpfs on older kernels, some overlayfs) silently gets
buffered preadv on the same thread pool, and the reason is recorded in
`stats()["fallbacks"]` so benches can stamp it into artifacts instead of
reporting page-cache numbers as device numbers.
"""

from __future__ import annotations

import ctypes
import errno
import logging
import mmap
import os
import queue
import threading
import time
from concurrent.futures import Future

log = logging.getLogger(__name__)

_PAGE = mmap.PAGESIZE
_O_DIRECT = getattr(os, "O_DIRECT", 0)      # 0 on platforms without it


# --------------------------------------------------------------------------
# aligned buffer pool
# --------------------------------------------------------------------------

class AlignedBuf:
    """Page-aligned reusable buffer (mmap allocations are page-aligned,
    which satisfies O_DIRECT's address alignment on every mainstream
    filesystem; 4K logical-block alignment of offset/len is the
    engine's job)."""

    __slots__ = ("mm", "size")

    def __init__(self, size: int):
        self.size = size
        self.mm = mmap.mmap(-1, size)

    def view(self, n: int | None = None) -> memoryview:
        return memoryview(self.mm)[: self.size if n is None else n]

    def close(self) -> None:
        self.mm.close()


class BufferPool:
    """Reusable aligned buffers in power-of-two size classes. Bounded:
    at most `per_class` parked buffers per class — steady-state IO
    recycles the same few buffers instead of faulting fresh pages
    (first-touch faults dominate large allocs on virtualized hosts)."""

    def __init__(self, min_size: int = 64 * 1024,
                 max_size: int = 8 * 1024 * 1024, per_class: int = 8):
        self.min_size = min_size
        self.max_size = max_size
        self.per_class = per_class
        self._classes: dict[int, list[AlignedBuf]] = {}
        self._lock = threading.Lock()

    def _class_for(self, n: int) -> int:
        c = self.min_size
        while c < n:
            c *= 2
        return c

    def acquire(self, n: int) -> AlignedBuf:
        if n > self.max_size:
            return AlignedBuf(n)          # outsized: unpooled one-off
        c = self._class_for(n)
        with self._lock:
            free = self._classes.get(c)
            if free:
                return free.pop()
        return AlignedBuf(c)

    def release(self, buf: AlignedBuf) -> None:
        if buf.size > self.max_size:
            buf.close()
            return
        with self._lock:
            free = self._classes.setdefault(buf.size, [])
            if len(free) < self.per_class:
                free.append(buf)
                return
        buf.close()

    def drain(self) -> None:
        with self._lock:
            for free in self._classes.values():
                for b in free:
                    b.close()
            self._classes.clear()


# --------------------------------------------------------------------------
# minimal io_uring via ctypes (OP_READ only — all this plane needs)
# --------------------------------------------------------------------------

_SYS_IO_URING_SETUP = 425
_SYS_IO_URING_ENTER = 426
_IORING_OFF_SQ_RING = 0
_IORING_OFF_CQ_RING = 0x8000000
_IORING_OFF_SQES = 0x10000000
_IORING_ENTER_GETEVENTS = 1
_IORING_FEAT_SINGLE_MMAP = 1
_IORING_OP_READ = 22                     # addr/len read, kernel >= 5.6


class _SqringOffsets(ctypes.Structure):
    _fields_ = [("head", ctypes.c_uint32), ("tail", ctypes.c_uint32),
                ("ring_mask", ctypes.c_uint32),
                ("ring_entries", ctypes.c_uint32),
                ("flags", ctypes.c_uint32), ("dropped", ctypes.c_uint32),
                ("array", ctypes.c_uint32), ("resv1", ctypes.c_uint32),
                ("resv2", ctypes.c_uint64)]


class _CqringOffsets(ctypes.Structure):
    _fields_ = [("head", ctypes.c_uint32), ("tail", ctypes.c_uint32),
                ("ring_mask", ctypes.c_uint32),
                ("ring_entries", ctypes.c_uint32),
                ("overflow", ctypes.c_uint32), ("cqes", ctypes.c_uint32),
                ("flags", ctypes.c_uint32), ("resv1", ctypes.c_uint32),
                ("resv2", ctypes.c_uint64)]


class _UringParams(ctypes.Structure):
    _fields_ = [("sq_entries", ctypes.c_uint32),
                ("cq_entries", ctypes.c_uint32),
                ("flags", ctypes.c_uint32),
                ("sq_thread_cpu", ctypes.c_uint32),
                ("sq_thread_idle", ctypes.c_uint32),
                ("features", ctypes.c_uint32),
                ("wq_fd", ctypes.c_uint32),
                ("resv", ctypes.c_uint32 * 3),
                ("sq_off", _SqringOffsets),
                ("cq_off", _CqringOffsets)]


class _Sqe(ctypes.Structure):
    """io_uring_sqe, 64 bytes. The unions collapse to the fields OP_READ
    uses; `rest` pads the tail (buf_index/personality/etc stay zero)."""
    _fields_ = [("opcode", ctypes.c_uint8), ("flags", ctypes.c_uint8),
                ("ioprio", ctypes.c_uint16), ("fd", ctypes.c_int32),
                ("off", ctypes.c_uint64), ("addr", ctypes.c_uint64),
                ("len", ctypes.c_uint32), ("rw_flags", ctypes.c_uint32),
                ("user_data", ctypes.c_uint64),
                ("rest", ctypes.c_uint8 * 24)]


class _Cqe(ctypes.Structure):
    _fields_ = [("user_data", ctypes.c_uint64), ("res", ctypes.c_int32),
                ("flags", ctypes.c_uint32)]


class UringRing:
    """A submission/completion ring over raw io_uring syscalls. Single
    owner thread: only the engine's ring thread touches the SQ/CQ, so no
    memory-order gymnastics are needed beyond ctypes' volatile-ish
    loads/stores (the kernel side uses acquire/release on head/tail;
    a single user-space writer never races itself)."""

    def __init__(self, entries: int = 32):
        self._libc = ctypes.CDLL(None, use_errno=True)
        p = _UringParams()
        fd = self._libc.syscall(_SYS_IO_URING_SETUP, entries,
                                ctypes.byref(p))
        if fd < 0:
            raise OSError(ctypes.get_errno(), "io_uring_setup failed")
        self.fd = fd
        self.entries = p.sq_entries
        try:
            sq_size = p.sq_off.array + p.sq_entries * 4
            cq_size = p.cq_off.cqes + p.cq_entries * ctypes.sizeof(_Cqe)
            if p.features & _IORING_FEAT_SINGLE_MMAP:
                sq_size = cq_size = max(sq_size, cq_size)
            self._sq_mm = mmap.mmap(fd, sq_size, offset=_IORING_OFF_SQ_RING)
            self._cq_mm = (self._sq_mm
                           if p.features & _IORING_FEAT_SINGLE_MMAP
                           else mmap.mmap(fd, cq_size,
                                          offset=_IORING_OFF_CQ_RING))
            self._sqes_mm = mmap.mmap(fd, p.sq_entries * ctypes.sizeof(_Sqe),
                                      offset=_IORING_OFF_SQES)
        except OSError:
            os.close(fd)
            raise

        def _u32(mm, off):
            return ctypes.c_uint32.from_buffer(mm, off)

        self._sq_head = _u32(self._sq_mm, p.sq_off.head)
        self._sq_tail = _u32(self._sq_mm, p.sq_off.tail)
        self._sq_mask = _u32(self._sq_mm, p.sq_off.ring_mask).value
        self._sq_array = (ctypes.c_uint32 * p.sq_entries).from_buffer(
            self._sq_mm, p.sq_off.array)
        self._cq_head = _u32(self._cq_mm, p.cq_off.head)
        self._cq_tail = _u32(self._cq_mm, p.cq_off.tail)
        self._cq_mask = _u32(self._cq_mm, p.cq_off.ring_mask).value
        self._cqes = (_Cqe * p.cq_entries).from_buffer(
            self._cq_mm, p.cq_off.cqes)
        self._sqes = (_Sqe * p.sq_entries).from_buffer(self._sqes_mm, 0)
        self.in_flight = 0

    def sq_space(self) -> int:
        return self.entries - (self._sq_tail.value - self._sq_head.value)

    def prep_read(self, fd: int, buf_addr: int, length: int, offset: int,
                  user_data: int) -> None:
        tail = self._sq_tail.value
        idx = tail & self._sq_mask
        sqe = self._sqes[idx]
        ctypes.memset(ctypes.byref(sqe), 0, ctypes.sizeof(_Sqe))
        sqe.opcode = _IORING_OP_READ
        sqe.fd = fd
        sqe.off = offset
        sqe.addr = buf_addr
        sqe.len = length
        sqe.user_data = user_data
        self._sq_array[idx] = idx
        self._sq_tail.value = tail + 1

    def submit_and_wait(self, min_complete: int) -> int:
        """Submit everything staged; block for at least `min_complete`
        completions (0 → just submit)."""
        to_submit = self._sq_tail.value - self._sq_head.value
        flags = _IORING_ENTER_GETEVENTS if min_complete else 0
        r = self._libc.syscall(_SYS_IO_URING_ENTER, self.fd, to_submit,
                               min_complete, flags, None, 0)
        if r < 0:
            e = ctypes.get_errno()
            if e == errno.EINTR:
                return 0
            raise OSError(e, "io_uring_enter failed")
        self.in_flight += r
        return r

    def reap(self) -> list[tuple[int, int]]:
        """Drain the CQ: [(user_data, res)]."""
        out = []
        head = self._cq_head.value
        tail = self._cq_tail.value
        while head != tail:
            cqe = self._cqes[head & self._cq_mask]
            out.append((cqe.user_data, cqe.res))
            head += 1
        self._cq_head.value = head
        self.in_flight -= len(out)
        return out

    def close(self) -> None:
        # ctypes structures hold exported buffers; drop them before the
        # mmaps close or mmap.close() raises BufferError
        for name in ("_sq_head", "_sq_tail", "_sq_array", "_cq_head",
                     "_cq_tail", "_cqes", "_sqes"):
            if hasattr(self, name):
                delattr(self, name)
        import gc
        gc.collect()
        for mm in {id(m): m for m in (getattr(self, "_sq_mm", None),
                                      getattr(self, "_cq_mm", None),
                                      getattr(self, "_sqes_mm", None))
                   if m is not None}.values():
            try:
                mm.close()
            except BufferError:        # a straggler view; kernel cleans up
                pass
        os.close(self.fd)


# --------------------------------------------------------------------------
# the engine
# --------------------------------------------------------------------------

class _Request:
    __slots__ = ("fd", "offset", "length", "buf_addr", "future", "buffered",
                 "t0")

    def __init__(self, fd: int, offset: int, length: int, buf_addr: int,
                 buffered: bool):
        self.fd = fd
        self.offset = offset
        self.length = length
        self.buf_addr = buf_addr
        self.buffered = buffered
        self.future: Future = Future()
        # submit timestamp: completion observes submit→complete latency
        # into the worker's io.submit_to_complete histogram
        self.t0 = time.perf_counter()


class EngineShutdown(RuntimeError):
    pass


class DirectIOEngine:
    """Batched O_DIRECT read engine. One instance serves every SSD/HDD
    tier on the worker; submissions come from the event loop (async) or
    from tier-move worker threads (sync) and resolve on the ring
    thread(s).

    `engine`: "auto" (io_uring when the kernel cooperates, else thread
    pool), "uring" (require io_uring, raise otherwise), "threads"
    (never try io_uring), "off" (constructor raises — callers keep the
    buffered path)."""

    def __init__(self, queue_depth: int = 32, alignment: int = 4096,
                 threads: int = 2, engine: str = "auto",
                 segment_bytes: int = 1024 * 1024):
        if engine == "off":
            raise ValueError("direct-IO engine disabled by conf")
        if alignment <= 0 or alignment & (alignment - 1):
            raise ValueError(f"alignment {alignment} not a power of two")
        self.queue_depth = max(1, queue_depth)
        self.alignment = alignment
        self.segment_bytes = max(alignment,
                                 (segment_bytes // alignment) * alignment)
        # park a full ring window per class: steady-state IO recycles
        # buffers instead of re-mmapping (first-touch faults) each batch
        self.pool = BufferPool(min_size=max(64 * 1024, alignment),
                               per_class=self.queue_depth + 4)
        self._q: queue.Queue[_Request | None] = queue.Queue()
        # optional MetricsRegistry (set by WorkerServer): completions
        # observe submit→complete latency (io.submit_to_complete).
        # Histogram mutation is dict arithmetic under the GIL — safe
        # enough from the engine threads for metrics purposes.
        self.metrics = None
        # optional DiskFaultInjector (fault/disk.py, set by WorkerServer
        # alongside BlockStore.fault_hook): submissions consult it so
        # injected per-dir EIO reaches direct-IO readers too
        self.fault_hook = None
        self._fds: dict[str, tuple[int, bool]] = {}   # path -> (fd, direct)
        self._fd_lock = threading.Lock()
        self._closed = False
        self.stats_lock = threading.Lock()
        self.counters: dict[str, int] = {
            "submitted": 0, "completed": 0, "batches": 0,
            "direct_bytes": 0, "buffered_bytes": 0, "errors": 0}
        self.fallbacks: dict[str, int] = {}       # reason -> count
        self._ring: UringRing | None = None
        if engine in ("auto", "uring"):
            try:
                self._ring = UringRing(self.queue_depth)
            except OSError as e:
                if engine == "uring":
                    raise
                self._note_fallback(f"io_uring unavailable: "
                                    f"{errno.errorcode.get(e.errno, e.errno)}")
        self.mode = "uring" if self._ring is not None else "threads"
        n_threads = 1 if self._ring is not None else max(1, threads)
        self._threads = [
            threading.Thread(target=self._ring_loop if self._ring is not None
                             else self._thread_loop,
                             name=f"direct-io-{i}", daemon=True)
            for i in range(n_threads)]
        for t in self._threads:
            t.start()

    # ---------------- fd cache / O_DIRECT probing ----------------

    def _note_fallback(self, reason: str) -> None:
        with self.stats_lock:
            self.fallbacks[reason] = self.fallbacks.get(reason, 0) + 1

    def _get_fd(self, path: str) -> tuple[int, bool]:
        """(fd, is_direct). One open per path; filesystems rejecting
        O_DIRECT get a buffered fd and a recorded reason — the
        per-request transparent fallback."""
        with self._fd_lock:
            ent = self._fds.get(path)
            if ent is not None:
                return ent
        if not _O_DIRECT:
            self._note_fallback("O_DIRECT unsupported on this platform")
            ent = (os.open(path, os.O_RDONLY), False)
        else:
            try:
                fd = os.open(path, os.O_RDONLY | _O_DIRECT)
                ent = (fd, True)
            except OSError as e:
                if e.errno not in (errno.EINVAL, errno.ENOTSUP,
                                   errno.EOPNOTSUPP):
                    raise
                self._note_fallback(
                    f"O_DIRECT rejected "
                    f"({errno.errorcode.get(e.errno, e.errno)})")
                ent = (os.open(path, os.O_RDONLY), False)
        with self._fd_lock:
            cur = self._fds.get(path)
            if cur is not None:           # raced another opener
                os.close(ent[0])
                return cur
            self._fds[path] = ent
        return ent

    def forget(self, path: str) -> None:
        """Drop the cached fd (block file deleted / tier moved)."""
        with self._fd_lock:
            ent = self._fds.pop(path, None)
        if ent is not None:
            try:
                os.close(ent[0])
            except OSError:
                pass

    # ---------------- submission ----------------

    def submit(self, path: str, offset: int, length: int,
               buf: AlignedBuf) -> Future:
        """Queue one aligned read into `buf`; returns a concurrent
        Future resolving to bytes-read. `offset` and `length` must
        already be aligned (use read_into for arbitrary ranges)."""
        if self._closed:
            f: Future = Future()
            f.set_exception(EngineShutdown("engine is shut down"))
            return f
        hook = self.fault_hook
        if hook is not None:
            try:
                hook.check_read(path)
            except OSError as e:
                with self.stats_lock:
                    self.counters["errors"] += 1
                f = Future()
                f.set_exception(e)
                return f
        fd, direct = self._get_fd(path)
        addr = ctypes.addressof(ctypes.c_char.from_buffer(buf.mm))
        req = _Request(fd, offset, length, addr, buffered=not direct)
        with self.stats_lock:
            self.counters["submitted"] += 1
        self._q.put(req)
        return req.future

    # ---------------- ring thread (io_uring mode) ----------------

    def _ring_loop(self) -> None:
        ring = self._ring
        pending: dict[int, _Request] = {}
        next_id = 1
        while True:
            # Idle → block for the first request (or shutdown). With IO
            # in flight → never block on the queue: grab whatever is
            # already there and go wait on COMPLETIONS (enter with
            # GETEVENTS), or completion latency becomes queue-poll
            # latency.
            if pending:
                try:
                    req = self._q.get_nowait()
                except queue.Empty:
                    req = False           # no new work; reap below
            else:
                req = self._q.get()
            if req is None:
                break
            batch: list[_Request] = [req] if req else []
            while len(batch) + len(pending) < self.queue_depth:
                try:
                    nxt = self._q.get_nowait()
                except queue.Empty:
                    break
                if nxt is None:
                    self._q.put(None)     # re-post for the outer check
                    break
                batch.append(nxt)
            staged_ids: list[int] = []
            for r in batch:
                if r.buffered:
                    self._do_buffered(r)
                    continue
                if ring.sq_space() <= 0:
                    # ring full: execute inline rather than stall the loop
                    self._do_preadv(r)
                    continue
                ring.prep_read(r.fd, r.buf_addr, r.length, r.offset, next_id)
                pending[next_id] = r
                staged_ids.append(next_id)
                next_id += 1
            if staged_ids or pending:
                try:
                    ring.submit_and_wait(1 if pending else 0)
                except OSError as e:
                    # a poisoned submission batch (bad fd after delete):
                    # fail THIS batch only — earlier submissions are
                    # in flight and the kernel still owns their buffers
                    with self.stats_lock:
                        self.counters["errors"] += len(staged_ids)
                    for sid in staged_ids:
                        r = pending.pop(sid, None)
                        if r is not None:
                            r.future.set_exception(e)
                    continue
                for user_data, res in ring.reap():
                    r = pending.pop(user_data, None)
                    if r is None:
                        continue
                    self._complete(r, res)
            with self.stats_lock:
                self.counters["batches"] += 1
        # shutdown: fail whatever is still queued, reap in-flight
        self._drain_on_shutdown(pending)

    def _drain_on_shutdown(self, pending: dict[int, _Request]) -> None:
        ring = self._ring
        while pending:
            try:
                ring.submit_and_wait(1)
            except OSError as e:
                for r in pending.values():
                    r.future.set_exception(e)
                pending.clear()
                break
            for user_data, res in ring.reap():
                r = pending.pop(user_data, None)
                if r is not None:
                    self._complete(r, res)
        while True:
            try:
                req = self._q.get_nowait()
            except queue.Empty:
                break
            if req is not None:
                req.future.set_exception(
                    EngineShutdown("engine is shut down"))

    # ---------------- thread pool mode ----------------

    def _thread_loop(self) -> None:
        while True:
            req = self._q.get()
            if req is None:
                self._q.put(None)         # wake the next worker
                break
            if req.buffered:
                self._do_buffered(req)
            else:
                self._do_preadv(req)
            with self.stats_lock:
                self.counters["batches"] += 1

    def _do_preadv(self, req: _Request) -> None:
        try:
            mv = (ctypes.c_char * req.length).from_address(req.buf_addr)
            got = os.preadv(req.fd, [memoryview(mv).cast("B")], req.offset)
        except OSError as e:
            if e.errno == errno.EINVAL:
                # the fs accepted O_DIRECT at open but rejects it at
                # read (some network/overlay stacks): buffered retry
                self._note_fallback("O_DIRECT read EINVAL")
                self._do_buffered(req)
                return
            with self.stats_lock:
                self.counters["errors"] += 1
            req.future.set_exception(e)
            return
        self._complete(req, got)

    def _do_buffered(self, req: _Request) -> None:
        try:
            mv = (ctypes.c_char * req.length).from_address(req.buf_addr)
            got = os.preadv(req.fd, [memoryview(mv).cast("B")], req.offset)
        except OSError as e:
            with self.stats_lock:
                self.counters["errors"] += 1
            req.future.set_exception(e)
            return
        with self.stats_lock:
            self.counters["completed"] += 1
            self.counters["buffered_bytes"] += max(0, got)
        req.future.set_result(got)

    def _complete(self, req: _Request, res: int) -> None:
        m = self.metrics
        if m is not None:
            m.observe("io.submit_to_complete",
                      time.perf_counter() - req.t0)
        if res < 0:
            with self.stats_lock:
                self.counters["errors"] += 1
            req.future.set_exception(OSError(-res, os.strerror(-res)))
            return
        with self.stats_lock:
            self.counters["completed"] += 1
            if req.buffered:
                self.counters["buffered_bytes"] += res
            else:
                self.counters["direct_bytes"] += res
        req.future.set_result(res)

    # ---------------- aligned-range frontends ----------------

    def _plan(self, offset: int, length: int) -> tuple[int, int]:
        """Covering aligned span (start, len) for [offset, offset+len)."""
        a = self.alignment
        start = (offset // a) * a
        end = -(-(offset + length) // a) * a
        return start, end - start

    def pread_sync(self, path: str, offset: int, length: int) -> bytes:
        """Blocking read of an arbitrary range — the tier-move copy path
        (already running on a worker thread). Splits the covering span
        into `segment_bytes` submissions so a multi-MB copy batches at
        `queue_depth` instead of serializing."""
        if length <= 0:
            return b""
        start, span = self._plan(offset, length)
        segs = []
        out = bytearray()
        try:
            pos = start
            while pos < start + span:
                n = min(self.segment_bytes, start + span - pos)
                buf = self.pool.acquire(n)
                segs.append((pos, n, buf, self.submit(path, pos, n, buf)))
                pos += n
            for seg_off, n, buf, fut in segs:
                got = fut.result()
                lo = max(0, offset - seg_off)
                hi = min(got, offset + length - seg_off)
                if hi > lo:
                    out += buf.view()[lo:hi]
                if got < n:
                    break                  # EOF inside this segment
        finally:
            for _o, _n, buf, fut in segs:
                if not fut.done():
                    try:
                        fut.result()
                    except Exception:  # noqa: BLE001 — buf reuse gate only
                        pass
                self.pool.release(buf)
        return bytes(out)

    async def read_into(self, path: str, offset: int, out) -> int:
        """Async read of an arbitrary range into `out` (memoryview /
        ndarray). Alignment is absorbed here: the engine reads the
        covering aligned span into pooled buffers and copies the
        requested slice out. Returns bytes filled (short on EOF)."""
        import asyncio
        length = len(out)
        if length <= 0:
            return 0
        start, span = self._plan(offset, length)
        segs = []
        filled = 0
        try:
            pos = start
            while pos < start + span:
                n = min(self.segment_bytes, start + span - pos)
                buf = self.pool.acquire(n)
                segs.append((pos, n, buf, asyncio.wrap_future(
                    self.submit(path, pos, n, buf))))
                pos += n
            mv = memoryview(out)
            if hasattr(mv, "cast"):
                mv = mv.cast("B")
            eof = False
            for seg_off, n, buf, fut in segs:
                got = await fut
                if eof:
                    continue               # drained for buffer safety only
                lo = max(0, offset - seg_off)
                hi = min(got, offset + length - seg_off)
                if hi > lo:
                    mv[filled:filled + hi - lo] = buf.view()[lo:hi]
                    filled += hi - lo
                if got < n:
                    eof = True
        finally:
            # a mid-loop error must not release buffers the kernel may
            # still be writing: wait out every in-flight segment first
            for _o, _n, buf, fut in segs:
                try:
                    await fut
                except Exception:  # noqa: BLE001 — buffer-reuse gate only
                    pass
                self.pool.release(buf)
        return filled

    async def pread(self, path: str, offset: int, length: int) -> bytes:
        import numpy as np
        buf = np.empty(length, dtype=np.uint8)
        got = await self.read_into(path, offset, buf)
        return buf[:got].tobytes()

    # ---------------- lifecycle / reporting ----------------

    def stats(self) -> dict:
        with self.stats_lock:
            out = dict(self.counters)
            out["fallbacks"] = dict(self.fallbacks)
        out["mode"] = self.mode
        out["queue_depth"] = self.queue_depth
        out["alignment"] = self.alignment
        return out

    def shutdown(self, wait: bool = True) -> None:
        """Stop the ring: in-flight submissions complete (their callers'
        futures resolve), queued-but-unstarted ones fail with
        EngineShutdown. Idempotent."""
        if self._closed:
            return
        self._closed = True
        self._q.put(None)
        if wait:
            for t in self._threads:
                t.join(timeout=10.0)
        # thread-pool mode leaves the sentinel cycling; drain leftovers
        while True:
            try:
                req = self._q.get_nowait()
            except queue.Empty:
                break
            if req is not None:
                req.future.set_exception(
                    EngineShutdown("engine is shut down"))
        if self._ring is not None:
            self._ring.close()
            self._ring = None
        with self._fd_lock:
            for fd, _direct in self._fds.values():
                try:
                    os.close(fd)
                except OSError:
                    pass
            self._fds.clear()
        self.pool.drain()


def create_engine(conf) -> DirectIOEngine | None:
    """Build the worker's engine from WorkerConf; None when disabled or
    construction fails (callers keep the buffered path)."""
    if not getattr(conf, "direct_io", True):
        return None
    mode = getattr(conf, "direct_io_engine", "auto")
    if mode == "off":
        return None
    try:
        return DirectIOEngine(
            queue_depth=getattr(conf, "direct_io_queue_depth", 32),
            alignment=getattr(conf, "direct_io_alignment", 4096),
            threads=getattr(conf, "direct_io_threads", 2),
            engine=mode,
            segment_bytes=getattr(conf, "direct_io_segment", 1024 * 1024))
    except (OSError, ValueError) as e:
        log.warning("direct-IO engine unavailable (%s); SSD/HDD tiers "
                    "stay on the buffered path", e)
        return None
