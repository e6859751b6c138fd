"""Syscall-lean wire transport shared by the RPC client and server:
a per-connection coalesced writer and a bulk-recv frame decoder.

Parity: orpc's framed transport gets its 100K+ QPS by amortizing
per-frame costs; this is the asyncio equivalent of its write-coalescing
and buffered-decode pipeline.

Send side — ``CoalescedWriter``: every connection owns ONE writer task
draining a FIFO send queue. All frames enqueued within one event-loop
tick leave in a single vectored send (bounded by
``rpc.send_coalesce_bytes``/``_frames``); small frames are flattened
into per-run batch buffers, large data payloads ride the iovec uncopied.
This also simplifies the PR-2 cancelled-send poisoning: a caller cancel
can only sever at a frame boundary now — a frame still queued is
dropped before any byte hits the wire, one the writer already picked up
is written out whole — so the connection stays parseable and is NOT
poisoned. Poisoning remains only for the writer itself dying mid-batch
(socket error or teardown), where a partial frame may be on the wire.

Receive side — ``BulkDecoder``: one reusable grow-only buffer per
connection; a single ``sock_recv_into`` typically lands MANY small
frames, decoded back-to-back with no further syscalls
(``frame.decode_envelope``). Oversized payloads fall back to exact
reads — either into the decoder's buffer (server upload path: same
grow-only reuse the old per-connection payload buffer had) or straight
into a caller-registered sink view (the zero-copy block-read path,
which must bypass the bulk buffer)."""

from __future__ import annotations

import asyncio
import mmap
import socket
import threading
import weakref
from collections import deque

from curvine_tpu.common.errors import ConnectError
from curvine_tpu.rpc.frame import Message, decode_envelope

SEND_COALESCE_BYTES = 256 * 1024
SEND_COALESCE_FRAMES = 128
SEND_INLINE_MAX = 8 * 1024
RECV_BUFFER_BYTES = 256 * 1024
# payloads larger than the recv buffer grow it (grow-only, like the old
# server payload buffer) up to this cap; beyond it the read goes through
# a transient allocation so one giant frame doesn't pin 64MB per conn
RECV_RETAIN_MAX = 8 * 1024 * 1024
# sendmsg iovec count per syscall (IOV_MAX is 1024 on linux)
_IOV_CAP = 512

# ---------------- registered receive buffers ----------------
#
# The client-side mirror of the worker's io_uring registered buffers
# (worker/io_engine.py AlignedBuf/BufferPool): remote block reads land
# in page-aligned, mmap-backed destinations so the readinto scatter
# path (rpc/client.py _Sink) delivers payload bytes straight into a
# buffer jax.device_put / numpy can consume with no realignment copy.
# Anonymous mmap gives page alignment by construction and returns pages
# to the OS on free — a caller keeping the array alive owns the pages,
# one dropping it releases them, so buffers handed to callers need no
# explicit release protocol.

_ALIGNED_MIN = 256 * 1024        # default reads-this-large-get-aligned
_REGISTERED_MIN = 64 * 1024      # smallest pooled size class
_REGISTERED_MAX = 8 * 1024 * 1024  # largest pooled size class


def alloc_aligned(n: int):
    """Page-aligned numpy uint8 buffer of length ``n``, backed by an
    anonymous mmap (freed on GC). The registered-receive destination
    for caller-visible reads."""
    import numpy as np
    if n <= 0:
        return np.empty(0, dtype=np.uint8)
    mm = mmap.mmap(-1, n)
    return np.frombuffer(mm, dtype=np.uint8, count=n)


class RegisteredBuffers:
    """Bounded reuse pool of page-aligned mmap regions, by power-of-two
    size class (mirror of io_engine.BufferPool for the receive side).
    ``acquire(n)`` returns a numpy view of length ``n`` onto a pooled
    region; ``release(arr)`` returns the region for reuse (up to
    ``max_bytes`` retained — beyond that the pages go back to the OS).
    Only INTERNAL consumers release (prefetch segments); buffers that
    escape to callers are simply never released and get collected."""

    def __init__(self, max_bytes: int = 32 * 1024 * 1024,
                 min_size: int = _REGISTERED_MIN,
                 max_size: int = _REGISTERED_MAX):
        self.min_size = min_size
        self.max_size = max_size
        self.max_bytes = max(0, max_bytes)
        # occupancy accounting: `retained` is POOL-RESIDENT bytes only
        # (the rpc.recv_registered_bytes gauge); `pinned` is bytes out
        # with callers — one checkout token per region, cleared exactly
        # once by whichever of release() / view-GC comes first, so a
        # caller-held view is never double-counted against the gauge.
        self.retained = 0
        self.pinned = 0
        self._free: dict[int, list[mmap.mmap]] = {}
        self._resident: set[int] = set()    # id(mm) parked in _free
        self._out: dict[int, list] = {}     # id(mm) → live token [size]
        self._lock = threading.Lock()
        self.acquired = 0
        self.reused = 0

    def _cls(self, n: int) -> int:
        size = self.min_size
        while size < n:
            size *= 2
        return size

    def _unpin(self, token: list, key: int) -> None:
        with self._lock:
            if token[0]:
                self.pinned -= token[0]
                token[0] = 0
            # drop the checkout record unless the region was already
            # released and re-acquired (same id, newer token)
            if self._out.get(key) is token:
                del self._out[key]

    def acquire(self, n: int):
        """Numpy uint8 view of length ``n`` on an aligned region; the
        view's ``.base`` mmap carries identity for ``release``."""
        import numpy as np
        if n <= 0:
            return np.empty(0, dtype=np.uint8)
        if n > self.max_size:
            return alloc_aligned(n)      # giant: unpooled one-off
        size = self._cls(n)
        with self._lock:
            free = self._free.get(size)
            mm = free.pop() if free else None
            if mm is not None:
                self.retained -= size
                self._resident.discard(id(mm))
                self.reused += 1
        if mm is None:
            mm = mmap.mmap(-1, size)
        token = [size]
        with self._lock:
            self.acquired += 1
            self.pinned += size
            self._out[id(mm)] = token
        arr = np.frombuffer(mm, dtype=np.uint8, count=size)[:n]
        # escape hatch for buffers handed to callers and never released:
        # the view's GC unpins (views keep their base chain alive, so
        # this fires only once nothing references the region)
        weakref.finalize(arr, self._unpin, token, id(mm))
        return arr

    def release(self, arr) -> None:
        """Return an ``acquire``d view's region to the pool (no-op for
        foreign buffers and for double releases — parking the same
        region twice would hand it to two concurrent acquirers)."""
        base = getattr(arr, "base", None)
        while base is not None and not isinstance(base, mmap.mmap):
            # numpy chains ndarray views down to a memoryview over the
            # region; .obj unwraps that last hop to the mmap itself
            if isinstance(base, memoryview):
                base = base.obj
            else:
                base = getattr(base, "base", None)
        if not isinstance(base, mmap.mmap):
            return
        size = len(base)
        if size < self.min_size or size > self.max_size:
            return
        with self._lock:
            if id(base) in self._resident:
                return                   # double release: already parked
            token = self._out.pop(id(base), None)
            if token is not None and token[0]:
                self.pinned -= token[0]
                token[0] = 0
            if self.retained + size <= self.max_bytes:
                self._free.setdefault(size, []).append(base)
                self._resident.add(id(base))
                self.retained += size

    def stats(self) -> dict:
        """Flattened gauges/counters for /metrics (worker heartbeat
        prefixes these with ``rpc.recv_``)."""
        return {
            "registered_bytes": self.retained,
            "pinned_bytes": self.pinned,
            "acquired": self.acquired,
            "reused": self.reused,
        }

    def drain(self) -> None:
        with self._lock:
            regions = [mm for lst in self._free.values() for mm in lst]
            self._free.clear()
            self._resident.clear()
            self.retained = 0
        for mm in regions:
            try:
                mm.close()
            except BufferError:
                pass                     # a live view pins it; GC frees


_recv_pool: RegisteredBuffers | None = None


def recv_pool() -> RegisteredBuffers:
    """Process-wide registered receive pool (sized by
    rpc.recv_registered_bytes at first client construction)."""
    global _recv_pool
    if _recv_pool is None:
        _recv_pool = RegisteredBuffers()
    return _recv_pool


async def recv_exact(loop: asyncio.AbstractEventLoop, sock: socket.socket,
                     view: memoryview) -> None:
    """Fill `view` completely from the socket (the oversized-frame /
    sink fallback path; the hot path is BulkDecoder.fill)."""
    off, n = 0, len(view)
    while off < n:
        got = await loop.sock_recv_into(sock, view[off:])
        if got == 0:
            raise ConnectionResetError("peer closed")
        off += got


async def _wait_writable(loop: asyncio.AbstractEventLoop,
                         sock: socket.socket) -> None:
    fut = loop.create_future()
    fd = sock.fileno()

    def _ready() -> None:
        if not fut.done():
            fut.set_result(None)

    loop.add_writer(fd, _ready)
    try:
        await fut
    finally:
        loop.remove_writer(fd)


async def vectored_sendall(loop: asyncio.AbstractEventLoop,
                           sock: socket.socket, bufs: list) -> None:
    """All buffers on the wire in as few syscalls as the socket buffer
    allows: one non-blocking ``sendmsg`` per writability window (asyncio
    has no sock_sendmsg, so waiting uses add_writer directly). Loops
    without sendmsg/add_writer fall back to sequential sendalls."""
    if not hasattr(sock, "sendmsg"):
        for b in bufs:
            await loop.sock_sendall(sock, b)
        return
    idx, off, n = 0, 0, len(bufs)
    while idx < n:
        iov = [memoryview(bufs[idx])[off:]]
        iov.extend(bufs[idx + 1:idx + _IOV_CAP])
        try:
            sent = sock.sendmsg(iov)
        except (BlockingIOError, InterruptedError):
            sent = 0
        while sent > 0 and idx < n:
            rem = len(bufs[idx]) - off
            if sent >= rem:
                sent -= rem
                idx += 1
                off = 0
            else:
                off += sent
                sent = 0
        if idx < n:
            try:
                await _wait_writable(loop, sock)
            except NotImplementedError:
                for i in range(idx, n):
                    b = memoryview(bufs[i])[off:] if i == idx else bufs[i]
                    off = 0
                    await loop.sock_sendall(sock, b)
                return


class _SendItem:
    __slots__ = ("head", "big", "size", "fut", "file", "offset", "count")

    def __init__(self, head, big, size, fut,
                 file=None, offset=0, count=0):
        self.head = head        # envelope (+ inlined small payload)
        self.big = big          # large data payload, emitted uncopied
        self.size = size
        self.fut = fut
        self.file = file        # sendfile items run alone, FIFO-ordered
        self.offset = offset
        self.count = count


class CoalescedWriter:
    """Single writer task per connection; see module docstring for the
    batching and cancellation contract."""

    def __init__(self, sock: socket.socket,
                 loop: asyncio.AbstractEventLoop, *,
                 max_bytes: int = SEND_COALESCE_BYTES,
                 max_frames: int = SEND_COALESCE_FRAMES,
                 inline_max: int = SEND_INLINE_MAX,
                 metrics=None, depth_cell: dict | None = None,
                 on_broken=None, name: str = "rpc"):
        self.sock = sock
        self.loop = loop
        self.max_bytes = max(1, max_bytes)
        self.max_frames = max(1, max_frames)
        self.inline_max = inline_max
        self.metrics = metrics
        # shared across a server's connections so the exported gauge is
        # the process-wide queued-frame count, not one conn's
        self._depth = depth_cell if depth_cell is not None else {"n": 0}
        self.on_broken = on_broken
        self.name = name
        self._q: deque[_SendItem] = deque()
        self._wake = asyncio.Event()
        self._task: asyncio.Task | None = None
        # serializes the wire between the writer task's batches and the
        # uncontended inline fast path (never held across idle waits)
        self._io_lock = asyncio.Lock()
        self.broken: BaseException | None = None
        self.closed = False
        self.bytes_sent = 0

    def qsize(self) -> int:
        return len(self._q)

    # -------- producer side --------

    def _enqueue(self, item: _SendItem) -> None:
        if self.closed:
            raise ConnectError(f"{self.name}: connection closed")
        if self.broken is not None:
            raise ConnectError(
                f"{self.name}: connection broken: {self.broken}")
        self._q.append(item)
        self._bump(1)
        self._wake.set()
        if self._task is None:
            self._task = self.loop.create_task(self._run())

    async def _await_item(self, item: _SendItem):
        try:
            return await item.fut
        except asyncio.CancelledError:
            # frame-boundary cancel: a frame still queued is dropped
            # before any byte hits the wire; one the writer already
            # picked up is written out WHOLE (the writer never observes
            # this cancel) — either way the stream stays parseable, so
            # the connection is NOT poisoned.
            try:
                self._q.remove(item)
                self._bump(-1)
            except ValueError:
                pass
            raise

    async def send(self, msg: Message) -> None:
        if not self._q and not self._io_lock.locked():
            # uncontended fast path: nothing queued and no batch in
            # flight — write inline, skipping two task hops that only
            # pay off when there is something to coalesce with. The
            # lock check-then-acquire is atomic (no await between them
            # when uncontended), so a frame can never interleave with a
            # writer batch.
            if self.closed:
                raise ConnectError(f"{self.name}: connection closed")
            if self.broken is not None:
                raise ConnectError(
                    f"{self.name}: connection broken: {self.broken}")
            await self._send_inline(msg)
            return
        head = bytearray()
        big = msg.encode_into(head, self.inline_max)
        fut = self.loop.create_future()
        size = len(big) if big is not None else 0
        item = _SendItem(head, big, len(head) + size, fut)
        self._enqueue(item)
        await self._await_item(item)

    async def _send_inline(self, msg: Message) -> None:
        head = bytearray()
        big = msg.encode_into(head, self.inline_max)
        nbytes = len(head) + (len(big) if big is not None else 0)
        async with self._io_lock:
            try:
                await self.loop.sock_sendall(self.sock, head)
                if big is not None:
                    await self.loop.sock_sendall(self.sock, big)
            except asyncio.CancelledError:
                # cancelled mid-write on the INLINE path: a partial
                # frame may be on the wire — the PR-2 poisoning,
                # unchanged for this path (only queued sends get the
                # frame-boundary guarantee)
                self._break(ConnectError(
                    f"{self.name}: send cancelled mid-frame"))
                raise
            except Exception as e:  # noqa: BLE001
                self._break(e)
                raise
        self.bytes_sent += nbytes
        m = self.metrics
        if m is not None:
            m.observe("rpc.send_batch_frames", 1)
            m.inc("rpc.bytes_sent", nbytes)

    def _break(self, exc: BaseException) -> None:
        if self.broken is None:
            self.broken = exc
        self._abort(exc)
        cb = self.on_broken
        if cb is not None:
            try:
                cb(exc)
            except Exception:  # noqa: BLE001
                pass

    async def send_file(self, head: bytes, f, offset: int,
                        count: int) -> int:
        """Queue a sendfile frame (envelope via sendall, payload via
        kernel sendfile); returns bytes of payload sent."""
        fut = self.loop.create_future()
        item = _SendItem(head, None, len(head) + count, fut,
                         file=f, offset=offset, count=count)
        self._enqueue(item)
        return await self._await_item(item)

    # -------- writer task --------

    def _bump(self, d: int) -> None:
        self._depth["n"] += d
        if self.metrics is not None:
            self.metrics.gauge("rpc.send_queue_depth", self._depth["n"])

    async def _run(self) -> None:
        try:
            while True:
                if not self._q:
                    self._wake.clear()
                    await self._wake.wait()
                    # coalescing window: let every producer already
                    # runnable in this tick enqueue (e.g. all replies a
                    # journal group commit just released together)
                    # before cutting the batch
                    await asyncio.sleep(0)
                batch: list[_SendItem] = []
                fitem: _SendItem | None = None
                nbytes = 0
                while (self._q and len(batch) < self.max_frames
                       and nbytes < self.max_bytes):
                    item = self._q[0]
                    if item.fut.cancelled():
                        self._q.popleft()
                        self._bump(-1)
                        continue
                    if item.file is not None:
                        if batch:
                            break       # flush queued frames first
                        self._q.popleft()
                        self._bump(-1)
                        fitem = item
                        break
                    self._q.popleft()
                    self._bump(-1)
                    batch.append(item)
                    nbytes += item.size
                if fitem is not None:
                    await self._write_file(fitem)
                elif batch:
                    await self._write_batch(batch, nbytes)
        except asyncio.CancelledError:
            self._abort(ConnectError(f"{self.name}: connection closed"))
            raise
        except Exception as e:  # noqa: BLE001 — socket errors poison
            self._break(e)

    async def _write_batch(self, batch: list[_SendItem],
                           nbytes: int) -> None:
        # flatten runs of small frames into contiguous buffers; large
        # payloads stay their own iovec entry (uncopied)
        parts: list = []
        cur = bytearray()
        for it in batch:
            cur += it.head
            if it.big is not None:
                if cur:
                    parts.append(cur)
                parts.append(it.big)
                cur = bytearray()
        if cur:
            parts.append(cur)
        try:
            async with self._io_lock:
                if len(parts) == 1:
                    await self.loop.sock_sendall(self.sock, parts[0])
                else:
                    await vectored_sendall(self.loop, self.sock, parts)
        except BaseException as e:
            self._resolve(batch, e)
            raise
        self.bytes_sent += nbytes
        m = self.metrics
        if m is not None:
            m.observe("rpc.send_batch_frames", len(batch))
            m.inc("rpc.bytes_sent", nbytes)
        self._resolve(batch, None)

    async def _write_file(self, item: _SendItem) -> None:
        try:
            async with self._io_lock:
                await self.loop.sock_sendall(self.sock, item.head)
                item.file.seek(item.offset)
                sent = await self.loop.sock_sendfile(
                    self.sock, item.file, item.offset, item.count,
                    fallback=True)
        except BaseException as e:
            self._resolve([item], e)
            raise
        self.bytes_sent += len(item.head) + sent
        if self.metrics is not None:
            self.metrics.inc("rpc.bytes_sent", len(item.head) + sent)
        if not item.fut.done():
            item.fut.set_result(sent)

    @staticmethod
    def _resolve(batch: list[_SendItem],
                 exc: BaseException | None) -> None:
        for it in batch:
            if it.fut.done():
                continue
            if exc is None:
                it.fut.set_result(None)
            elif isinstance(exc, asyncio.CancelledError):
                it.fut.cancel()
            else:
                it.fut.set_exception(exc)

    def _abort(self, exc: BaseException) -> None:
        while self._q:
            it = self._q.popleft()
            self._bump(-1)
            if not it.fut.done():
                it.fut.set_exception(
                    exc if not isinstance(exc, asyncio.CancelledError)
                    else ConnectError(f"{self.name}: connection closed"))

    # -------- teardown --------

    def close(self) -> None:
        self.closed = True
        if self._task is not None:
            self._task.cancel()

    async def aclose(self) -> None:
        self.close()
        t, self._task = self._task, None
        if t is not None:
            try:
                await t
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass
        self._abort(ConnectError(f"{self.name}: connection closed"))


class BulkDecoder:
    """Incremental multi-frame decoder over one reusable recv buffer."""

    def __init__(self, size: int = RECV_BUFFER_BYTES, metrics=None):
        self._buf = bytearray(max(size, 16 * 1024))
        self._pos = 0
        self._limit = 0
        self.metrics = metrics
        self.bytes_recv = 0

    def pending(self) -> int:
        return self._limit - self._pos

    def _compact(self) -> None:
        rem = self._limit - self._pos
        if rem:
            self._buf[:rem] = self._buf[self._pos:self._limit]
        self._pos, self._limit = 0, rem

    def _grow(self, need: int) -> None:
        buf = bytearray(max(need, 2 * len(self._buf)))
        rem = self._limit - self._pos
        buf[:rem] = self._buf[self._pos:self._limit]
        self._buf, self._pos, self._limit = buf, 0, rem

    def _account(self, got: int) -> None:
        self.bytes_recv += got
        if self.metrics is not None:
            self.metrics.inc("rpc.bytes_recv", got)

    async def fill(self, loop: asyncio.AbstractEventLoop,
                   sock: socket.socket) -> int:
        """ONE recv into the buffer tail; typically lands many frames'
        worth of bytes. Raises ConnectionResetError on EOF."""
        if self._pos == self._limit:
            self._pos = self._limit = 0
        elif self._limit == len(self._buf):
            self._compact()
            if self._limit == len(self._buf):
                # a single envelope larger than the whole buffer (giant
                # msgpack header): grow so decode can ever complete
                self._grow(2 * len(self._buf))
        got = await loop.sock_recv_into(
            sock, memoryview(self._buf)[self._limit:])
        if got == 0:
            raise ConnectionResetError("peer closed")
        self._limit += got
        self._account(got)
        return got

    def try_next(self):
        """Decode the next frame's envelope if fully buffered,
        consuming it and leaving the payload unread. Returns
        ``(code, req_id, status, flags, header, data_len)`` or None
        (call ``fill()``). Raises CurvineError on malformed frames."""
        env = decode_envelope(self._buf, self._pos, self._limit)
        if env is None:
            return None
        end, code, req_id, status, flags, header, data_len = env
        self._pos = end
        return code, req_id, status, flags, header, data_len

    def take_into(self, dst: memoryview) -> int:
        """Copy up to len(dst) already-buffered payload bytes into
        ``dst`` (the sink fast-path prefix), consuming them."""
        n = min(self.pending(), len(dst))
        if n:
            dst[:n] = self._buf[self._pos:self._pos + n]
            self._pos += n
        return n

    async def recv_exact(self, loop, sock, view: memoryview) -> None:
        """Exact read that bypasses the bulk buffer (sink remainder),
        with recv accounting."""
        await recv_exact(loop, sock, view)
        self._account(len(view))

    async def read_payload(self, loop, sock, n: int) -> memoryview:
        """A contiguous view of the next ``n`` payload bytes, valid
        until the next decoder call. Fully-buffered payloads cost no
        syscall; larger ones are completed with exact reads into the
        grow-only buffer (or a transient allocation past the retain
        cap, so one giant frame doesn't pin its size forever)."""
        if self.pending() >= n:
            v = memoryview(self._buf)[self._pos:self._pos + n]
            self._pos += n
            return v
        if n > len(self._buf) and n > RECV_RETAIN_MAX:
            tmp = bytearray(n)
            mv = memoryview(tmp)
            got = self.take_into(mv)
            await self.recv_exact(loop, sock, mv[got:])
            return mv
        if n > len(self._buf):
            self._grow(n)
        elif self._pos:
            self._compact()
        rem = self._limit          # buffered prefix of this payload
        await self.recv_exact(loop, sock, memoryview(self._buf)[rem:n])
        # the whole payload is consumed: reset so the next fill starts
        # at offset 0 (the returned view stays valid until then)
        self._pos = self._limit = 0
        return memoryview(self._buf)[:n]
