"""RPC client: connection, response routing, pool, retry policy.

Parity: orpc/src/client/ (ClusterConnector/conn pool) and
orpc/src/io/retry/ (exponential backoff, retryable error classification).

The connection runs on a raw non-blocking socket (loop.sock_* APIs, no
asyncio streams) through the coalesced transport (rpc/transport.py):
sends from all in-flight requests leave in vectored batches drained by
one writer task, and the read loop bulk-decodes many frames per
recv_into. A caller-registered *sink* buffer still lets block-read
streams land directly in the destination (numpy/HBM staging) buffer —
no intermediate bytes objects, which matters doubly on virtualized
hosts where first-touch page faults dominate large allocations."""

from __future__ import annotations

import asyncio
import itertools
import logging
import random
import socket
from dataclasses import dataclass
from typing import Any, AsyncIterator

from curvine_tpu.common.errors import ConnectError, CurvineError, RpcTimeout
from curvine_tpu.common.qos import TENANT_KEY, current_tenant
from curvine_tpu.obs.trace import TRACE_KEY, current_ctx
from curvine_tpu.rpc.deadline import DEADLINE_KEY, Deadline
from curvine_tpu.rpc.frame import SRV_KEY, Flags, Message, pack, unpack
from curvine_tpu.rpc.transport import BulkDecoder, CoalescedWriter

log = logging.getLogger(__name__)

_req_ids = itertools.count(1)


@dataclass
class _Sink:
    """Destination buffer for a streaming read; chunk payloads are
    scattered into `view` at `filled`."""

    view: memoryview
    filled: int = 0


class Connection:
    """One TCP connection; multiplexes concurrent requests by req_id."""

    def __init__(self, addr: str, timeout_ms: int = 30_000,
                 rpc_conf=None, metrics=None):
        self.addr = addr
        self.timeout = timeout_ms / 1000
        self.rpc_conf = rpc_conf
        self.metrics = metrics
        self._sock: socket.socket | None = None
        self._loop: asyncio.AbstractEventLoop | None = None
        self._waiters: dict[int, asyncio.Queue] = {}
        self._sinks: dict[int, _Sink] = {}
        self._reader_task: asyncio.Task | None = None
        self._writer: CoalescedWriter | None = None
        self._dec: BulkDecoder | None = None
        self.closed = False
        # client-side fault hook mirroring RpcServer.fault_hook: called
        # with (addr, msg) before each request leaves; may sleep (delay),
        # raise (error), or return False to swallow the send — the caller
        # then times out exactly as if the request was lost on the wire.
        self.fault_hook = None
        # server-push receiver: unsolicited REQUEST frames (no waiter,
        # e.g. META_INVALIDATE with req_id=0) land here synchronously on
        # the read loop; handlers must be non-blocking
        self.on_push = None

    async def connect(self) -> "Connection":
        host, port = self.addr.rsplit(":", 1)
        self._loop = asyncio.get_running_loop()
        sock = None
        try:
            sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            sock.setblocking(False)
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            await asyncio.wait_for(
                self._loop.sock_connect(sock, (host, int(port))), self.timeout)
            self._sock = sock
        except (OSError, asyncio.TimeoutError) as e:
            raise ConnectError(f"connect {self.addr}: {e}") from e
        finally:
            # a dial refused, timed out or cancelled (the pool closing):
            # its socket goes now, not when the collector finds it
            if self._sock is None and sock is not None:
                sock.close()
        rc = self.rpc_conf
        self._writer = CoalescedWriter(
            sock, self._loop,
            max_bytes=getattr(rc, "send_coalesce_bytes", 256 * 1024),
            max_frames=getattr(rc, "send_coalesce_frames", 128),
            inline_max=getattr(rc, "send_inline_max", 8 * 1024),
            metrics=self.metrics, on_broken=self._on_send_broken,
            name=f"client {self.addr}")
        self._dec = BulkDecoder(
            size=getattr(rc, "recv_buffer_bytes", 256 * 1024),
            metrics=self.metrics)
        self._reader_task = asyncio.ensure_future(self._read_loop())
        return self

    def _on_send_broken(self, exc: BaseException) -> None:
        # the writer died mid-batch: a partial frame may be on the wire,
        # so the stream is unrecoverable — poison the connection (the
        # pool must never hand it to another request) and close the
        # socket so the read loop fails every waiter out
        self.closed = True
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass

    # ---------------- receive plumbing ----------------

    async def _read_loop(self) -> None:
        dec, loop, sock = self._dec, self._loop, self._sock
        assert dec is not None and loop is not None and sock is not None
        try:
            while True:
                env = dec.try_next()
                if env is None:
                    await dec.fill(loop, sock)
                    continue
                code, req_id, status, flags, header, data_len = env
                sink = self._sinks.get(req_id)
                data: bytes = b""
                if data_len:
                    if (sink is not None and status == 0
                            and sink.filled + data_len <= len(sink.view)):
                        # zero-copy sink: the buffered prefix of this
                        # chunk is copied out of the bulk buffer, the
                        # (typically multi-MB) remainder is received
                        # straight into the caller's view
                        dst = sink.view[sink.filled:
                                        sink.filled + data_len]
                        got = dec.take_into(dst)
                        if got < data_len:
                            await dec.recv_exact(loop, sock, dst[got:])
                        sink.filled += data_len
                    else:
                        data = bytes(await dec.read_payload(
                            loop, sock, data_len))
                # the server's own time leaves the header here, so a
                # caller that parses `header or unpack(data)` sees what
                # it saw before
                msg = Message(code=code, req_id=req_id, status=status,
                              flags=flags, header=header, data=data,
                              srv=header.pop(SRV_KEY, None))
                q = self._waiters.get(req_id)
                if q is not None:
                    # streaming chunks landed in a sink don't need delivery
                    if not (sink is not None and msg.is_chunk
                            and status == 0):
                        q.put_nowait(msg)
                elif self.on_push is not None and not msg.is_response:
                    # unsolicited server push (lease invalidation rail)
                    try:
                        self.on_push(msg)
                    except Exception:   # noqa: BLE001 — push must not
                        log.exception("push handler %s", self.addr)
                else:
                    log.debug("drop orphan frame req_id=%d", req_id)
        except (ConnectionResetError, OSError):
            pass
        except asyncio.CancelledError:
            pass
        except Exception:
            log.exception("connection %s read loop", self.addr)
        finally:
            self.closed = True
            # the read loop dying is the one teardown path every broken
            # connection goes through (peer reset, poison, close): take
            # the writer task down with it or it leaks, parked on its
            # wake event forever
            if self._writer is not None:
                self._writer.close()
            err = Message(status=1, header={"error_code": 26,
                                            "error": f"connection {self.addr} closed"},
                          flags=Flags.RESPONSE | Flags.EOF)
            for q in self._waiters.values():
                q.put_nowait(err)

    async def close(self) -> None:
        self.closed = True
        if self._writer is not None:
            await self._writer.aclose()
        task, self._reader_task = self._reader_task, None
        try:
            if task is not None and task is not asyncio.current_task():
                # the cancelled read loop must have left the selector
                # BEFORE the fd closes: a reconnect in the same tick can
                # be handed the same fd number, and the stale reader
                # registration then kills its sock_connect with ENOENT
                task.cancel()
                await asyncio.wait([task])
        finally:
            if self._sock is not None:
                try:
                    self._sock.close()
                except OSError:
                    pass
                self._sock = None

    # ---------------- send plumbing ----------------

    async def send(self, msg: Message) -> None:
        if self.closed or self._writer is None:
            raise ConnectError(f"connection {self.addr} is closed")
        try:
            await self._writer.send(msg)
        except asyncio.CancelledError:
            # cancelled send (teardown of a prefetch/stream task): on
            # the coalesced queue path a cancel severs at a frame
            # boundary — a queued frame is dropped whole, an in-flight
            # one is written out whole — so the connection stays usable
            # un-poisoned. Only the uncontended INLINE fast path keeps
            # the PR-2 behavior: a cancel mid-write leaves a partial
            # frame, and the writer poisons us via _on_send_broken.
            raise
        except ConnectError:
            self.closed = True
            raise
        except (OSError, RuntimeError) as e:
            self.closed = True
            raise ConnectError(f"send to {self.addr}: {e}") from e

    def register(self, req_id: int) -> asyncio.Queue:
        q: asyncio.Queue = asyncio.Queue()
        self._waiters[req_id] = q
        return q

    def unregister(self, req_id: int) -> None:
        self._waiters.pop(req_id, None)
        self._sinks.pop(req_id, None)

    # ---------------- request patterns ----------------

    async def _launch(self, msg: Message,
                      deadline: "Deadline | None") -> None:
        """Stamp the remaining budget into the header, run the client
        fault hook, then send. A hook returning False swallows the send:
        the caller's response wait times out exactly as if the request
        was lost on the wire."""
        if deadline is not None:
            deadline.check(f"rpc {msg.code} to {self.addr}")
            deadline.stamp(msg.header)
        # trace propagation: the ambient span context (obs/trace.py)
        # rides the header so the receiving server's span links to the
        # span this request was made under — no per-call-site plumbing
        ctx = current_ctx()
        if ctx is not None and TRACE_KEY not in msg.header:
            ctx.stamp(msg.header)
        # tenant identity rides the same rail: the ambient tenant (set
        # per-request by the gateway, per-process by native clients)
        # lets the receiving server's admission control see the caller
        tenant = current_tenant()
        if tenant is not None and TENANT_KEY not in msg.header:
            msg.header[TENANT_KEY] = tenant
        if self.fault_hook is not None:
            if not await self.fault_hook(self.addr, msg):
                return
        await self.send(msg)

    def _wait_s(self, timeout: float | None,
                deadline: "Deadline | None") -> float:
        """Per-wait timeout: min(conf/explicit timeout, remaining budget).
        Recomputed per wait so stream reads never outlive the budget."""
        t = timeout or self.timeout
        return deadline.cap(t) if deadline is not None else t

    async def call(self, code: int, header: dict | None = None,
                   data: bytes | memoryview = b"",
                   timeout: float | None = None,
                   deadline: "Deadline | None" = None) -> Message:
        """Unary request → single response."""
        req_id = next(_req_ids)
        q = self.register(req_id)
        try:
            await self._launch(Message(code=int(code), req_id=req_id,
                                       header=dict(header or {}), data=data),
                               deadline)
            try:
                rep: Message = await asyncio.wait_for(
                    q.get(), self._wait_s(timeout, deadline))
            except asyncio.TimeoutError as e:
                raise RpcTimeout(f"rpc {code} to {self.addr} timed out") from e
            return rep.check()
        finally:
            self.unregister(req_id)

    async def call_stream(self, code: int, header: dict | None = None,
                          timeout: float | None = None,
                          deadline: "Deadline | None" = None,
                          ) -> AsyncIterator[Message]:
        """Unary request → stream of chunk frames ending with EOF."""
        req_id = next(_req_ids)
        q = self.register(req_id)
        try:
            await self._launch(Message(code=int(code), req_id=req_id,
                                       header=dict(header or {})), deadline)
            while True:
                try:
                    rep: Message = await asyncio.wait_for(
                        q.get(), self._wait_s(timeout, deadline))
                except asyncio.TimeoutError as e:
                    raise RpcTimeout(f"stream rpc {code} to {self.addr} timed out") from e
                rep.check()
                yield rep
                if rep.is_eof:
                    return
        finally:
            self.unregister(req_id)

    async def call_readinto(self, code: int, sink: memoryview,
                            header: dict | None = None,
                            timeout: float | None = None,
                            deadline: "Deadline | None" = None,
                            eof_header: dict | None = None) -> int:
        """Streaming read whose chunk payloads are scattered straight into
        `sink`; returns bytes filled (the zero-copy remote-read path).
        When `eof_header` is given, the EOF frame's header fields are
        merged into it — the caller sees server-side trailers (e.g. the
        block's commit-time checksum) without a second RPC."""
        req_id = next(_req_ids)
        q = self.register(req_id)
        state = _Sink(view=sink)
        self._sinks[req_id] = state
        try:
            await self._launch(Message(code=int(code), req_id=req_id,
                                       header=dict(header or {})), deadline)
            while True:
                try:
                    rep: Message = await asyncio.wait_for(
                        q.get(), self._wait_s(timeout, deadline))
                except asyncio.TimeoutError as e:
                    raise RpcTimeout(
                        f"readinto rpc {code} to {self.addr} timed out") from e
                rep.check()
                if len(rep.data):       # overflow chunk delivered inline
                    n = min(len(rep.data), len(sink) - state.filled)
                    sink[state.filled:state.filled + n] = rep.data[:n]
                    state.filled += n
                if rep.is_eof:
                    if eof_header is not None and rep.header:
                        eof_header.update(rep.header)
                    return state.filled
        finally:
            self.unregister(req_id)

    class _UploadStream:
        """Chunked upload for one req_id; ends with EOF then awaits the ack."""

        def __init__(self, conn: "Connection", code: int, req_id: int,
                     q: asyncio.Queue, timeout: float):
            self.conn, self.code, self.req_id, self.q = conn, code, req_id, q
            self.timeout = timeout

        async def send_chunk(self, data: bytes | memoryview,
                             header: dict | None = None) -> None:
            # before EOF the only message the server can have sent is an
            # error (refused open, mid-stream write failure): surface it
            # NOW so the caller fails over instead of streaming the rest
            # of the block into the void and learning at finish()
            if not self.q.empty():
                self.q.get_nowait().check()
            await self.conn.send(Message(code=self.code, req_id=self.req_id,
                                         flags=Flags.CHUNK, header=header or {},
                                         data=data))

        async def finish(self, header: dict | None = None) -> Message:
            await self.conn.send(Message(code=self.code, req_id=self.req_id,
                                         flags=Flags.EOF, header=header or {}))
            try:
                rep: Message = await asyncio.wait_for(self.q.get(), self.timeout)
            except asyncio.TimeoutError as e:
                raise RpcTimeout(f"upload {self.code} ack timed out") from e
            finally:
                self.conn.unregister(self.req_id)
            return rep.check()

        async def abort(self) -> None:
            """Best-effort cancel: an EOF frame flagged `abort` tells the
            server to discard the superseded stream's temp state now
            instead of waiting for connection teardown; then stop
            listening for the ack. A dead conn just unregisters."""
            try:
                await self.conn.send(Message(
                    code=self.code, req_id=self.req_id, flags=Flags.EOF,
                    header={"abort": True}))
            except Exception:   # noqa: BLE001 — conn already down
                pass
            finally:
                self.conn.unregister(self.req_id)

    async def open_upload(self, code: int, header: dict | None = None,
                          timeout: float | None = None,
                          deadline: "Deadline | None" = None,
                          ) -> "Connection._UploadStream":
        """Start a chunked upload: request frame, then CHUNK*, EOF → ack."""
        req_id = next(_req_ids)
        q = self.register(req_id)
        await self._launch(Message(code=int(code), req_id=req_id,
                                   header=dict(header or {})), deadline)
        return Connection._UploadStream(self, int(code), req_id, q,
                                        self._wait_s(timeout, deadline))


class ConnectionPool:
    """Per-address connection pool with lazy dial and broken-conn
    eviction. A pool of `size` is at most `size` sockets an address,
    open and being dialled together, at every instant: a caller that
    arrives while the pool is short shares what the pool has — an open
    connection at once, else the dials in flight — and never dials
    beyond `size` (`Connection` multiplexes by req_id, so a burst over
    four connections is served as concurrently as over a thousand).

    `counters`, where given (CurvineClient hands both of its pools its
    own), counts `rpc.dials` (dials started) and `rpc.dial_joins` (gets
    that found the pool short with every missing connection already
    being dialled, and were served without a dial of their own)."""

    def __init__(self, size: int = 4, timeout_ms: int = 30_000,
                 rpc_conf=None, metrics=None,
                 counters: dict | None = None):
        self.size = size
        self.timeout_ms = timeout_ms
        self.rpc_conf = rpc_conf
        self.metrics = metrics
        self.counters = counters
        self._conns: dict[str, list[Connection]] = {}
        # dials in flight, each a task of the POOL's own: a caller's
        # wait_for / cancellation must not cancel a dial that others
        # wait on, nor leave its connection to no one
        self._dials: dict[str, list[asyncio.Task]] = {}
        self._rr: dict[str, int] = {}
        # client-side fault hook, inherited by every dialed Connection
        # (FaultInjector.install_client); see Connection.fault_hook
        self.fault_hook = None
        # server-push receiver, inherited the same way (meta lease cache
        # invalidation); see Connection.on_push
        self.push_handler = None

    def set_fault_hook(self, hook) -> None:
        """Install/remove the client fault hook on this pool AND every
        already-dialed connection (a dial takes it as it registers)."""
        self.fault_hook = hook
        for conns in self._conns.values():
            for c in conns:
                c.fault_hook = hook

    def set_push_handler(self, handler) -> None:
        """Install/remove the server-push receiver on this pool AND
        every already-dialed connection (a dial takes it as it
        registers)."""
        self.push_handler = handler
        for conns in self._conns.values():
            for c in conns:
                c.on_push = handler

    def _count(self, key: str) -> None:
        if self.counters is not None:
            self.counters[key] = self.counters.get(key, 0) + 1

    async def get(self, addr: str) -> Connection:
        # no await down to the wait below: the loop runs nothing else
        # between the look at the pool and the dial it starts
        conns = self._conns.setdefault(addr, [])
        conns[:] = [c for c in conns if not c.closed]
        dials = self._dials.setdefault(addr, [])
        dials[:] = [t for t in dials if not t.done()]
        if len(conns) < self.size:
            if len(conns) + len(dials) < self.size:
                self._count("rpc.dials")
                dials.append(asyncio.ensure_future(self._dial(addr)))
                dials[-1].add_done_callback(_retrieve)
            else:
                self._count("rpc.dial_joins")
        if not conns:
            # nothing open yet: share the first dial in flight. wait()
            # leaves it running if this caller is cancelled; if it fails
            # it fails its waiters as their own dial would have, and the
            # next get, finding it done, dials anew
            dial = dials[0]
            await asyncio.wait([dial])
            conns = [c for c in self._conns.get(addr, ()) if not c.closed]
            if not conns:
                if dial.cancelled():
                    raise ConnectError(f"connect {addr}: pool closed")
                failed = dial.exception()
                if failed is not None:
                    raise ConnectError(str(failed)) from failed
                return dial.result()
        i = self._rr[addr] = (self._rr.get(addr, -1) + 1) % len(conns)
        return conns[i]

    async def _dial(self, addr: str, attempts: int = 3) -> Connection:
        """One dial in flight: connect, take the pool's hooks as they
        are NOW (not as they were when the dial began), register."""
        # transient connect failures (sandboxed loopback occasionally
        # returns ENOENT) are retried here so every caller benefits
        last: Exception | None = None
        for i in range(attempts):
            try:
                conn = await Connection(addr, self.timeout_ms,
                                        rpc_conf=self.rpc_conf,
                                        metrics=self.metrics).connect()
            except ConnectError as e:
                last = e
                await asyncio.sleep(0.05 * (2 ** i))
            else:
                conn.fault_hook = self.fault_hook
                conn.on_push = self.push_handler
                self._conns.setdefault(addr, []).append(conn)
                return conn
        assert last is not None
        raise last

    async def close(self) -> None:
        # dials first: one that came up all the same has registered its
        # connection by the time it is done, and is closed with the rest
        dials = [t for ts in self._dials.values() for t in ts]
        self._dials.clear()
        for t in dials:
            t.cancel()
        if dials:
            await asyncio.wait(dials)
        conns = [c for cs in self._conns.values() for c in cs]
        self._conns.clear()
        for c in conns:
            await c.close()


def _retrieve(task: asyncio.Task) -> None:
    """A dial nobody waited on (its starter was served by an open
    connection) must not die as 'exception was never retrieved'."""
    if not task.cancelled():
        task.exception()


class RetryPolicy:
    """Exponential backoff with jitter on retryable errors.

    With a `deadline`, the policy never sleeps past the budget: if the
    next backoff would cross the expiry (or the budget is already gone),
    the last error propagates immediately — the caller's deadline wins
    over retry persistence."""

    def __init__(self, max_retries: int = 3, base_ms: int = 100,
                 max_ms: int = 5_000):
        self.max_retries = max_retries
        self.base_ms = base_ms
        self.max_ms = max_ms

    async def run(self, fn, *args, deadline: Deadline | None = None,
                  **kwargs) -> Any:
        attempt = 0
        while True:
            try:
                return await fn(*args, **kwargs)
            except CurvineError as e:
                if not e.retryable or attempt >= self.max_retries:
                    raise
                hint = getattr(e, "retry_after_ms", None)
                if hint is not None:
                    # server-supplied backoff (THROTTLED): the server
                    # knows when its bucket refills — honor the hint
                    # instead of blind exponential backoff, jittered
                    # UP so a retry never lands before capacity exists
                    delay = float(hint) * (1.0 + random.random() / 4) / 1000
                else:
                    delay = min(self.max_ms, self.base_ms * (2 ** attempt))
                    delay = delay * (0.5 + random.random() / 2) / 1000
                if deadline is not None and \
                        delay >= deadline.remaining():
                    raise            # sleeping would outlive the budget
                log.debug("retry %d after %.3fs: %s", attempt + 1, delay, e)
                await asyncio.sleep(delay)
                attempt += 1


def obj_call(conn: Connection, code: int, obj: Any, **kw) -> Any:
    """Convenience: msgpack-object request body in `data`."""
    return conn.call(code, data=pack(obj), **kw)


def unpack_data(msg: Message) -> Any:
    return unpack(msg.data)
