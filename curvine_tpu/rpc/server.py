"""RPC server: raw-socket loop, handler registry, streaming support.

Parity: orpc/src/server/ + orpc/src/handler/. Handlers are registered per
RpcCode. A handler may:
  * return a (header, data) tuple / dict / None → single response frame;
  * call ``conn.send`` itself for streaming responses and return None
    after sending an EOF frame;
  * consume an inbound chunk stream either via ``conn.open_stream``
    (queue of copied Messages) or — zero-copy — ``conn.set_stream_sink``
    (an async callback invoked inline from the receive loop with a view
    into the connection's reusable buffer).

The receive path allocates nothing per frame: frames are bulk-decoded
out of one grow-only buffer per connection (first-touch page faults are
paid once, and one recv_into typically lands many small frames), which
is what makes multi-GiB/s upload streams AND 100K+ small-op rates
possible in Python. Sends ride the coalesced writer (rpc/transport.py):
replies released together — e.g. a whole journal group commit — leave
in one vectored send instead of one syscall+wakeup each."""

from __future__ import annotations

import asyncio
import logging
import socket
import time
from typing import Awaitable, Callable

from curvine_tpu.common.errors import CurvineError, Throttled
from curvine_tpu.common.qos import TENANT_KEY
from curvine_tpu.obs import loop_meter
from curvine_tpu.rpc.frame import (
    FIXED_LEN, LEN_PREFIX, SRV_KEY, Flags, Message, error_for, response_for,
)
from curvine_tpu.rpc import frame as frame_mod
from curvine_tpu.rpc.transport import BulkDecoder, CoalescedWriter

log = logging.getLogger(__name__)

Handler = Callable[[Message, "ServerConn"], Awaitable[object]]
# async fn(header: dict, view: memoryview, is_eof: bool) -> None
StreamSink = Callable[[dict, memoryview, bool], Awaitable[None]]


class ServerConn:
    """One accepted connection; single receive loop, serialized sends."""

    def __init__(self, sock: socket.socket, loop: asyncio.AbstractEventLoop,
                 rpc_conf=None, metrics=None, depth_cell: dict | None = None):
        self.sock = sock
        self.loop = loop
        try:
            self.peer = sock.getpeername()
        except OSError:
            self.peer = None
        self._streams: dict[int, asyncio.Queue] = {}
        self._sinks: dict[int, StreamSink] = {}
        self._writer = CoalescedWriter(
            sock, loop,
            max_bytes=getattr(rpc_conf, "send_coalesce_bytes", 256 * 1024),
            max_frames=getattr(rpc_conf, "send_coalesce_frames", 128),
            inline_max=getattr(rpc_conf, "send_inline_max", 8 * 1024),
            metrics=metrics, depth_cell=depth_cell,
            on_broken=self._on_send_broken, name="server")
        self._dec = BulkDecoder(
            size=getattr(rpc_conf, "recv_buffer_bytes", 256 * 1024),
            metrics=metrics)
        self.closed = False

    def _on_send_broken(self, exc: BaseException) -> None:
        # writer died mid-batch → a partial frame may be on the wire:
        # close the socket so the conn loop tears the connection down
        self.closed = True
        try:
            self.sock.close()
        except OSError:
            pass

    # -------- inbound streams --------

    def open_stream(self, req_id: int, maxsize: int = 256) -> asyncio.Queue:
        q = self._streams.get(req_id)
        if q is None:
            q = self._streams[req_id] = asyncio.Queue(maxsize=maxsize)
        return q

    def close_stream(self, req_id: int) -> None:
        self._streams.pop(req_id, None)
        self._sinks.pop(req_id, None)

    def set_stream_sink(self, req_id: int, sink: StreamSink) -> None:
        """Zero-copy upload consumption: `sink` runs inline in the receive
        loop with a view into the reusable buffer (valid only during the
        call). Chunks that raced ahead of registration (they were queued)
        are replayed into the sink first."""
        self._sinks[req_id] = sink
        q = self._streams.get(req_id)
        if q is not None and not q.empty():
            asyncio.ensure_future(self._drain_queue_into_sink(req_id))

    async def _drain_queue_into_sink(self, req_id: int) -> None:
        q = self._streams.get(req_id)
        sink = self._sinks.get(req_id)
        while q is not None and sink is not None and not q.empty():
            m = q.get_nowait()
            try:
                await sink(m.header, memoryview(m.data), m.is_eof)
            except Exception:
                log.exception("stream sink (drain)")
                self.close_stream(req_id)
                return
            sink = self._sinks.get(req_id)

    # -------- io --------

    async def send(self, msg: Message) -> None:
        if self.closed:
            raise CurvineError("connection closed")
        await self._writer.send(msg)

    async def send_chunk_from_file(self, code: int, req_id: int, f,
                                   offset: int, count: int,
                                   flags: int = Flags.RESPONSE | Flags.CHUNK,
                                   ) -> int:
        """Zero-copy chunk frame: header via sendall, payload via
        kernel-side sendfile straight from the block file (orpc sendfile
        parity — data never enters userspace). Rides the coalesced
        writer queue so it stays FIFO-ordered with regular frames."""
        if self.closed:
            raise CurvineError("connection closed")
        prefix = LEN_PREFIX.pack(FIXED_LEN + count) + frame_mod._FIXED.pack(
            frame_mod.VERSION, code, req_id, 0, flags, 0)
        return await self._writer.send_file(prefix, f, offset, count)


class RpcServer:
    def __init__(self, host: str, port: int, name: str = "rpc",
                 rpc_conf=None):
        self.host = host
        self.port = port
        self.name = name
        self.rpc_conf = rpc_conf
        # shared by every connection's writer: the exported
        # rpc.send_queue_depth gauge is the process-wide queued count
        self._sendq_depth: dict = {"n": 0}
        self._handlers: dict[int, Handler] = {}
        self._lsock: socket.socket | None = None
        self._accept_task: asyncio.Task | None = None
        self._conns: set[ServerConn] = set()
        self._conn_tasks: set[asyncio.Task] = set()
        # optional fault-injection hook (curvine_tpu.fault): called per
        # request, may sleep, raise, or ask for the request to be dropped
        self.fault_hook = None
        # optional DirWatchdog: every in-flight request registers so a
        # wedged dispatch (including one stalled in the fault hook) is
        # visible to the stuck-op sentinel (master/monitor.py)
        self.watchdog = None
        # optional Tracer (curvine_tpu/obs): dispatch picks the caller's
        # trace context off the header (msg.trace, same rail as the
        # deadline) and records a server span per request
        self.obs = None
        # optional MetricsRegistry: per-code dispatch latency histograms
        # (rpc.<code_name>), uniform across master and worker
        self.metrics = None
        self._loop_meter = None
        # optional AdmissionController (common/qos.py): tenant admission
        # runs synchronously in the conn loop BEFORE the dispatch task
        # is created — a throttled request never queues, never runs a
        # handler, never touches a commit barrier (shed-before-queue)
        self.qos = None

    def register(self, code: int, handler: Handler) -> None:
        self._handlers[int(code)] = handler

    def handler(self, code: int):
        def deco(fn: Handler) -> Handler:
            self.register(code, fn)
            return fn
        return deco

    async def start(self) -> None:
        loop = asyncio.get_running_loop()
        sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        sock.bind((self.host, self.port))
        sock.listen(128)
        sock.setblocking(False)
        self._lsock = sock
        if self.port == 0:
            self.port = sock.getsockname()[1]
        self._accept_task = asyncio.ensure_future(self._accept_loop(loop))
        if self.metrics is not None:
            # the loop this server runs on, counted into its registry
            # until stop() (loop.busy_s / cpu_s / runs: obs/loop_meter.py)
            self._loop_meter = loop_meter.attach(self.metrics.counters)
        log.info("%s server listening on %s:%d", self.name, self.host,
                 self.port)

    async def stop(self) -> None:
        if self._loop_meter is not None:
            self._loop_meter.detach(self.metrics.counters)
            self._loop_meter = None
        accept = self._accept_task
        if accept is not None:
            accept.cancel()
            self._accept_task = None
        if self._lsock is not None:
            self._lsock.close()
            self._lsock = None
        for conn in list(self._conns):
            conn.closed = True
            try:
                conn.sock.close()
            except OSError:
                pass
        tasks = list(self._conn_tasks)
        for t in tasks:
            t.cancel()
        # AWAIT the teardown, don't just request it: the caller closes
        # backing resources (the native KV store, io engines) right after
        # stop() returns, and a dispatch task resuming past that point
        # would touch freed state — a real use-after-free segfault under
        # master-restart storms. Each conn loop awaits its own pending
        # dispatches out the same way.
        for t in ([accept] if accept is not None else []) + tasks:
            try:
                await t
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass
        self._conns.clear()

    @property
    def addr(self) -> str:
        return f"{self.host}:{self.port}"

    @property
    def _server(self):
        """Liveness probe used by tests (legacy streams-era attribute)."""
        return self._lsock

    async def _accept_loop(self, loop) -> None:
        assert self._lsock is not None
        while True:
            try:
                sock, _ = await loop.sock_accept(self._lsock)
            except (asyncio.CancelledError, OSError):
                return
            sock.setblocking(False)
            try:
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            except OSError:
                pass
            conn = ServerConn(sock, loop, rpc_conf=self.rpc_conf,
                              metrics=self.metrics,
                              depth_cell=self._sendq_depth)
            self._conns.add(conn)
            t = asyncio.ensure_future(self._conn_loop(conn))
            self._conn_tasks.add(t)
            t.add_done_callback(self._conn_tasks.discard)

    async def _conn_loop(self, conn: ServerConn) -> None:
        dec = conn._dec
        pending: set[asyncio.Task] = set()
        try:
            while True:
                try:
                    env = dec.try_next()
                    if env is None:
                        # one recv typically lands many frames; every
                        # complete frame already buffered is dispatched
                        # above without touching the socket again
                        await dec.fill(conn.loop, conn.sock)
                        continue
                except (ConnectionResetError, OSError):
                    break
                except asyncio.CancelledError:
                    raise
                except Exception as e:  # noqa: BLE001 — hostile bytes
                    log.warning("%s: malformed frame from %s: %s",
                                self.name, conn.peer, e)
                    break
                code, req_id, status, flags, header, data_len = env
                is_chunk = bool(flags & (Flags.CHUNK | Flags.EOF)) and \
                    not (flags & Flags.RESPONSE)

                if is_chunk and req_id in conn._sinks:
                    # zero-copy upload: consume inline from the decoder
                    # buffer (replay any chunks queued pre-registration)
                    q = conn._streams.get(req_id)
                    if q is not None and not q.empty():
                        await conn._drain_queue_into_sink(req_id)
                    try:
                        view = await dec.read_payload(
                            conn.loop, conn.sock, data_len)
                    except (ConnectionResetError, OSError):
                        break
                    sink = conn._sinks.get(req_id)
                    if sink is None:       # sink errored during drain
                        continue
                    try:
                        await sink(header, view, bool(flags & Flags.EOF))
                    except asyncio.CancelledError:
                        raise
                    except Exception:
                        log.exception("%s stream sink", self.name)
                        conn.close_stream(req_id)
                    continue

                data = b""
                if data_len:
                    try:
                        data = bytes(await dec.read_payload(
                            conn.loop, conn.sock, data_len))
                    except (ConnectionResetError, OSError):
                        break
                msg = Message(code=code, req_id=req_id, status=status,
                              flags=flags, header=header, data=data,
                              parsed=time.perf_counter())
                if is_chunk:
                    # NEVER block the receive loop on a stream queue: if
                    # the request frame was dropped (fault injection) or
                    # its handler died, nothing will ever consume these
                    # chunks — an `await put` on the full queue would
                    # wedge this connection (and, through a filling
                    # socket buffer, the sender) permanently. Shed the
                    # oldest chunk instead: a legit-but-raced upload
                    # surfaces the loss at EOF (crc/length mismatch) as
                    # a clean error the client can retry.
                    q = conn.open_stream(req_id)
                    if q.full():
                        try:
                            q.get_nowait()
                        except asyncio.QueueEmpty:
                            pass
                        log.debug("%s: shed chunk for unconsumed stream "
                                  "req_id=%d", self.name, req_id)
                    q.put_nowait(msg)
                    continue
                qtok = None
                if self.qos is not None:
                    # admission BEFORE the dispatch task exists: the
                    # rejection reply leaves without the request ever
                    # queueing behind admitted work (Tail-at-Scale /
                    # DAGOR shed-at-the-door). Chunk frames above are
                    # exempt — they belong to an already-admitted
                    # upload stream.
                    try:
                        qtok = self.qos.admit_msg(code, header)
                    except CurvineError as e:
                        t = asyncio.ensure_future(
                            self._send_error(conn, msg, e))
                        pending.add(t)
                        t.add_done_callback(pending.discard)
                        continue
                t = asyncio.ensure_future(self._dispatch(msg, conn, qtok))
                pending.add(t)
                t.add_done_callback(pending.discard)
        finally:
            conn.closed = True
            self._conns.discard(conn)
            for t in pending:
                t.cancel()
            # prove the dispatches exited (see RpcServer.stop): a
            # handler mid-flight must not outlive the server teardown
            for t in list(pending):
                try:
                    await t
                except (asyncio.CancelledError, Exception):  # noqa: BLE001
                    pass
            await conn._writer.aclose()
            try:
                conn.sock.close()
            except OSError:
                pass

    async def _send_error(self, conn: ServerConn, msg: Message,
                          e: Exception) -> None:
        try:
            await conn.send(error_for(msg, e))
        except Exception:  # noqa: BLE001 — conn died, nothing to do
            pass

    async def _dispatch(self, msg: Message, conn: ServerConn,
                        qtok=None) -> None:
        handler = self._handlers.get(msg.code)
        name = _code_name(msg.code)
        token = None
        if self.watchdog is not None:
            token = self.watchdog.op_enter(name)
        # trace propagation: the caller's span context rides the header
        # the same way the deadline does; this dispatch becomes a child
        # span and sets the ambient context so the handler's own
        # downstream calls (replication pulls, peer streams) carry it on
        msg.trace = msg.trace_ctx()
        span = None
        if self.obs is not None:
            span = self.obs.span(name, parent=msg.trace)
            tenant = msg.header.get(TENANT_KEY)
            if tenant:
                span.set_attr("tenant", tenant)
            span.__enter__()
        t0 = time.perf_counter()
        try:
            # deadline propagation: restart the caller's remaining budget
            # on our clock once; handlers that make downstream calls
            # (replication pulls, peer streams) read msg.deadline
            msg.deadline = msg.budget()
            if msg.deadline is not None:
                msg.deadline.check(f"{self.name} {_code_name(msg.code)}")
            if self.fault_hook is not None:
                if not await self.fault_hook(self.name, msg):
                    return          # fault: drop the request silently
            if msg.deadline is not None:
                # fast-fail dead work: the budget may have died while the
                # request sat behind the fault hook / dispatch queue —
                # the caller already gave up, so doing the work (or
                # applying the mutation) only burns server time
                msg.deadline.check(f"{self.name} {_code_name(msg.code)}")
            if handler is None:
                raise CurvineError(f"no handler for code {msg.code}")
            t_handle = time.perf_counter()
            result = await handler(msg, conn)
            if result is None:
                return  # handler streamed its own response
            if isinstance(result, tuple):
                header, data = result
            elif isinstance(result, (bytes, bytearray, memoryview)):
                header, data = {}, result
            else:
                header, data = result, b""
            # the server's own time rides the reply (a copy: the
            # handler's dict may be one it keeps)
            header = {**(header or {}), SRV_KEY: [
                int((t_handle - (msg.parsed or t_handle)) * 1e6),
                int((time.perf_counter() - t_handle) * 1e6)]}
            await conn.send(response_for(
                msg, header=header, data=data, flags=Flags.RESPONSE | Flags.EOF))
        except asyncio.CancelledError:
            if span is not None:
                span.error("cancelled")
            raise
        except Exception as e:  # noqa: BLE001 — all errors cross the wire
            if span is not None:
                span.error(e)
            if isinstance(e, Throttled) and self.qos is not None:
                # the shed-before-queue contract says Throttled is only
                # ever raised at admission, never from inside a handler
                # after the request queued — count violations so the
                # storm harness can assert the invariant held
                self.qos.note_shed_after_queue()
            if not isinstance(e, CurvineError):
                log.exception("%s handler error code=%s", self.name, msg.code)
            try:
                await conn.send(error_for(msg, e))
            except Exception:
                pass
        finally:
            if span is not None:
                span.__exit__(None, None, None)
            elapsed = time.perf_counter() - t0
            if self.qos is not None:
                # feeds the load monitor's service-time estimate (DOA
                # drop) and decrements the tenant's inflight count
                self.qos.release(qtok, elapsed)
            if self.metrics is not None:
                self.metrics.observe(f"rpc.{name}", elapsed)
            if token is not None:
                self.watchdog.op_exit(token)


def _code_name(code: int) -> str:
    from curvine_tpu.rpc.codes import RpcCode
    try:
        return RpcCode(code).name.lower()
    except ValueError:
        return f"code_{code}"
