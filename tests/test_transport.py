"""Wire-level transport fast path: coalesced vectored sends, bulk-recv
frame decode (rpc/transport.py, frame.decode_envelope/encode_into).

Covers the PR's hard cases: envelope decode across arbitrary recv split
points, MAX_FRAME rejection mid-batch, interleaved CHUNK-sink + control
frames landing in one bulk buffer, the cancelled-send contract under the
coalesced writer (queued cancel = frame-boundary drop, NOT poisoned;
inline cancel mid-write = poisoned, PR-2 semantics), batch coalescing
metrics, and sink payloads received straight into aligned buffers."""

import asyncio
import socket

import pytest

from curvine_tpu.common.errors import ConnectError, CurvineError
from curvine_tpu.common.metrics import MetricsRegistry
from curvine_tpu.rpc import RpcServer
from curvine_tpu.rpc import transport as transport_mod
from curvine_tpu.rpc.client import Connection, ConnectionPool
from curvine_tpu.rpc.frame import (
    ENVELOPE_MAX, FIXED_LEN, LEN_PREFIX, MAX_FRAME, Flags, Message,
    decode_envelope,
)
from curvine_tpu.rpc.transport import (
    BulkDecoder, CoalescedWriter, alloc_aligned, vectored_sendall,
)


def _frame_bytes(msg: Message) -> bytes:
    return b"".join(bytes(b) for b in msg.encode())


def _nb_socketpair():
    a, b = socket.socketpair()
    a.setblocking(False)
    b.setblocking(False)
    return a, b


async def _drain(loop, sock, n: int) -> bytes:
    out = bytearray()
    while len(out) < n:
        got = await loop.sock_recv(sock, n - len(out))
        if not got:
            break
        out += got
    return bytes(out)


# ---------------------------------------------------------------- frame


def test_encode_into_matches_encode():
    cases = [
        Message(code=7, req_id=1),                                # bare
        Message(code=7, req_id=2, header={"p": "/a", "n": 3}),    # header
        Message(code=7, req_id=3, header={"x": 1}, data=b"tiny"),
        Message(code=7, req_id=4, data=b"z" * 100_000),           # big
    ]
    for msg in cases:
        ref = _frame_bytes(msg)
        out = bytearray()
        big = msg.encode_into(out, inline_max=4096)
        flat = bytes(out) + (bytes(big) if big is not None else b"")
        assert flat == ref
        # payloads over inline_max must NOT be copied into the head
        if len(msg.data) > 4096:
            assert big is not None and bytes(big) == bytes(msg.data)
        else:
            assert big is None


def test_decode_envelope_every_split_point():
    """The envelope parser must return None (never raise, never consume)
    for every truncation point of a valid frame, then decode exactly."""
    msg = Message(code=9, req_id=42, status=0, flags=Flags.RESPONSE,
                  header={"k": "v", "n": 7}, data=b"payload-bytes")
    wire = _frame_bytes(msg)
    payload_off = len(wire) - len(msg.data)
    buf = bytearray()
    for i in range(payload_off):
        assert decode_envelope(buf, 0, len(buf)) is None, f"split at {i}"
        buf.append(wire[i])
    env = decode_envelope(buf, 0, len(buf))
    assert env is not None
    end, code, req_id, status, flags, header, data_len = env
    assert (code, req_id, status, flags) == (9, 42, 0, Flags.RESPONSE)
    assert header == {"k": "v", "n": 7}
    assert data_len == len(msg.data)
    assert end == payload_off


def test_decode_envelope_rejects_bad_frames():
    # oversized total length — rejected from the 4-byte prefix alone
    bad = LEN_PREFIX.pack(MAX_FRAME + 1) + b"\x00" * ENVELOPE_MAX
    with pytest.raises(CurvineError):
        decode_envelope(bad, 0, len(bad))
    # header_len overrunning the frame total
    good = bytearray(_frame_bytes(Message(code=1, header={"a": 1})))
    good[-1] ^= 0xFF  # corrupt header bytes -> msgpack error or similar
    # hdr_len > total
    hdr_overrun = LEN_PREFIX.pack(FIXED_LEN) + bytearray(FIXED_LEN)
    hdr_overrun = bytearray(hdr_overrun)
    hdr_overrun[4] = 1                       # version
    hdr_overrun[-1] = 200                    # header_len >> total
    with pytest.raises(CurvineError):
        decode_envelope(hdr_overrun, 0, len(hdr_overrun))


async def test_bulk_decoder_byte_at_a_time():
    """Frames split at EVERY wire boundary: the peer dribbles one byte
    per send; the decoder must reassemble all frames intact."""
    loop = asyncio.get_running_loop()
    a, b = _nb_socketpair()
    try:
        msgs = [Message(code=3, req_id=i, header={"i": i},
                        data=bytes([i]) * (i * 7)) for i in range(1, 6)]
        wire = b"".join(_frame_bytes(m) for m in msgs)

        async def dribble():
            for i in range(len(wire)):
                await loop.sock_sendall(a, wire[i:i + 1])

        send = asyncio.ensure_future(dribble())
        dec = BulkDecoder(size=64 * 1024)
        got = []
        while len(got) < len(msgs):
            env = dec.try_next()
            if env is None:
                await dec.fill(loop, b)
                continue
            code, req_id, status, flags, header, data_len = env
            data = bytes(await dec.read_payload(loop, b, data_len))
            got.append((req_id, header, data))
        await send
        for m, (req_id, header, data) in zip(msgs, got):
            assert req_id == m.req_id
            assert header == m.header
            assert data == bytes(m.data)
        assert dec.bytes_recv == len(wire)
    finally:
        a.close()
        b.close()


async def test_bulk_decoder_max_frame_mid_batch():
    """A hostile length prefix AFTER valid frames in the same recv
    buffer: the good frames decode, the bad one raises (and the server
    conn loop maps that to a connection teardown)."""
    loop = asyncio.get_running_loop()
    a, b = _nb_socketpair()
    try:
        good = _frame_bytes(Message(code=1, req_id=1, header={"ok": 1}))
        evil = LEN_PREFIX.pack(MAX_FRAME + 1) + b"\x00" * FIXED_LEN
        await loop.sock_sendall(a, good + good + evil)
        dec = BulkDecoder(size=64 * 1024)
        seen = 0
        with pytest.raises(CurvineError):
            while True:
                env = dec.try_next()
                if env is None:
                    await dec.fill(loop, b)
                    continue
                *_, data_len = env
                await dec.read_payload(loop, b, data_len)
                seen += 1
        assert seen == 2
    finally:
        a.close()
        b.close()


async def test_read_payload_transient_past_retain_cap(monkeypatch):
    """Payloads beyond RECV_RETAIN_MAX must use a transient allocation
    (the grow-only buffer must not balloon), smaller ones reuse it."""
    monkeypatch.setattr(transport_mod, "RECV_RETAIN_MAX", 20 * 1024)
    loop = asyncio.get_running_loop()
    a, b = _nb_socketpair()
    try:
        dec = BulkDecoder(size=16 * 1024)
        big = bytes(range(256)) * 128           # 32KB > cap
        send = asyncio.ensure_future(loop.sock_sendall(a, big))
        view = await dec.read_payload(loop, b, len(big))
        await send
        assert bytes(view) == big
        assert len(dec._buf) < len(big)         # buffer did not balloon
        # over the buffer but under the cap: grows and retains
        mid = b"m" * (18 * 1024)
        send = asyncio.ensure_future(loop.sock_sendall(a, mid))
        view = await dec.read_payload(loop, b, len(mid))
        await send
        assert bytes(view) == mid
        assert len(dec._buf) >= len(mid)
    finally:
        a.close()
        b.close()


# ---------------------------------------------------------- send path


async def test_vectored_sendall_many_buffers(monkeypatch):
    """More buffers than one iovec allows: content must arrive intact
    across the syscall splits."""
    monkeypatch.setattr(transport_mod, "_IOV_CAP", 4)
    loop = asyncio.get_running_loop()
    a, b = _nb_socketpair()
    try:
        bufs = [bytes([i]) * (i * 997 + 1) for i in range(20)]
        want = b"".join(bufs)
        recv = asyncio.ensure_future(_drain(loop, b, len(want)))
        await vectored_sendall(loop, a, list(bufs))
        assert await recv == want
    finally:
        a.close()
        b.close()


async def test_writer_coalesces_batch_and_metrics():
    """Sends enqueued while the wire is busy leave as ONE vectored
    batch: the rpc.send_batch_frames histogram must observe a multi-
    frame batch and bytes_sent must match the wire bytes."""
    loop = asyncio.get_running_loop()
    a, b = _nb_socketpair()
    m = MetricsRegistry("test")
    w = CoalescedWriter(a, loop, metrics=m, name="t")
    try:
        msgs = [Message(code=5, req_id=i, header={"i": i}) for i in range(8)]
        want = b"".join(_frame_bytes(msg) for msg in msgs)
        # hold the io lock so every send takes the QUEUE path, then
        # release: the writer drains them all in one batch
        async with w._io_lock:
            sends = [asyncio.ensure_future(w.send(msg)) for msg in msgs]
            await asyncio.sleep(0)
            assert w.qsize() == len(msgs)
        recv = asyncio.ensure_future(_drain(loop, b, len(want)))
        await asyncio.gather(*sends)
        assert await recv == want
        h = m.histograms["rpc.send_batch_frames"]
        assert h.max >= 2, "no multi-frame batch was coalesced"
        assert m.counters["rpc.bytes_sent"] == len(want)
        assert w.bytes_sent == len(want)
        # queue fully drained -> exported depth gauge back to zero
        assert m.gauges["rpc.send_queue_depth"] == 0
        assert "curvine_test_rpc_send_queue_depth" in m.prometheus_text()
    finally:
        await w.aclose()
        a.close()
        b.close()


async def test_queued_cancel_severs_at_frame_boundary():
    """PR-2 contract under coalescing: cancelling a QUEUED send drops
    the frame whole before any byte hits the wire — the stream stays
    parseable and the writer is NOT poisoned."""
    loop = asyncio.get_running_loop()
    a, b = _nb_socketpair()
    w = CoalescedWriter(a, loop, name="t")
    try:
        m1 = Message(code=5, req_id=1, header={"n": 1})
        m2 = Message(code=5, req_id=2, header={"n": 2})
        m3 = Message(code=5, req_id=3, header={"n": 3})
        async with w._io_lock:            # force the queue path
            t1 = asyncio.ensure_future(w.send(m1))
            t2 = asyncio.ensure_future(w.send(m2))
            await asyncio.sleep(0)
            assert w.qsize() == 2
            t2.cancel()                   # still queued: dropped whole
            await asyncio.sleep(0)
        await t1
        with pytest.raises(asyncio.CancelledError):
            await t2
        assert w.broken is None, "queued cancel must not poison"
        await w.send(m3)                  # connection still usable
        want = _frame_bytes(m1) + _frame_bytes(m3)
        assert await _drain(loop, b, len(want)) == want
        dec = BulkDecoder()
        dec._buf[:len(want)] = want       # stream parseable end-to-end
        dec._limit = len(want)
        assert dec.try_next()[1] == 1
        assert dec.try_next()[1] == 3
    finally:
        await w.aclose()
        a.close()
        b.close()


async def test_inline_cancel_mid_write_poisons():
    """The INLINE fast path keeps PR-2 poisoning: a cancel while bytes
    are mid-wire may leave a partial frame, so the writer must break
    and refuse further sends."""
    loop = asyncio.get_running_loop()
    a, b = _nb_socketpair()
    # tiny send buffer so a large inline send must block in sock_sendall
    a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 8 * 1024)
    broken = []
    w = CoalescedWriter(a, loop, inline_max=64 * 1024 * 1024,
                        on_broken=broken.append, name="t")
    try:
        big = Message(code=5, req_id=1, data=b"x" * (8 * 1024 * 1024))
        t = asyncio.ensure_future(w.send(big))
        for _ in range(20):               # let it enter the blocked write
            await asyncio.sleep(0)
        assert not t.done()
        t.cancel()
        with pytest.raises(asyncio.CancelledError):
            await t
        assert isinstance(w.broken, ConnectError)
        assert broken, "on_broken callback did not fire"
        with pytest.raises(ConnectError):
            await w.send(Message(code=5, req_id=2))
    finally:
        await w.aclose()
        a.close()
        b.close()


# ------------------------------------------------------- end to end


def _chunk(i: int, size: int) -> bytes:
    """Chunk ``i`` of a stream: every byte depends on its position, so a
    remainder that lands shifted or twice does not compare equal."""
    return (bytes(range(256)) * (size // 256 + 2))[i % 256:i % 256 + size]


async def _echo_server(metrics=None):
    srv = RpcServer("127.0.0.1", 0, "test")
    srv.metrics = metrics

    async def echo(msg, conn):
        return dict(msg.header), bytes(msg.data)
    srv.register(9_900, echo)

    async def stream(msg, conn):
        # CHUNK frames + EOF: at the default size several fit one recv
        n = int(msg.header.get("chunks", 4))
        size = int(msg.header.get("chunk_size", 1024))
        for i in range(n):
            await conn.send(Message(
                code=msg.code, req_id=msg.req_id,
                flags=Flags.RESPONSE | Flags.CHUNK,
                data=_chunk(i, size)))
        await conn.send(Message(code=msg.code, req_id=msg.req_id,
                                flags=Flags.RESPONSE | Flags.EOF))
        return None
    srv.register(9_901, stream)
    await srv.start()
    return srv


async def test_interleaved_chunk_sink_and_control_frames():
    """A sink-routed CHUNK stream and unary responses multiplexed on
    one connection: chunk payloads land in the sink view, control
    frames keep resolving, even when one bulk recv carries both."""
    m = MetricsRegistry("test")
    srv = await _echo_server(metrics=m)
    conn = await Connection(f"127.0.0.1:{srv.port}", metrics=m).connect()
    try:
        chunks = 6
        sink = bytearray(chunks * 1024)

        async def unary_storm():
            for i in range(32):
                rep = await conn.call(9_900, {"i": i}, data=b"d" * 64)
                assert rep.header["i"] == i
        storm = asyncio.ensure_future(unary_storm())
        got = await conn.call_readinto(9_901, memoryview(sink),
                                       header={"chunks": chunks})
        await storm
        assert got == chunks * 1024
        for i in range(chunks):
            assert sink[i * 1024:(i + 1) * 1024] == _chunk(i, 1024)
        # transport counters flowed on both peers
        assert m.counters["rpc.bytes_sent"] > 0
        assert m.counters["rpc.bytes_recv"] > 0
        text = m.prometheus_text()
        assert "curvine_test_rpc_bytes_sent" in text
        assert "curvine_test_rpc_bytes_recv" in text
        assert "curvine_test_rpc_send_batch_frames_count" in text
    finally:
        await conn.close()
        await srv.stop()


async def test_connection_survives_queued_cancel_end_to_end():
    """A cancelled in-flight call (prefetch teardown) on the queue path
    must leave the Connection usable for subsequent calls."""
    srv = await _echo_server()
    conn = await Connection(f"127.0.0.1:{srv.port}").connect()
    try:
        # force the queue path for the victim send by keeping the wire
        # busy with a concurrent burst
        burst = [asyncio.ensure_future(conn.call(9_900, {"i": i}))
                 for i in range(16)]
        victim = asyncio.ensure_future(conn.call(9_900, {"v": 1}))
        await asyncio.sleep(0)
        victim.cancel()
        try:
            await victim
        except asyncio.CancelledError:
            pass
        await asyncio.gather(*burst)
        assert not conn.closed
        rep = await conn.call(9_900, {"after": True})
        assert rep.header["after"] is True
    finally:
        await conn.close()
        await srv.stop()


async def test_server_rejects_oversized_frame_mid_stream():
    """A client that turns hostile mid-connection (good frames, then a
    giant length prefix) gets the connection torn down, not the
    process."""
    srv = await _echo_server()
    loop = asyncio.get_running_loop()
    sock = socket.socket()
    sock.setblocking(False)
    try:
        await loop.sock_connect(sock, ("127.0.0.1", srv.port))
        good = _frame_bytes(Message(code=9_900, req_id=1, header={"a": 1}))
        evil = LEN_PREFIX.pack(MAX_FRAME + 4096) + b"\x00" * FIXED_LEN
        await loop.sock_sendall(sock, good + evil)
        # the server tears the connection down (EOF to us) instead of
        # crashing or stalling
        while True:
            got = await asyncio.wait_for(loop.sock_recv(sock, 65536), 5)
            if not got:
                break                     # EOF: server closed on us
        # ... and keeps serving well-behaved clients
        conn = await Connection(f"127.0.0.1:{srv.port}").connect()
        try:
            rep = await conn.call(9_900, {"alive": 1})
            assert rep.header["alive"] == 1
        finally:
            await conn.close()
    finally:
        sock.close()
        await srv.stop()


# ------------------------------------------------ registered receive


def test_registered_pool_pinned_accounting_and_double_release():
    """Satellite-1 accounting contract: `pinned` tracks checked-out
    bytes cleared exactly once (release or view-GC, whichever first),
    `retained` is pool-resident bytes only, and a double release never
    parks the same region twice (which would hand one region to two
    concurrent acquirers)."""
    import gc
    MB = 1024 * 1024
    pool = transport_mod.RegisteredBuffers(max_bytes=2 * MB,
                                           min_size=64 * 1024,
                                           max_size=MB)
    cls = 128 * 1024                        # power-of-two class of 100K
    a = pool.acquire(100_000)
    assert pool.pinned == cls and pool.retained == 0
    pool.release(a)
    assert pool.pinned == 0 and pool.retained == cls
    pool.release(a)                         # double release: no-op
    assert pool.pinned == 0 and pool.retained == cls
    b = pool.acquire(100_000)
    c = pool.acquire(100_000)
    assert b.ctypes.data != c.ctypes.data, \
        "double release handed one region to two acquirers"
    assert pool.pinned == 2 * cls
    pool.release(b)
    pool.release(c)
    assert pool.pinned == 0
    # escaped buffer: GC unpins without ever re-entering the pool
    d = pool.acquire(100_000)
    retained = pool.retained                # after the checkout
    assert pool.pinned == cls
    del d
    gc.collect()
    assert pool.pinned == 0 and pool.retained == retained
    # release-then-GC must not double-decrement pinned
    e = pool.acquire(100_000)
    pool.release(e)
    del e
    gc.collect()
    assert pool.pinned == 0
    # stats() exposes the /metrics keys
    st = pool.stats()
    assert set(st) == {"registered_bytes", "pinned_bytes", "acquired",
                       "reused"}
    assert st["registered_bytes"] == pool.retained
    assert st["pinned_bytes"] == 0
    pool.drain()
    assert pool.retained == 0


@pytest.mark.parametrize("chunks,chunk_size", [
    (8, 1024),                    # several frames in one bulk recv
    (1, 256 * 1024),              # one frame the size of the recv buffer
    (1, 1024 * 1024 + 1),         # one frame, odd length, mostly remainder
])
async def test_large_sink_payload_end_to_end(chunks, chunk_size):
    """The data remainder of a reply frame is received straight into an
    aligned destination, byte-exact, whether the frame fits the bulk
    buffer or nearly all of it arrives after the envelope."""
    srv = await _echo_server()
    conn = await Connection(f"127.0.0.1:{srv.port}").connect()
    try:
        sink = alloc_aligned(chunks * chunk_size)
        assert sink.ctypes.data % 4096 == 0
        got = await conn.call_readinto(
            9_901, memoryview(sink),
            header={"chunks": chunks, "chunk_size": chunk_size})
        assert got == chunks * chunk_size
        for i in range(chunks):
            assert (bytes(sink[i * chunk_size:(i + 1) * chunk_size])
                    == _chunk(i, chunk_size))
    finally:
        await conn.close()
        await srv.stop()


async def test_peer_close_mid_sink_remainder_fails_the_read():
    """The peer dies with half of a sink remainder on the wire:
    call_readinto raises (the sink is never handed back as complete),
    the connection is closed, and the pool dials a new one for the next
    call, which is served whole."""
    size = 512 * 1024                    # twice the bulk recv buffer
    served = []

    async def serve(reader, writer):
        total, = LEN_PREFIX.unpack(await reader.readexactly(4))
        body = await reader.readexactly(total)
        _, code, req_id, *_ = decode_envelope(
            bytearray(LEN_PREFIX.pack(total) + body), 0, 4 + total)
        frame = _frame_bytes(Message(
            code=code, req_id=req_id, flags=Flags.RESPONSE | Flags.CHUNK,
            data=_chunk(0, size)))
        first = not served
        served.append(req_id)
        if first:
            writer.write(frame[:len(frame) // 2])
        else:
            writer.write(frame + _frame_bytes(Message(
                code=code, req_id=req_id,
                flags=Flags.RESPONSE | Flags.EOF)))
        await writer.drain()
        writer.close()

    srv = await asyncio.start_server(serve, "127.0.0.1", 0)
    addr = "127.0.0.1:%d" % srv.sockets[0].getsockname()[1]
    pool = ConnectionPool(size=1)
    try:
        conn = await pool.get(addr)
        sink = alloc_aligned(size)
        with pytest.raises(ConnectError):
            await conn.call_readinto(9_901, memoryview(sink))
        assert conn.closed
        assert bytes(sink) != _chunk(0, size)
        again = await pool.get(addr)
        assert again is not conn and not again.closed
        assert await again.call_readinto(9_901, memoryview(sink)) == size
        assert bytes(sink) == _chunk(0, size)
    finally:
        await pool.close()
        srv.close()
        await srv.wait_closed()
