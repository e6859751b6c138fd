"""ConnectionPool (rpc/client.py): a pool of `size` is at most `size`
sockets an address — open and being dialled together — whatever burst
meets it cold. Callers that find it short share the open connections
and the dials in flight; the dials are the pool's own tasks, so a
caller's cancellation ends nothing but itself, a failed dial fails its
waiters as their own would have, and `close()` leaves neither a socket
nor a task. Every case runs against a real RpcServer on loopback and
under its own time limit."""

import asyncio
import functools
import gc
import os
import socket

import pytest

from curvine_tpu.common.errors import ConnectError
from curvine_tpu.rpc import RpcServer
from curvine_tpu.rpc.client import Connection, ConnectionPool

ECHO = 9_900


def _fd_count() -> int:
    """Fds the process holds, with nothing unreachable among them: an
    earlier test file's garbage (sockets in reference cycles) collected
    while a case runs would read as fds this pool gave back."""
    gc.collect()
    return len(os.listdir("/proc/self/fd"))


def _limited(seconds: float):
    """The case's own time limit (no pytest-timeout in the image)."""
    def deco(fn):
        @functools.wraps(fn)
        async def run(*a, **kw):
            await asyncio.wait_for(fn(*a, **kw), seconds)
        return run
    return deco


async def _echo_server(port: int = 0) -> RpcServer:
    srv = RpcServer("127.0.0.1", port, "pool-test")

    async def echo(msg, conn):
        return dict(msg.header), bytes(msg.data)
    srv.register(ECHO, echo)
    srv.accepts = 0
    accept = srv._conn_loop

    async def counted(conn):
        srv.accepts += 1
        await accept(conn)
    srv._conn_loop = counted
    await srv.start()
    return srv


async def _settled(srv: RpcServer, tasks: set, fds: int) -> None:
    """After pool.close(): the server's side of each connection ends
    when its loop reads the EOF, a turn or two later. Then the process
    holds the tasks and the fds it held before the pool dialled."""
    for _ in range(200):
        if not srv._conns and asyncio.all_tasks() == tasks:
            break
        await asyncio.sleep(0.01)
    gc.collect()
    assert asyncio.all_tasks() == tasks
    assert _fd_count() == fds


def _held(pool: ConnectionPool, addr: str) -> int:
    """Sockets the pool answers for: open, and being dialled."""
    return (sum(not c.closed for c in pool._conns.get(addr, ()))
            + sum(not t.done() for t in pool._dials.get(addr, ())))


def _gate_connect(monkeypatch):
    """Park every dial before its socket exists until the gate opens."""
    gate = asyncio.Event()
    connect = Connection.connect

    async def gated(self):
        await gate.wait()
        return await connect(self)
    monkeypatch.setattr(Connection, "connect", gated)
    return gate


@pytest.mark.parametrize("n", [24, 915])
@_limited(60)
async def test_cold_burst_dials_size_and_close_leaves_nothing(n):
    """n concurrent callers meet a cold pool of 4: four dials, four
    accepts, everyone else joins; every call is answered; close()
    gives back every fd and every task."""
    srv = await _echo_server()
    tasks, fds = asyncio.all_tasks(), _fd_count()
    counters: dict = {}
    pool = ConnectionPool(size=4, counters=counters)
    peak = 0

    async def one(i: int):
        nonlocal peak
        conn = await pool.get(srv.addr)
        peak = max(peak, _held(pool, srv.addr))
        rep = await conn.call(ECHO, {"i": i})
        assert rep.header["i"] == i
        return conn

    conns = await asyncio.gather(*(one(i) for i in range(n)))
    assert counters == {"rpc.dials": 4, "rpc.dial_joins": n - 4}
    assert srv.accepts == 4 and len(srv._conns) == 4
    assert peak <= 4 and _held(pool, srv.addr) == 4
    assert len({id(c) for c in conns}) <= 4
    assert _fd_count() == fds + 8            # 4 here, 4 in the server
    # warm: the steady state counts nothing and dials nothing
    assert (await (await pool.get(srv.addr)).call(ECHO, {})).header == {}
    assert counters == {"rpc.dials": 4, "rpc.dial_joins": n - 4}
    await pool.close()
    assert not pool._conns and not pool._dials
    await _settled(srv, tasks, fds)
    await srv.stop()


@_limited(30)
async def test_failed_dial_fails_every_waiter_and_next_get_dials_anew():
    """Nobody listens: every caller that waited on the dials gets the
    ConnectError its own dial would have got (retryable, so
    RetryPolicy reads it as before), none waits on a dead dial, and
    once a server is there the next get succeeds."""
    lsock = socket.socket()
    lsock.bind(("127.0.0.1", 0))
    port = lsock.getsockname()[1]
    lsock.close()
    addr = f"127.0.0.1:{port}"
    counters: dict = {}
    pool = ConnectionPool(size=4, counters=counters)
    got = await asyncio.gather(*(pool.get(addr) for _ in range(12)),
                               return_exceptions=True)
    assert all(isinstance(e, ConnectError) and e.retryable for e in got)
    assert len({id(e) for e in got}) == 12       # each caller its own
    assert "connect " + addr in str(got[0])
    assert counters == {"rpc.dials": 4, "rpc.dial_joins": 8}
    await asyncio.sleep(0)                       # the other three end
    assert _held(pool, addr) == 0
    srv = await _echo_server(port)
    try:
        conn = await pool.get(addr)
        assert not conn.closed
        assert (await conn.call(ECHO, {"ok": 1})).header == {"ok": 1}
        assert counters["rpc.dials"] == 5
    finally:
        await pool.close()
        await srv.stop()


@_limited(30)
async def test_cancelled_caller_cancels_nothing_but_itself(monkeypatch):
    """The first caller started the dial everyone waits on; its
    wait_for fires mid-dial. The dial goes on, the others are served,
    and close() leaves nothing: there is no orphan to close."""
    srv = await _echo_server()
    tasks, fds = asyncio.all_tasks(), _fd_count()
    gate = _gate_connect(monkeypatch)
    counters: dict = {}
    pool = ConnectionPool(size=4, counters=counters)
    first = asyncio.ensure_future(
        asyncio.wait_for(pool.get(srv.addr), 0.05))
    rest = [asyncio.ensure_future(pool.get(srv.addr)) for _ in range(7)]
    with pytest.raises(asyncio.TimeoutError):
        await first
    assert _held(pool, srv.addr) == 4            # its dial lives on
    assert not any(t.done() for t in rest)
    gate.set()
    conns = await asyncio.gather(*rest)
    for c in conns:
        assert (await c.call(ECHO, {"a": 1})).header == {"a": 1}
    await asyncio.sleep(0.05)
    assert _held(pool, srv.addr) == 4 == srv.accepts
    assert counters == {"rpc.dials": 4, "rpc.dial_joins": 4}
    await pool.close()
    await _settled(srv, tasks, fds)
    await srv.stop()


@_limited(30)
async def test_close_during_a_dial_leaves_no_socket():
    """close() ends the dials in flight — here parked inside
    sock_connect, their sockets made — and closes those sockets; a
    caller still waiting is told the pool closed, not cancelled."""
    srv = await _echo_server()
    tasks, fds = asyncio.all_tasks(), _fd_count()
    loop = asyncio.get_running_loop()
    parked = asyncio.Event()
    in_flight: list[socket.socket] = []

    async def never_connects(sock, address):
        in_flight.append(sock)
        parked.set()
        await asyncio.Event().wait()
    loop.sock_connect = never_connects           # this run's loop only
    pool = ConnectionPool(size=2)
    waiters = [asyncio.ensure_future(pool.get(srv.addr)) for _ in range(5)]
    await parked.wait()
    await asyncio.sleep(0)
    assert len(in_flight) == 2 == _held(pool, srv.addr)
    assert _fd_count() == fds + 2
    await pool.close()
    assert all(s.fileno() == -1 for s in in_flight)
    got = await asyncio.gather(*waiters, return_exceptions=True)
    assert all(isinstance(e, ConnectError) and "pool closed" in str(e)
               for e in got)
    del loop.sock_connect
    assert srv.accepts == 0
    await _settled(srv, tasks, fds)
    # the pool is as new: a get after close() dials again
    conn = await pool.get(srv.addr)
    assert (await conn.call(ECHO, {"b": 2})).header == {"b": 2}
    await pool.close()
    await srv.stop()


@_limited(30)
async def test_poisoned_connection_is_replaced_within_size():
    """A connection found closed is pruned and one dial replaces it;
    callers meanwhile share the healthy one; never more than size."""
    srv = await _echo_server()
    counters: dict = {}
    pool = ConnectionPool(size=2, counters=counters)
    a = await pool.get(srv.addr)
    assert await pool.get(srv.addr) is a         # served at once, while
    await asyncio.sleep(0.05)                    # its dial fills the pool
    a, b = pool._conns[srv.addr]
    assert a is not b and counters == {"rpc.dials": 2}
    a._on_send_broken(OSError("poisoned"))
    assert a.closed
    peak = 0

    async def one():
        nonlocal peak
        conn = await pool.get(srv.addr)
        peak = max(peak, _held(pool, srv.addr))
        assert conn is not a and not conn.closed
        assert (await conn.call(ECHO, {"c": 3})).header == {"c": 3}
        return conn

    got = await asyncio.gather(*(one() for _ in range(10)))
    assert got[0] is b                           # served at once
    assert peak <= 2
    # the poisoned socket's fd number may be handed to the new dial with
    # the old reader still registered: its first attempt is then refused
    # (ENOENT) and _dial tries again after its backoff
    for _ in range(100):
        if len(pool._conns[srv.addr]) == 2:
            break
        await asyncio.sleep(0.02)
    assert _held(pool, srv.addr) == 2 == len(pool._conns[srv.addr])
    assert counters == {"rpc.dials": 3, "rpc.dial_joins": 9}
    await pool.close()
    await a.close()
    await srv.stop()


@_limited(30)
async def test_hooks_set_during_a_dial_reach_the_connection(monkeypatch):
    srv = await _echo_server()
    gate = _gate_connect(monkeypatch)
    pool = ConnectionPool(size=1)
    getting = asyncio.ensure_future(pool.get(srv.addr))
    await asyncio.sleep(0.01)
    assert _held(pool, srv.addr) == 1 and not getting.done()
    seen = []

    async def hook(addr, msg):
        seen.append(msg.code)
        return True

    def on_push(msg):
        pass
    pool.set_fault_hook(hook)
    pool.set_push_handler(on_push)
    gate.set()
    conn = await getting
    assert conn.fault_hook is hook and conn.on_push is on_push
    await conn.call(ECHO, {})
    assert seen == [ECHO]
    await pool.close()
    await srv.stop()


@_limited(120)
async def test_client_burst_of_opens_leaks_no_fd(tmp_path):
    """64 concurrent open + close through a new CurvineClient, three
    clients in turn (the shape of a restore: two cold pools a client):
    at most eight dials a client, the rest joined, and the process's
    fds after each client.close() where they were before it."""
    from curvine_tpu.common.conf import ClusterConf
    from curvine_tpu.testing import MiniCluster
    conf = ClusterConf()
    conf.data_dir = str(tmp_path)
    async with MiniCluster(workers=1, conf=conf, base_dir=str(tmp_path),
                           block_size=256 * 1024) as mc:
        w = mc.client()
        await w.meta.mkdir("/burst")
        for i in range(64):
            await w.write_all(f"/burst/{i}.bin", bytes([i]) * 4096)
        await w.close()
        size = conf.client.conn_pool_size

        async def one(c, i):
            r = await c.open(f"/burst/{i}.bin")
            data = await r.read_all()
            await r.close()
            assert bytes(data) == bytes([i]) * 4096

        async def burst():
            c = mc.client()
            await asyncio.gather(*(one(c, i) for i in range(64)))
            got = dict(c.counters)
            await c.close()
            return got

        await burst()                # exports made, the worker's pools up
        for _ in range(3):
            await asyncio.sleep(0.1)
            gc.collect()
            fds = _fd_count()
            got = await burst()
            assert got["read.files"] == 64
            assert 2 <= got["rpc.dials"] <= 2 * size
            assert got["rpc.dial_joins"] >= 64 - size
            for _ in range(100):     # the servers' sides read the EOFs
                if abs(_fd_count() - fds) <= 4:
                    break
                await asyncio.sleep(0.01)
            gc.collect()
            assert abs(_fd_count() - fds) <= 4
