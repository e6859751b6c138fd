"""Shared-memory block export: sealed memfds + SCM_RIGHTS hand-off.

The worker-side half of the shm short-circuit read plane
(docs/data-plane.md). For MEM-tier file-layout blocks the worker keeps
a table of sealed memfd copies, bounded in bytes: a block is copied on
its first grant and stays exported while it is resident and the table
has room. A co-located client that saw the ``shm``/``shm_sock``
capability flags on its GET_BLOCK_INFO probe connects to the unix side
channel, sends the block id, and receives the fd in SCM_RIGHTS
ancillary data — after which every read of the block is an mmap slice
with zero RPCs and zero copies.

Shape: HDFS short-circuit local reads (DfsClientShm / the
DomainSocket fd-passing plane), adapted to sealed memfds so the handed
fd is immutable by construction: F_SEAL_SHRINK|GROW|WRITE mean the
bytes a client mapped can never change under it, and eviction on the
worker merely closes OUR fd — client-held dups keep the pages alive
(the same unlink semantics the fd-based short-circuit path relies on).

asyncio cannot carry SCM_RIGHTS, so the side channel is a small
blocking AF_UNIX listener on a daemon thread; requests are one fixed
8-byte frame and replies one 16-byte frame, so a request is served in
microseconds and a thread per accepted connection stays cheap (clients
connect once per block, not per read)."""

from __future__ import annotations

import array
import logging
import os
import socket
import struct
import tempfile
import threading
import time

log = logging.getLogger(__name__)

# request: little-endian u64 block id.  reply: i8 status + 7 pad bytes
# + u64 block length; status 0 carries the fd in SCM_RIGHTS ancillary.
_REQ = struct.Struct("<Q")
_REP = struct.Struct("<b7xQ")
OK = 0
NOT_FOUND = 1
ERROR = 2

_SENDFILE_CHUNK = 8 * 1024 * 1024


def shm_supported() -> bool:
    """memfd_create + unix-socket fd passing: Linux, py3.8+."""
    return hasattr(os, "memfd_create") and hasattr(socket, "SCM_RIGHTS")


# what the MEM table's copies may cost in host memory, beside the tier
# and never more than the tier's capacity (worker/server.py): the 128
# blocks of 64 MiB the table held when it was bounded in entries alone
EXPORT_CAP_BYTES = 8 << 30

# an entry is an open fd, so a table is bounded in entries too: this
# share of the process's limit on open files, read once. Under the
# usual soft limit of 1,024 that is the 128 entries the table held when
# entries were its only bound
_FD_SHARE = 8


def _fd_limit() -> int:
    """The soft RLIMIT_NOFILE as it stands (it is left alone)."""
    import resource
    soft, _hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    return 1 << 20 if soft == resource.RLIM_INFINITY else soft


def entry_bound() -> int:
    """Entries a table may hold in this process by default."""
    return max(1, _fd_limit() // _FD_SHARE)


def channel_path(port: int) -> str:
    """Side-channel socket path: short (AF_UNIX caps sun_path at ~108
    bytes, so the worker's data dir — often a deep tmp path in tests —
    is not usable), unique per process+port."""
    return os.path.join(tempfile.gettempdir(),
                        f"cv-shm-{os.getpid()}-{port}.sock")


def _seal(fd: int) -> None:
    import fcntl
    seals = (fcntl.F_SEAL_SHRINK | fcntl.F_SEAL_GROW
             | fcntl.F_SEAL_WRITE | fcntl.F_SEAL_SEAL)
    fcntl.fcntl(fd, fcntl.F_ADD_SEALS, seals)


class ShmExporter:
    """Byte-bounded table of sealed-memfd block copies, LRU past the
    bound.

    A committed MEM-tier block is copied ONCE — its file's bytes
    sendfile'd into a memfd, then sealed — and stays exported for as
    long as it is resident and the table has room; every later grant of
    it is a ``dup``. ``export`` hands the caller an fd of its own (taken
    under the lock, so no eviction can close or recycle it on the way
    to the client); the caller closes it once it is sent. Eviction (LRU
    past ``cap_bytes``) and ``invalidate`` (block deleted or moved)
    close the table's fd only — dups already handed to clients stay
    valid. A block larger than the whole bound is copied, served and
    not kept. Thread-safe: called from the side channel's threads and
    the event loop.

    An entry is an open fd, and the process has a limit on those: the
    table is bounded in entries as well (``cap_entries``; by default
    ``entry_bound()``, an eighth of ``ulimit -n`` as the table is
    made), LRU past either bound, so many small blocks are held by
    their number before they are held by their bytes. The bound is the
    table's share, not a promise that the rest is free: fds are handed
    out lowest first, so a grant's own fd numbered n says at least n
    are open, and one numbered within half the table's bound of the
    limit finds a process about to run out (a restore that leaks its
    sockets: ROADMAP Speed 0). The table then closes what it holds and
    keeps nothing while that lasts — every grant a copy, as before
    there was a table — because a process out of fds loses its reads,
    the socket rung's with them.

    With ``metrics`` (the worker's registry) the table publishes what
    it costs where the tier's occupancy is: counters ``shm.exports``
    (copies made) and ``shm.export_evictions``, gauges
    ``shm.export_bytes`` and ``shm.export_entries``, all written under
    the table's lock."""

    def __init__(self, cap_bytes: int, metrics=None,
                 cap_entries: int | None = None):
        self.cap_bytes = max(0, cap_bytes)
        self.cap_entries = (entry_bound() if cap_entries is None
                            else max(1, cap_entries))
        self._fd_mark = _fd_limit() - self.cap_entries // 2
        self._lock = threading.Lock()
        # block_id -> (memfd, length); for this class dict order is the
        # LRU order (a subclass's policy may own the order instead)
        self._fds: dict[int, tuple[int, int]] = {}
        self.bytes = 0
        self.exports = 0        # memfd copies materialized
        self.hits = 0           # grants served from the table
        self.evictions = 0
        self._metrics = metrics
        self._publish_locked()

    def export(self, block_id: int, path: str, length: int,
               resident=None) -> tuple[int, int]:
        """(fd, length) for the block file at ``path``; the fd is the
        caller's to close. ``resident()`` is asked once after a new
        copy entered the table: a block deleted or moved while it was
        being copied had nothing to invalidate, so the entry is taken
        out again and the grant refused (LookupError)."""
        with self._lock:
            got = self._hit_locked(block_id)
        if got is not None:
            return got
        fd = self._copy_to_memfd(block_id, path, length)
        with self._lock:
            got = self._hit_locked(block_id)
            if got is not None:
                # raced with another grant: keep the first copy
                self._close(fd)
                return got
            self.exports += 1
            if length > self.cap_bytes or self._short_of_fds_locked(fd):
                # served, not kept: emptying the table for one block
                # that cannot stay would cost every other block a copy,
                # and a process short of fds is not given one more
                self._publish_locked()
                return fd, length
            try:
                mine = os.dup(fd)
            except OSError:
                self._close(fd)
                raise
            self._evict_locked(length)
            self._fds[block_id] = (fd, length)
            self.bytes += length
            self._admit_locked(block_id, length)
            self._publish_locked()
        if resident is not None and not resident():
            self.invalidate(block_id)
            self._close(mine)
            raise LookupError(f"block {block_id} left while exported")
        return mine, length

    def _hit_locked(self, block_id: int) -> tuple[int, int] | None:
        ent = self._fds.get(block_id)
        if ent is None:
            return None
        mine = os.dup(ent[0])
        self.hits += 1
        self._touch_locked(block_id)
        if self._short_of_fds_locked(mine):
            self._publish_locked()
        return mine, ent[1]

    def _short_of_fds_locked(self, fd: int) -> bool:
        """``fd`` was just handed out: at the mark or over it, give the
        table's own back (class docstring)."""
        if fd < self._fd_mark:
            return False
        for block_id in list(self._fds):
            self._remove_locked(block_id, evicted=True)
            self.evictions += 1
        return True

    # the policy: what a hit, an admission and a removal mean to the
    # eviction order, and the order itself
    def _touch_locked(self, block_id: int) -> None:
        self._fds[block_id] = self._fds.pop(block_id)

    def _admit_locked(self, block_id: int, length: int) -> None:
        pass

    def _dropped_locked(self, block_id: int, evicted: bool) -> None:
        pass

    def _victims_locked(self):
        """Victims in eviction order; each is removed before the next
        is asked for."""
        while self._fds:
            yield next(iter(self._fds))

    def _evict_locked(self, need: int) -> None:
        """Make room for one more entry of ``need`` bytes, closing
        victims in policy order."""
        def room() -> bool:
            return (self.bytes + need <= self.cap_bytes
                    and len(self._fds) < self.cap_entries)
        if room():
            return
        for victim in self._victims_locked():
            if room():
                break
            if self._remove_locked(victim, evicted=True):
                self.evictions += 1

    def _remove_locked(self, block_id: int, evicted: bool) -> bool:
        ent = self._fds.pop(block_id, None)
        if ent is None:
            return False
        self._close(ent[0])
        self.bytes -= ent[1]
        self._dropped_locked(block_id, evicted)
        return True

    def _publish_locked(self) -> None:
        m = self._metrics
        if m is None:
            return
        m.counters["shm.exports"] = self.exports
        m.counters["shm.export_evictions"] = self.evictions
        m.gauge("shm.export_bytes", self.bytes)
        m.gauge("shm.export_entries", len(self._fds))

    @staticmethod
    def _copy_to_memfd(block_id: int, path: str, length: int) -> int:
        src = os.open(path, os.O_RDONLY)
        try:
            fd = os.memfd_create(f"cv-blk-{block_id}",
                                 os.MFD_CLOEXEC | os.MFD_ALLOW_SEALING)
            try:
                os.ftruncate(fd, length)
                off = 0
                while off < length:
                    n = os.sendfile(fd, src, off,
                                    min(_SENDFILE_CHUNK, length - off))
                    if n == 0:
                        raise OSError(
                            f"short copy of block {block_id}: "
                            f"{off}/{length}")
                    off += n
                _seal(fd)
            except OSError:
                os.close(fd)
                raise
            return fd
        finally:
            os.close(src)

    @staticmethod
    def _close(fd: int) -> None:
        try:
            os.close(fd)
        except OSError:
            pass

    def invalidate(self, block_id: int) -> None:
        """Block deleted or moved tiers: drop its copy (a plain
        removal, not an eviction — the block is gone)."""
        with self._lock:
            if self._remove_locked(block_id, evicted=False):
                self._publish_locked()

    def __contains__(self, block_id: int) -> bool:
        with self._lock:
            return block_id in self._fds

    def __len__(self) -> int:
        with self._lock:
            return len(self._fds)

    def stats(self) -> dict:
        with self._lock:
            return {"entries": len(self._fds), "bytes": self.bytes,
                    "exports": self.exports, "hits": self.hits,
                    "evictions": self.evictions}

    def close(self) -> None:
        with self._lock:
            for block_id in list(self._fds):
                self._remove_locked(block_id, evicted=False)
            self._publish_locked()


class WarmShmCache(ShmExporter):
    """The same table for blocks BELOW the MEM tier, under another
    policy (docs/data-plane.md).

    A read-hot SSD/HDD block (heat over ``worker.shm_warm_min_reads``,
    accumulated through the SC_READ_REPORT rail) gets its bytes copied
    once into a sealed memfd; from then on co-located clients serve it
    exactly like a MEM export — zero RPCs, zero syscalls per read. The
    bound (``worker.shm_warm_cap_mb``) is small because warm copies are
    anonymous memory no tier accounts for, and eviction runs through
    the same admission policy family as the MEM tier (S3-FIFO by
    default): a one-touch scan that sneaks a copy in leaves through the
    probationary queue without displacing the warm working set. A block
    larger than the whole cache is refused, not served from a copy made
    each time."""

    def __init__(self, cap_bytes: int, admission: str = "s3fifo",
                 ghost_entries: int = 1024):
        from curvine_tpu.common.cache import make_policy
        super().__init__(cap_bytes)
        self.policy = make_policy(admission, ghost_entries=ghost_entries)
        self._atime: dict[int, float] = {}

    def export(self, block_id: int, path: str, length: int,
               resident=None) -> tuple[int, int]:
        if length > self.cap_bytes:
            raise LookupError(
                f"block {block_id} ({length}B) exceeds warm cache")
        return super().export(block_id, path, length, resident)

    def _touch_locked(self, block_id: int) -> None:
        self._atime[block_id] = time.time()
        self.policy.hits += 1
        self.policy.on_access(block_id)

    def _admit_locked(self, block_id: int, length: int) -> None:
        self._atime[block_id] = time.time()
        self.policy.on_admit(block_id, length)

    def _dropped_locked(self, block_id: int, evicted: bool) -> None:
        self._atime.pop(block_id, None)
        self.policy.on_remove(block_id, evicted=evicted)

    def _victims_locked(self):
        """S3-FIFO: probationary one-touch copies first; insertion
        order for whatever the policy does not name."""
        yield from self.policy.victim_order(
            [(k, self._atime.get(k, 0.0)) for k in self._fds])
        yield from super()._victims_locked()

    def stats(self) -> dict:
        out = super().stats()
        out.update({f"policy_{k}": v for k, v in self.policy.stats().items()})
        return out


class ShmChannel:
    """AF_UNIX SCM_RIGHTS side channel serving block fds.

    ``grant(block_id) -> (fd, length)`` is the server's policy hook
    (resolve the block, check the tier, export through the
    ShmExporter); it runs on the channel's threads, so it must only
    touch thread-safe state (BlockStore and ShmExporter both take their
    own locks). The fd it returns is the channel's: closed once the
    reply has carried it (SCM_RIGHTS installs the receiver's own)."""

    def __init__(self, path: str, grant):
        self.path = path
        self.grant = grant
        self._srv: socket.socket | None = None
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()

    def start(self) -> None:
        try:
            os.unlink(self.path)
        except OSError:
            pass
        srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        try:
            srv.bind(self.path)
            srv.listen(64)
        except OSError:
            srv.close()
            raise
        self._srv = srv
        self._thread = threading.Thread(
            target=self._accept_loop, name="shm-channel", daemon=True)
        self._thread.start()

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._srv.accept()
            except OSError:
                break                    # listener closed (stop)
            t = threading.Thread(target=self._serve, args=(conn,),
                                 daemon=True)
            t.start()

    def _serve(self, conn: socket.socket) -> None:
        """One client connection: fixed-size request/reply frames until
        EOF (clients typically fetch one fd per connection)."""
        with conn:
            conn.settimeout(5.0)
            while not self._stop.is_set():
                try:
                    req = self._recv_exact(conn, _REQ.size)
                except OSError:
                    return
                if req is None:
                    return               # clean EOF
                (block_id,) = _REQ.unpack(req)
                try:
                    fd, length = self.grant(block_id)
                except LookupError:
                    self._reply(conn, NOT_FOUND, 0, None)
                    continue
                except Exception as e:  # noqa: BLE001 — keep serving
                    log.debug("shm grant for %d failed: %s", block_id, e)
                    self._reply(conn, ERROR, 0, None)
                    continue
                try:
                    sent = self._reply(conn, OK, length, fd)
                finally:
                    os.close(fd)
                if not sent:
                    return

    @staticmethod
    def _recv_exact(conn: socket.socket, n: int) -> bytes | None:
        buf = b""
        while len(buf) < n:
            got = conn.recv(n - len(buf))
            if not got:
                return None if not buf else buf
            buf += got
        return buf

    @staticmethod
    def _reply(conn: socket.socket, status: int, length: int,
               fd: int | None) -> bool:
        anc = []
        if fd is not None:
            anc = [(socket.SOL_SOCKET, socket.SCM_RIGHTS,
                    array.array("i", [fd]))]
        try:
            conn.sendmsg([_REP.pack(status, length)], anc)
            return True
        except OSError:
            return False

    def stop(self) -> None:
        self._stop.set()
        srv, self._srv = self._srv, None
        if srv is not None:
            # close() alone does NOT wake a thread blocked in accept()
            # on Linux; shutdown() forces accept to return so the join
            # below is immediate instead of eating its timeout
            try:
                srv.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                srv.close()
            except OSError:
                pass
        try:
            os.unlink(self.path)
        except OSError:
            pass
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=2.0)


def fetch_block_fd(sock_path: str, block_id: int,
                   timeout: float = 5.0) -> tuple[int, int]:
    """Client half: connect to the worker's side channel, request one
    block, return (fd, length). Blocking — run under asyncio.to_thread.
    Raises LookupError when the worker no longer serves the block and
    OSError on channel trouble (both are clean fallbacks to the socket
    read path)."""
    with socket.socket(socket.AF_UNIX, socket.SOCK_STREAM) as s:
        s.settimeout(timeout)
        s.connect(sock_path)
        return _ask(s, block_id)


def _ask(s: socket.socket, block_id: int) -> tuple[int, int]:
    """One request and its reply over a connection to a side channel."""
    s.sendall(_REQ.pack(block_id))
    got = _answer(s, block_id)
    if isinstance(got, Exception):
        raise got
    return got


def _answer(s: socket.socket, block_id: int) -> tuple[int, int] | Exception:
    """The next reply on a connection, the one to the request for
    `block_id` → (fd, length), or the refusal the worker answered with
    (LookupError: not served; OSError: the grant failed), returned and
    not raised. Raises OSError where the channel itself fails."""
    data, anc, _flags, _addr = s.recvmsg(
        _REP.size, socket.CMSG_SPACE(array.array("i").itemsize))
    if len(data) < _REP.size:
        raise ConnectionResetError("shm channel closed mid-reply")
    status, length = _REP.unpack(data)
    fds = array.array("i")
    for level, ctype, cdata in anc:
        if level == socket.SOL_SOCKET and ctype == socket.SCM_RIGHTS:
            fds.frombytes(cdata[:len(cdata)
                                - (len(cdata) % fds.itemsize)])
    if status == NOT_FOUND:
        for fd in fds:
            os.close(fd)
        return LookupError(f"block {block_id} not shm-served")
    if status != OK or not fds:
        for fd in fds:
            os.close(fd)
        return OSError(f"shm grant failed (status {status})")
    fd = fds[0]
    for extra in list(fds)[1:]:
        os.close(extra)
    return fd, length


class ShmConns:
    """Connections to workers' side channels that stay open between
    grants, for a client whose fetch threads ask often. Over a kept
    connection a grant is a request and a reply; a new one a grant also
    costs the worker an accept and a thread of its own, and on a host
    with several cores every one of those hand-overs is paid in waits
    for the interpreter (PERF.md §6). One fetch thread at a time takes a
    connection and puts it back, and may ask for many blocks at once
    over it (`pipeline`): the worker answers a connection's requests one
    after another, in order. The worker closes a connection it has not
    heard from for 5 s, so a channel that fails under a batch is asked
    again, once, on a new connection."""

    def __init__(self):
        self._idle: dict[str, list[socket.socket]] | None = {}
        self._lock = threading.Lock()

    def pipeline(self, sock_path: str, block_ids: list[int],
                 timeout: float = 5.0):
        """Grants of `block_ids`, their requests sent at once over one
        connection → yields each block's answer, in order, as it is
        read: (fd, length), or the exception its grant failed with
        (returned, not raised: LookupError where the worker does not
        serve the block, OSError where the grant or the channel failed).
        Where the channel fails, the blocks not answered yet are asked
        again on a new connection, once; after that each gets the error.
        Every fd yielded is the caller's. A generator left before its
        end closes its connection: answers are still on the way."""
        left = list(block_ids)
        s = None
        with self._lock:
            idle = (self._idle or {}).get(sock_path)
            if idle:
                s = idle.pop()
        tries = 2
        try:
            while left:
                try:
                    if s is None:
                        s = self._dial(sock_path, timeout)
                    s.sendall(b"".join(_REQ.pack(b) for b in left))
                    while left:
                        got = _answer(s, left[0])
                        left.pop(0)
                        yield got
                except OSError as e:
                    if s is not None:
                        s.close()
                        s = None
                    tries -= 1
                    while left and not tries:
                        left.pop(0)
                        yield e
        finally:
            if s is not None:
                if left:
                    s.close()
                else:
                    self._keep(sock_path, s)

    @staticmethod
    def _dial(sock_path: str, timeout: float) -> socket.socket:
        s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        try:
            s.settimeout(timeout)
            s.connect(sock_path)
        except OSError:
            s.close()
            raise
        return s

    def _keep(self, sock_path: str, s: socket.socket) -> None:
        with self._lock:
            if self._idle is not None:
                self._idle.setdefault(sock_path, []).append(s)
                return
        s.close()                        # the client closed meanwhile

    def close(self) -> None:
        with self._lock:
            idle, self._idle = self._idle, None
        for conns in (idle or {}).values():
            for s in conns:
                s.close()
