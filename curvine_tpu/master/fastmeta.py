"""Native metadata read plane (csrc/meta_mirror.cc → libcurvine_meta.so).

The master's hot read-only RPCs (FILE_STATUS, EXISTS) are served by C++
threads from a mirror of the inode tree, on a separate fast port that
speaks the normal wire protocol. Python remains the single writer: the
``MirroredStore`` wrapper below intercepts the MetaStore mutation
surface (put/remove/child_put/child_remove) and pushes each committed
change into the mirror — buffered per journal entry for the KV store
(flush on commit_applied/commit_runtime, dropped on rollback), eager for
the mem store (whose applies are eager and rollback-free too). The
mirror therefore always reflects exactly the state a Python-served read
would see between journal entries.

The fast server answers only what it can answer authoritatively; every
other case (absent path that a mounted UFS might resolve, gated-off
non-leader, unknown op) returns ErrorCode.FAST_MISS and the client
falls back to the Python port.

Parity: the reference serves its 100K+ QPS headline from multithreaded
Rust (curvine-server/src/master/master_handler.rs); this is the
rebuild's native read plane over the Python mutation plane.
"""

from __future__ import annotations

import ctypes
import logging

import msgpack

log = logging.getLogger(__name__)

_lib = None
_tried = False

c_i64 = ctypes.c_int64
c_ll = ctypes.c_longlong


def _load():
    global _lib, _tried
    if _tried:
        return _lib
    _tried = True
    from curvine_tpu.common import native
    so = native.build("libcurvine_meta.so")
    if so is None:
        return None
    lib = ctypes.CDLL(so)
    lib.mm_new.restype = ctypes.c_void_p
    lib.mm_new.argtypes = [ctypes.c_int, ctypes.c_char_p, ctypes.c_char_p]
    lib.mm_free.argtypes = [ctypes.c_void_p]
    lib.mm_stop.argtypes = [ctypes.c_void_p]
    lib.mm_clear.argtypes = [ctypes.c_void_p]
    lib.mm_put.argtypes = [
        ctypes.c_void_p, c_i64, c_i64, ctypes.c_int, c_i64, c_i64,
        ctypes.c_int, ctypes.c_char_p, ctypes.c_char_p, c_i64, c_i64,
        ctypes.c_int, ctypes.c_int, ctypes.c_int, c_i64, ctypes.c_char_p,
        ctypes.c_char_p, ctypes.c_int, ctypes.c_int, c_ll, ctypes.c_int,
        c_ll, ctypes.c_int, ctypes.c_char_p]
    lib.mm_remove.argtypes = [ctypes.c_void_p, c_i64]
    lib.mm_child_put.argtypes = [ctypes.c_void_p, c_i64, ctypes.c_char_p,
                                 c_i64]
    lib.mm_child_remove.argtypes = [ctypes.c_void_p, c_i64, ctypes.c_char_p]
    lib.mm_mount_add.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.mm_mount_remove.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.mm_serve.restype = ctypes.c_int
    lib.mm_serve.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int]
    lib.mm_fleet_attach.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.mm_set_serving.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.mm_counter.restype = ctypes.c_ulonglong
    lib.mm_counter.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.mm_bench_stat.restype = ctypes.c_double
    lib.mm_bench_stat.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                  ctypes.c_char_p, ctypes.c_char_p,
                                  ctypes.c_int, ctypes.c_int]
    _lib = lib
    return lib


def available() -> bool:
    return _load() is not None


def bench_stat(host: str, port: int, path: str, user: str = "root",
               n: int = 100_000, pipeline: int = 64) -> float:
    """Pipelined native stat storm against a fast port; returns QPS."""
    lib = _load()
    if lib is None:
        raise RuntimeError("libcurvine_meta.so not built")
    qps = lib.mm_bench_stat(host.encode(), port, path.encode(),
                            user.encode(), n, pipeline)
    if qps < 0:
        raise RuntimeError(f"fast-path bench failed (rc={qps})")
    return qps


class FastMeta:
    """One native mirror + its serve loop."""

    def __init__(self, acl_enabled: bool = True, superuser: str = "root",
                 supergroup: str = "supergroup"):
        lib = _load()
        if lib is None:
            raise RuntimeError("libcurvine_meta.so not built")
        self._lib = lib
        self._h = lib.mm_new(1 if acl_enabled else 0, superuser.encode(),
                             supergroup.encode())
        self.port: int | None = None
        # sharded fleet (router front only): member mirrors this front
        # routes to, in shard order — see fleet_attach
        self.members: list["FastMeta"] = []

    def close(self) -> None:
        if self._h:
            self._lib.mm_free(self._h)
            self._h = None

    def stop_serving(self) -> None:
        """Join the native serve threads without freeing the mirror.
        A sharded router MUST call this before stopping the shard fleet:
        the front's threads read the member mirrors' memory."""
        if self._h:
            self._lib.mm_stop(self._h)

    # ---- mirror maintenance (single writer: the master actor loop) ----
    # Every method no-ops after close(): the MirroredStore wrapper keeps
    # feeding mutations even if the serve plane was disabled at startup.

    def put_inode(self, node) -> None:
        if not self._h:
            return
        x = msgpack.packb(node.x_attr, use_bin_type=True) if node.x_attr \
            else b""
        sp = node.storage_policy
        self._lib.mm_put(
            self._h, node.id, node.parent_id, int(node.file_type),
            node.mtime, node.atime, node.mode, node.owner.encode(),
            node.group.encode(), node.len, node.block_size, node.replicas,
            1 if node.is_complete else 0, node.nlink, node.children_num,
            node.target.encode() if node.target is not None else None,
            x, len(x), int(sp.storage_type), sp.ttl_ms,
            int(sp.ttl_action), sp.ufs_mtime, int(sp.state),
            sp.ec.encode())

    def remove_inode(self, inode_id: int) -> None:
        if self._h:
            self._lib.mm_remove(self._h, inode_id)

    def child_put(self, parent_id: int, name: str, child_id: int) -> None:
        if self._h:
            self._lib.mm_child_put(self._h, parent_id, name.encode(),
                                   child_id)

    def child_remove(self, parent_id: int, name: str) -> None:
        if self._h:
            self._lib.mm_child_remove(self._h, parent_id, name.encode())

    def mount_add(self, cv_path: str) -> None:
        if self._h:
            self._lib.mm_mount_add(self._h, cv_path.encode())

    def mount_remove(self, cv_path: str) -> None:
        if self._h:
            self._lib.mm_mount_remove(self._h, cv_path.encode())

    def clear(self) -> None:
        if self._h:
            self._lib.mm_clear(self._h)

    def load_from_store(self, store) -> None:
        """Bulk (re)load — called before enabling serving, on the master
        actor loop, so the store is quiescent."""
        self.clear()
        for node in store.iter_inodes():
            self.put_inode(node)
        for pid, name, cid in store.iter_children_all():
            self.child_put(pid, name, cid)
        for wire in store.iter_mounts():
            self.mount_add(wire["cv_path"])

    def fleet_attach(self, member: "FastMeta") -> None:
        """Sharded namespace: route this (router front) mirror's reads
        to `member`'s data by crc32(parent) % n — the same partition
        function the Python router uses (master/sharding.py shard_of).
        Attach every member BEFORE serve(); members must outlive this
        mirror's serve threads (stop_serving before the fleet stops)."""
        self._lib.mm_fleet_attach(self._h, member._h)
        self.members.append(member)

    # ---- serving control ----

    def serve(self, host: str, port: int = 0) -> int:
        rc = self._lib.mm_serve(self._h, host.encode(), port)
        if rc < 0:
            raise RuntimeError(f"fast meta serve failed on {host}:{port}")
        self.port = rc
        return rc

    def set_serving(self, on: bool) -> None:
        self._lib.mm_set_serving(self._h, 1 if on else 0)

    def counters(self) -> dict:
        out = {"inodes": self._lib.mm_counter(self._h, 0),
               "served": self._lib.mm_counter(self._h, 1),
               "fallbacks": self._lib.mm_counter(self._h, 2),
               "denied": self._lib.mm_counter(self._h, 3)}
        if self.members:
            # per-shard fast hits: the front bumps the owning member's
            # served counter on every routed answer
            out["shard_hits"] = [int(m._lib.mm_counter(m._h, 1))
                                 for m in self.members]
        return out


class MirroredStore:
    """MetaStore decorator that replicates the inode/dentry mutation
    stream into a FastMeta mirror with the store's commit semantics."""

    def __init__(self, inner, mirror: FastMeta):
        self._inner = inner
        self._mirror = mirror
        # mem-store applies are eager and rollback() is a no-op, so the
        # mirror must track it eagerly too; the KV store's pending
        # overlay commits per journal entry, so buffer until then
        self._eager = inner.kind == "mem"
        self._buf: list[tuple] = []        # current entry's mirror ops
        self._staged_buf: list[tuple] = []  # earlier group entries' ops
        # bind the hot read-only delegates once: path resolution calls
        # get/child_get per component, and __getattr__ dispatch is
        # measurable at namespace-bench rates
        for m in ("get", "child_get", "children_of", "get_counter",
                  "set_counter", "bump_counter"):
            if hasattr(inner, m):
                setattr(self, m, getattr(inner, m))

    # -- attribute passthrough (blocks, mounts, jobs, counters, ...) --
    def __getattr__(self, name):
        return getattr(self._inner, name)

    @property
    def kind(self):
        return self._inner.kind

    # -- intercepted mutations --
    def _op(self, op: tuple) -> None:
        if self._eager:
            self._apply_one(op)
        else:
            self._buf.append(op)

    def _apply_one(self, op: tuple) -> None:
        kind = op[0]
        if kind == "put":
            node = op[1]
            if isinstance(node, int):       # kv mode: id capture
                node = self._inner.get(node)
                if node is None:            # deleted later in the group
                    return
            self._mirror.put_inode(node)
        elif kind == "del":
            self._mirror.remove_inode(op[1])
        elif kind == "cput":
            self._mirror.child_put(op[1], op[2], op[3])
        elif kind == "cdel":
            self._mirror.child_remove(op[1], op[2])
        elif kind == "mput":
            self._mirror.mount_add(op[1])
        elif kind == "mdel":
            self._mirror.mount_remove(op[1])

    def put(self, inode, new: bool = False) -> None:
        self._inner.put(inode, new=new)
        # kv mode captures only the id: _flush runs after commit_applied,
        # so reading the inode back from the inner store yields exactly
        # the committed state — no per-put copy (a buffered object
        # reference could be mutated by a later failed apply), and puts
        # of the same inode dedupe naturally
        self._op(("put", inode if self._eager else inode.id))

    def remove(self, inode_id: int) -> None:
        self._inner.remove(inode_id)
        self._op(("del", inode_id))

    def child_put(self, parent_id: int, name: str, child_id: int) -> None:
        self._inner.child_put(parent_id, name, child_id)
        self._op(("cput", parent_id, name, child_id))

    def child_remove(self, parent_id: int, name: str) -> None:
        self._inner.child_remove(parent_id, name)
        self._op(("cdel", parent_id, name))

    def mount_put(self, cv_path: str, wire: dict) -> None:
        self._inner.mount_put(cv_path, wire)
        self._op(("mput", cv_path))

    def mount_remove(self, cv_path: str) -> None:
        self._inner.mount_remove(cv_path)
        self._op(("mdel", cv_path))

    # -- commit surface --
    # Two-level buffering mirrors the store's group-commit overlay:
    # stage_entry moves the entry's ops to _staged_buf so a LATER entry's
    # rollback() (which clears only _buf) can't drop them.
    def stage_entry(self) -> None:
        self._inner.stage_entry()
        if self._buf:
            self._staged_buf.extend(self._buf)
            self._buf.clear()

    def commit_applied(self, seq: int) -> None:
        self._inner.commit_applied(seq)
        self._flush()

    def commit_runtime(self) -> None:
        self._inner.commit_runtime()
        self._flush()

    def rollback(self) -> None:
        self._inner.rollback()
        self._buf.clear()

    def rollback_group(self) -> None:
        self._inner.rollback_group()
        self._buf.clear()
        self._staged_buf.clear()

    def _flush(self) -> None:
        ops = self._staged_buf + self._buf
        self._staged_buf.clear()
        self._buf.clear()
        if len(ops) > 1:
            # last-wins per logical key: a group of N creates in one dir
            # puts the parent inode N times — the mirror only needs the
            # final state (ops are independent upserts, so cross-key
            # order is irrelevant)
            last: dict[tuple, tuple] = {}
            for op in ops:
                k = op[0]
                if k == "put":
                    v = op[1]
                    key = ("i", v if isinstance(v, int) else v.id)
                elif k == "del":
                    key = ("i", op[1])
                elif k in ("cput", "cdel"):
                    key = ("c", op[1], op[2])
                else:
                    key = ("m", op[1])
                last[key] = op
            ops = list(last.values())
        for op in ops:
            self._apply_one(op)

    def clear(self) -> None:
        self._inner.clear()
        self._buf.clear()
        self._staged_buf.clear()
        self._mirror.clear()
