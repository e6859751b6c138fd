"""ctypes binding for the native LSM KV engine (csrc/kv_engine.cc).

Same public surface and the SAME on-disk format as the Python engine
(common/kvstore.py) — either opens the other's directory, so switching
engines is a restart. This is the RocksDB role of the reference master
(curvine-common/src/rocksdb/db_engine.rs) finally served by native
code, like the reference; the Python engine remains the always-available
fallback.
"""

from __future__ import annotations

import ctypes
import logging
import os

import msgpack

log = logging.getLogger(__name__)

_lib = None
_tried = False

_u8p = ctypes.POINTER(ctypes.c_uint8)


def _load():
    global _lib, _tried
    if _tried:
        return _lib
    _tried = True
    from curvine_tpu.common import native
    so = native.build("libcurvine_kv.so")
    if so is None:
        return None
    try:
        lib = ctypes.CDLL(so)
        lib.kv_errmsg.restype = ctypes.c_char_p
        lib.kv_open.restype = ctypes.c_void_p
        lib.kv_open.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                ctypes.c_uint64, ctypes.c_int]
        lib.kv_write_batch.restype = ctypes.c_int
        lib.kv_write_batch.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                       ctypes.c_uint32]
        lib.kv_get.restype = ctypes.c_int
        lib.kv_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                               ctypes.c_uint32, ctypes.POINTER(_u8p),
                               ctypes.POINTER(ctypes.c_uint32)]
        lib.kv_free.argtypes = [ctypes.c_void_p]
        for name in ("kv_flush", "kv_compact", "kv_clear"):
            fn = getattr(lib, name)
            fn.restype = ctypes.c_int
            fn.argtypes = [ctypes.c_void_p]
        lib.kv_close.argtypes = [ctypes.c_void_p]
        lib.kv_scan_open.restype = ctypes.c_void_p
        lib.kv_scan_open.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     ctypes.c_uint32, ctypes.c_char_p,
                                     ctypes.c_uint32]
        lib.kv_scan_next.restype = ctypes.c_int
        lib.kv_scan_next.argtypes = [ctypes.c_void_p,
                                     ctypes.POINTER(_u8p),
                                     ctypes.POINTER(ctypes.c_uint32),
                                     ctypes.POINTER(_u8p),
                                     ctypes.POINTER(ctypes.c_uint32)]
        lib.kv_scan_close.argtypes = [ctypes.c_void_p]
        lib.kv_scan_many.restype = ctypes.c_int64
        lib.kv_scan_many.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     ctypes.c_uint32]
        lib.kv_segment_count.restype = ctypes.c_uint64
        lib.kv_segment_count.argtypes = [ctypes.c_void_p]
        _lib = lib
    except OSError as e:  # pragma: no cover
        log.debug("native kv load failed: %s", e)
    return _lib


def available() -> bool:
    return _load() is not None


class NativeKvStore:
    """KvStore-compatible wrapper over the native engine."""

    def __init__(self, kv_dir: str, memtable_max_bytes: int = 8 << 20,
                 compact_threshold: int = 8, fsync: bool = False):
        lib = _load()
        if lib is None:
            raise RuntimeError("native kv engine unavailable")
        self._lib = lib
        self.dir = kv_dir
        os.makedirs(kv_dir, exist_ok=True)
        self._h = lib.kv_open(kv_dir.encode(), 1 if fsync else 0,
                              memtable_max_bytes, compact_threshold)
        if not self._h:
            raise RuntimeError(
                f"kv_open: {lib.kv_errmsg().decode(errors='replace')}")

    def _check(self, rc: int) -> None:
        if rc < 0:
            raise RuntimeError(
                f"kv: {self._lib.kv_errmsg().decode(errors='replace')}")

    # ---- writes (same WAL bytes as the python engine: the batch is
    # packed HERE and the native side journals it verbatim) ----

    def write_batch(self, items) -> None:
        items = list(items)
        if not items:
            return
        payload = msgpack.packb(items, use_bin_type=True)
        self._check(self._lib.kv_write_batch(self._h, payload,
                                             len(payload)))

    def put(self, key: bytes, value: bytes) -> None:
        self.write_batch([(key, value)])

    def delete(self, key: bytes) -> None:
        self.write_batch([(key, None)])

    # ---- reads ----

    def get(self, key: bytes) -> bytes | None:
        out = _u8p()
        n = ctypes.c_uint32()
        rc = self._lib.kv_get(self._h, key, len(key),
                              ctypes.byref(out), ctypes.byref(n))
        self._check(rc)
        if rc == 0:
            return None
        try:
            return ctypes.string_at(out, n.value)
        finally:
            self._lib.kv_free(out)

    _SCAN_BUF = 1 << 20

    def scan(self, prefix: bytes = b"", start: bytes | None = None):
        """Batched: one FFI round trip per ~1 MiB of records instead of
        per item (the per-item cursor benched SLOWER than pure python)."""
        import struct
        it = self._lib.kv_scan_open(self._h, prefix, len(prefix),
                                    start or b"",
                                    len(start) if start else 0)
        if not it:
            raise RuntimeError(
                f"kv_scan: {self._lib.kv_errmsg().decode(errors='replace')}")
        bufsize = self._SCAN_BUF
        buf = ctypes.create_string_buffer(bufsize)
        u32x2 = struct.Struct("<II")
        try:
            while True:
                n = self._lib.kv_scan_many(it, buf, bufsize)
                if n < -1:
                    # one record larger than the buffer: grow + retry
                    # (values have no size cap — python-engine parity)
                    bufsize = -n
                    buf = ctypes.create_string_buffer(bufsize)
                    continue
                self._check(n)
                if n == 0:
                    return
                data = buf.raw[:n]
                off = 0
                while off < n:
                    kl, vl = u32x2.unpack_from(data, off)
                    off += 8
                    yield data[off:off + kl], data[off + kl:off + kl + vl]
                    off += kl + vl
        finally:
            self._lib.kv_scan_close(it)

    # ---- maintenance ----

    def flush(self) -> None:
        self._check(self._lib.kv_flush(self._h))

    def compact(self) -> None:
        self._check(self._lib.kv_compact(self._h))

    def clear(self) -> None:
        self._check(self._lib.kv_clear(self._h))

    def close(self) -> None:
        if self._h:
            self._lib.kv_close(self._h)
            self._h = None

    @property
    def segment_count(self) -> int:
        return int(self._lib.kv_segment_count(self._h))
