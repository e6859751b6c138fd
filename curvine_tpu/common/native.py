"""ctypes bindings for the native C++ helpers (csrc/native.cc), and the
one place the four native libraries get built.

Each .so is built on demand (make in csrc/); every function here has a
pure-Python fallback so nothing hard-depends on a compiler at runtime.
A failed build is logged at WARNING — callers that must not run on the
fallback (chip_smoke.py) check `build()` / `available()` and raise."""

from __future__ import annotations

import ctypes
import fcntl
import logging
import os
import shutil
import subprocess
import zlib

log = logging.getLogger(__name__)

_CSRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "csrc")
_BUILD = os.path.join(_CSRC, "build")

_lib = None
_tried = False


def build(so_name: str) -> str | None:
    """Path of csrc/build/<so_name>, brought up to date with `make`
    first (make compares the .so with its sources, so a stale prebuilt
    library that lacks newer symbols is rebuilt, and a current one costs
    one no-op make). None when it is absent and cannot be built.
    CURVINE_NO_AUTOBUILD=1 (deploy images ship prebuilt libraries) only
    looks. Concurrent processes serialise on a lock file so they never
    interleave writes into the shared build directory."""
    so = os.path.join(_BUILD, so_name)
    if (os.environ.get("CURVINE_NO_AUTOBUILD") != "1"
            and shutil.which("make")
            and os.path.exists(os.path.join(_CSRC, "Makefile"))):
        try:
            os.makedirs(_BUILD, exist_ok=True)
            with open(os.path.join(_BUILD, ".build.lock"), "w") as lf:
                fcntl.flock(lf, fcntl.LOCK_EX)
                subprocess.run(["make", "-C", _CSRC, f"build/{so_name}"],
                               capture_output=True, timeout=300,
                               check=True)
        except subprocess.CalledProcessError as e:
            log.warning("native build of %s failed: %s", so_name,
                        e.stderr.decode(errors="replace")[-2000:])
        except (OSError, subprocess.TimeoutExpired) as e:
            log.warning("native build of %s failed: %s", so_name, e)
    return so if os.path.exists(so) else None


def _load():
    global _lib, _tried
    if _tried:
        return _lib
    _tried = True
    so = build("libcurvine_native.so")
    if so is not None:
        try:
            lib = ctypes.CDLL(so)
            lib.cv_crc32c.restype = ctypes.c_uint32
            # void*: bytes, a ctypes array or a bare address alike
            lib.cv_crc32c.argtypes = [ctypes.c_void_p, ctypes.c_size_t,
                                      ctypes.c_uint32]
            lib.cv_xxh64.restype = ctypes.c_uint64
            lib.cv_xxh64.argtypes = [ctypes.c_char_p, ctypes.c_size_t,
                                     ctypes.c_uint64]
            lib.cv_read_file.restype = ctypes.c_int64
            lib.cv_read_file.argtypes = [ctypes.c_char_p, ctypes.c_uint64,
                                         ctypes.c_char_p, ctypes.c_uint64]
            lib.cv_write_file.restype = ctypes.c_int64
            lib.cv_write_file.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                          ctypes.c_uint64, ctypes.c_int]
            lib.cv_checksum_file.restype = ctypes.c_int64
            lib.cv_checksum_file.argtypes = [
                ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint64,
                ctypes.POINTER(ctypes.c_uint32)]
            try:
                # newer symbol — a stale prebuilt .so (rebuild refused by
                # a missing compiler) must not take down the older paths
                lib.cv_gf_mul_xor.restype = None
                lib.cv_gf_mul_xor.argtypes = [
                    ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t,
                    ctypes.c_uint8]
                lib._has_gf = True
            except AttributeError:
                lib._has_gf = False
            _lib = lib
            log.info("native helpers loaded: %s", so)
        except OSError as e:
            log.warning("native load failed: %s", e)
    return _lib


def available() -> bool:
    return _load() is not None


def crc32c(data, seed: int = 0) -> int:
    return crc32c_counted(data, seed)[0]


def crc32c_counted(data, seed: int = 0) -> tuple[int, int]:
    """(crc32c of `data`, bytes that had to be copied to hash it). Any
    contiguous buffer is hashed where it lies: a read-only one (a
    sealed shm mapping, a slice of the caller's bytes) has no ctypes
    `from_buffer`, so its address is taken through numpy. The hash is a
    CDLL call: no GIL while it runs, nor in the page faults inside it."""
    lib = _load()
    owned = isinstance(data, bytes)
    n = data.nbytes if isinstance(data, memoryview) else len(data)
    if lib is None:
        return _crc32c_py(data, seed), 0 if owned else n
    if owned:
        return lib.cv_crc32c(data, n, seed), 0
    try:
        # zero-copy for writable buffers (read-path views into sinks):
        # hashing at hardware speed is pointless behind a memcpy
        buf = (ctypes.c_char * n).from_buffer(data)
    except TypeError:
        import numpy as np
        try:
            # `keep` holds the buffer export for as long as the call
            keep = np.frombuffer(data, dtype=np.uint8)
        except (TypeError, ValueError, BufferError):
            # no buffer protocol, or not contiguous: the one case left
            # that hashes a copy
            return lib.cv_crc32c(bytes(data), n, seed), n
        return lib.cv_crc32c(keep.ctypes.data, n, seed), 0
    return lib.cv_crc32c(buf, n, seed), 0


def has_gf() -> bool:
    lib = _load()
    return lib is not None and getattr(lib, "_has_gf", False)


def gf_mul_xor(dst, src, coef: int) -> bool:
    """dst[i] ^= gf_mul(coef, src[i]) over GF(256)/0x11d — the RS codec
    hot loop. dst must be a writable contiguous buffer (numpy uint8
    array); src any contiguous buffer of the same length. Returns False
    when the native kernel is unavailable (caller falls back to the
    table path in common/ec.py)."""
    lib = _load()
    if lib is None or not getattr(lib, "_has_gf", False):
        return False
    n = dst.nbytes if hasattr(dst, "nbytes") else len(dst)
    # numpy arrays hand over their data pointer (read-only views too —
    # from_buffer would refuse those); other buffers go through ctypes
    dbuf = dst.ctypes.data if hasattr(dst, "ctypes") \
        else (ctypes.c_char * n).from_buffer(dst)
    if hasattr(src, "ctypes"):
        sbuf = src.ctypes.data
    else:
        try:
            sbuf = (ctypes.c_char * n).from_buffer(src)
        except TypeError:
            sbuf = bytes(src)
    lib.cv_gf_mul_xor(dbuf, sbuf, n, coef)
    return True


def xxh64(data, seed: int = 0) -> int:
    lib = _load()
    if lib is not None:
        buf = bytes(data) if not isinstance(data, bytes) else data
        return lib.cv_xxh64(buf, len(buf), seed)
    # fallback: not xxh64, but a stable 64-bit fingerprint
    return (zlib.crc32(data) << 32) | zlib.adler32(data)


def checksum_file(path: str, offset: int = 0, length: int = 0) -> int | None:
    """CRC32C of a file range computed natively; None when unavailable."""
    lib = _load()
    if lib is None:
        try:
            with open(path, "rb") as f:
                f.seek(offset)
                data = f.read(length or None)
            return _crc32c_py(data, 0)
        except OSError:
            return None
    out = ctypes.c_uint32(0)
    n = lib.cv_checksum_file(path.encode(), offset, length,
                             ctypes.byref(out))
    return out.value if n >= 0 else None


# ---------------- pure-python crc32c (table, slow; correctness ref) ----

_PY_TABLE: list[int] | None = None


def _table() -> list[int]:
    global _PY_TABLE
    if _PY_TABLE is None:
        t = []
        for i in range(256):
            crc = i
            for _ in range(8):
                crc = (crc >> 1) ^ 0x82F63B78 if crc & 1 else crc >> 1
            t.append(crc)
        _PY_TABLE = t
    return _PY_TABLE


def _crc32c_py(data, seed: int = 0) -> int:
    t = _table()
    crc = seed ^ 0xFFFFFFFF
    for b in bytes(data):
        crc = t[(crc ^ b) & 0xFF] ^ (crc >> 8)
    return crc ^ 0xFFFFFFFF
