"""ctypes binding for the native C-ABI SDK (csrc/sdk.cc).

Parity: curvine-libsdk — the reference ships a native SDK (JNI + PyO3)
built on its Rust client; `libcurvine_sdk.so` is the rebuild's native
client speaking the wire protocol directly (own msgpack codec, framed
TCP, block streaming), and this module is the Python face of its C ABI.
A Java JNI shim would bind the same ABI (no JVM in this image to compile
one — the C surface below is the contract it would wrap)."""

from __future__ import annotations

import ctypes
import json
import logging

from curvine_tpu.common import errors as err

log = logging.getLogger(__name__)

_lib = None
_tried = False


def _load():
    global _lib, _tried
    if _tried:
        return _lib
    _tried = True
    from curvine_tpu.common import native
    so = native.build("libcurvine_sdk.so")
    if so is not None:
        lib = ctypes.CDLL(so)
        lib.cv_sdk_connect.restype = ctypes.c_void_p
        lib.cv_sdk_connect.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                       ctypes.c_char_p]
        lib.cv_sdk_close.argtypes = [ctypes.c_void_p]
        lib.cv_sdk_last_error.restype = ctypes.c_char_p
        lib.cv_sdk_last_error_code.restype = ctypes.c_int
        lib.cv_sdk_mkdir.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.cv_sdk_put.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                   ctypes.c_char_p, ctypes.c_int64]
        lib.cv_sdk_get.restype = ctypes.c_int64
        lib.cv_sdk_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                   ctypes.c_void_p, ctypes.c_int64]
        lib.cv_sdk_len.restype = ctypes.c_int64
        lib.cv_sdk_len.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.cv_sdk_delete.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                      ctypes.c_int]
        lib.cv_sdk_rename.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                      ctypes.c_char_p]
        lib.cv_sdk_exists.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.cv_sdk_list.restype = ctypes.c_void_p
        lib.cv_sdk_list.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.cv_sdk_stat.restype = ctypes.c_void_p
        lib.cv_sdk_stat.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.cv_sdk_free.argtypes = [ctypes.c_void_p]
        lib.cv_sdk_open_reader.restype = ctypes.c_void_p
        lib.cv_sdk_open_reader.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.cv_sdk_read.restype = ctypes.c_int64
        lib.cv_sdk_read.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                    ctypes.c_int64]
        lib.cv_sdk_seek.restype = ctypes.c_int64
        lib.cv_sdk_seek.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.cv_sdk_reader_len.restype = ctypes.c_int64
        lib.cv_sdk_reader_len.argtypes = [ctypes.c_void_p]
        lib.cv_sdk_reader_pos.restype = ctypes.c_int64
        lib.cv_sdk_reader_pos.argtypes = [ctypes.c_void_p]
        lib.cv_sdk_close_reader.argtypes = [ctypes.c_void_p]
        lib.cv_sdk_open_writer.restype = ctypes.c_void_p
        lib.cv_sdk_open_writer.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                           ctypes.c_int]
        lib.cv_sdk_write.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     ctypes.c_int64]
        lib.cv_sdk_flush.argtypes = [ctypes.c_void_p]
        lib.cv_sdk_writer_pos.restype = ctypes.c_int64
        lib.cv_sdk_writer_pos.argtypes = [ctypes.c_void_p]
        lib.cv_sdk_close_writer.argtypes = [ctypes.c_void_p]
        _lib = lib
    return _lib


def available() -> bool:
    return _load() is not None


class NativeCurvineClient:
    """Blocking native client: every byte of the protocol handled in C++
    (connect → mkdir/put/get/ls/stat/rename/delete)."""

    def __init__(self, host: str, port: int, user: str | None = None):
        lib = _load()
        if lib is None:
            raise err.Unsupported("libcurvine_sdk.so not built")
        self._lib = lib
        self._h = lib.cv_sdk_connect(host.encode(), port,
                                     (user or "").encode())
        if not self._h:
            raise err.ConnectError(self._err())

    def _err(self) -> str:
        return self._lib.cv_sdk_last_error().decode(errors="replace")

    def _raise(self):
        code = self._lib.cv_sdk_last_error_code()
        raise err.CurvineError.from_wire(code, self._err()) if code else \
            err.CurvineError(self._err())

    def _check(self, rc: int):
        if rc != 0:
            self._raise()

    def close(self) -> None:
        if self._h:
            self._lib.cv_sdk_close(self._h)
            self._h = None

    def mkdir(self, path: str) -> None:
        self._check(self._lib.cv_sdk_mkdir(self._h, path.encode()))

    def put(self, path: str, data: bytes) -> None:
        self._check(self._lib.cv_sdk_put(self._h, path.encode(), data,
                                         len(data)))

    def get(self, path: str) -> bytes:
        n = self.stat_len(path)
        if n < 0:
            # the typed remote error (FileNotFound vs a transport blip)
            # comes from the wire error_code — a network failure must NOT
            # masquerade as not-found
            self._raise()
        buf = ctypes.create_string_buffer(max(1, n))
        got = self._lib.cv_sdk_get(self._h, path.encode(), buf, n)
        if got < 0:
            self._raise()
        return buf.raw[:got]

    def stat_len(self, path: str) -> int:
        return self._lib.cv_sdk_len(self._h, path.encode())

    def exists(self, path: str) -> bool:
        rc = self._lib.cv_sdk_exists(self._h, path.encode())
        if rc < 0:
            self._raise()
        return rc == 1

    def delete(self, path: str, recursive: bool = False) -> None:
        self._check(self._lib.cv_sdk_delete(self._h, path.encode(),
                                            1 if recursive else 0))

    def rename(self, src: str, dst: str) -> None:
        self._check(self._lib.cv_sdk_rename(self._h, src.encode(),
                                            dst.encode()))

    def list(self, path: str) -> list[dict]:
        p = self._lib.cv_sdk_list(self._h, path.encode())
        if not p:
            raise err.CurvineError(self._err())
        try:
            return json.loads(ctypes.string_at(p).decode())
        finally:
            self._lib.cv_sdk_free(p)

    def stat(self, path: str) -> dict:
        p = self._lib.cv_sdk_stat(self._h, path.encode())
        if not p:
            self._raise()
        try:
            return json.loads(ctypes.string_at(p).decode())
        finally:
            self._lib.cv_sdk_free(p)

    def open_reader(self, path: str) -> "NativeReader":
        h = self._lib.cv_sdk_open_reader(self._h, path.encode())
        if not h:
            self._raise()
        return NativeReader(self, h)

    def open_writer(self, path: str,
                    overwrite: bool = True) -> "NativeWriter":
        h = self._lib.cv_sdk_open_writer(self._h, path.encode(),
                                         1 if overwrite else 0)
        if not h:
            self._raise()
        return NativeWriter(self, h)

    def __enter__(self) -> "NativeCurvineClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class NativeReader:
    """Streaming file reader over a native handle (lib_fs_reader parity:
    read/seek/len on an open stream, block streams reopened at offset
    after a seek)."""

    def __init__(self, client: NativeCurvineClient, handle: int):
        self._c = client
        self._h = handle

    def _handle(self) -> int:
        if not self._h:
            raise ValueError("I/O operation on closed reader")
        return self._h

    def __len__(self) -> int:
        return self._c._lib.cv_sdk_reader_len(self._handle())

    def read(self, n: int = -1) -> bytes:
        if n < 0:
            n = max(0, len(self) - self.tell())
        buf = ctypes.create_string_buffer(max(1, n))
        got = self._c._lib.cv_sdk_read(self._handle(), buf, n)
        if got < 0:
            self._c._raise()
        return buf.raw[:got]

    def tell(self) -> int:
        return self._c._lib.cv_sdk_reader_pos(self._handle())

    def seek(self, pos: int) -> int:
        rc = self._c._lib.cv_sdk_seek(self._handle(), pos)
        if rc < 0:
            self._c._raise()
        return rc

    def close(self) -> None:
        if self._h:
            self._c._lib.cv_sdk_close_reader(self._h)
            self._h = None

    def __enter__(self) -> "NativeReader":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class NativeWriter:
    """Streaming file writer over a native handle (lib_fs_writer parity);
    close() commits outstanding blocks and completes the file."""

    def __init__(self, client: NativeCurvineClient, handle: int):
        self._c = client
        self._h = handle

    def _handle(self) -> int:
        if not self._h:
            raise ValueError("I/O operation on closed writer")
        return self._h

    def write(self, data: bytes) -> int:
        if self._c._lib.cv_sdk_write(self._handle(), data, len(data)) != 0:
            self._c._raise()
        return len(data)

    def flush(self) -> None:
        if self._c._lib.cv_sdk_flush(self._handle()) != 0:
            self._c._raise()

    def tell(self) -> int:
        return self._c._lib.cv_sdk_writer_pos(self._handle())

    def close(self) -> None:
        if self._h:
            h, self._h = self._h, None
            if self._c._lib.cv_sdk_close_writer(h) != 0:
                self._c._raise()

    def __enter__(self) -> "NativeWriter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
