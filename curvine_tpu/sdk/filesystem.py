"""Synchronous Python SDK.

Parity: curvine-libsdk/src/python/ (python_abi.rs, python_filesystem.rs) —
a blocking FileSystem facade over the async client, safe to call from any
thread (dedicated event-loop thread under the hood), with file-like
reader/writer objects (lib_fs_reader.rs / lib_fs_writer.rs)."""

from __future__ import annotations

import asyncio
import threading
from typing import Any

from curvine_tpu.common.conf import ClusterConf
from curvine_tpu.common.types import FileStatus, SetAttrOpts


class LoopThread:
    """One shared asyncio loop running on a daemon thread."""

    def __init__(self, name: str = "curvine-sdk") -> None:
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever,
                                       daemon=True, name=name)
        self.thread.start()

    def run(self, coro, timeout: float | None = 120) -> Any:
        return asyncio.run_coroutine_threadsafe(coro, self.loop).result(timeout)

    def close(self) -> None:
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(5)


class CurvineFile:
    """File-like object (binary). Mode 'rb' wraps FsReader (seekable);
    'wb'/'ab' wrap FsWriter (sequential)."""

    def __init__(self, lt: LoopThread, inner, mode: str):
        self._lt = lt
        self._inner = inner
        self.mode = mode
        self.closed = False

    # -- reading --
    def read(self, n: int = -1) -> bytes:
        return self._lt.run(self._inner.read(n))

    def pread(self, offset: int, n: int) -> bytes:
        return self._lt.run(self._inner.pread(offset, n))

    def seek(self, pos: int, whence: int = 0) -> int:
        if whence == 1:
            pos += self._inner.pos
        elif whence == 2:
            pos += self._inner.len
        self._inner.seek(pos)
        return pos

    def tell(self) -> int:
        return self._inner.pos

    # -- writing --
    def write(self, data: bytes) -> int:
        return self._lt.run(self._inner.write(data))

    def flush(self) -> None:
        if self.mode != "rb":
            self._lt.run(self._inner.flush())

    def close(self) -> None:
        if not self.closed:
            self._lt.run(self._inner.close())
            self.closed = True

    def __enter__(self) -> "CurvineFile":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class CurvineFileSystem:
    """Blocking FS API: the SDK entry point.

    >>> fs = CurvineFileSystem(master="127.0.0.1:8995")
    >>> with fs.open("/data/x.bin", "wb") as f: f.write(b"...")
    """

    def __init__(self, conf: ClusterConf | None = None,
                 master: str | None = None, conf_path: str | None = None):
        self.conf = conf or ClusterConf.load(conf_path)
        if master:
            self.conf.client.master_addrs = [master]
        self._lt = LoopThread()
        from curvine_tpu.client import CurvineClient

        async def make():
            return CurvineClient(self.conf)
        self._client = self._lt.run(make())

    @property
    def client(self):
        return self._client

    # ---------------- namespace ----------------

    def mkdir(self, path: str, create_parent: bool = True) -> FileStatus:
        return self._lt.run(self._client.meta.mkdir(path, create_parent))

    def exists(self, path: str) -> bool:
        return self._lt.run(self._client.meta.exists(path))

    def get_status(self, path: str) -> FileStatus:
        return self._lt.run(self._client.meta.file_status(path))

    def list_status(self, path: str) -> list[FileStatus]:
        return self._lt.run(self._client.meta.list_status(path))

    def delete(self, path: str, recursive: bool = False) -> None:
        self._lt.run(self._client.meta.delete(path, recursive))

    def rename(self, src: str, dst: str) -> bool:
        return self._lt.run(self._client.meta.rename(src, dst))

    def set_attr(self, path: str, **kw) -> None:
        self._lt.run(self._client.meta.set_attr(path, SetAttrOpts(**kw)))

    # ---------------- io ----------------

    def open(self, path: str, mode: str = "rb") -> CurvineFile:
        if mode in ("r", "rb"):
            return CurvineFile(self._lt, self._lt.run(self._client.open(path)),
                               "rb")
        if mode in ("w", "wb"):
            return CurvineFile(self._lt,
                               self._lt.run(self._client.create(
                                   path, overwrite=True)), "wb")
        if mode in ("a", "ab"):
            return CurvineFile(self._lt,
                               self._lt.run(self._client.append(path)), "ab")
        raise ValueError(f"unsupported mode {mode!r}")

    def read_all(self, path: str) -> bytes:
        async def go():
            r = await self._client.open(path)
            try:
                return await r.read_all()
            finally:
                await r.close()
        return self._lt.run(go())

    def write_all(self, path: str, data: bytes) -> None:
        self._lt.run(self._client.write_all(path, data))

    # ---------------- cluster ----------------

    def master_info(self):
        return self._lt.run(self._client.meta.master_info())

    def mount(self, cv_path: str, ufs_path: str, **kw):
        return self._lt.run(self._client.meta.mount(cv_path, ufs_path, **kw))

    def submit_load(self, path: str, recursive: bool = True) -> str:
        return self._lt.run(self._client.meta.submit_load(path, recursive))

    def job_status(self, job_id: str):
        return self._lt.run(self._client.meta.job_status(job_id))

    def close(self) -> None:
        try:
            self._lt.run(self._client.close())
        finally:
            self._lt.close()

    def __enter__(self) -> "CurvineFileSystem":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
