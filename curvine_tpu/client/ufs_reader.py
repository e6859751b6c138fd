"""Reader over an uncached UFS object (FsReader-compatible surface).

Backs the unified read path for files that exist under a mount but have
no cached blocks yet: ranged reads go straight to the under-store."""

from __future__ import annotations

from curvine_tpu.obs.trace import NULL_SPAN, Timed


class UfsReader:
    """`counters` / `tracer` are the client's (docs/observability.md):
    bytes handed on count as read.ufs.bytes, the time a read spends in
    the under-store as the phase `ufs` (read.phase.ufs.s, span
    phase.ufs)."""

    def __init__(self, ufs, uri: str, length: int,
                 chunk_size: int = 4 * 1024 * 1024,
                 counters: dict | None = None, tracer=None):
        self.ufs = ufs
        self.uri = uri
        self.len = length
        self.chunk_size = chunk_size
        self.pos = 0
        self.counters = counters if counters is not None else {}
        self.tracer = tracer

    def _phase(self):
        span = NULL_SPAN if self.tracer is None else self.tracer.span(
            "phase.ufs", attrs={"uri": self.uri}, detail=True)
        return Timed(self.counters, "read.phase.ufs", span)

    def _count(self, n: int) -> None:
        c = self.counters
        c["read.ufs.bytes"] = c.get("read.ufs.bytes", 0) + n

    def seek(self, pos: int) -> None:
        self.pos = max(0, min(pos, self.len))

    async def read(self, n: int = -1) -> bytes:
        if n < 0:
            n = self.len - self.pos
        data = await self.pread(self.pos, n)
        self.pos += len(data)
        return data

    async def read_all(self) -> bytes:
        self.seek(0)
        return await self.read(self.len)

    async def pread(self, offset: int, n: int) -> bytes:
        n = max(0, min(n, self.len - offset))
        if n == 0:
            return b""
        out = bytearray()
        with self._phase():
            async for chunk in self.ufs.read(self.uri, offset=offset,
                                             length=n):
                out += chunk
        self._count(len(out))
        return bytes(out)

    async def pread_view(self, offset: int, n: int):
        import numpy as np
        return np.frombuffer(await self.pread(offset, n), dtype=np.uint8)

    async def read_range(self, offset: int, n: int, parallel: int = 1):
        # UFS objects stream sequentially; parallel is a no-op here
        return await self.pread_view(offset, n)

    async def mmap_view(self, offset: int, n: int):
        return None      # no local block files to map

    async def chunks(self, chunk_size: int | None = None):
        chunk_size = chunk_size or self.chunk_size
        self.seek(0)
        async for chunk in self.ufs.read(self.uri, chunk_size=chunk_size):
            self.pos += len(chunk)
            self._count(len(chunk))
            yield chunk

    async def close(self) -> None:
        return None
