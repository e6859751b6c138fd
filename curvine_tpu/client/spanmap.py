"""One range of addresses over the sealed memfds of consecutive blocks.

A file that spans blocks is several memfds on the worker's side
(docs/data-plane.md); a consumer that wants it whole — a tensor for
`device_put` — wants one contiguous buffer. `SpanMap` reserves the
range once (`PROT_NONE`, no memory behind it) and maps each block's
memfd at its offset in it (`MAP_FIXED`), so the blocks lie side by side
and are each mapped once: no bytes move. libc's `mmap` through ctypes
(Python's `mmap` module cannot place a mapping); the calls run without
the GIL."""

from __future__ import annotations

import ctypes
import functools
import mmap
import os
import weakref

_PROT_NONE = 0
_MAP_FIXED = 0x10          # Linux (x86, arm): Python's mmap has no name for it
_MAP_FAILED = ctypes.c_void_p(-1).value


@functools.cache
def _libc():
    lib = ctypes.CDLL(None, use_errno=True)
    lib.mmap.restype = ctypes.c_void_p
    lib.mmap.argtypes = [ctypes.c_void_p, ctypes.c_size_t, ctypes.c_int,
                         ctypes.c_int, ctypes.c_int, ctypes.c_int64]
    lib.munmap.restype = ctypes.c_int
    lib.munmap.argtypes = [ctypes.c_void_p, ctypes.c_size_t]
    return lib


def _mmap(addr, length: int, prot: int, flags: int, fd: int) -> int:
    got = _libc().mmap(addr, length, prot, flags, fd, 0)
    if got is None or got == _MAP_FAILED:
        e = ctypes.get_errno()
        raise OSError(e, f"mmap of {length} bytes: {os.strerror(e)}")
    return got


class SpanMap:
    """`nbytes` of reserved addresses. `map` places a memfd in them;
    `view` is the read-only uint8 array over all of them, and every
    array derived from it keeps the range mapped: it is unmapped when
    the last of them is collected (or by `close`, for a range that was
    never handed out). The kernel's mapping holds a memfd's pages, so
    the caller may close the fd as soon as `map` has returned."""

    def __init__(self, nbytes: int):
        if nbytes <= 0:
            raise ValueError(f"span of {nbytes} bytes")
        self.nbytes = nbytes
        self._addr = _mmap(None, nbytes, _PROT_NONE,
                           mmap.MAP_PRIVATE | mmap.MAP_ANONYMOUS, -1)
        self.close = weakref.finalize(self, _libc().munmap, self._addr,
                                      nbytes)

    @property
    def __array_interface__(self) -> dict:
        # numpy keeps this object as the array's base; True = read-only
        return {"version": 3, "shape": (self.nbytes,), "typestr": "|u1",
                "data": (self._addr, True)}

    def view(self):
        import numpy as np
        return np.asarray(self)

    def map(self, fd: int, length: int, at: int, flags: int):
        """Map `length` bytes of `fd` read-only at byte `at` of the
        range (a multiple of the page size), over the reservation or
        whatever was mapped there before. → that slice of `view()`.
        OSError where the kernel refuses."""
        if at % mmap.PAGESIZE or at < 0 or length <= 0 \
                or at + length > self.nbytes:
            raise ValueError(f"map [{at}, {at + length}) into a span of "
                             f"{self.nbytes} bytes")
        _mmap(self._addr + at, length, mmap.PROT_READ,
              flags | _MAP_FIXED, fd)
        return self.view()[at:at + length]

    def unmap(self, at: int, length: int) -> None:
        """Take back what was mapped at byte `at` for `length` bytes:
        that part of the range is reserved again, no memory behind it
        (a block that failed its checksum leaves nothing readable)."""
        if at % mmap.PAGESIZE or at < 0 or length <= 0 \
                or at + length > self.nbytes:
            raise ValueError(f"unmap [{at}, {at + length}) of a span of "
                             f"{self.nbytes} bytes")
        _mmap(self._addr + at, length, _PROT_NONE,
              mmap.MAP_PRIVATE | mmap.MAP_ANONYMOUS | _MAP_FIXED, -1)

    def hold(self, at: int, length: int, free):
        """What `map` placed at byte `at`, as a read-only array that
        keeps it there: when the last array over it is collected, that
        part of the range is reserved again (`unmap`) and `free(at)` is
        told the place is empty. A place that no array holds any more
        can be mapped into again; one that an array holds is never."""
        place = _Window(self._addr + at, length, self)
        weakref.finalize(place, self._release, at, length, free)
        return _array(place)

    def _release(self, at: int, length: int, free) -> None:
        self.unmap(at, length)
        free(at)

    def window(self, at: int, n: int, held: list):
        """`n` bytes from byte `at` of the range as one read-only array
        that keeps `held` — the arrays `hold` gave for the places under
        it — alive, and so mapped, as long as it is."""
        return _array(_Window(self._addr + at, n, (self, tuple(held))))


class _Window:
    """`n` bytes at address `addr` as numpy sees them (read-only); what
    it `keeps` lives as long as an array over it does."""

    __slots__ = ("_iface", "keeps", "__weakref__")

    def __init__(self, addr: int, n: int, keeps):
        self._iface = {"version": 3, "shape": (n,), "typestr": "|u1",
                       "data": (addr, True)}
        self.keeps = keeps

    @property
    def __array_interface__(self) -> dict:
        return self._iface


def _array(window: _Window):
    import numpy as np
    return np.asarray(window)
