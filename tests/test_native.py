"""Native C++ helpers (csrc/): checksums, file IO, scrub integration."""

import os

import pytest

from curvine_tpu.common import native
from curvine_tpu.common.types import StorageType
from curvine_tpu.worker.storage import BlockStore, TierDir

MB = 1024 * 1024


def test_native_builds_and_loads():
    assert native.available(), "csrc should build with the baked-in g++"


def test_crc32c_vectors():
    # RFC 3720 test vector
    assert native.crc32c(b"123456789") == 0xE3069283
    assert native._crc32c_py(b"123456789") == 0xE3069283
    assert native.crc32c(b"") == 0
    data = os.urandom(100_000)
    assert native.crc32c(data) == native._crc32c_py(data)
    # seeding chains: crc(a+b) == crc(b, seed=crc(a))
    a, b = data[:40_000], data[40_000:]
    assert native.crc32c(b, seed=native.crc32c(a)) == native.crc32c(data)


def _readonly(kind: str, data: bytes):
    if kind == "memoryview":
        return memoryview(data)
    if kind == "numpy":
        import numpy as np
        return np.frombuffer(data, dtype=np.uint8)
    import mmap
    fd = os.memfd_create("cv-test-crc")
    try:
        os.write(fd, data)
        return mmap.mmap(fd, len(data), access=mmap.ACCESS_READ)
    finally:
        os.close(fd)


@pytest.mark.parametrize("kind", ["mmap", "numpy", "memoryview"])
@pytest.mark.parametrize("seed", [0, 0x9E3779B9])
def test_crc32c_hashes_readonly_buffers_in_place(kind, seed):
    """A read-only buffer (a sealed shm mapping, a slice of the caller's
    bytes) is hashed at its own address: the right checksum, chained
    from any seed, and the block is not allocated a second time."""
    import tracemalloc
    if not native.available():
        pytest.skip("native unavailable")
    data = os.urandom(2 * MB + 13)
    buf = _readonly(kind, data)
    assert memoryview(buf).readonly
    want = native._crc32c_py(data[:4096], seed)
    assert native.crc32c(memoryview(buf)[:4096], seed) == want
    want = native.crc32c(data, seed)         # bytes: the path it had
    native.crc32c(buf, seed)                 # numpy's own imports, once
    tracemalloc.start()
    got, copied = native.crc32c_counted(buf, seed)
    peak = tracemalloc.get_traced_memory()[1]
    tracemalloc.stop()
    assert (got, copied) == (want, 0)
    assert peak < len(data) // 4
    if kind == "mmap":
        buf.close()                          # no export left behind


def test_crc32c_counts_the_copy_it_cannot_avoid():
    """Writable buffers hash in place as before; a buffer that is not
    contiguous is the one input still copied, and says so."""
    data = os.urandom(64 * 1024)
    assert native.crc32c_counted(bytearray(data)) \
        == (native.crc32c(data), 0)
    strided = memoryview(data)[::2]
    assert native.crc32c_counted(strided) \
        == (native.crc32c(bytes(strided)), len(data) // 2)


def test_xxh64_vectors():
    if not native.available():
        pytest.skip("native unavailable")
    assert native.xxh64(b"") == 0xEF46DB3751D8E999
    assert native.xxh64(b"a") == 0xD24EC4F1A98C6E5B
    assert native.xxh64(b"abc") == 0x44BC2CF5AD770999
    long = bytes(range(256)) * 100
    assert native.xxh64(long) == native.xxh64(long)
    assert native.xxh64(long) != native.xxh64(long[:-1])


def test_checksum_file(tmp_path):
    p = tmp_path / "f.bin"
    data = os.urandom(3 * MB + 17)
    p.write_bytes(data)
    assert native.checksum_file(str(p)) == native.crc32c(data)
    # ranged
    assert native.checksum_file(str(p), offset=100, length=1000) == \
        native.crc32c(data[100:1100])


def test_scrub_detects_corruption(tmp_path):
    tier = TierDir(StorageType.MEM, str(tmp_path / "mem"), capacity=64 * MB)
    store = BlockStore([tier])
    for bid in (1, 2):
        info = store.create_temp(bid, size_hint=MB)
        with open(info.path, "wb") as f:
            f.write(os.urandom(MB))
        store.commit(bid, MB)
    assert store.verify(1) and store.verify(2)
    # flip a byte in block 2's file
    path = store.get(2, touch=False).path
    with open(path, "r+b") as f:
        f.seek(1234)
        b = f.read(1)
        f.seek(1234)
        f.write(bytes([b[0] ^ 0xFF]))
    corrupt = store.scrub()
    assert corrupt == [2]
    # the corrupt block is REPORTED, not deleted — only the master may
    # order the delete, once a clean replica exists elsewhere
    assert store.contains(2)
    assert store.contains(1)


import pytest as _pytest


@_pytest.fixture
def cluster_loop_native():
    """MiniCluster on a background loop/thread: the native SDK is a
    blocking TCP client and must not run on the cluster's own loop."""
    import asyncio
    import threading
    from curvine_tpu.testing import MiniCluster
    loop = asyncio.new_event_loop()
    mc = MiniCluster(workers=1, block_size=4 * 1024 * 1024)
    t = threading.Thread(target=loop.run_forever, daemon=True)
    t.start()
    asyncio.run_coroutine_threadsafe(mc.start(), loop).result(30)
    yield mc
    asyncio.run_coroutine_threadsafe(mc.stop(), loop).result(30)
    loop.call_soon_threadsafe(loop.stop)
    t.join(5)


def test_native_sdk_end_to_end(cluster_loop_native):
    """The C++ SDK (csrc/sdk.cc, own msgpack + framing + block streaming)
    drives a real cluster over TCP: mkdir/put/get/ls/stat/rename/delete.
    Parity: curvine-libsdk native client."""
    import pytest
    from curvine_tpu.sdk import native_sdk
    if not native_sdk.available():
        pytest.skip("libcurvine_sdk.so not built")
    mc = cluster_loop_native
    host, port = mc.master.addr.rsplit(":", 1)
    with native_sdk.NativeCurvineClient(host, int(port)) as c:
        c.mkdir("/csdk")
        payload = os.urandom(9 * 1024 * 1024)       # spans 3 blocks @ 4MB
        c.put("/csdk/blob.bin", payload)
        assert c.stat_len("/csdk/blob.bin") == len(payload)
        assert c.get("/csdk/blob.bin") == payload
        assert c.exists("/csdk/blob.bin")
        ls = c.list("/csdk")
        assert [e["name"] for e in ls] == ["blob.bin"]
        assert ls[0]["len"] == len(payload)
        c.rename("/csdk/blob.bin", "/csdk/renamed.bin")
        assert not c.exists("/csdk/blob.bin")
        assert c.get("/csdk/renamed.bin") == payload
        c.delete("/csdk/renamed.bin")
        assert not c.exists("/csdk/renamed.bin")
        # empty file round trip
        c.put("/csdk/empty", b"")
        assert c.stat_len("/csdk/empty") == 0
        assert c.get("/csdk/empty") == b""
        # errors surface with messages
        with pytest.raises(Exception):
            c.get("/csdk/nope")


def test_native_sdk_streams(cluster_loop_native):
    """Streaming handles (lib_fs_reader/lib_fs_writer parity): chunked
    writes spanning blocks, sequential + seek reads, stat JSON."""
    import pytest
    from curvine_tpu.sdk import native_sdk
    if not native_sdk.available():
        pytest.skip("libcurvine_sdk.so not built")
    mc = cluster_loop_native
    host, port = mc.master.addr.rsplit(":", 1)
    payload = os.urandom(9 * MB + 12345)            # spans 3 blocks @ 4MB
    with native_sdk.NativeCurvineClient(host, int(port)) as c:
        with c.open_writer("/csdk/stream.bin") as w:
            # uneven chunk sizes straddle block boundaries
            pos = 0
            for n in (1, 3 * MB, 5 * MB + 7, MB, len(payload)):
                chunk = payload[pos:min(n + pos, len(payload))]
                if not chunk:
                    break
                w.write(chunk)
                pos += len(chunk)
                assert w.tell() == pos
            w.flush()
        st = c.stat("/csdk/stream.bin")
        assert st["len"] == len(payload)
        assert st["is_complete"] is True and st["is_dir"] is False
        with c.open_reader("/csdk/stream.bin") as r:
            assert len(r) == len(payload)
            # sequential read across block boundaries in odd sizes
            got = bytearray()
            while True:
                b = r.read(1_000_003)
                if not b:
                    break
                got.extend(b)
            assert bytes(got) == payload
            # seek back mid-file (abandons the stream) and re-read a slice
            at = 4 * MB - 100
            assert r.seek(at) == at
            assert r.tell() == at
            assert r.read(300) == payload[at:at + 300]
            # small forward hop is served from the buffered stream
            here = r.tell()
            r.seek(here + 64)
            assert r.read(100) == payload[here + 64:here + 164]
            # seek to EOF → read returns empty
            r.seek(len(payload))
            assert r.read(10) == b""
        # whole-file read() convenience
        with c.open_reader("/csdk/stream.bin") as r:
            assert r.read() == payload
        # streamed empty file
        with c.open_writer("/csdk/stream_empty") as w:
            pass
        assert c.stat("/csdk/stream_empty")["len"] == 0
        with c.open_reader("/csdk/stream_empty") as r:
            assert r.read() == b""
        # post-close use raises instead of crashing on a NULL handle
        with pytest.raises(ValueError):
            r.read(1)
        with pytest.raises(ValueError):
            w.write(b"x")
