"""The timed path, broken underneath on purpose. Each fault breaks one
guarantee a configuration states; `control.py` runs a cell under one on
the chip, the tests at a tiny size, and both have to see `correct` come
out false. The benchmark's own runs never import this file.

Faults are planted in the program's classes for the length of a `with`
block, where an answer is produced: at the reader (the client's ladder),
at the prefetcher's transfer, at the manifest, at the placement."""

from __future__ import annotations

import contextlib

import numpy as np


@contextlib.contextmanager
def _patched(obj, name: str, make):
    inner = getattr(obj, name)
    setattr(obj, name, make(inner))
    try:
        yield
    finally:
        setattr(obj, name, inner)


@contextlib.contextmanager
def altered_answer(every: int = 97):
    """One bit of one byte flipped in every `every`-th view the reader
    hands out (mmap_view or read_all): "byte for byte what was written"
    broken at the client's ladder."""
    from curvine_tpu.client.reader import FsReader
    calls = {"n": 0}

    def flip(path, buf):
        if buf is None or path.endswith(".json"):    # data, not manifests
            return buf
        calls["n"] += 1
        if calls["n"] % every or len(buf) < 64:
            return buf
        out = np.array(np.frombuffer(buf, dtype=np.uint8)
                       if isinstance(buf, (bytes, bytearray)) else buf)
        out[len(out) // 2] ^= 0x10
        return out.tobytes() if isinstance(buf, (bytes, bytearray)) else out

    def wrap(inner):
        async def broken(self, *a, **kw):
            return flip(self.path, await inner(self, *a, **kw))
        return broken

    with _patched(FsReader, "mmap_view", wrap), \
            _patched(FsReader, "read_all", wrap):
        yield


@contextlib.contextmanager
def stale_batch(every: int = 53):
    """Every `every`-th transfer hands the device the previous batch
    again: a sample delivered twice and one never — "exactly once, in
    the seeded order" broken at the prefetcher."""
    from curvine_tpu.tpu.ingest import AsyncDevicePrefetcher
    state = {"n": 0, "last": None}

    def wrap(inner):
        def broken(self, batch):
            state["n"] += 1
            if state["n"] % every == 0 and state["last"] is not None \
                    and state["last"].shape == batch.shape:
                batch = state["last"]
            state["last"] = batch
            return inner(self, batch)
        return broken

    with _patched(AsyncDevicePrefetcher, "_transfer", wrap):
        yield


@contextlib.contextmanager
def missing_tensor():
    """The manifest comes back without its last leaf: the restore hands
    over a tree that lacks one tensor without failing."""
    from curvine_tpu.tpu import broadcast

    def wrap(inner):
        async def broken(*a, **kw):
            manifest, skel, treedef = await inner(*a, **kw)
            if skel is not None and skel.get("k") == "dict" and skel["v"]:
                skel = {"k": "dict", "v": dict(list(skel["v"].items())[:-1])}
            return manifest, skel, treedef
        return broken

    with _patched(broadcast, "_load_manifest", wrap):
        yield


@contextlib.contextmanager
def exchange_left_out():
    """A replicated placement lands on the first chip of its mesh only:
    the fan-out to the other chips is left out."""
    import jax
    from jax.sharding import NamedSharding, SingleDeviceSharding
    from curvine_tpu.tpu import broadcast

    class OneChip:
        """Stands where `broadcast` looks up `jax`: device_put narrowed,
        everything else passed through."""

        def __getattr__(self, name):
            return getattr(jax, name)

        @staticmethod
        def device_put(x, target=None, **kw):
            if isinstance(target, NamedSharding):
                target = SingleDeviceSharding(
                    target.mesh.devices.reshape(-1)[0])
            return jax.device_put(x, target, **kw)

    with _patched(broadcast, "jax", lambda inner: OneChip()):
        yield


FAULTS = {"altered_answer": altered_answer, "stale_batch": stale_batch,
          "missing_tensor": missing_tensor,
          "exchange_left_out": exchange_left_out}
