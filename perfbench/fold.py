"""The fold that stands for "byte for byte": two position-weighted sums
mod 2^32 over an array's bit patterns, computed on the device from what
the timed path left there and on the host from the seeded reference. Any
changed, moved, missing or extra element changes both words (up to a
2^-64 coincidence). Reading gigabytes back over the link to compare them
on the host would cost more than the window it checks."""

from __future__ import annotations

import functools

import numpy as np

_MUL = 2654435761
_ADD = 0x9E3779B9
_UINT = {1: "uint8", 2: "uint16", 4: "uint32"}


def _weigh(k):
    """The two weights of uint32 positions `k`, wrapping mod 2^32; the
    same line for the device's arrays and the host's."""
    return (k * np.uint32(2) + np.uint32(1),
            k * np.uint32(_MUL) + np.uint32(_ADD))


@functools.cache
def _device_fold():
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def fold(x):
        """x: (rows, ...) → uint32[rows, 2], each row folded alone."""
        rows = x.shape[0]
        x = x.reshape(rows, -1) if x.ndim != 2 else x
        bits = lax.bitcast_convert_type(
            x, jnp.dtype(_UINT[x.dtype.itemsize])).astype(jnp.uint32)
        w1, w2 = _weigh(lax.broadcasted_iota(jnp.uint32, bits.shape, 1))
        return jnp.stack([jnp.sum(bits * w1, axis=1, dtype=jnp.uint32),
                          jnp.sum(bits * w2, axis=1, dtype=jnp.uint32)],
                         axis=1)

    return fold


def device_fold_rows(x):
    """Each row of a device array folded alone: uint32[rows, 2]."""
    return _device_fold()(x)


def device_fold(x):
    """A whole device array as one row, in row-major order: uint32[1, 2].
    A matrix keeps its layout on the device: element (r, c) is weighted
    as position r * cols + c without a reshape of the data."""
    return _device_fold_whole()(x)


@functools.cache
def _device_fold_whole():
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def fold(x):
        if x.ndim == 0:
            x = x.reshape(1)
        bits = lax.bitcast_convert_type(
            x, jnp.dtype(_UINT[x.dtype.itemsize])).astype(jnp.uint32)
        k = jnp.zeros(bits.shape, jnp.uint32)
        stride = 1
        for axis in reversed(range(bits.ndim)):
            k = k + lax.broadcasted_iota(jnp.uint32, bits.shape, axis) \
                * jnp.uint32(stride % (1 << 32))
            stride *= bits.shape[axis]
        w1, w2 = _weigh(k)
        return jnp.stack([jnp.sum(bits * w1, dtype=jnp.uint32),
                          jnp.sum(bits * w2, dtype=jnp.uint32)])[None, :]

    return fold


_CHUNK = 1 << 22


def _weights(n: int, off: int):
    return _weigh(np.arange(off, off + n, dtype=np.uint32))  # < 2^32


_weights0 = functools.lru_cache(maxsize=16)(lambda n: _weights(n, 0))


def host_fold(a: np.ndarray) -> np.ndarray:
    """The same two words from a host array, in plain numpy: uint32[2]."""
    a = np.ascontiguousarray(a).reshape(-1)
    flat = a.view(np.dtype(_UINT[a.dtype.itemsize]))
    if flat.size >= 1 << 32:
        raise ValueError("host_fold weights positions mod 2^32 only")
    s1 = s2 = 0
    for off in range(0, flat.size, _CHUNK):
        bits = flat[off:off + _CHUNK].astype(np.uint32)
        w1, w2 = _weights0(bits.size) if off == 0 \
            else _weights(bits.size, off)
        s1 += int(np.sum(bits * w1, dtype=np.uint32))
        s2 += int(np.sum(bits * w2, dtype=np.uint32))
    return np.array([s1 & 0xFFFFFFFF, s2 & 0xFFFFFFFF], dtype=np.uint32)
