"""Pallas TPU kernels for the data plane.

block_checksum: integrity hash of an HBM-resident cached block computed
on-device (VPU tile reduction) — verifying a block after an ICI/DCN
transfer without ever copying it back to the host.

pq_lut_scan: the IVF-PQ ADC inner loop (vector/index.py) — score W
candidates by summing M one-byte codeword lookups against a per-query
LUT, fused over candidate tiles so codes stream HBM→VMEM once and the
score accumulation never leaves the chip."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

LANE = 128
SUBLANE = 8
TILE_WORDS = 64 * SUBLANE * LANE     # 64 f32-tiles per grid step (256 KiB)


def interpret_for(arr) -> bool:
    """Pallas interpret mode is for arrays that live on a CPU device (the
    test mesh) and nothing else: any accelerator gets the compiled Mosaic
    kernel or an error, never a silent emulation. A tracer or a host
    array has no device yet: the backend it will be compiled for or put
    on decides."""
    if isinstance(arr, jax.Array) and not isinstance(arr, jax.core.Tracer):
        return next(iter(arr.devices())).platform == "cpu"
    return jax.default_backend() == "cpu"


def _checksum_kernel(x_ref, out_ref):
    # wraparound sums in int32 (same bit pattern as uint32; Mosaic has no
    # unsigned reductions) + a position-mixed term for order sensitivity.
    # Scalars can't be stored to VMEM → accumulate (8,128) partial tiles;
    # the final cross-lane reduction happens outside the kernel.
    x = x_ref[:]                                   # (TILE_WORDS/LANE, LANE)
    step = pl.program_id(0)
    sub = x.shape[0] // SUBLANE
    s_part = jnp.sum(x.reshape(sub, SUBLANE, LANE), axis=0, dtype=jnp.int32)
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    mixed = (x ^ (idx + step * TILE_WORDS)).reshape(sub, SUBLANE, LANE)
    m_part = jnp.sum(mixed, axis=0, dtype=jnp.int32)

    @pl.when(step == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    out_ref[0:SUBLANE, :] += s_part
    out_ref[SUBLANE:, :] += m_part


@functools.partial(jax.jit, static_argnames=("interpret",))
def _checksum_words(words: jax.Array, interpret: bool = False) -> jax.Array:
    n = words.shape[0]
    padded = ((n + TILE_WORDS - 1) // TILE_WORDS) * TILE_WORDS
    words = jnp.pad(words, (0, padded - n))
    rows = padded // LANE
    grid = rows // (TILE_WORDS // LANE)
    out = pl.pallas_call(
        _checksum_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((TILE_WORDS // LANE, LANE),
                               lambda i: (i, 0))],
        out_specs=pl.BlockSpec((2 * SUBLANE, LANE), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((2 * SUBLANE, LANE), jnp.int32),
        interpret=interpret,
    )(words.reshape(rows, LANE))
    s = jax.lax.bitcast_convert_type(
        jnp.sum(out[:SUBLANE], dtype=jnp.int32), jnp.uint32)
    m = jax.lax.bitcast_convert_type(
        jnp.sum(out[SUBLANE:], dtype=jnp.int32), jnp.uint32)
    return s ^ (m << jnp.uint32(1))


def block_checksum(block: jax.Array) -> int:
    """Checksum of a device-resident uint8 block (stays on device)."""
    interpret = interpret_for(block)
    nbytes = block.shape[0]
    pad = (-nbytes) % 4
    if pad:
        block = jnp.pad(block, (0, pad))
    words = jax.lax.bitcast_convert_type(
        block.reshape(-1, 4), jnp.int32).reshape(-1)
    return int(_checksum_words(words, interpret=interpret))


def block_checksum_host(data: bytes | np.ndarray) -> int:
    """Reference/host implementation (numpy) of the same hash. All sums
    wrap mod 2^32 on purpose — that is the kernel's int32 arithmetic."""
    arr = np.frombuffer(data, dtype=np.uint8) if isinstance(
        data, (bytes, bytearray, memoryview)) else np.asarray(data)
    n = -(-arr.size // 4)
    tiles = -(-n // TILE_WORDS)
    if arr.size == tiles * TILE_WORDS * 4:
        words = arr.view(np.uint32)
    else:
        words = np.zeros(tiles * TILE_WORDS, dtype=np.uint32)
        words.view(np.uint8)[:arr.size] = arr
    w = words.reshape(tiles, TILE_WORDS // LANE, LANE)
    s = w.sum(dtype=np.uint32)
    # mixed term: lane index within each row, offset by the tile's base
    mix = (np.arange(LANE, dtype=np.uint32)[None, None, :]
           + (np.arange(tiles, dtype=np.uint32)
              * np.uint32(TILE_WORDS))[:, None, None])
    m = np.bitwise_xor(w, mix).sum(dtype=np.uint32)
    return int(s ^ (m << np.uint32(1)))


# ---------------------------------------------------------------- PQ ADC

PQ_TILE = 128      # candidates scored per grid step


def _pq_scan_kernel(lut_ref, codes_ref, out_ref, *, pre_offset: bool):
    # ADC without a hardware gather: codes are compared against a lane
    # iota and the matching LUT entry selected per subspace — an
    # [TILE, ksub] VPU select+reduce per subspace, all in VMEM. The
    # subspace count M is small (8-64) so the python loop unrolls.
    # pre_offset: codes carry the m·ksub flat-LUT offset already (the
    # device-pinned layout the IVF-PQ search uses).
    m, ksub = lut_ref.shape
    codes = codes_ref[:]                         # [PQ_TILE, M] int32
    col = jax.lax.broadcasted_iota(jnp.int32, (codes.shape[0], ksub), 1)
    acc = jnp.zeros((codes.shape[0], 1), jnp.float32)
    for mi in range(m):
        want = col + mi * ksub if pre_offset else col
        eq = codes[:, mi:mi + 1] == want
        acc = acc + jnp.sum(
            jnp.where(eq, lut_ref[mi:mi + 1, :], 0.0),
            axis=1, keepdims=True)
    out_ref[:] = acc


@functools.partial(jax.jit, static_argnames=("interpret", "pre_offset"))
def _pq_scan_padded(lut: jax.Array, codes: jax.Array,
                    interpret: bool = False,
                    pre_offset: bool = False) -> jax.Array:
    w, m = codes.shape
    ksub = lut.shape[1]
    out = pl.pallas_call(
        functools.partial(_pq_scan_kernel, pre_offset=pre_offset),
        grid=(w // PQ_TILE,),
        in_specs=[pl.BlockSpec((m, ksub), lambda i: (0, 0)),
                  pl.BlockSpec((PQ_TILE, m), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((PQ_TILE, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((w, 1), jnp.float32),
        interpret=interpret,
    )(lut, codes)
    return out[:, 0]


def pq_lut_scan(lut: jax.Array, codes: jax.Array,
                interpret: bool | None = None,
                pre_offset: bool = False) -> jax.Array:
    """ADC scores out[w] = sum_m lut[m, codes[w, m]].

    lut [M, ksub] f32 (one query's per-codeword contributions), codes
    [W, M] int — W is padded to the candidate tile internally.
    pre_offset=True means codes already hold code + m·ksub (the pinned
    flat-LUT layout). Traceable (used inside the jitted IVF-PQ search);
    interpret=None decides like block_checksum (interpret_for)."""
    if interpret is None:
        interpret = interpret_for(codes)
    w = codes.shape[0]
    pad = (-w) % PQ_TILE
    if pad:
        codes = jnp.pad(codes, ((0, pad), (0, 0)))
    return _pq_scan_padded(lut.astype(jnp.float32),
                           codes.astype(jnp.int32),
                           interpret=interpret,
                           pre_offset=pre_offset)[:w]
