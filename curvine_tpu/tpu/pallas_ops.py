"""Pallas TPU kernels for the data plane.

block_checksum: integrity hash of an HBM-resident cached block computed
on-device (VPU tile reduction) — verifying a block after an ICI/DCN
transfer without ever copying it back to the host.

pq_lut_scan: the IVF-PQ ADC inner loop (vector/index.py) — score W
candidates by summing M one-byte codeword lookups against a per-query
LUT, fused over candidate tiles so codes stream HBM→VMEM once and the
score accumulation never leaves the chip.

fp8_block_dequant: a weight stored as float8 e4m3 with one float32
scale a block (DeepSeek-V3's published format) expanded where it lies
into the dtype the model computes in: each block's f32(Wq) x its scale,
one float32 product rounded once. The chip is sent one byte a weight
and writes two."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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


# ------------------------------------------------ fp8 block dequant

DQ_ROWS, DQ_COLS = 4, 16    # scale blocks a grid step: (512, 2048) at 128


def _e4m3_to_f32(b):
    """float8 e4m3fn bit patterns (uint8) → their float32 values, exactly,
    by integer ops: on a TPU v5e twice as fast as Mosaic's own f8
    convert (PERF.md §6). Sign, 4 exponent bits (bias 7), 3 mantissa
    bits; exponent 0 is subnormal (m × 2^-9); 0x7F / 0xFF are NaN (no
    infinity)."""
    b = b.astype(jnp.int32)
    e = (b >> 3) & 0xF
    m = b & 7
    sign = (b >> 7) << 31
    normal = jax.lax.bitcast_convert_type(
        sign | ((e + 120) << 23) | (m << 20), jnp.float32)
    sub = jax.lax.bitcast_convert_type(sign | jax.lax.bitcast_convert_type(
        m.astype(jnp.float32) * (2.0 ** -9), jnp.int32), jnp.float32)
    x = jnp.where(e == 0, sub, normal)
    return jnp.where((b & 0x7F) == 0x7F, jnp.float32(jnp.nan), x)


def _dequant_kernel(s_ref, w_ref, o_ref, *, rows, cols, bo, bi, so, si):
    # one scale a (bo, bi) block, read as a scalar from SMEM (the whole
    # scale, row-major); the unrolled loop multiplies each block of the
    # tile by its own. A tile past the weight's edge reads a clamped
    # scale and Pallas drops what it writes there.
    r0 = pl.program_id(0) * rows
    c0 = pl.program_id(1) * cols
    for a in range(rows):
        for b in range(cols):
            s = s_ref[jnp.minimum(r0 + a, so - 1) * si
                      + jnp.minimum(c0 + b, si - 1)]
            rs, cs = slice(a * bo, (a + 1) * bo), slice(b * bi, (b + 1) * bi)
            o_ref[rs, cs] = (_e4m3_to_f32(w_ref[rs, cs])
                             * s).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "dtype", "interpret"))
def _dequant(codes: jax.Array, scale: jax.Array, block: tuple[int, int],
             dtype, interpret: bool = False) -> jax.Array:
    o, i = codes.shape
    bo, bi = block
    so, si = -(-o // bo), -(-i // bi)
    rows, cols = min(DQ_ROWS, so), min(DQ_COLS, si)
    return pl.pallas_call(
        functools.partial(_dequant_kernel, rows=rows, cols=cols, bo=bo,
                          bi=bi, so=so, si=si),
        grid=(-(-so // rows), -(-si // cols)),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((rows * bo, cols * bi), lambda r, c: (r, c))],
        out_specs=pl.BlockSpec((rows * bo, cols * bi), lambda r, c: (r, c)),
        out_shape=jax.ShapeDtypeStruct((o, i), dtype),
        name="fp8_block_dequant",
        interpret=interpret,
    )(scale, codes)


def fp8_block_dequant(codes: jax.Array, scale: jax.Array,
                      block: tuple[int, int], dtype=jnp.bfloat16
                      ) -> jax.Array:
    """W[r, c] = dtype(f32(e4m3(codes[r, c])) * scale[r // bo, c // bi])
    on the device `codes` lies on. codes: the float8 e4m3fn bit patterns
    as uint8 [o, i]; scale: float32, the [ceil(o/bo), ceil(i/bi)] grid
    flattened row-major to one axis (it lives in the kernel's scalar
    memory); block: (bo, bi), on a TPU bo a multiple of 32 and bi of 128.
    Every scale is applied in float32 and the product rounded once to
    `dtype`; a ragged last block is the part of its block that the
    weight holds. Device ops named ``fp8_block_dequant``."""
    return _dequant(codes, scale, block=tuple(block),
                    dtype=jnp.dtype(dtype), interpret=interpret_for(codes))
