"""Block-checksum algorithm selection.

Every commit-time checksum travels with its algorithm name ("crc32" =
zlib/IEEE, "crc32c" = Castagnoli via the native lib), so any verifier
can recompute it later regardless of what the writer chose. Writers
prefer crc32c whenever the native lib is loaded — on x86 it rides the
SSE4.2 crc32 instruction at many GiB/s, which is what makes always-on
read verification affordable — and fall back to zlib crc32 otherwise,
which every Python runtime can both produce and verify."""

from __future__ import annotations

import zlib

from curvine_tpu.common import native

ALGO_CRC32 = "crc32"
ALGO_CRC32C = "crc32c"


def preferred_algo() -> str:
    return ALGO_CRC32C if native.available() else ALGO_CRC32


def crc_update(algo: str, data, crc: int = 0) -> int:
    """One streaming step of `algo` over `data`, chained from `crc`."""
    if algo == ALGO_CRC32C:
        return native.crc32c(data, crc)
    return zlib.crc32(data, crc)


def supported(algo: str) -> bool:
    return algo in (ALGO_CRC32, ALGO_CRC32C)
