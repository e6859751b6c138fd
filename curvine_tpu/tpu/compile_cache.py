"""Persistent XLA compilation cache, placed from outside the program.

The 1B consumer's train step takes tens of seconds to compile and
`block_checksum` specialises on every distinct block length, so a process
that owns a chip wants yesterday's executables back. The directory is
part of the cache key: it must not move between runs, so it is never a
temp name, a pid or a time."""

from __future__ import annotations

import os

import jax

DEFAULT_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str | None:
    """Turn the persistent cache on for this process and return its
    directory. Where JAX_COMPILATION_CACHE_DIR is given JAX reads it
    itself and the program sets no directory; otherwise DEFAULT_DIR.
    Call before the first compilation — chip_smoke.py and the worker's
    tier-0 start-up do. The CPU backend (the test mesh) is left
    alone: its compiles are cheap, and tests would fill the checkout with
    entries."""
    if jax.default_backend() == "cpu":
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # default floor is 1 s of compile time: the checksum, consume and
    # scan kernels compile faster than that and would never be kept
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
