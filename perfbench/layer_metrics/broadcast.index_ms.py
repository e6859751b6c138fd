"""Milliseconds a restore spent reading a safetensors index and reading
and checking its shards' headers (client counters ckpt.index.s plus
ckpt.headers.s, over ckpt.index.n); priming and opening the shards are
not in it."""

from perfbench import range_readers


def read(run):
    return range_readers.index_ms(run)
