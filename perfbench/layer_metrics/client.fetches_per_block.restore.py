"""Blocks granted, mapped and verified per block a reader came to hold
in the window (client counters read.block_fetches over
read.blocks_mapped): 1.0 where each block is fetched once however many
ranges lie in it or cross it."""

from perfbench import range_readers


def read(run):
    return range_readers.fetches_per_block(run)
