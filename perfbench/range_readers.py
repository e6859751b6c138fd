"""Arithmetic of the readers of ranged reads (PR 39): a restore that
reads byte ranges of shared files — tensors of a safetensors shard, many
to a block, some across two or more — and its index.

- Blocks fetched per block held: the client counts each block it
  granted, mapped and verified (read.block_fetches) and each block a
  reader came to hold (read.blocks_mapped). 1.0 when every block is
  fetched once however many reads lie in it or cross it; more where
  concurrent reads of one block each fetch it, or a range is fetched
  again for every view of it.
- The index's cost: seconds a restore spent reading the index
  (ckpt.index.s) and reading and checking the shards' headers
  (ckpt.headers.s, which holds the fetch of each shard's first block)
  per restore that read an index (ckpt.index.n), in ms. Priming and
  opening the shards, between the two, are in neither.

A program that keeps neither counter (an older one) gives nothing to
read, and so does a window in which nothing was mapped or no index was
read: None, never 0."""

from __future__ import annotations


def fetches_per_block(run):
    held = run.delta("client", "read.blocks_mapped")
    if "read.block_fetches" not in run.after["client"] or held <= 0:
        return None
    return run.delta("client", "read.block_fetches") / held


def index_ms(run):
    n = run.delta("client", "ckpt.index.n")
    if n <= 0:
        return None
    return (run.delta("client", "ckpt.index.s")
            + run.delta("client", "ckpt.headers.s")) / n * 1e3
