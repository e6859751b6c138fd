"""Connections the client's two pools dialled in the window per file
opened in it (client counters rpc.dials over read.files): 8 / 916 where
a restore's burst of opens shares a pool of four a peer."""

from perfbench import pool_readers


def read(run):
    return pool_readers.dials_per_file(run)
