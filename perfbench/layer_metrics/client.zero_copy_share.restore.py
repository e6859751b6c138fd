"""Client counter read.zero_copy_bytes over all bytes the reads fetched in
the window."""

from perfbench import readers


def read(run):
    return readers.zero_copy_share(run)
