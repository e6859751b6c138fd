"""Share of the restores' wall time during which only tensors larger than
one block were still outstanding."""

from perfbench import readers


def read(run):
    return readers.multiblock_share(run)
