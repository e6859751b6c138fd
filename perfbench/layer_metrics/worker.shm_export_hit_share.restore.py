"""Share of the window's shm grants served from the worker's export
table without a new sealed-memfd copy (worker counters shm.exports over
shm.grants)."""

from perfbench import export_readers


def read(run):
    return export_readers.export_hit_share(run)
