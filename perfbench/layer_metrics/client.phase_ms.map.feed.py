"""Read-ladder phase map: mmap of the granted memfds on their fetch
threads (MAP_POPULATE where the block is verified), summed over a file's
blocks; client counter read.phase.map.s per file opened (read.files)."""

from perfbench import phase_readers


def read(run):
    return phase_readers.phase_ms(run, "map")
