"""Read-ladder phase grant: fetch_block_fd on its thread (the shm grant);
client counter read.phase.grant.s per file opened (read.files)."""

from perfbench import phase_readers


def read(run):
    return phase_readers.phase_ms(run, "grant")
