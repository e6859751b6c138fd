"""Read-ladder phase copy: bytes that leave the view rungs into a buffer
(read_all of the block-spanning tensors); client counter
read.phase.copy.s per file opened (read.files)."""

from perfbench import phase_readers


def read(run):
    return phase_readers.phase_ms(run, "copy")
