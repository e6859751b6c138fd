"""Read-ladder phase copy: bytes that leave the view rungs into a buffer
(a file that fell back to read_all or to preadv); client counter
read.phase.copy.s per file opened (read.files). 0 where every file came
out of mmap_view as a view."""

from perfbench import phase_readers


def read(run):
    return phase_readers.phase_ms(run, "copy")
