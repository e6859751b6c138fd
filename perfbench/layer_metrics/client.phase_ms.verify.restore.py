"""Read-ladder phase verify: the full-block checksum on the client's loop;
client counter read.phase.verify.s per file opened (read.files)."""

from perfbench import phase_readers


def read(run):
    return phase_readers.phase_ms(run, "verify")
