"""Read-ladder phase resume: the hand-off to the fetch thread and back, beside the grant itself;
client counter read.phase.resume.s per file opened (read.files)."""

from perfbench import phase_readers


def read(run):
    return phase_readers.phase_ms(run, "resume")
