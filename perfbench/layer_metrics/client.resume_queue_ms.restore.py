"""The fetch hand-off's wait for a thread, ms per file opened: submit
to the fetch thread running (client counter read.resume.queue.s over
read.files): the pool of fetch threads and the GIL."""

from perfbench import loop_readers


def read(run):
    return loop_readers.resume_ms(run, "queue")
