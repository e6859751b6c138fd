"""The fetch hand-off's wait for the loop, ms per file opened: the fetch
thread returned to the awaiting task running again (client counter
read.resume.wake.s over read.files): the client's loop."""

from perfbench import loop_readers


def read(run):
    return loop_readers.resume_ms(run, "wake")
