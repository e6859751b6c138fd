"""StepProfiler stage input_wait (consumer blocked on an empty prefetch
queue): seconds in the window over the window."""

from perfbench import readers


def read(run):
    return readers.stage_share(run, "input_wait")
