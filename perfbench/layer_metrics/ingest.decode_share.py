"""StepProfiler stage decode: seconds in the window over the window."""

from perfbench import readers


def read(run):
    return readers.stage_share(run, "decode")
