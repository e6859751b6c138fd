"""Read-ladder phase probe: the GET_BLOCK_INFO round trip to the worker;
client counter read.phase.probe.s per file opened (read.files)."""

from perfbench import phase_readers


def read(run):
    return phase_readers.phase_ms(run, "probe")
