"""GB/s the worker served over its socket rung in the window (worker
counter bytes.read)."""

from perfbench import readers


def read(run):
    return readers.counter_rate(run, "worker", "bytes.read", 1e9)
