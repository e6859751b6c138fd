"""GB/s the worker's load tasks brought from the under-store into its
tiers in the window (worker counter load.bytes): what the cache spends on
admission beside serving."""

from perfbench import readers


def read(run):
    if "load.bytes" not in run.after["worker"]:
        return None
    return readers.counter_rate(run, "worker", "load.bytes", 1e9)
