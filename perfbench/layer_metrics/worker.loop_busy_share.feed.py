"""Share of the window the embedded worker's event loop (its own thread)
was busy, outside the selector's select() (worker counter
loop.busy_s): probes, grants' bookkeeping, read reports, heartbeats."""

from perfbench import loop_readers


def read(run):
    return loop_readers.busy_share(run, "worker")
