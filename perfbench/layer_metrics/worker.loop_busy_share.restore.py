"""Share of the window the embedded worker's event loop (its own thread)
was busy, outside the selector's select() (worker counter
loop.busy_s): a restore's batched probes and its grants."""

from perfbench import loop_readers


def read(run):
    return loop_readers.busy_share(run, "worker")
