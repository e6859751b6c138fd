"""Share of the window the client's event loop was busy, outside the
selector's select() (client counter loop.busy_s, summed over the
restores' clients, one open at a time): 915 opens, their hand-offs
and the transfers' dispatch share it."""

from perfbench import loop_readers


def read(run):
    return loop_readers.busy_share(run, "client")
