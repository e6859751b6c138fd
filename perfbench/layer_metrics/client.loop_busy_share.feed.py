"""Share of the window the client's event loop was busy, outside the
selector's select() (client counter loop.busy_s): the loop a feed's
readers, master calls, decode and transfers share."""

from perfbench import loop_readers


def read(run):
    return loop_readers.busy_share(run, "client")
