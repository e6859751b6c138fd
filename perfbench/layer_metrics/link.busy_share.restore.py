"""Union of the trace's host-to-device transfers over the traced window;
cannot pass 1."""

from perfbench import readers


def read(run):
    return readers.link_busy_share(run)
