"""1 - union of device operations over the traced window."""

from perfbench import readers


def read(run):
    return readers.device_idle_share(run)
