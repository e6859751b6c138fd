"""Union of device-to-device copies and collectives over the traced window."""

from perfbench import readers


def read(run):
    return readers.ici_copy_share(run)
