"""Median gap between one batch ready on the device and the next, over
every gap of the window."""

from perfbench import readers


def read(run):
    return readers.gap_p50_ms(run)
