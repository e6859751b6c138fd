"""Bytes over device time of the trace's host-to-device transfers."""

from perfbench import readers


def read(run):
    return readers.h2d_gbps(run)
