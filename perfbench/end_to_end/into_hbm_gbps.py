"""Bytes of samples that became ready on the device in the window, over
the window's whole duration, in GB/s (10^9)."""


def read(run):
    return run.window.rate() / 1e9
