"""Checkpoint bytes (counted once, however many chips receive them) of
every restore completed in the window, over the window's whole duration,
in GB/s (10^9)."""


def read(run):
    return run.window.rate() / 1e9
