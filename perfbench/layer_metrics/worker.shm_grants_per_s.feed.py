"""Sealed-memfd exports the worker granted per second of the window (worker
counter shm.grants)."""

from perfbench import readers


def read(run):
    return readers.counter_rate(run, "worker", "shm.grants")
