"""Tensor files opened, placed and closed per second of the window:
benchmark span from CurvineClient.open to the reader's close."""

from perfbench import readers


def read(run):
    return readers.tensors_per_s(run)
