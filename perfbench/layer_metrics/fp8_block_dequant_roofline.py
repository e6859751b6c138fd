"""The fp8 expansion kernel's share of the chip's HBM roofline, in %:
the bytes a load's expansion must move (from the configuration's
shapes) times the loads of the window, over the device seconds of the
trace's fp8_block_dequant ops times the published HBM bandwidth."""

from perfbench import dequant_readers


def read(run):
    return dequant_readers.roofline_pct(run)
