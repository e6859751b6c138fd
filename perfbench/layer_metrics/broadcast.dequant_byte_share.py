"""The share of a load's bytes read that the chip expanded: fp8 weights
and their scales (client counter ckpt.dequant.bytes_in) over every range
read (ckpt.bytes)."""

from perfbench import dequant_readers


def read(run):
    return dequant_readers.byte_share(run)
