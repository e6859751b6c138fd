"""Milliseconds the load's loop spends dispatching one fp8 weight's
expansion on the chip (client counters ckpt.dequant.s over
ckpt.dequant.n)."""

from perfbench import dequant_readers


def read(run):
    return dequant_readers.dispatch_ms(run)
