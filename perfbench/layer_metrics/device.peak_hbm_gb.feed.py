"""Peak bytes in use on the fullest chip (memory_stats), in GB."""

from perfbench import readers


def read(run):
    return readers.peak_hbm_gb(run)
