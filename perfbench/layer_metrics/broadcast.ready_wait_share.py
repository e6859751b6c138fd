"""Share of the restores' wall time in the closing block_until_ready sweep
(client counters ckpt.ready_wait.s / ckpt.wall_s)."""

from perfbench import phase_readers


def read(run):
    return phase_readers.ready_wait_share(run)
