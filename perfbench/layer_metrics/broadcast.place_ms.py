"""Mean time of one placer call in load_checkpoint (client counters
ckpt.place.s / ckpt.place.n)."""

from perfbench import phase_readers


def read(run):
    return phase_readers.place_ms(run)
