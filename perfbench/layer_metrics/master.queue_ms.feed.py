"""Mean time a request waited in the master, frame parsed to handler start
(client counters meta.srv_queue_s / meta.calls)."""

from perfbench import phase_readers


def read(run):
    return phase_readers.queue_ms(run)
