"""Mean wall time of a master call less the master's own queue and handle
time (client counters meta.wall_s, meta.srv_*, meta.calls): connection,
wire, the client's loop."""

from perfbench import phase_readers


def read(run):
    return phase_readers.meta_wait_ms(run)
