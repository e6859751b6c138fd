"""Share of the window the client's event loop held work without a CPU
(client counters loop.busy_s less loop.cpu_s, the loop thread's CPU
seconds): its wait for the GIL, a blocking call, or descheduled."""

from perfbench import loop_readers


def read(run):
    return loop_readers.offcpu_share(run, "client")
