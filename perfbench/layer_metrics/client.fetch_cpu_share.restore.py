"""CPU seconds of the steps on the fetch threads over their wall (client
counters read.phase.{grant,map,verify}.cpu_s over .cpu_wall_s, both of
the one step in eight whose thread CPU clock was read): low where each
thread waits for the GIL after its calls, or for the worker's reply."""

from perfbench import loop_readers


def read(run):
    return loop_readers.fetch_cpu_share(run)
