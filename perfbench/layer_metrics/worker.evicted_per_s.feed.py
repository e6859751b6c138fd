"""Blocks the worker dropped under cache pressure per second of the window
(worker counter blocks.evicted; demotions to a slower tier not counted)."""

from perfbench import readers


def read(run):
    if "blocks.evicted" not in run.after["worker"]:
        return None
    return readers.counter_rate(run, "worker", "blocks.evicted")
