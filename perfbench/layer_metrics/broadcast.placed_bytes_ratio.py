"""Bytes all chips together received over the checkpoint's bytes counted
once (client counters ckpt.placed_bytes / ckpt.bytes, both from shapes
and shardings, no device read): 1.4358 where each chip receives its own
quarter of every expert tensor and a copy of the rest, 4.0 where every
chip receives everything. A program that keeps no such counters gives
nothing to read."""


def read(run):
    once = run.delta("client", "ckpt.bytes")
    if "ckpt.placed_bytes" not in run.after["client"] or once <= 0:
        return None
    return run.delta("client", "ckpt.placed_bytes") / once
