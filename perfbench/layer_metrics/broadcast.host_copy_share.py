"""Share of the restores' wall time spent copying tensors' views into
host memory of their own before anything is placed (client counters
ckpt.host_copy.s / ckpt.wall_s; the copies run one after another on the
client's loop, so the sum is wall time). A restore that places each
tensor from its view reads 0. A program that keeps no ckpt.host_copy.s
(an older one counted this copy as ckpt.place) gives nothing to read."""


def read(run):
    wall = run.delta("client", "ckpt.wall_s")
    if "ckpt.host_copy.s" not in run.after["client"] or wall <= 0:
        return None
    return run.delta("client", "ckpt.host_copy.s") / wall
