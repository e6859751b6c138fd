"""Arithmetic of the reader of the client's connection pools (PR 34): a
pool of `size` dials at most `size` connections an address, whatever
burst of opens meets it cold, and counts each dial it starts in the
client's counter rpc.dials (both of a CurvineClient's pools, to the
master and to the workers, count into it). Over a window: the growth of
rpc.dials per file opened (read.files). A restore that gives its 916
opens a new client reads 8 / 916; a program whose every concurrent
caller dialled for itself would read about 2. A program that keeps no
rpc.dials (an older one: its dials were uncounted) gives nothing to
read, and so does a window in which no file was opened: None, never 0."""

from __future__ import annotations


def dials_per_file(run):
    files = run.delta("client", "read.files")
    if "rpc.dials" not in run.after["client"] or files <= 0:
        return None
    return run.delta("client", "rpc.dials") / files
