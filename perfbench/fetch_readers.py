"""Arithmetic of the reader of the client's fetch hand-offs: each
time the client hands shm fetch work to a thread it counts one in its
counter read.fetch.hops — one a block where a reader's fetch is a thread
of its own, one a batch where a primed client's batch threads take many
blocks at a time. Over a window: the growth of read.fetch.hops per file
opened (read.files). A restore of 915 tensors (921 blocks) reads about
1.005 with a thread a block, a few hundredths in batches. A program that
keeps no read.fetch.hops (an older one: its hand-offs were uncounted)
gives nothing to read, and so does a window in which no file was
opened: None, never 0."""

from __future__ import annotations


def hops_per_file(run):
    files = run.delta("client", "read.files")
    if "read.fetch.hops" not in run.after["client"] or files <= 0:
        return None
    return run.delta("client", "read.fetch.hops") / files
