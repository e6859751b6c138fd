"""Arithmetic of the reader of the client's primed opens (PR 36): a
caller that names its files up front (`CurvineClient.prime`: a restore
names its manifest) gets every one of them located in one call to the
master, and each `open` served from that answer counts in the client's
counter read.primed.files beside read.files. Over a window: the growth
of the first over the growth of the second. A restore of 915 tensors
reads 915 / 916 — the manifest is opened before anything is known. A
program that keeps no read.primed.files (an older one, or a master that
answered no list while the program has one: the counter is made by the
first primed open) gives nothing to read, and so does a window in which
no file was opened: None, never 0."""

from __future__ import annotations


def primed_open_share(run):
    files = run.delta("client", "read.files")
    if "read.primed.files" not in run.after["client"] or files <= 0:
        return None
    return run.delta("client", "read.primed.files") / files
