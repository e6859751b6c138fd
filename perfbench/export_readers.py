"""Arithmetic of the readers of the worker's export table (PR 32): a
committed MEM block is copied into a sealed memfd once and every later
grant of it is a dup, so over a window the share of grants that cost no
copy is 1 - (growth of shm.exports) / (growth of shm.grants), both
counters of the worker. A program that keeps no shm.exports (an older
one: every grant past its 128 entries was a copy, uncounted) gives
nothing to read, and so does a window in which nothing was granted:
None, never 1.0."""

from __future__ import annotations


def export_hit_share(run):
    grants = run.delta("worker", "shm.grants")
    if "shm.exports" not in run.after["worker"] or grants <= 0:
        return None
    return 1.0 - run.delta("worker", "shm.exports") / grants
