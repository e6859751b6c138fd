"""Thread hand-offs of shm fetch work per file opened in the window
(client counters read.fetch.hops over read.files): ~1.005 where each of
a restore's 921 blocks is a thread hop of its own, a few hundredths
where a primed client's batch threads take many blocks a hop."""

from perfbench import fetch_readers


def read(run):
    return fetch_readers.hops_per_file(run)
