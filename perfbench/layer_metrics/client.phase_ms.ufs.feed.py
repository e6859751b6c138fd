"""Read phase ufs: the time a read the cache did not serve spends streaming
from the under-store (UfsReader.pread); client counter read.phase.ufs.s per
file read from the UFS (read.ufs.files)."""


def read(run):
    files = run.delta("client", "read.ufs.files")
    if "read.phase.ufs.s" not in run.after["client"] or files <= 0:
        return None
    return run.delta("client", "read.phase.ufs.s") / files * 1e3
