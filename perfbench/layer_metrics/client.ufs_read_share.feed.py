"""Bytes the client read from the under-store (a miss under a mount, or a
cached block dropped under the reader) over all bytes the driver's reads
fetched in the window: client counter read.ufs.bytes. A program that keeps
no such counter gives nothing to read."""


def read(run):
    fetched = run.moved("fetched_bytes")
    if "read.ufs.bytes" not in run.after["client"] or fetched <= 0:
        return None
    return run.delta("client", "read.ufs.bytes") / fetched
