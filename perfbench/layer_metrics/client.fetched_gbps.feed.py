"""Bytes the driver's reads fetched from the cache in the window (whole
files, as `mmap_view` or `read_all` handed them over) per second, GB/s:
the cache's own rate where a sample handed on is a small part of the
file read."""


def read(run):
    fetched = run.moved("fetched_bytes")
    if fetched <= 0:
        return None
    return fetched / run.window.duration / 1e9
