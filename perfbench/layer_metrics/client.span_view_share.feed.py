"""Client counter read.span_view_bytes (bytes served as one view over
the mappings of several blocks side by side) over all bytes the reads
fetched in the window; the files of one block make up the rest of
client.zero_copy_share.feed. A program that keeps no such counter gives
nothing to read."""


def read(run):
    fetched = run.moved("fetched_bytes")
    if "read.span_view_bytes" not in run.after["client"] or fetched <= 0:
        return None
    return run.delta("client", "read.span_view_bytes") / fetched
