"""Share of the reader tasks' time spent inside the client (open +
mmap_view / read_all): benchmark span, summed over the readers."""

from perfbench import readers


def read(run):
    lanes = int(run.cell.config["read_threads"])
    return readers.span_sum_share(run, "client.fetch", lanes)
