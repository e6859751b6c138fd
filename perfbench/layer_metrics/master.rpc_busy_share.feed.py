"""Share of the window in which a client call into the master
(FsClient.call or its fast plane) was in flight: client-side time,
benchmark span."""

from perfbench import readers


def read(run):
    return readers.span_union_share(run, "master.rpc")
