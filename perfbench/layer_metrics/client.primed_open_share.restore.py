"""Share of the files opened in the window whose locations came from the
one batched call a restore makes for its manifest (client counters
read.primed.files over read.files): 915 / 916 where every tensor's open
finds its answer in the client, 0 where each asks the master for itself."""

from perfbench import prime_readers


def read(run):
    return prime_readers.primed_open_share(run)
