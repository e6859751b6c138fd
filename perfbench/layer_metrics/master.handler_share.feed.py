"""The master's own handler time for this client's calls (client counter
meta.srv_handle_s, from the srv field of each reply) over the window."""

from perfbench import phase_readers


def read(run):
    return phase_readers.handler_share(run)
