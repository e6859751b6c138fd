"""Client counter read.span_view_bytes (bytes served as one view over
the mappings of several blocks side by side) over all bytes the restores
fetched in the window: client.span_view_share.feed's reader, loaded from
beside this file."""

import os

from perfbench import harness

feed = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "client.span_view_share.feed.py"))


def read(run):
    return feed.read(run)
