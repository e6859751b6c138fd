"""Arithmetic of the readers of what the program accounts from inside
(PR 26): the server's own time in every reply of the master (client
counters meta.*), the phases of a read (read.phase.<p>.s, per file opened:
read.files) and of a restore (ckpt.*). Every counter is the client's, read
as its growth over the window. A program that keeps none of them (an older
one) gives nothing to read: None. A phase that no file of the window went
through reads 0."""

from __future__ import annotations


def _kept(run, key: str) -> bool:
    return key in run.after["client"]


def handler_share(run):
    """Seconds the master's handlers spent on this client's calls (from
    their replies) over the window. Handlers that do not await run one
    at a time, so this is the master's loop busy on them."""
    if not _kept(run, "meta.srv_handle_s"):
        return None
    return run.delta("client", "meta.srv_handle_s") / run.window.duration


def queue_ms(run):
    """Mean time a request spent in the master between its frame being
    parsed and its handler starting."""
    calls = run.delta("client", "meta.calls")
    if not _kept(run, "meta.srv_queue_s") or calls <= 0:
        return None
    return run.delta("client", "meta.srv_queue_s") / calls * 1e3


def meta_wait_ms(run):
    """Mean of a master call's wall time at the client less the master's
    own queue and handle time: the connection, the wire, and the
    client's loop getting round to the reply."""
    calls = run.delta("client", "meta.calls")
    if not _kept(run, "meta.wall_s") or calls <= 0:
        return None
    rest = (run.delta("client", "meta.wall_s")
            - run.delta("client", "meta.srv_handle_s")
            - run.delta("client", "meta.srv_queue_s"))
    return rest / calls * 1e3


def phase_ms(run, phase: str):
    """Seconds in one phase of the read ladder per file opened in the
    window, so the phases of a cell add up to what a file cost."""
    files = run.delta("client", "read.files")
    if not _kept(run, "read.files") or files <= 0:
        return None
    return run.delta("client", f"read.phase.{phase}.s") / files * 1e3


def place_ms(run):
    """Mean time of one placer call (the dispatch of a tensor's
    transfer) in load_checkpoint."""
    n = run.delta("client", "ckpt.place.n")
    if n <= 0:
        return None
    return run.delta("client", "ckpt.place.s") / n * 1e3


def ready_wait_share(run):
    """Share of the restores' wall time spent in the closing
    block_until_ready sweep, after the last tensor was dispatched."""
    wall = run.delta("client", "ckpt.wall_s")
    if wall <= 0:
        return None
    return run.delta("client", "ckpt.ready_wait.s") / wall
