"""Arithmetic of the readers of the event loops' own clock (PR 37).
Every asyncio loop the program runs counts, into each sink attached to
it, loop.busy_s (wall seconds outside the selector's select(): running
callbacks), loop.cpu_s (the loop thread's CPU seconds, user + system)
and loop.runs (iterations); a CurvineClient's counters are attached
while it is open, an RpcServer's registry while it runs — the client
group is the client's loop, the worker group the embedded worker's own
loop thread. busy − cpu is the loop holding work without a CPU: the
GIL, a blocking call, descheduled. Beside them, the two halves of a
fetch's hand-off to a thread (read.resume.queue.s: submit → the thread
running; read.resume.wake.s: the thread returned → the task running
again) and the CPU seconds of the steps on the fetch thread
(read.phase.<p>.cpu_s, over the same steps' wall, .cpu_wall_s). Over a
window: growth over the window, per file opened, or over the steps'
wall. A program that keeps none of these (an older one) gives nothing
to read: None, never 0."""

from __future__ import annotations

FETCH_STEPS = ("grant", "map", "verify")


def _kept(run, group: str, key: str) -> bool:
    return key in run.after[group]


def busy_share(run, group: str):
    """Share of the window the group's loop spent outside select()."""
    if not _kept(run, group, "loop.busy_s"):
        return None
    return run.delta(group, "loop.busy_s") / run.window.duration


def offcpu_share(run, group: str):
    """Share of the window the group's loop was busy without a CPU."""
    if not (_kept(run, group, "loop.busy_s")
            and _kept(run, group, "loop.cpu_s")):
        return None
    off = run.delta(group, "loop.busy_s") - run.delta(group, "loop.cpu_s")
    return max(0.0, off) / run.window.duration


def resume_ms(run, part: str):
    """One half of the fetch hand-off ("queue" or "wake"), ms per file
    opened: the two add up to client.phase_ms.resume."""
    files = run.delta("client", "read.files")
    if not _kept(run, "client", f"read.resume.{part}.s") or files <= 0:
        return None
    return run.delta("client", f"read.resume.{part}.s") / files * 1e3


def fetch_cpu_share(run):
    """CPU seconds of the steps on the fetch threads over their wall,
    both of the steps whose CPU clock was read (read.phase.<p>.cpu_s over
    read.phase.<p>.cpu_wall_s: one step in eight is, at random — a read
    of a thread's CPU clock is a system call). What is left of 1 is each
    thread's wait for the GIL after the call that released it, and time
    off the CPU inside the call (a grant waits for the worker's reply)."""
    if not _kept(run, "client", "read.phase.grant.cpu_s"):
        return None
    cpu = sum(run.delta("client", f"read.phase.{p}.cpu_s")
              for p in FETCH_STEPS)
    wall = sum(run.delta("client", f"read.phase.{p}.cpu_wall_s")
               for p in FETCH_STEPS)
    return cpu / wall if wall > 0 else None
